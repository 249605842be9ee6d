"""Compiles for a described TPU v5e (no chip attached) at real widths.

What interpret mode cannot show, the chip's compiler refuses here: a block
not aligned to the tiling, more VMEM than a kernel may use, a program that
does not fit the device. Nothing runs, so these tests say nothing about
results or time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.serving import cache_ops  # noqa: E402
from repro.serving.engine import PrefillEngine  # noqa: E402

#: HBM one v5e chip lets a program use (the figure the compiler's own
#: out-of-memory message gives, 15.75 of 16 GiB).
HBM_BYTES = int(15.75 * 2**30)

@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip can be written to the persistent
    # cache but never read back here; keep it out of any cache in use.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _pallas_fn(name):
    """The kernel's pallas_call wrapper, with chip_smoke's static args."""
    import functools

    from repro.kernels.dispatch_quant.dispatch_quant import \
        dispatch_quantize_pallas
    from repro.kernels.int8_gemm.int8_gemm import int8_matmul_pallas
    from repro.kernels.mla_attention.mla_attention import \
        mla_decode_attention_pallas
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
    fn = {"mla_decode_attention": mla_decode_attention_pallas,
          "int8_matmul": int8_matmul_pallas, "ssd_scan": ssd_scan_pallas,
          "dispatch_quantize": dispatch_quantize_pallas}[name]
    return functools.partial(fn, **chip_smoke.KERNEL_KW.get(name, {}))


@pytest.mark.parametrize("name", ["mla_decode_attention", "int8_matmul",
                                  "ssd_scan", "dispatch_quantize"])
def test_kernel_compiles_for_v5e(one_chip, name):
    """Each kernel at the widths chip_smoke runs it."""
    shapes = jax.eval_shape(chip_smoke.kernel_inputs, jax.random.PRNGKey(0))
    args = _on(shapes[name], one_chip)
    compiled = jax.jit(_pallas_fn(name)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite(one_chip):
    """chip_smoke's deployment: its flags, the config, the weights' shapes
    and ``caches(batch)``, the float32 serving caches at its capacity."""
    args = serve.build_parser().parse_args(list(chip_smoke.SERVE_ARGV))
    cfg = get_config(args.arch)
    cap = serve.capacity_for(args)
    params = _on(jax.eval_shape(lambda k: model_mod.init_params(k, cfg),
                                jax.random.PRNGKey(0)), one_chip)

    def caches(b):
        return _on(jax.eval_shape(lambda: model_mod.make_caches(
            cfg, b, cap, jnp.float32)), one_chip)

    return args, cfg, params, caches


def _deepseek(one_chip):
    """The ``deepseek.reason-decode`` cell: its configuration file, its
    traffic, the program config, the weights' shapes and ``caches(batch)``
    at the cell's capacity."""
    from bench import harness
    here = os.path.dirname(__file__)
    conf = harness.load_json(os.path.join(
        here, "..", "bench", "configs", "deepseek-v3.ep32.json"))
    traffic = harness.load_json(os.path.join(
        here, "..", "bench", "traffic", "reason-decode.json"))
    cfg = harness.program_config(conf)
    cap = (traffic["suffix_tokens"]["max"] + traffic["output_tokens"]["max"]
           + 8)
    params = _on(harness.param_shapes(cfg), one_chip)

    def caches(b):
        return _on(jax.eval_shape(lambda: model_mod.make_caches(
            cfg, b, cap, jnp.float32)), one_chip)

    return conf, traffic, cfg, params, caches


def _lower_decode_loop(cfg, params, caches, batch, width, one_chip):
    """The decode engine's scanned loop (held-routing count on), caches
    donated, as ``DecodeEngine`` jits it."""
    rows = _spec((batch,), jnp.int32, one_chip)

    def loop(p, t, c, n, left):
        step = lambda tt, cc, ll, live: model_mod.decode_step_held(  # noqa: E731
            p, cfg, tt, cc, ll, live=live)
        return model_mod.decode_loop(p, cfg, t, c, n, width, steps_left=left,
                                     step_fn=step)

    return jax.jit(loop, donate_argnums=(2,)).lower(params, rows, caches,
                                                    rows, rows)


@pytest.mark.parametrize("program", ["decode_step", "decode_loop", "prefill",
                                     "prefill_continue", "ems_insert"])
def test_granite_serve_programs_fit_one_v5e(one_chip, program):
    """Each full-width program chip_smoke's deployment compiles, at its
    batch and capacity, with caches donated as the engines donate them,
    fits one chip next to every request's B=1 prefill cache (a closed-loop
    wave holds them all until decode admits them)."""
    args, cfg, params, caches = _granite(one_chip)
    batch = args.decode_batch
    rows = _spec((batch,), jnp.int32, one_chip)
    if program == "decode_step":
        fn = jax.jit(lambda p, t, c, n: model_mod.decode_step(p, cfg, t, c, n),
                     donate_argnums=(2,))
        lowered = fn.lower(params, _spec((batch, 1), jnp.int32, one_chip),
                           caches(batch), rows)
    elif program == "decode_loop":
        fn = jax.jit(lambda p, t, c, n, left: model_mod.decode_loop(
            p, cfg, t, c, n, args.decode_chunk, steps_left=left),
            donate_argnums=(2,))
        lowered = fn.lower(params, rows, caches(batch), rows, rows)
    elif program == "prefill":
        fn = jax.jit(lambda p, t: model_mod.prefill(
            p, cfg, {"tokens": t}, serve.capacity_for(args),
            cache_dtype=jnp.float32))
        lowered = fn.lower(params, _spec((1, args.prompt_len), jnp.int32,
                                         one_chip))
    elif program == "ems_insert":
        # The shared prefix's EMS blocks, 8 tokens each as serve.build's
        # EMSService holds them, into a fresh prefill cache.
        block = 8
        n_blocks = args.shared_prefix // block
        row = sum(a.size for a in jax.tree.leaves(jax.eval_shape(
            lambda c: cache_ops.seq_slice(cfg, c, 0, block), caches(1))))
        lowered = cache_ops.insert_blocks.lower(
            cfg, caches(1), [_spec((row,), jnp.float32, one_chip)] * n_blocks,
            block)
    else:
        width = PrefillEngine.SUFFIX_CHUNK
        fn = jax.jit(lambda p, t, c, off: model_mod.prefill_continue(
            p, cfg, t, c, off), donate_argnums=(2,))
        lowered = fn.lower(params, _spec((1, width), jnp.int32, one_chip),
                           caches(1), _spec((), jnp.int32, one_chip))
    mem = lowered.compile().memory_analysis()
    held = args.n_requests * sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(caches(1)))
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + held
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes, held)


@pytest.mark.parametrize("program", ["decode_loop", "prefill_continue"])
def test_deepseek_v3_cell_programs_fit_one_v5e(one_chip, program):
    """The ``deepseek.reason-decode`` cell's decode loop (batch 32, scan
    width 4, held-routing count on, as the decode engine jits it) and its
    fresh-prefill chunk, at the configuration file's published widths and
    capacity, fit one chip next to every client's B=1 prefill cache."""
    conf, traffic, cfg, params, caches = _deepseek(one_chip)
    dep = conf["deployment"]
    if program == "decode_loop":
        batch = dep["decode_batch"]
        lowered = _lower_decode_loop(cfg, params, caches(batch), batch,
                                     dep["decode_chunk"], one_chip)
    else:
        fn = jax.jit(lambda p, t, c, off: model_mod.prefill_continue(
            p, cfg, t, c, off), donate_argnums=(2,))
        lowered = fn.lower(params, _spec((1, dep["prefill_chunk"]), jnp.int32,
                                         one_chip),
                           caches(1), _spec((), jnp.int32, one_chip))
    mem = lowered.compile().memory_analysis()
    held = traffic["clients"] * sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(caches(1)))
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes + held
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes, held)


def _whole_cache_ops(hlo: str, caches) -> list:
    """Instructions of ``hlo`` that select, slice or update-slice a whole
    float32 sequence cache of ``caches``: the layer stack (L, B, S, ...),
    or one layer of it, (B, S, ...) or (1, B, S, ...)."""
    shapes = set()
    for a in jax.tree.leaves(caches):
        if a.ndim > 1:
            shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    dims = "|".join(re.escape(",".join(map(str, s))) for s in shapes)
    pat = re.compile(r"= f32\[(%s)\]\{[^}]*\} "
                     r"(select|dynamic-slice|dynamic-update-slice)\(" % dims)
    return [m.group(0) for m in pat.finditer(hlo)]


@pytest.mark.parametrize("cell", ["granite", "deepseek"])
def test_decode_loop_writes_one_row_in_place(one_chip, cell):
    """The scanned decode loop, at chip_smoke's granite widths and at the
    ``deepseek.reason-decode`` cell's, neither selects nor slices and
    restacks a whole sequence cache: each layer scatters one row per slot
    into the stack (a frozen slot's to the capacity index, dropped). The
    granite loop's temporaries stay under 3 GiB (a whole-cache select and
    restack took 4.4 GB)."""
    if cell == "granite":
        args, cfg, params, caches = _granite(one_chip)
        batch, width = args.decode_batch, args.decode_chunk
    else:
        conf, _, cfg, params, caches = _deepseek(one_chip)
        dep = conf["deployment"]
        batch, width = dep["decode_batch"], dep["decode_chunk"]
    c = caches(batch)
    compiled = _lower_decode_loop(cfg, params, c, batch, width,
                                  one_chip).compile()
    hlo = compiled.as_text()
    assert _whole_cache_ops(hlo, c) == []
    stacks = {",".join(map(str, a.shape)) for a in jax.tree.leaves(c)
              if a.ndim > 1}
    assert any(f"= f32[{s}]" in line and " scatter(" in line
               for s in stacks for line in hlo.splitlines())
    if cell == "granite":
        assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2**30


@pytest.mark.parametrize("tokens", [128, 8192])
@pytest.mark.parametrize("quantize", [True, False])
def test_lep_moe_on_four_v5e_has_two_all_to_alls(topo, tokens, quantize):
    """olmoe-1b-7b widths, experts sharded over "model" of a (1, 4) mesh:
    one all-to-all to dispatch (scales packed into the int8 payload when
    quantized) and one to combine."""
    from repro.core.lep import make_lep_moe_fn
    from repro.models import moe as moe_mod

    cfg = chip_smoke.lep_config()
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    expert, repl = NamedSharding(mesh, P("model")), NamedSharding(mesh, P())
    p1 = jax.eval_shape(lambda k: moe_mod.init_moe_params(
        k, cfg, 1, jnp.bfloat16), jax.random.PRNGKey(0))
    p = {k: _spec(v.shape[1:], v.dtype, expert if k.startswith("w_") else repl)
         for k, v in p1.items()}
    x = _spec((tokens, cfg.d_model), jnp.bfloat16, repl)
    fn = make_lep_moe_fn(mesh, ep_axes=("model",), quantize=quantize)
    compiled = jax.jit(lambda pp, xx: fn(pp, xx, cfg)).lower(p, x).compile()
    assert chip_smoke.count_all_to_all(compiled.as_text()) == 2

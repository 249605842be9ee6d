"""Run the serving path once on a TPU, at published widths, and check it.

  python chip_smoke.py               # one chip: serve phase + kernel phase
  python chip_smoke.py --four-chips  # four chips: the LEP MoE layer only

Serve phase: ``granite-3-2b`` as published (40 layers, d_model 2048, GQA
32/8, vocab 49155, bf16 weights drawn from a fixed seed) served through
``launch/serve.py``'s own wiring: 2 prefill engines, 1 decode engine, an
EMS context cache, 16 requests of 512 tokens sharing a 256-token prefix,
32 new tokens each, served twice (the first wave pays compilation).

Kernel phase: each of the four Pallas kernels once at real widths, compiled
for the chip (never interpret mode), against its ``ref.py``.

Four-chip phase: the LEP expert-parallel MoE layer at ``olmoe-1b-7b``
widths on a (1, 4) mesh, against ``moe_reference`` on one chip.

Every time printed is host wall clock. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``; any failure exits non-zero first.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import kernels  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.lep import make_lep_moe_fn  # noqa: E402
from repro.kernels.dispatch_quant.ops import dispatch_quantize  # noqa: E402
from repro.kernels.dispatch_quant.ref import \
    dispatch_quantize_ref  # noqa: E402
from repro.kernels.int8_gemm.ops import int8_matmul  # noqa: E402
from repro.kernels.int8_gemm.ref import int8_matmul_ref  # noqa: E402
from repro.kernels.mla_attention.ops import mla_decode_attention  # noqa: E402
from repro.kernels.mla_attention.ref import \
    mla_decode_attention_ref  # noqa: E402
from repro.kernels.ssd_scan.ops import ssd_scan  # noqa: E402
from repro.kernels.ssd_scan.ref import ssd_scan_ref  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import (CompileCounter,  # noqa: E402
                                        enable_compile_cache)
from repro.launch.mesh import make_debug_mesh  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.serving.engine import PrefillEngine  # noqa: E402

SERVE_ARGV = ("--arch", "granite-3-2b", "--full",
              "--n-requests", "16", "--prompt-len", "512",
              "--shared-prefix", "256", "--max-new", "32",
              "--prefill-engines", "2", "--decode-engines", "1",
              "--decode-batch", "8", "--decode-chunk", "4")

#: Served prefill logits, max |a - b| / max |b| over the vocabulary.
#: LOGIT_TOL bounds the EMS-reused prefill (prefix fetched, suffix through
#: ``prefill_continue``) and a fresh prefill against ``model.forward``;
#: REUSE_TOL bounds the reused prefill against the fresh one. On a v5e in
#: bf16 the reused prefill read 0.017-0.018 from forward; PERF.md ("Witness
#: for the logits gap") has what rounding and a path fault read there.
LOGIT_TOL = 3e-2
REUSE_TOL = 3e-2

#: Kernel output against its ref.py, max |kernel - ref| / max |ref|. The
#: refs run at highest matmul precision; f32 kernels may contract in fewer
#: MXU passes. int8_matmul is exact in int32 and rounds once to bf16.
KERNEL_TOL = {"mla_decode_attention": 2e-2, "int8_matmul": 1e-2,
              "ssd_scan": 2e-2}

#: Static arguments of the kernels at the widths of ``kernel_inputs``.
KERNEL_KW = {"mla_decode_attention": {"scale": 1.0 / np.sqrt(192.0),
                                      "kvr": 512},
             "ssd_scan": {"chunk": 128}}

#: LEP against moe_reference (tests/test_multidevice.py): early int8
#: quantization of the dispatch payload, else the same arithmetic.
LEP_TOL_QUANT = 0.05
LEP_TOL_EXACT = {jnp.float32: 1e-4,
                 # bf16: both paths round every expert matmul to bf16, in
                 # a different accumulation order; two bf16 ulps.
                 jnp.bfloat16: 2.0 ** -7}


def _rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if not (np.isfinite(out).all() and np.isfinite(ref).all()):
        raise AssertionError("non-finite values in output or reference")
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# Serve phase
# ---------------------------------------------------------------------------


def serve_phase(argv=SERVE_ARGV) -> dict:
    """Build the deployment through ``serve.build``, serve its requests in
    two waves, and check tokens, finite logits, prefix reuse and prefill
    logits."""
    args = serve.build_parser().parse_args(list(argv))
    t0 = time.perf_counter()
    dep = serve.build(args)
    cfg, system = dep.cfg, dep.system
    print(f"build (weights from seed, engines): "
          f"{time.perf_counter() - t0:.3f} s host wall clock", flush=True)
    print(f"serve: {cfg.name} {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}; {len(system.prefills)} prefill + {system.pool.n} "
          f"decode engines, decode_batch {args.decode_batch}, decode_chunk "
          f"{args.decode_chunk}, capacity {system.capacity}; "
          f"{len(dep.requests)} requests x {args.prompt_len} prompt tokens "
          f"({args.shared_prefix} shared), max_new {args.max_new}",
          flush=True)

    waves = []
    for wave, label in ((1, "cold, compilation included"), (2, "warm")):
        t0 = time.perf_counter()
        results = system.serve(dep.requests, open_loop=dep.open_loop)
        seconds = time.perf_counter() - t0
        results = sorted(results, key=lambda r: r.rid)
        n_tok = sum(len(r.tokens) for r in results)
        print(f"serve wave {wave} ({label}): {len(results)} requests, "
              f"{n_tok} tokens in {seconds:.3f} s host wall clock", flush=True)
        print(f"  reused_tokens by rid: {[r.reused_tokens for r in results]}")
        print(f"  non-finite logits rows: "
              f"{sum(r.nonfinite_logits for r in results)}")
        _check(len(results) == len(dep.requests), "a request went missing")
        for r in results:
            _check(not r.shed, f"rid {r.rid} was shed")
            _check(len(r.tokens) == args.max_new,
                   f"rid {r.rid} returned {len(r.tokens)} tokens, "
                   f"not {args.max_new}")
            _check(all(0 <= t < cfg.vocab_size for t in r.tokens),
                   f"rid {r.rid} returned a token outside the vocabulary")
            _check(r.nonfinite_logits == 0,
                   f"rid {r.rid} in wave {wave}: {r.nonfinite_logits} "
                   f"logits rows with NaN or Inf")
            if wave > 1 or r.rid != results[0].rid:
                _check(r.reused_tokens > 0,
                       f"rid {r.rid} in wave {wave} reused no prefix")
        waves.append({"seconds": seconds, "tokens": n_tok,
                      "first_tokens": {r.rid: r.tokens[0] for r in results}})

    # Served prefill logits (EMS prefix fetch + prefill_continue, as every
    # request of the warm wave ran) against a plain forward pass, and
    # against a fresh prefill of the same prompt by an engine with no cache.
    t0 = time.perf_counter()
    fwd = jax.jit(lambda p, t: model_mod.forward(p, cfg, {"tokens": t})[0][0, -1])
    fresh_engine = PrefillEngine(dep.params, cfg, system.capacity)
    by_rid = {r.rid: r for r in dep.requests}
    errs = {"logit_err": {}, "fresh_err": {}, "reuse_err": {}}
    for rid in (0, 1):
        req = by_rid[rid]
        ref = np.asarray(fwd(dep.params, jnp.asarray([req.prompt], jnp.int32)),
                         np.float32)
        last, _, res = system.prefills[0].run_logits(req)
        _check(res.reused_tokens > 0, f"rid {rid}: check took no EMS reuse")
        fresh, _, res = fresh_engine.run_logits(req)
        _check(res.reused_tokens == 0, f"rid {rid}: fresh prefill reused")
        errs["logit_err"][rid] = _rel_err(last, ref)
        errs["fresh_err"][rid] = _rel_err(fresh, ref)
        errs["reuse_err"][rid] = _rel_err(last, fresh)
        scale = np.max(np.abs(ref))
        for w, wave in enumerate(waves, 1):
            tok = wave["first_tokens"][rid]
            # Greedy on logits within LOGIT_TOL * scale of forward's picks
            # a token within twice that of forward's maximum.
            _check(ref[tok] >= ref.max() - 2 * LOGIT_TOL * scale,
                   f"rid {rid} wave {w}: first token {tok} is not a "
                   f"near-argmax of forward's logits")
    for name, what, tol in (
            ("logit_err", "reused prefill vs forward", LOGIT_TOL),
            ("fresh_err", "fresh prefill vs forward", LOGIT_TOL),
            ("reuse_err", "reused vs fresh prefill", REUSE_TOL)):
        print(f"logits, {what} (max |a - b| / max |b|): "
              + ", ".join(f"rid {rid} {e:.6g}"
                          for rid, e in errs[name].items())
              + f" (tolerance {tol})", flush=True)
    print(f"logits check: {time.perf_counter() - t0:.3f} s host wall clock",
          flush=True)
    for name, tol in (("logit_err", LOGIT_TOL), ("fresh_err", LOGIT_TOL),
                      ("reuse_err", REUSE_TOL)):
        _check(all(e <= tol for e in errs[name].values()),
               f"served prefill logits: {name} over {tol}")

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"device peak_bytes_in_use: "
          f"{peak if peak is not None else 'not reported'}"
          + (f" ({peak / 2**30:.3f} GiB)" if peak is not None else ""),
          flush=True)
    return {"waves": waves, **errs, "peak_bytes": peak}


# ---------------------------------------------------------------------------
# Kernel phase
# ---------------------------------------------------------------------------


def kernel_inputs(key) -> dict:
    """Arguments of each Pallas kernel at real widths, drawn from ``key``
    (``jax.eval_shape`` of it gives their shapes without allocating)."""
    ks = iter(jax.random.split(key, 16))
    normal = lambda shape: jax.random.normal(next(ks), shape)  # noqa: E731
    uniform = lambda shape: jax.random.uniform(next(ks), shape)  # noqa: E731
    r = KERNEL_KW["mla_decode_attention"]["kvr"]
    # deepseek-r1 absorbed MLA decode: H=128, R=512, Dr=64; 3000 of 4096
    # cache positions valid.
    b, h, dr, s = 4, 128, 64, 4096
    mla = (normal((b, h, r)), normal((b, h, dr)), normal((b, s, r + dr)),
           jnp.arange(s) < 3000)
    # INT8 GEMM at a 2048 x 8192 weight.
    m, k, n = 256, 2048, 8192
    int8 = (jax.random.randint(next(ks), (m, k), -127, 128, jnp.int8),
            jax.random.randint(next(ks), (k, n), -127, 128, jnp.int8),
            uniform((m, 1)) * 0.01, uniform((1, n)) * 0.01)
    # mamba2-780m SSD scan: H=48, P=64, N=128.
    b, s, h, p, n = 1, 2048, 48, 64, 128
    ssd = (normal((b, s, h, p)), 0.001 + 0.099 * uniform((b, s, h)),
           normal((h,)) * 0.1, normal((b, s, n)), normal((b, s, n)))
    # Dispatch quantization at deepseek-r1's d_model (7168).
    quant = ((normal((256, 7168)) * 5).astype(jnp.bfloat16),)
    return {"mla_decode_attention": mla, "int8_matmul": int8,
            "ssd_scan": ssd, "dispatch_quantize": quant}


def kernel_phase() -> dict:
    """Each Pallas kernel once, compiled for the chip, against its ref."""
    _check(kernels.INTERPRET is False, "Pallas kernels are in interpret mode")
    args = kernel_inputs(jax.random.PRNGKey(7))
    kw = KERNEL_KW
    errs = {}

    a = args["mla_decode_attention"]
    out = mla_decode_attention(*a, **kw["mla_decode_attention"])
    with jax.default_matmul_precision("highest"):
        ref = mla_decode_attention_ref(*a, **kw["mla_decode_attention"])
    errs["mla_decode_attention"] = _rel_err(out, ref)

    a = args["int8_matmul"]
    errs["int8_matmul"] = _rel_err(int8_matmul(*a), int8_matmul_ref(*a))

    a = args["ssd_scan"]
    y, hf = ssd_scan(*a, **kw["ssd_scan"])
    with jax.default_matmul_precision("highest"):
        yr, hr = ssd_scan_ref(*a)
    errs["ssd_scan"] = max(_rel_err(y, yr), _rel_err(hf, hr))

    a = args["dispatch_quantize"]
    q, sc = dispatch_quantize(*a)
    qr_, sr = dispatch_quantize_ref(*a)
    code_err = int(np.max(np.abs(np.asarray(q, np.int32)
                                 - np.asarray(qr_, np.int32))))
    errs["dispatch_quantize"] = _rel_err(sc, sr)

    for name, tol in KERNEL_TOL.items():
        print(f"kernel {name}: max rel err {errs[name]:.6g} "
              f"(tolerance {tol})", flush=True)
        _check(errs[name] <= tol, f"kernel {name} diverges from its ref")
    print(f"kernel dispatch_quantize: max code diff {code_err} (tolerance 1), "
          f"scale rel err {errs['dispatch_quantize']:.6g} (tolerance 1e-6)",
          flush=True)
    _check(code_err <= 1 and errs["dispatch_quantize"] <= 1e-6,
           "kernel dispatch_quantize diverges from its ref")
    return errs


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------


def count_all_to_all(hlo_text: str) -> int:
    """All-to-all ops (sync or async start) in a compiled HLO module."""
    return sum(1 for line in hlo_text.splitlines()
               if " all-to-all(" in line or " all-to-all-start(" in line)


def lep_config():
    """olmoe-1b-7b as published, with room in every expert for every token:
    a dropped token would keep the layer from equalling the dense oracle."""
    return dataclasses.replace(get_config("olmoe-1b-7b"), capacity_factor=2.0)


def lep_phase(devices) -> dict:
    """The LEP MoE layer at olmoe-1b-7b widths (64 experts, top-8, d_model
    2048, d_ff 1024), experts sharded over ``model`` of a (1, 4) mesh, with
    quantized single-collective dispatch and with ``quantize=False``, at a
    decode-size and a prefill-size batch, in bf16 (published) and float32,
    against ``moe_reference`` on ``devices[0]``."""
    mesh = make_debug_mesh(1, 4, devices=devices[:4])
    cfg = lep_config()
    print(f"lep: {cfg.name} {cfg.num_experts} experts top-"
          f"{cfg.num_experts_per_tok}, d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, mesh {dict(mesh.shape)}, capacity_factor "
          f"{cfg.capacity_factor}", flush=True)
    one = jax.sharding.SingleDeviceSharding(devices[0])
    errs = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        p1 = moe_mod.init_moe_params(jax.random.PRNGKey(0), cfg, 1, dtype)
        p_host = jax.tree.map(lambda a: np.asarray(a[0]), p1)
        del p1
        expert = NamedSharding(mesh, P("model"))
        repl = NamedSharding(mesh, P())
        p_ep = {k: jax.device_put(v, expert if k.startswith("w_") else repl)
                for k, v in p_host.items()}
        jax.block_until_ready(p_ep)
        print(f"  {jnp.dtype(dtype).name} expert weights sharded, bytes in "
              "use per device: " + ", ".join(
                  f"{d.id}:{d.memory_stats()['bytes_in_use']}"
                  if d.memory_stats() else f"{d.id}:not reported"
                  for d in devices[:4]), flush=True)
        p_one = jax.device_put(p_host, one)
        ref_fn = jax.jit(lambda pp, xx: moe_mod.moe_reference(pp, xx, cfg)[0])
        for t in (128, 8192):
            x = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.d_model),
                                  dtype)
            # Dense oracle in 1024-token slices (its (T, E, D) temporaries
            # would not fit one chip at prefill size).
            ref = np.concatenate([
                np.asarray(ref_fn(p_one, jax.device_put(x[i:i + 1024], one)),
                           np.float32) for i in range(0, t, 1024)])
            x_ep = jax.device_put(x, repl)
            for quantize in (True, False):
                fn = make_lep_moe_fn(mesh, ep_axes=("model",),
                                     quantize=quantize)
                compiled = jax.jit(lambda pp, xx: fn(pp, xx, cfg)).lower(
                    p_ep, x_ep).compile()
                n_a2a = count_all_to_all(compiled.as_text())
                out, aux = compiled(p_ep, x_ep)
                err = _rel_err(out, ref)
                tol = LEP_TOL_QUANT if quantize else LEP_TOL_EXACT[dtype]
                key = (jnp.dtype(dtype).name, t, quantize)
                errs[key] = err
                print(f"  {key[0]} tokens {t} quantize={quantize}: max rel "
                      f"err {err:.6g} (tolerance {tol}), all-to-all in HLO "
                      f"{n_a2a}, dropped {int(aux['dropped'])}", flush=True)
                _check(err <= tol, f"LEP {key} diverges from moe_reference")
                _check(n_a2a == 2, f"LEP {key}: {n_a2a} all-to-alls, not 2")
                _check(int(aux["dropped"]) == 0, f"LEP {key} dropped tokens")
        del p_ep, p_one
    return errs


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip LEP phase and its reference")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips; JAX found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {len(devices)}x {dev.platform} {dev.device_kind}, "
          f"jax {jax.__version__}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compiles = CompileCounter()
    t0 = time.perf_counter()
    phases = ((("lep", lambda: lep_phase(devices)),) if args.four_chips
              else (("serve", serve_phase), ("kernel", kernel_phase)))
    for name, run in phases:
        t = time.perf_counter()
        run()
        print(f"{name} phase: {time.perf_counter() - t:.3f} s host wall "
              f"clock; compile so far: {compiles}", flush=True)
    print(f"total: {time.perf_counter() - t0:.3f} s host wall clock",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

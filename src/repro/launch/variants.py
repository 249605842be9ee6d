import os
# 512 placeholder CPU devices; never the accelerator, which another process
# (the benchmark that started this one) may hold.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""§Perf hillclimbing harness: lower+compile named VARIANTS of a
(arch × shape) pair and report roofline-term deltas vs baseline.

Each variant is one hypothesis from the EXPERIMENTS.md §Perf log —
paper-faithful baselines (naive Fig-10a MoE, fused LEP) and beyond-paper
changes (token-gather 2-level EP, INT8 weight streaming, microbatch overlap,
sequence-parallel encoder activations) — compiled with the same dry-run
machinery so before/after numbers are directly comparable.

  PYTHONPATH=src python -m repro.launch.variants --arch kimi-k2-1t-a32b \
      --shape decode_32k --variant token_gather
"""
import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_shape
from repro.core.lep import make_lep_moe_fn, pick_lep_plan
from repro.core.microbatch import microbatched
from repro.launch import hlo_analysis as hlo
from repro.launch.dryrun import (OUT_DIR, analytic_flops, input_specs,
                                 train_memory_bytes)
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_pspecs, cache_pspecs, param_pspecs,
                                   to_shardings)
from repro.models import model as model_mod
from repro.quant.int8 import should_quantize

HC_DIR = os.path.join(os.path.dirname(OUT_DIR), "hillclimb")


# ---------------------------------------------------------------------------
# INT8 weight streaming: params stored int8 (+f32 scale), dequantized inline.
# Halves the per-step HBM weight traffic — §4.5's INT8 benefit on the
# memory-bound decode roofline.
# ---------------------------------------------------------------------------


def quantized_param_shapes(params_shape):
    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        if hasattr(tree, "ndim") and tree.ndim >= 2 and should_quantize(path):
            return {"__q__": jax.ShapeDtypeStruct(tree.shape, jnp.int8),
                    "__scale__": jax.ShapeDtypeStruct(
                        tree.shape[:-2] + (1, tree.shape[-1]), jnp.float32)}
        return tree
    return walk(params_shape)


def quantized_param_specs(spec_tree, params_shape):
    def walk(spec, shape, path=""):
        if isinstance(shape, dict):
            return {k: walk(spec[k], shape[k], f"{path}/{k}")
                    for k in shape}
        if hasattr(shape, "ndim") and shape.ndim >= 2 and should_quantize(path):
            return {"__q__": spec, "__scale__": P()}
        return spec
    return walk(spec_tree, params_shape)


def dequantize_tree(tree, dtype=jnp.bfloat16):
    if isinstance(tree, dict):
        if "__q__" in tree:
            return (tree["__q__"].astype(jnp.float32)
                    * tree["__scale__"]).astype(dtype)
        return {k: dequantize_tree(v, dtype) for k, v in tree.items()}
    return tree


# ---------------------------------------------------------------------------
# Variant registry
# ---------------------------------------------------------------------------


def build_variant(cfg, shape, mesh, variant: str):
    """Returns (step_fn, args, in_spec)."""
    params_shape = jax.eval_shape(
        functools.partial(model_mod.init_params, cfg=cfg), jax.random.PRNGKey(0))
    p_spec = param_pspecs(cfg, mesh, params_shape, train=(shape.kind == "train"))
    bsh = input_specs(cfg, shape)
    b_spec = batch_pspecs(cfg, mesh, bsh)

    lep_kw: Dict[str, Any] = {}
    if cfg.is_moe:
        lep_kw = dict(pick_lep_plan(cfg, mesh, serving=shape.kind != "train"))

    int8_weights = False
    n_micro = 1
    if variant == "baseline":
        pass
    elif variant == "paper_naive":          # paper's own Fig-10a baseline
        lep_kw.update(naive=True)
    elif variant == "no_early_quant":       # fused ops but BF16 dispatch
        lep_kw.update(quantize=False)
    elif variant == "token_gather":         # beyond-paper 2-level EP
        lep_kw.update(ffn_shard_axis="data", ffn_gather="tokens")
    elif variant == "int8_weights":
        int8_weights = True
    elif variant == "int8_weights_token_gather":
        int8_weights = True
        lep_kw.update(ffn_shard_axis="data", ffn_gather="tokens")
    elif variant == "token_gather_tight":
        # + exact capacity (drop the 8-sublane floor: ~4× fewer buffer rows
        #   at decode token counts) + int8 second-hop gather
        lep_kw.update(ffn_shard_axis="data", ffn_gather="tokens",
                      quantize_gather=True, capacity_align=1)
    elif variant == "full_opt":
        # everything: int8 weights + tight quantized token-gather + donation
        int8_weights = True
        lep_kw.update(ffn_shard_axis="data", ffn_gather="tokens",
                      quantize_gather=True, capacity_align=1)
    elif variant == "donate_cache":
        pass  # handled below (decode only)
    elif variant in ("aligned_decode", "int8_aligned", "best"):
        pass  # handled in the decode step builder
    elif variant == "microbatch2":
        n_micro = 2
    elif variant == "tp_only":
        # train: drop FSDP — weights TP-sharded over model only (trades
        # per-layer weight all-gathers for replicated weight memory)
        p_spec = param_pspecs(cfg, mesh, params_shape, train=False)
    elif variant == "block_skip":
        # beyond-paper: flash-style causal block skipping in prefill
        # (visits only kv blocks <= query block; ~2x fewer executed pairs)
        os.environ["REPRO_BLOCK_SKIP"] = "1"
    elif variant in ("hybrid_a2a", "hybrid_rs"):
        # paper §4.3.1 SP→TP→SP MLA prefill ("a2a" = paper-faithful Fig 17;
        # "rs" = beyond-paper reduce-scatter o_proj)
        os.environ["REPRO_MLA_HYBRID"] = variant.split("_")[1]
    elif variant == "seq_parallel_inputs":  # SP for encoder prefill
        key = "frames" if cfg.frontend == "audio_frames" else "tokens"
        old = b_spec[key]
        b_spec[key] = P(old[0], "model", *([None] * (len(old) - 2)))
    else:
        raise ValueError(f"unknown variant {variant!r}")

    if variant == "int8_aligned":
        int8_weights = True
    if variant == "best":
        int8_weights = True
        ep = lep_kw.get("ep_axes")
        if ep == ("model",):   # 2-level EP possible (kimi-class)
            lep_kw.update(ffn_shard_axis="data", ffn_gather="tokens",
                          quantize_gather=True)
        lep_kw.update(capacity_align=1)

    moe_fn = None
    if cfg.is_moe:
        moe_fn = make_lep_moe_fn(mesh, lep_kw.pop("ep_axes"), **lep_kw)

    if int8_weights:
        q_shapes = quantized_param_shapes(params_shape)
        q_spec = quantized_param_specs(p_spec, params_shape)
        params_shape, p_spec = q_shapes, q_spec

        def adapt(p):
            return dequantize_tree(p, jnp.dtype(cfg.dtype))
    else:
        adapt = lambda p: p

    if shape.kind == "decode":
        caches_shape = jax.eval_shape(
            lambda: model_mod.make_caches(cfg, shape.global_batch, shape.seq_len))
        c_spec = cache_pspecs(cfg, mesh, caches_shape)

        aligned = variant in ("aligned_decode", "int8_aligned", "best")

        def serve_step(params, tokens, caches, cache_len):
            p = adapt(params)
            if aligned:
                # pseudo-synchronous batching (paper §4.1): all requests at
                # one position => scalar length => dynamic-slice cache writes
                # (no per-row scatter; partitioner-friendly on sharded caches)
                cache_len = cache_len[0]

            def base(tt, c):
                return model_mod.decode_step(p, cfg, tt["t"], c, tt["len"],
                                             moe_fn)

            return microbatched(base, n_micro)(
                {"t": tokens, "len": cache_len}, caches)

        args = (params_shape, bsh["tokens"], caches_shape, bsh["cache_len"])
        in_spec = (p_spec, b_spec["tokens"], c_spec, P())
        donate = (2,) if variant in ("donate_cache", "full_opt") else ()
        return serve_step, args, in_spec, donate

    if shape.kind == "prefill":
        def step(params, batch):
            return model_mod.prefill(adapt(params), cfg, batch,
                                     capacity=shape.seq_len, moe_fn=moe_fn)
        return step, (params_shape, bsh), (p_spec, b_spec), ()

    # train
    from repro.train.loop import make_train_step
    from repro.train.optimizer import OptConfig, init_opt_state
    assert not int8_weights, "int8 weights are a serving variant"
    opt_shape = jax.eval_shape(init_opt_state, params_shape)
    o_spec = type(opt_shape)(P(), jax.tree.map(lambda s: s, p_spec),
                             jax.tree.map(lambda s: s, p_spec))
    step = make_train_step(cfg, OptConfig(), moe_fn, n_micro=n_micro)
    return step, (params_shape, opt_shape, bsh), (p_spec, o_spec, b_spec), ()


def run_variant(arch: str, shape_name: str, variant: str,
                multi_pod: bool = False, save: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "variant": variant}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        from repro.core.parallel import set_current_mesh
        set_current_mesh(mesh)
        with mesh:
            step, args, in_spec, donate = build_variant(cfg, shape, mesh, variant)
            lowered = jax.jit(step, in_shardings=to_shardings(mesh, in_spec),
                              donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        coll = hlo.collective_bytes(compiled.as_text())
        args_b = float(getattr(mem, "argument_size_in_bytes", 0))
        if shape.kind == "train":
            struct = train_memory_bytes(cfg, shape, args_b, mesh.size)
        else:
            struct = (getattr(mem, "temp_size_in_bytes", 0) + args_b
                      + getattr(mem, "output_size_in_bytes", 0))
        cost = {"flops": analytic_flops(cfg, shape) / mesh.size}
        rl = hlo.roofline_terms(cost, coll, mesh.size, struct_bytes=float(struct))
        rec.update(status="ok", compile_s=round(time.time() - t0, 1),
                   argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
                   temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
                   flops_per_device=rl.flops,
                   collective_bytes_per_device=rl.coll_bytes,
                   collectives=coll,
                   compute_s=rl.compute_s, memory_s=rl.memory_s,
                   memory_hlo_s=rl.memory_hlo_s,
                   collective_s=rl.collective_s, dominant=rl.dominant)
        step_t = max(rl.compute_s, rl.memory_s) + rl.collective_s
        rec["step_s"] = step_t
        print(f"[OK] {arch}×{shape_name}×{variant}: step={step_t*1e3:.1f}ms "
              f"dom={rl.dominant} cmp={rl.compute_s*1e3:.1f} "
              f"mem={rl.memory_s*1e3:.1f} coll={rl.collective_s*1e3:.1f} "
              f"args={rec['argument_bytes']/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[ERR] {arch}×{shape_name}×{variant}: {rec['error'][:200]}")
    if save:
        os.makedirs(HC_DIR, exist_ok=True)
        with open(os.path.join(
                HC_DIR, f"{arch}__{shape_name}__{variant}.json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    run_variant(args.arch, args.shape, args.variant, args.multi_pod)


if __name__ == "__main__":
    main()

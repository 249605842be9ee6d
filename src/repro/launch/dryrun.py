import os
# 512 placeholder CPU devices; never the accelerator, which another process
# (the benchmark that started this one) may hold.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape × mesh) combination with ShapeDtypeStruct
stand-ins — no allocation — and extract memory / cost / collective analysis
for the roofline report (deliverable g).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape decode_32k --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --all            # full 40-pair sweep
"""
import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, get_config, get_shape, INPUT_SHAPES
from repro.configs.base import InputShape, ModelConfig
from repro.core.lep import make_lep_moe_fn, pick_lep_plan
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (batch_pspecs, cache_pspecs, dp_axes,
                                   param_pspecs, to_shardings)
from repro.models import model as model_mod
from repro.train.loop import make_train_step
from repro.train.optimizer import OptConfig, init_opt_state

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Applicability / skips (DESIGN.md §3)
# ---------------------------------------------------------------------------


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.kind == "decode" and not cfg.supports_decode:
        return "encoder-only: no autoregressive decode (DESIGN.md §3)"
    if shape.name == "long_500k" and not cfg.supports_long_context():
        return "full attention at 500k: no sub-quadratic path"
    return None


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins (weak-type-correct, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        if cfg.frontend == "audio_frames":
            batch = {"frames": jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)}
        elif cfg.frontend == "vision_patches":
            p = cfg.num_prefix_embeddings
            batch = {"prefix_emb": jax.ShapeDtypeStruct((b, p, cfg.d_model), jnp.bfloat16),
                     "tokens": jax.ShapeDtypeStruct((b, s - p), i32)}
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if shape.kind == "train":
            # labels align with text tokens (audio: per-frame targets)
            n_lbl = batch.get("tokens", batch.get("frames")).shape[1]
            batch["labels"] = jax.ShapeDtypeStruct((b, n_lbl), i32)
        return batch
    # decode: one token per request + KV cache of seq_len
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
            "cache_len": jax.ShapeDtypeStruct((b,), i32)}


def _moe_fn_for(cfg: ModelConfig, mesh, serving: bool):
    if not cfg.is_moe:
        return None
    plan = pick_lep_plan(cfg, mesh, serving=serving)
    return make_lep_moe_fn(mesh, plan["ep_axes"], redundancy=plan["redundancy"],
                           ffn_shard_axis=plan["ffn_shard_axis"], quantize=True)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def build_step(cfg: ModelConfig, shape: InputShape, mesh):
    """Returns (jitted_fn, arg_shape_structs, in_shardings) for the combo."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(
        functools.partial(model_mod.init_params, cfg=cfg),
        jax.random.PRNGKey(0))
    p_spec = param_pspecs(cfg, mesh, params_shape, train=(shape.kind == "train"))
    batch_shape = input_specs(cfg, shape)

    if shape.kind == "train":
        moe_fn = _moe_fn_for(cfg, mesh, serving=False)
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        o_spec = type(opt_shape)(jax.sharding.PartitionSpec(),
                                 jax.tree.map(lambda s: s, p_spec),
                                 jax.tree.map(lambda s: s, p_spec))
        b_spec = batch_pspecs(cfg, mesh, batch_shape)
        step = make_train_step(cfg, OptConfig(), moe_fn)
        args = (params_shape, opt_shape, batch_shape)
        in_spec = (p_spec, o_spec, b_spec)
        return step, args, in_spec

    if shape.kind == "prefill":
        moe_fn = _moe_fn_for(cfg, mesh, serving=True)

        def step(params, batch):
            logits, caches = model_mod.prefill(params, cfg, batch,
                                               capacity=shape.seq_len,
                                               moe_fn=moe_fn)
            return logits, caches

        b_spec = batch_pspecs(cfg, mesh, batch_shape)
        return step, (params_shape, batch_shape), (p_spec, b_spec)

    # decode: serve_step — ONE new token against a seq_len cache
    moe_fn = _moe_fn_for(cfg, mesh, serving=True)
    caches_shape = jax.eval_shape(
        lambda: model_mod.make_caches(cfg, shape.global_batch, shape.seq_len))
    c_spec = cache_pspecs(cfg, mesh, caches_shape)
    b_spec = batch_pspecs(cfg, mesh, input_specs(cfg, shape))

    def serve_step(params, tokens, caches, cache_len):
        return model_mod.decode_step(params, cfg, tokens, caches, cache_len,
                                     moe_fn)

    args = (params_shape, input_specs(cfg, shape)["tokens"], caches_shape,
            input_specs(cfg, shape)["cache_len"])
    in_spec = (p_spec, b_spec["tokens"], c_spec, jax.sharding.PartitionSpec())
    return serve_step, args, in_spec


# ---------------------------------------------------------------------------
# Analytic compute term
#
# XLA's HloCostAnalysis counts a rolled while-loop (lax.scan over layers /
# attention chunks) body ONCE, and fully unrolling 61-layer × 64-chunk graphs
# is intractable to compile on this 1-core container. The compute term is
# therefore computed analytically from the exact architecture math (linear
# layers from active params, EXECUTED attention pairs, SSD chunk algebra) and
# the HLO-reported FLOPs are recorded as a diagnostic. Memory (structural
# bytes) and collectives (loop-aware HLO parsing with trip-count multipliers)
# come from the real compiled artifact. See EXPERIMENTS.md §Methodology.
# ---------------------------------------------------------------------------


def analytic_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """Total (all-device) executed FLOPs for one step of this combo."""
    b, s = shape.global_batch, shape.seq_len
    decode = shape.kind == "decode"
    tokens = b if decode else b * s
    fwd_bwd = 3.0 if shape.kind == "train" else 1.0

    # Linear/matmul work: 2 FLOPs per active param per token (includes
    # attention projections, (active) experts, unembedding).
    total = 2.0 * cfg.param_count(active_only=True) * tokens

    # Attention core — EXECUTED pairs (the chunked baseline computes every
    # (q, kv) pair and masks; causal/window block-skipping is a §Perf
    # optimization, not part of the baseline).
    if cfg.num_heads > 0:
        n_attn = (cfg.num_layers // cfg.attn_every if cfg.is_hybrid
                  else cfg.num_layers)
        if decode:
            ring = bool(cfg.sliding_window) and s > cfg.sliding_window \
                and cfg.attention_kind != "mla"
            kv_len = cfg.sliding_window if ring else s
            pairs = float(b) * kv_len
        else:
            from repro.models.attention import _pick_chunk, block_skip_enabled
            if block_skip_enabled() and cfg.attention_kind != "bidirectional":
                chunk = _pick_chunk(s)
                if cfg.sliding_window and cfg.sliding_window < s:
                    pairs = float(b) * s * min(s, cfg.sliding_window + chunk)
                else:
                    pairs = float(b) * s * s / 2 * (1 + chunk / s)
            else:
                pairs = float(b) * s * s
        if cfg.attention_kind == "mla":
            if decode:  # absorbed: scores vs latent + pv in latent space
                per_pair = 2.0 * cfg.num_heads * (
                    2 * cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            else:       # unabsorbed MHA form
                per_pair = 2.0 * cfg.num_heads * (
                    cfg.qk_nope_head_dim + cfg.qk_rope_head_dim + cfg.v_head_dim)
        else:
            per_pair = 4.0 * cfg.num_heads * cfg.head_dim  # qk + pv
        total += n_attn * pairs * per_pair

    # SSD (mamba2 / zamba2)
    if cfg.ssm_state > 0:
        n_ssm = cfg.num_layers if cfg.is_ssm else \
            cfg.num_layers - cfg.num_layers // cfg.attn_every
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        if decode:
            total += n_ssm * 6.0 * b * h * p * n
        else:
            q = min(cfg.ssm_chunk, s)
            nc = max(1, s // q)
            per_chunk = (2.0 * b * q * q * n
                         + 2.0 * b * q * q * h * p
                         + 4.0 * b * q * h * p * n)
            total += n_ssm * per_chunk * nc
    return total * fwd_bwd


def train_memory_bytes(cfg: ModelConfig, shape: InputShape, args_bytes: float,
                       n_dev: int) -> float:
    """Per-device HBM traffic model for a train step: optimizer read+write
    of params/moments/grads (~2× argument bytes) + forward-write/backward-
    read of ~12 d_model-wide activations per layer per token."""
    tok_dev = shape.global_batch * shape.seq_len / n_dev
    act = cfg.num_layers * tok_dev * cfg.d_model * 2 * 12
    return 2.0 * args_bytes + act


def _measure(cfg, shape, mesh):
    step, args, in_spec = build_step(cfg, shape, mesh)
    shardings = to_shardings(mesh, in_spec)
    lowered = jax.jit(step, in_shardings=shardings).lower(*args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = hlo.collective_bytes(compiled.as_text())
    struct = (getattr(mem, "temp_size_in_bytes", 0)
              + getattr(mem, "argument_size_in_bytes", 0)
              + getattr(mem, "output_size_in_bytes", 0))
    return dict(mem=mem, flops=float(cost.get("flops", 0.0)),
                hbm=float(cost.get("bytes accessed", 0.0)),
                coll=coll,
                coll_total=float(sum(coll[k] for k in hlo.COLLECTIVE_OPS)),
                struct=float(struct))


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            save: bool = True, verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name, "mesh": mesh_name}

    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        if verbose:
            print(f"[SKIP] {arch} × {shape_name} × {mesh_name}: {reason}")
        _save(rec, save)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh.size
        with mesh:
            real = _measure(cfg, shape, mesh)
            t_compile = time.time() - t0
            t_lower = 0.0
        mem, coll = real["mem"], real["coll"]
        args_b = float(getattr(mem, "argument_size_in_bytes", 0))
        if shape.kind == "train":
            struct = train_memory_bytes(cfg, shape, args_b, n_dev)
        else:
            struct = real["struct"]
        # compute term: analytic executed FLOPs (see module comment);
        # HLO flops recorded as a diagnostic (loop bodies counted once).
        flops_dev = analytic_flops(cfg, shape) / n_dev
        cost = {"flops": flops_dev, "bytes accessed": real["hbm"]}

        n_tok = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        mf = hlo.model_flops(cfg, n_tok, shape.kind)
        rl = hlo.roofline_terms(cost, coll, n_dev, model_flops_total=mf,
                                struct_bytes=float(struct))
        rec["hlo_flops_per_device"] = real["flops"]

        rec.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            n_devices=n_dev,
            bytes_per_device=int(getattr(mem, "temp_size_in_bytes", 0)
                                 + getattr(mem, "argument_size_in_bytes", 0)
                                 + getattr(mem, "output_size_in_bytes", 0)),
            temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
            argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            flops_per_device=rl.flops,
            hbm_bytes_per_device=rl.hbm_bytes,
            struct_bytes_per_device=rl.struct_bytes,
            collective_bytes_per_device=rl.coll_bytes,
            collectives=coll,
            compute_s=rl.compute_s, memory_s=rl.memory_s,
            memory_hlo_s=rl.memory_hlo_s,
            collective_s=rl.collective_s, dominant=rl.dominant,
            model_flops_per_device=rl.model_flops,
            useful_ratio=rl.useful_ratio,
        )
        if verbose:
            print(f"[OK]   {arch} × {shape_name} × {mesh_name}: "
                  f"dom={rl.dominant} compute={rl.compute_s*1e3:.1f}ms "
                  f"mem={rl.memory_s*1e3:.1f}ms coll={rl.collective_s*1e3:.1f}ms "
                  f"args={rec['argument_bytes']/2**30:.2f}GiB/dev "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[ERR]  {arch} × {shape_name} × {mesh_name}: {rec['error']}")
    _save(rec, save)
    return rec


def _save(rec: Dict[str, Any], save: bool) -> None:
    if not save:
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    fn = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(OUT_DIR, fn), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--include-paper-arch", action="store_true",
                    help="also run deepseek-r1 (the paper's own model)")
    args = ap.parse_args()

    if args.all:
        archs = list(ASSIGNED_ARCHS)
        if args.include_paper_arch:
            archs.append("deepseek-r1")
        for arch in archs:
            for shape in INPUT_SHAPES:
                run_one(arch, shape, multi_pod=args.multi_pod)
        return
    assert args.arch and args.shape, "--arch and --shape (or --all)"
    run_one(args.arch, args.shape, multi_pod=args.multi_pod)


if __name__ == "__main__":
    main()

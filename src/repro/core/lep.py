"""Large-scale Expert Parallelism (LEP) — paper §4.2, the core contribution.

Maps the paper's FusedDispatch / FusedCombine onto TPU-native constructs:

* **Static pre-allocated buffers** (paper Eq. 1–2): the capacity-bounded
  (slots, C, D) dispatch buffer is a static shape — XLA requires this anyway,
  making the paper's "static execution" the natural design point.
* **Early INT8 quantization** (Opt. ②): the dispatch payload is quantized to
  int8 + per-slot fp32 scale *before* the all_to_all, cutting collective
  bytes ~2× vs BF16. Combine returns unquantized BF16 (paper Fig. 12).
* **AIV-direct writes** (Opt. ①) have no public-XLA analogue; the latency
  insight is realized by fusing quantize+pack into the dispatch producer
  (kernels/dispatch_quant) and exposing independent microbatch streams for
  collective/compute overlap (core/microbatch.py). See DESIGN.md §5.2.
* **EPLB redundancy** (paper: 32 redundant router experts): optional
  ``redundancy=r`` replicates each expert r× so slots fill the mesh exactly
  (e.g. olmoe's 64 experts × 4 = 256 slots = one slot per die on a 256-chip
  pod — the paper's "one expert per NPU die" EP320 configuration).

Sharding modes
--------------
Tokens are always sharded over *all* mesh axes (the paper's DP-attention +
EP-MoE over the same dies). ``ep_axes`` selects the EP domain:

* ``("data","model")`` — full-mesh EP (paper-faithful LEP; requires
  E·r % n_devices == 0). DeepSeek-R1's 256 experts on a 256-die pod give
  exactly one expert per die.
* ``("model",)`` — EP over the model axis, experts replicated over data
  (small MoEs like olmoe in training), or FFN-sharded over data with ZeRO-3
  style weight all-gather (``ffn_shard_axis="data"``, required for the
  1T-param kimi-k2 to fit HBM; see DESIGN.md §4).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import moe as moe_mod
from repro.models.layers import swiglu


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def lep_capacity(t_loc: int, k: int, slots: int, factor: float,
                 align: int = 8) -> int:
    """Static buffer depth per (slot, source-rank) — paper Eq. 2.
    ``align`` pads to TPU sublanes; decode paths may use align=1 (the
    8-floor causes up to 8× over-dispatch when t_loc·k/slots ≈ 1)."""
    cap = _cdiv(int(t_loc * k * factor), slots) + 1
    return max(align, ((cap + align - 1) // align) * align)


def _quantize_rows(x: jax.Array, use_kernel: bool) -> Tuple[jax.Array, jax.Array]:
    """Per-row int8 quantization (early quantization, paper Opt. ②)."""
    if use_kernel:
        from repro.kernels.dispatch_quant.ops import dispatch_quantize
        shp = x.shape
        q, s = dispatch_quantize(x.reshape(-1, shp[-1]))
        return q.reshape(shp), s.reshape(shp[:-1] + (1,))
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def make_lep_moe_fn(
    mesh: Mesh,
    ep_axes: Tuple[str, ...] = ("model",),
    *,
    quantize: bool = True,
    redundancy: int = 1,
    ffn_shard_axis: Optional[str] = None,
    ffn_gather: str = "weights",     # "weights" (ZeRO-3) | "tokens"
    quantize_gather: bool = False,   # int8 payload for the token all-gather
    capacity_factor: Optional[float] = None,
    capacity_align: int = 8,
    use_quant_kernel: bool = False,
    naive: bool = False,
    pack_scales: bool = True,
):
    """Build a MoeFn executing routed experts with shard_map LEP.

    ``naive=True`` reproduces the paper's Fig. 10a baseline: BF16 payloads
    (no early quantization) plus an explicit routing-metadata all_to_all —
    the configuration FusedDispatch/FusedCombine improve upon.

    ``pack_scales`` (default on) rides the per-row fp32 dequant scale inside
    the int8 dispatch payload (bitcast to 4 trailing int8 lanes), so the
    quantized dispatch hop issues exactly ONE all_to_all — the paper's
    FusedDispatch "one collective per hop" property. ``pack_scales=False``
    keeps the two-collective (payload + scales) baseline for comparison.
    """
    mesh_axes = tuple(mesh.axis_names)
    n_dev = math.prod(mesh.shape[a] for a in mesh_axes)
    ep_total = math.prod(mesh.shape[a] for a in ep_axes)
    if naive:
        quantize = False

    def moe_fn(p: dict, x: jax.Array, cfg: ModelConfig
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        if not cfg.holds_every_expert:
            raise ValueError(
                f"{cfg.name}: LEP spreads all of the router's experts over "
                f"its mesh; this config holds {cfg.num_experts} of "
                f"{cfg.router_width}")
        t, d = x.shape
        e, k = cfg.num_experts, cfg.num_experts_per_tok
        r = redundancy
        slots = e * r
        assert slots % ep_total == 0, (
            f"experts*redundancy ({slots}) must divide over EP domain "
            f"({ep_total}); adjust ep_axes or redundancy")
        slots_loc = slots // ep_total
        factor = capacity_factor or cfg.capacity_factor

        # Pad tokens to the device count so every rank gets equal rows.
        t_pad = _cdiv(t, n_dev) * n_dev
        x_pad = jnp.pad(x, ((0, t_pad - t), (0, 0)))
        valid = (jnp.arange(t_pad, dtype=jnp.int32) < t)
        t_loc = t_pad // n_dev
        cap = lep_capacity(t_loc, k, slots, factor, capacity_align)

        # Expert weights: slot-replicated layout when redundancy > 1.
        wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
        if r > 1:
            rep = lambda w: jnp.repeat(w, r, axis=0)
            wg, wu, wd = rep(wg), rep(wu), rep(wd)

        tok_spec = P(mesh_axes)           # flat token dim over every axis
        w_spec = P(ep_axes, None, ffn_shard_axis)
        wd_spec = P(ep_axes, ffn_shard_axis, None)

        def body(x_loc, valid_loc, router_w, wg_l, wu_l, wd_l, *bias):
            tl = x_loc.shape[0]
            top_i, top_p, aux = moe_mod.route(router_w, x_loc, cfg, *bias)
            # Padded rows: spread over experts, zero combine weight.
            row = jnp.arange(tl, dtype=jnp.int32)
            spread = (row[:, None] * k + jnp.arange(k)[None, :]) % e
            top_i = jnp.where(valid_loc[:, None], top_i, spread)
            top_p = jnp.where(valid_loc[:, None], top_p, 0.0)

            # Redundancy: replica chosen by token index (EPLB load spread).
            slot_ids = top_i * r + (row[:, None] % r) if r > 1 else top_i

            meta_term = 0.0
            if naive:
                # Fig. 10a baseline: explicit metadata all_to_all first.
                counts = jnp.sum(
                    jax.nn.one_hot(slot_ids, slots, dtype=jnp.int32), axis=(0, 1))
                counts = counts.reshape(ep_total, slots_loc)
                counts_recv = jax.lax.all_to_all(counts, ep_axes, 0, 0)
                # keep the collective live (mirrors the real data dependency
                # of Fig. 10a's metadata exchange on the dispatch step)
                meta_term = jnp.sum(counts_recv).astype(jnp.float32) * 0.0

            # --- FusedDispatch: pack into the static (slots, C, D) buffer ---
            slot_pos, in_cap = moe_mod.dispatch_indices(slot_ids, slots, cap)
            flat_slot = slot_ids.reshape(-1)
            flat_pos = jnp.where(in_cap.reshape(-1), slot_pos.reshape(-1), cap - 1)
            tok_of = jnp.repeat(jnp.arange(tl), k)
            contrib = jnp.where(in_cap.reshape(-1)[:, None], x_loc[tok_of], 0)
            buf = jnp.zeros((slots, cap, d), x_loc.dtype)
            buf = buf.at[flat_slot, flat_pos].add(contrib)

            if quantize:   # early quantization BEFORE the collective
                q, scale = _quantize_rows(buf, use_quant_kernel)
                if pack_scales:
                    # Single-collective dispatch: bitcast each row's fp32
                    # scale to 4 int8 lanes riding at the payload tail, so
                    # the hop is ONE all_to_all instead of payload + scales.
                    sb = jax.lax.bitcast_convert_type(scale, jnp.int8)
                    packed = jnp.concatenate(
                        [q, sb.reshape(slots, cap, 4)], axis=-1)
                    p4 = packed.reshape(ep_total, slots_loc, cap, d + 4)
                    p_recv = jax.lax.all_to_all(p4, ep_axes, 0, 0)
                    q_recv = p_recv[..., :d]
                    s_recv = jax.lax.bitcast_convert_type(
                        p_recv[..., d:].reshape(ep_total, slots_loc, cap, 1, 4),
                        jnp.float32)
                else:
                    q4 = q.reshape(ep_total, slots_loc, cap, d)
                    s4 = scale.reshape(ep_total, slots_loc, cap, 1)
                    q_recv = jax.lax.all_to_all(q4, ep_axes, 0, 0)
                    s_recv = jax.lax.all_to_all(s4, ep_axes, 0, 0)
                recv = q_recv.astype(jnp.float32) * s_recv
                recv = recv.astype(x_loc.dtype)
            else:
                buf4 = buf.reshape(ep_total, slots_loc, cap, d)
                recv = jax.lax.all_to_all(buf4, ep_axes, 0, 0)
            # (ep, slots_loc, C, D) -> (slots_loc, ep*C, D)
            tokens = jnp.moveaxis(recv, 0, 1).reshape(slots_loc, ep_total * cap, d)

            # --- Expert FFN over local slots ---
            if ffn_shard_axis and ffn_gather == "tokens":
                # Beyond-paper (decode-optimized 2-level EP): keep the FFN
                # dim sharded, all-gather the (small) token buffer over the
                # shard axis, compute partial-F FFN, and psum-scatter the
                # partial sums back to token owners. For decode this moves
                # ~2×tokens·D instead of 2×(3·E_loc·D·F) per layer.
                if quantize_gather:
                    # early quantization applied to the second hop too
                    tq, tscale = _quantize_rows(tokens, use_quant_kernel)
                    tq_g = jax.lax.all_gather(tq, ffn_shard_axis, axis=1,
                                              tiled=True)
                    ts_g = jax.lax.all_gather(tscale, ffn_shard_axis, axis=1,
                                              tiled=True)
                    tok_g = (tq_g.astype(jnp.float32) * ts_g).astype(tokens.dtype)
                else:
                    tok_g = jax.lax.all_gather(tokens, ffn_shard_axis, axis=1,
                                               tiled=True)
                g = jnp.einsum("scd,sdf->scf", tok_g, wg_l)
                u = jnp.einsum("scd,sdf->scf", tok_g, wu_l)
                y_part = jnp.einsum("scf,sfd->scd", jax.nn.silu(g) * u, wd_l)
                y = jax.lax.psum_scatter(y_part, ffn_shard_axis,
                                         scatter_dimension=1, tiled=True)
            else:
                if ffn_shard_axis:
                    # ZeRO-3-style: gather the FFN shard of the weights.
                    wg_f = jax.lax.all_gather(wg_l, ffn_shard_axis, axis=2, tiled=True)
                    wu_f = jax.lax.all_gather(wu_l, ffn_shard_axis, axis=2, tiled=True)
                    wd_f = jax.lax.all_gather(wd_l, ffn_shard_axis, axis=1, tiled=True)
                else:
                    wg_f, wu_f, wd_f = wg_l, wu_l, wd_l
                g = jnp.einsum("scd,sdf->scf", tokens, wg_f)
                u = jnp.einsum("scd,sdf->scf", tokens, wu_f)
                y = jnp.einsum("scf,sfd->scd", jax.nn.silu(g) * u, wd_f)

            # --- FusedCombine: BF16 payload back to source ranks ---
            y4 = jnp.moveaxis(y.reshape(slots_loc, ep_total, cap, d), 1, 0)
            y_back = jax.lax.all_to_all(y4, ep_axes, 0, 0)     # (ep, slots_loc, C, D)
            y_flat = y_back.reshape(slots, cap, d)

            gathered = y_flat[flat_slot, flat_pos]
            gathered = jnp.where(in_cap.reshape(-1)[:, None], gathered, 0)
            weighted = gathered.astype(jnp.float32) * top_p.reshape(-1)[:, None]
            out = jnp.zeros((tl, d), jnp.float32).at[tok_of].add(weighted)
            out = out + meta_term

            aux = jax.lax.pmean(aux, mesh_axes)
            dropped = jax.lax.psum(jnp.sum(~in_cap), mesh_axes)
            return out.astype(x_loc.dtype), aux, dropped

        # The sigmoid router's selection bias, replicated, where it has one.
        bias = tuple(p[n] for n in ("e_score_correction_bias",) if n in p)
        routed, aux, dropped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(tok_spec, P(mesh_axes), P(), w_spec, w_spec, wd_spec)
            + (P(),) * len(bias),
            out_specs=(tok_spec, P(), P()),
            check_vma=False,
        )(x_pad, valid, p["router"], wg, wu, wd, *bias)
        routed = routed[:t]

        # Shared experts: dense, partitioned by the XLA SPMD partitioner
        # (weights F-sharded over "model" via param specs; see sharding.py).
        if "shared_gate" in p:
            routed = routed + swiglu(x, p["shared_gate"], p["shared_up"],
                                     p["shared_down"]).astype(routed.dtype)
        return routed, {"aux_loss": aux, "dropped": dropped}

    return moe_fn


def pick_lep_plan(cfg: ModelConfig, mesh: Mesh, serving: bool = False) -> dict:
    """Choose EP domain / redundancy / FFN sharding for an arch on a mesh.

    Paper-faithful order of preference:
      1. full-mesh EP, one(+) expert per die (the paper's LEP, §4.2)
      2. full-mesh EP via EPLB redundancy (serving only, paper's 32-redundant)
      3. model-axis EP (+ FFN sharding over data when weights cannot be
         replicated — the kimi-k2 1T case)
    """
    axes = tuple(a for a in mesh.axis_names if a != "pod")
    full = tuple(a for a in axes)                      # ("data","model")
    n_full = math.prod(mesh.shape[a] for a in full)
    e = cfg.num_experts
    if e % n_full == 0:
        return dict(ep_axes=full, redundancy=1, ffn_shard_axis=None)
    if serving and n_full % e == 0:
        return dict(ep_axes=full, redundancy=n_full // e, ffn_shard_axis=None)
    # model-axis EP; decide if expert weights fit replicated over data.
    n_model = mesh.shape["model"]
    bytes_per_dev = (cfg.num_layers - cfg.first_k_dense) * (e / n_model) \
        * 3 * cfg.d_model * cfg.d_ff * 2
    ffn_shard = "data" if bytes_per_dev > 4e9 else None
    return dict(ep_axes=("model",), redundancy=1, ffn_shard_axis=ffn_shard)

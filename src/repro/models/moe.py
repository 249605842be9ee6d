"""MoE layer: top-k router, shared experts, and two reference executions.

* ``moe_reference`` — dense all-experts compute (exact, O(T·E) FLOPs); the
  oracle for everything else.
* ``moe_capacity`` — static capacity-bounded gather→expert→scatter, the
  single-device semantics of the paper's FusedDispatch/FusedCombine static
  pre-allocated buffers (paper Eq. 1–2). ``core/lep.py`` wraps this with
  shard_map + all_to_all (+ early INT8 quantization) for large-scale EP.

Two routers (``cfg.router_score``): OLMoE's softmax → top-k → renormalize,
with a Switch-style load-balance auxiliary loss (the serving-side analogue of
the paper's EPLB is in core/lep.py via redundant expert replicas); and
DeepSeek-V3's ``noaux_tc``: sigmoid scores, experts chosen on score plus a
per-expert selection bias within the best groups, weighted by the scores.

An expert layer may hold only some of the router's experts, as one device of
an expert-parallel deployment does: ``moe_capacity`` routes over all
``cfg.router_width`` experts and computes the part of the result that the
held experts ``[first_held_expert, first_held_expert + num_experts)`` give,
plus the shared expert. With every expert held it is the whole layer.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, swiglu


def init_moe_params(key, cfg: ModelConfig, n_layers: int, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 7)
    p = {
        "ln": jnp.ones((n_layers, d), dtype),
        "router": dense_init(ks[0], (n_layers, d, cfg.router_width),
                             jnp.float32),
        "w_gate": dense_init(ks[1], (n_layers, e, d, f), dtype),
        "w_up": dense_init(ks[2], (n_layers, e, d, f), dtype),
        "w_down": dense_init(ks[3], (n_layers, e, f, d), dtype),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared_gate"] = dense_init(ks[4], (n_layers, d, fs), dtype)
        p["shared_up"] = dense_init(ks[5], (n_layers, d, fs), dtype)
        p["shared_down"] = dense_init(ks[6], (n_layers, fs, d), dtype)
    if cfg.router_score == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((n_layers, cfg.router_width),
                                                 jnp.float32)
    return p


def route(router_w: jax.Array, x: jax.Array, cfg: ModelConfig,
          bias: jax.Array | None = None
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, D) -> (top-k ids (T,K) over the router's experts, their
    weights (T,K), aux loss). ``bias`` is the sigmoid router's per-expert
    selection bias (``e_score_correction_bias``)."""
    with jax.named_scope("route"):
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w)
        if cfg.router_score == "sigmoid":
            return _route_noaux_tc(logits, cfg, bias)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.num_experts_per_tok)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        # Switch-style load-balance loss: E * sum_e f_e * P_e
        e = logits.shape[-1]
        frac = jnp.mean(
            jnp.sum(jax.nn.one_hot(top_i, e, dtype=jnp.float32), axis=1), axis=0)
        mean_p = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * mean_p)
        return top_i, top_p, aux


def _route_noaux_tc(logits: jax.Array, cfg: ModelConfig,
                    bias: jax.Array | None):
    """DeepSeek-V3's published ``noaux_tc`` gate: choose on sigmoid score
    plus bias; a group scores the sum of its best two; the best
    ``topk_group`` groups are kept and the top-k experts taken inside them;
    the weights are the chosen experts' scores without the bias,
    renormalised, times ``routed_scaling``. Balance comes from the bias, so
    there is no auxiliary loss."""
    t, e = logits.shape
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias
    g = cfg.n_group
    top2 = jax.lax.top_k(choice.reshape(t, g, e // g), 2)[0]
    _, groups = jax.lax.top_k(jnp.sum(top2, axis=-1), cfg.topk_group)
    kept = jnp.sum(jax.nn.one_hot(groups, g, dtype=jnp.int32), axis=1) > 0
    choice = jnp.where(jnp.repeat(kept, e // g, axis=1), choice, -jnp.inf)
    _, top_i = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    top_p = jnp.take_along_axis(scores, top_i, axis=1)
    top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    return top_i, top_p * cfg.routed_scaling, jnp.zeros((), jnp.float32)


def held_routes(top_i: jax.Array, top_p: jax.Array, cfg: ModelConfig):
    """The routings onto the experts held here: (local expert ids, 0 where
    not held; weights, 0 where not held; held mask), all (T, K)."""
    local = top_i - cfg.first_held_expert
    held = (local >= 0) & (local < cfg.num_experts)
    return jnp.where(held, local, 0), jnp.where(held, top_p, 0.0), held


def _shared_out(p: dict, x: jax.Array) -> jax.Array:
    if "shared_gate" not in p:
        return jnp.zeros_like(x)
    return swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])


def moe_reference(p: dict, x: jax.Array, cfg: ModelConfig
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dense all-experts oracle. x: (T, D)."""
    top_i, top_p, aux = route(p["router"], x, cfg,
                              p.get("e_score_correction_bias"))
    if not cfg.holds_every_expert:
        top_i, top_p, _ = held_routes(top_i, top_p, cfg)
    g = jnp.einsum("td,edf->tef", x, p["w_gate"])
    u = jnp.einsum("td,edf->tef", x, p["w_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, p["w_down"])
    w = jnp.sum(
        jax.nn.one_hot(top_i, cfg.num_experts, dtype=jnp.float32)
        * top_p[..., None], axis=1)                                # (T, E)
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), w).astype(x.dtype)
    return out + _shared_out(p, x), {"aux_loss": aux}


def capacity_for(cfg: ModelConfig, n_tokens: int, ep_degree: int = 1) -> int:
    """Static buffer depth per expert — the paper's max_tokens (Eq. 2)."""
    per = n_tokens * cfg.num_experts_per_tok / max(cfg.num_experts, 1)
    cap = int(per * cfg.capacity_factor) + 1
    return max(8, ((cap + 7) // 8) * 8)  # 8-aligned for TPU sublanes


def dispatch_indices(top_i: jax.Array, num_experts: int, capacity: int,
                     held: jax.Array | None = None):
    """Compute scatter locations for capacity-bounded dispatch.

    top_i: (T, K). Returns (expert_slot (T,K), valid (T,K)) where expert_slot
    is the position within the expert's capacity buffer. Routings where
    ``held`` (T, K) is False take no slot and are not valid.
    """
    t, k = top_i.shape
    flat_e = top_i.reshape(-1)                                     # (T*K,)
    # Stable ordering: tokens keep arrival order within an expert, matching
    # the paper's deterministic pre-allocated buffer offsets.
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)  # (TK, E)
    if held is not None:
        onehot = onehot * held.reshape(-1, 1).astype(jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1                      # running count
    slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    valid = slot < capacity
    if held is not None:
        valid &= held.reshape(-1)
    return slot.reshape(t, k), valid.reshape(t, k)


def moe_capacity(p: dict, x: jax.Array, cfg: ModelConfig,
                 capacity: int | None = None
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Capacity-bounded gather→expert→scatter (single-device FusedDispatch)
    over the experts held here. ``aux["held"]`` (T,) counts each token's
    routings onto held experts."""
    t, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cap = capacity or capacity_for(cfg, t)
    top_i, top_p, aux = route(p["router"], x, cfg,
                              p.get("e_score_correction_bias"))
    with jax.named_scope("experts"):
        if cfg.holds_every_expert:
            held = None
            n_held = jnp.full((t,), k, jnp.int32)
        else:
            top_i, top_p, held = held_routes(top_i, top_p, cfg)
            n_held = jnp.sum(held, axis=1, dtype=jnp.int32)
        slot, valid = dispatch_indices(top_i, e, cap, held)

        # Scatter tokens into the (E, C, D) buffer ("FusedDispatch").
        buf = jnp.zeros((e, cap, d), x.dtype)
        flat_e, flat_s = top_i.reshape(-1), slot.reshape(-1)
        flat_v = valid.reshape(-1)
        tok_ids = jnp.repeat(jnp.arange(t), k)
        safe_s = jnp.where(flat_v, flat_s, cap - 1)  # clamp; invalid contributions zeroed
        contrib = jnp.where(flat_v[:, None], x[tok_ids], 0)
        buf = buf.at[flat_e, safe_s].add(contrib)

        # Expert FFN over the static buffer.
        g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])

        # Gather back + weighted combine ("FusedCombine").
        gathered = y[flat_e, safe_s]                                  # (T*K, D)
        gathered = jnp.where(flat_v[:, None], gathered, 0)
        weighted = gathered.astype(jnp.float32) * top_p.reshape(-1)[:, None]
        out = jnp.zeros((t, d), jnp.float32).at[tok_ids].add(weighted).astype(x.dtype)

        dropped = jnp.sum(~flat_v) if held is None else jnp.sum(held & ~valid)
        return out + _shared_out(p, x), {"aux_loss": aux, "dropped": dropped,
                                         "held": n_held}

"""Plain reference of DeepSeek-V3 on one chip of an expert-parallel
deployment, in float32, layer by layer (the configuration file's
``reference`` key). It imports nothing of the program; weights are drawn
again from the seed through ``bench/weights.py``, one layer at a time.

Layers ``0 .. first_k_dense_replace - 1`` are dense, the rest MoE::

    h = rmsnorm(x) * ln
    q = (rmsnorm(h @ wq_a) * q_ln) @ wq_b          -> q_nope, q_rope per head
    c, k_rope = h @ wkv_a                          (latent, shared rope key)
    c = rmsnorm(c) * kv_ln
    k_nope, v = c @ wk_b, c @ wv_b                 (per head)
    q_rope, k_rope = yarn_rope(q_rope), yarn_rope(k_rope)
    x = x + softmax((q_nope k_nope + q_rope k_rope) * scale, causal) v @ wo
    h = rmsnorm(x) * ln
    x = x + swiglu(h)                              (dense, intermediate_size)
    x = x + sum_{k: e_k held} w_k swiglu_{e_k}(h) + swiglu_shared(h)

with ``scale = mscale(factor, mscale_all_dim)**2 / sqrt(nope + rope)`` and
``mscale(f, m) = 0.1 m ln f + 1``. ``yarn_rope`` is DeepSeek-V3's
``DeepseekV3YarnRotaryEmbedding`` (arXiv:2309.00071, ``rope_scaling``):
inverse frequencies ``extra = theta ** (-2i / dim)`` and ``extra / factor``
joined by a linear ramp over ``i`` between the correction dimensions of
``beta_fast`` and ``beta_slow`` rotations over ``original_max_position_
embeddings``, cos and sin scaled by ``mscale(f, mscale) / mscale(f,
mscale_all_dim)``. The router is the published ``noaux_tc``: ``s =
sigmoid(h @ router)``; on ``s + e_score_correction_bias`` a group of
``n_experts / n_group`` scores the sum of its best two, the best
``topk_group`` groups are kept, the top ``num_experts_per_tok`` experts are
taken inside them; ``w = s`` of those, divided by their sum where
``norm_topk_prob``, times ``routed_scaling_factor``. The router spans
``program.fields.router_experts`` experts (the published 256); this chip
holds ``n_routed_experts`` of them from ``first_held_expert`` on, and only
their part is summed, as the program sums it: what the absent experts add is
left out here too. Dropless. Then ``logits = rmsnorm(x) * final_norm @
lm_head``. Every matrix product runs at "highest" precision.

Departures from the published model, as the program has them: rope pairs are
the two halves of the rope columns (the published interleaving is a fixed
permutation of the rope columns of ``wq_b`` and ``wkv_a``, which random
draws do not see); no MTP layer. ``precision="fp8"`` rounds both operands of
every weight product (router and head included) as ``bench/reference.py``.
"""
from __future__ import annotations

import functools
import math
import types
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W

#: the program's segment names for the dense and the MoE layers
SEGMENTS = ("dense_lead", "moe")
#: query rows per block of attention (scores of one block: N x heads x
#: QUERY_ROWS x T values)
QUERY_ROWS = 128


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """``DeepseekV3YarnRotaryEmbedding``'s inverse frequencies."""
    base = theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]

    def corr_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(x, pos, s):
    """x: (N, S, H, rope); rotate-half over YaRN's frequencies."""
    ang = pos.astype(jnp.float32)[:, None] * s.inv_freq
    cos = jnp.cos(ang)[:, None, :] * s.rope_mscale
    sin = jnp.sin(ang)[:, None, :] * s.rope_mscale
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, s, fp8):
    n, t, _ = h.shape
    nope, rope = s.nope, s.rope
    q = R._mm(R._rms(R._mm(h, p["wq_a"], fp8), p["q_ln"], s.eps),
              p["wq_b"], fp8).reshape(n, t, s.heads, nope + rope)
    kv = R._mm(h, p["wkv_a"], fp8)
    c = R._rms(kv[..., :s.kv_rank], p["kv_ln"], s.eps)
    k_nope = R._mm(c, p["wk_b"], fp8).reshape(n, t, s.heads, nope)
    v = R._mm(c, p["wv_b"], fp8).reshape(n, t, s.heads, s.v_dim)
    pos = jnp.arange(t)
    q_rope = _rope(q[..., nope:], pos, s)
    k_rope = _rope(kv[..., s.kv_rank:][:, :, None, :], pos, s)[:, :, 0]
    outs = []
    for q0 in range(0, t, QUERY_ROWS):
        q1 = min(q0 + QUERY_ROWS, t)
        scores = (jnp.einsum("nqhe,nthe->nhqt", q[:, q0:q1, :, :nope], k_nope)
                  + jnp.einsum("nqhe,nte->nhqt", q_rope[:, q0:q1], k_rope))
        scores = scores * s.scale
        causal = pos[None, :] <= pos[q0:q1, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        outs.append(jnp.einsum("nhqt,nthe->nqhe",
                               jax.nn.softmax(scores, -1), v))
    o = jnp.concatenate(outs, axis=1)
    return R._mm(o.reshape(n, t, s.heads * s.v_dim), p["wo"], fp8)


def route(scores_in, bias, s):
    """The published ``noaux_tc`` choice over all of the router's experts:
    (expert ids (T, K), weights (T, K))."""
    scores = jax.nn.sigmoid(scores_in)
    choice = scores + bias
    t, e = scores.shape
    per = e // s.n_group
    grouped = jnp.sort(choice.reshape(t, s.n_group, per), axis=-1)
    group_score = grouped[..., -1] + grouped[..., -2]
    _, best = jax.lax.top_k(group_score, s.topk_group)
    keep = jnp.zeros((t, s.n_group), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    choice = jnp.where(jnp.repeat(keep, per, axis=1), choice, -jnp.inf)
    _, ids = jax.lax.top_k(choice, s.top_k)
    w = jnp.take_along_axis(scores, ids, axis=1)
    if s.norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return ids, w * s.scaling


def _moe(p, h, s, fp8):
    """The held experts' part, dropless."""
    shape = h.shape
    x = h.reshape(-1, s.d)
    rows = x.shape[0]
    pad = (-rows) % R.MOE_ROWS
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, R.MOE_ROWS, s.d)

    def block(xb):
        ids, w = route(R._mm(xb, p["router"], fp8),
                       p["e_score_correction_bias"], s)
        local = ids - s.first_held
        wts = jnp.einsum("tke,tk->te", jax.nn.one_hot(local, s.held), w)
        wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
        if fp8:
            xb_q = R._quant(xb, -1)
            wg, wu, wd = R._quant(wg, 1), R._quant(wu, 1), R._quant(wd, 1)
        else:
            xb_q = xb
        a = jax.nn.silu(jnp.einsum("td,edf->tef", xb_q, wg)) \
            * jnp.einsum("td,edf->tef", xb_q, wu)
        if fp8:
            a = R._quant(a, -1)
        y = jnp.einsum("tef,efd->ted", a, wd)
        return jnp.einsum("ted,te->td", y, wts)

    out = jax.lax.map(block, x).reshape(-1, s.d)[:rows]
    return out.reshape(shape)


def _layer(p, x, s, fp8):
    x = x + _attention(p["attn"], R._rms(x, p["attn"]["ln"], s.eps), s, fp8)
    if "mlp" in p:
        m = p["mlp"]
        h = R._rms(x, m["ln"], s.eps)
        return x + R._swiglu(h, m["w_gate"], m["w_up"], m["w_down"], fp8)
    m = p["moe"]
    h = R._rms(x, m["ln"], s.eps)
    shared = R._swiglu(h, m["shared_gate"], m["shared_up"],
                       m["shared_down"], fp8)
    return x + _moe(m, h, s, fp8) + shared


def shape_of(conf: dict) -> types.SimpleNamespace:
    """The sizes and constants the reference needs, from the file."""
    fields = conf["program"].get("fields", {})
    rs = conf.get("rope_scaling") or {"factor": 1}
    rope = conf["qk_rope_head_dim"]
    factor = rs["factor"]
    if factor > 1:
        inv_freq = yarn_inv_freq(rope, conf["rope_theta"], rs)
        rope_mscale = mscale(factor, rs["mscale"]) \
            / mscale(factor, rs["mscale_all_dim"])
        attn_mscale = mscale(factor, rs["mscale_all_dim"]) ** 2
    else:
        inv_freq = (1.0 / conf["rope_theta"] ** (
            np.arange(0, rope, 2, dtype=np.float64) / rope)).astype(np.float32)
        rope_mscale, attn_mscale = 1.0, 1.0
    return types.SimpleNamespace(
        d=conf["hidden_size"], heads=conf["num_attention_heads"],
        nope=conf["qk_nope_head_dim"], rope=rope,
        v_dim=conf["v_head_dim"], kv_rank=conf["kv_lora_rank"],
        eps=conf["rms_norm_eps"], inv_freq=inv_freq, rope_mscale=rope_mscale,
        scale=attn_mscale / math.sqrt(conf["qk_nope_head_dim"] + rope),
        held=conf["n_routed_experts"],
        first_held=fields.get("first_held_expert", 0),
        top_k=conf["num_experts_per_tok"], n_group=conf["n_group"],
        topk_group=conf["topk_group"], norm_topk=conf["norm_topk_prob"],
        scaling=conf["routed_scaling_factor"])


class Reference:
    """``Reference(conf, specs).logits(seed, tokens, rows, precision)``."""

    def __init__(self, conf: dict, specs: Sequence[Tuple[str, tuple, object]]):
        self.s = shape_of(conf)
        self.layers = [(SEGMENTS[0], l)
                       for l in range(conf["first_k_dense_replace"])]
        self.layers += [(SEGMENTS[1], l) for l in range(
            conf["num_hidden_layers"] - conf["first_k_dense_replace"])]
        self.rules = conf.get("weights")
        self.leaves = {seg: [(path.split("/", 2)[2], path, shape[1:], dtype)
                             for path, shape, dtype in specs
                             if path.startswith(f"segments/{seg}/")]
                       for seg in SEGMENTS}
        self.other = {path: (shape, dtype) for path, shape, dtype in specs
                      if not W.layered(path)}

    @functools.partial(jax.jit, static_argnums=(0, 2))
    def _draw_layer(self, key, seg, layer):
        out = {}
        for name, path, shape, dtype in self.leaves[seg]:
            group, leaf = name.split("/")
            out.setdefault(group, {})[leaf] = W.draw_layer(
                key, path, layer, shape, dtype, self.rules).astype(jnp.float32)
        return out

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _draw(self, path, key):
        shape, dtype = self.other[path]
        return W.draw(W.leaf_key(key, path), path, shape, dtype,
                      self.rules).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnums=(0, 3))
    def _run_layer(self, p, x, fp8):
        with jax.default_matmul_precision("highest"):
            return _layer(p, x, self.s, fp8)

    @functools.partial(jax.jit, static_argnums=(0, 5))
    def _head(self, x, rows, norm, head, fp8):
        with jax.default_matmul_precision("highest"):
            h = R._rms(x[rows[:, 0], rows[:, 1]], norm, self.s.eps)
            return R._mm(h, head, fp8)

    def logits(self, seed: int, tokens: np.ndarray, rows: np.ndarray,
               precision: str = "float32") -> np.ndarray:
        fp8 = precision == "fp8"
        key = W.base_key(seed)
        x = self._draw("embed", key)[jnp.asarray(tokens)]
        for seg, layer in self.layers:
            x = self._run_layer(self._draw_layer(key, seg, layer), x, fp8)
        out = self._head(x, jnp.asarray(rows, jnp.int32),
                         self._draw("final_norm", key),
                         self._draw("lm_head", key), fp8)
        return np.asarray(out, np.float32)

"""Plain reference of the decoder configurations, in float32, layer by layer.

It is the default of a configuration file's ``reference`` key: a module
named there gives ``Reference(conf, specs)`` with ``.logits(seed, tokens,
rows, precision="float32"|"fp8")``, and may use the generic parts here
(``check_rows``, ``served_gaps``, the fp8 rounding, the layer helpers).
It imports nothing of the program. It reads a configuration file of
``bench/configs`` (Hugging Face key names, as run) and draws every weight
again from the run's seed through :mod:`weights`, one layer at a time, so
that it fits next to nothing else on the chip once the program is freed.

Per layer, as the configuration file states it::

    h = rmsnorm(x) * ln
    q, k, v = h @ wq, h @ wk, h @ wv          (GQA; per-head RMSNorm of q, k
                                               where "qk_norm" is "per_head")
    q, k = rope(q), rope(k)                   (rotate-half, rope_theta)
    x = x + softmax(q k^T / sqrt(head_dim), causal) v @ wo
    h = rmsnorm(x) * ln
    x = x + swiglu(h)                         (dense), or
    x = x + sum_k p_k * swiglu_{e_k}(h)       (softmax router over all
                                               experts, top-k, p renormalised
                                               where norm_topk_prob; dropless)

then ``logits = rmsnorm(x) * final_norm @ head`` (the embedding, transposed,
where the embeddings are tied). Every matrix product runs at "highest"
precision.

``precision="fp8"`` is the control: the same computation with both operands
of every weight product (the router and the head included) rounded to
float8 e4m3, weights per output channel and activations per token, each
scaled so that its largest magnitude maps to 448.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

E4M3_MAX = 448.0
#: token rows per block of the dropless expert sum
MOE_ROWS = 512


def _round_e4m3(x: jax.Array) -> jax.Array:
    """Round to the nearest float8 e4m3 value (3 mantissa bits, smallest
    normal exponent -6, subnormal step 2**-9); ``|x| <= 448`` assumed."""
    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9)))
    step = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    return jnp.round(x / step) * step


def _quant(x: jax.Array, axis: int) -> jax.Array:
    """fp8 e4m3 with one scale per slice along every axis but ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return _round_e4m3(x / scale) * scale


def _mm(x: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """x (..., d) @ w (d, f)."""
    if fp8:
        x, w = _quant(x, -1), _quant(w, 0)
    return jnp.einsum("...d,df->...f", x, w)


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x: (N, S, H, hd); pos: (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, wg, wu, wd, fp8):
    return _mm(jax.nn.silu(_mm(h, wg, fp8)) * _mm(h, wu, fp8), wd, fp8)


class Shape:
    """The sizes the reference needs, from a configuration file."""

    def __init__(self, conf: dict):
        self.d = conf["hidden_size"]
        self.heads = conf["num_attention_heads"]
        self.kv_heads = conf["num_key_value_heads"]
        self.head_dim = conf.get("head_dim") or self.d // self.heads
        self.layers = conf["num_hidden_layers"]
        self.vocab = conf["vocab_size"]
        self.eps = conf["rms_norm_eps"]
        self.theta = conf["rope_theta"]
        self.tied = conf["tie_word_embeddings"]
        self.experts = conf.get("num_experts", 0)
        self.top_k = conf.get("num_experts_per_tok", 0)
        self.norm_topk = conf.get("norm_topk_prob", True)
        self.qk_norm = conf.get("qk_norm") == "per_head"
        self.segment = "moe" if self.experts else "dense"
        self.ffn = "moe" if self.experts else "mlp"


def _attention(p: dict, h: jax.Array, s: Shape, fp8: bool) -> jax.Array:
    n, t, _ = h.shape
    q = _mm(h, p["wq"], fp8).reshape(n, t, s.heads, s.head_dim)
    k = _mm(h, p["wk"], fp8).reshape(n, t, s.kv_heads, s.head_dim)
    v = _mm(h, p["wv"], fp8).reshape(n, t, s.kv_heads, s.head_dim)
    if s.qk_norm:
        q = _rms(q, p["q_norm"], s.eps)
        k = _rms(k, p["k_norm"], s.eps)
    pos = jnp.arange(t)
    q, k = _rope(q, pos, s.theta), _rope(k, pos, s.theta)
    g = s.heads // s.kv_heads
    q = q.reshape(n, t, s.kv_heads, g, s.head_dim)
    scores = jnp.einsum("nqkgh,ntkh->nkgqt", q, k) / np.sqrt(s.head_dim)
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("nkgqt,ntkh->nqkgh", jax.nn.softmax(scores, -1), v)
    return _mm(o.reshape(n, t, s.heads * s.head_dim), p["wo"], fp8)


def _moe(p: dict, h: jax.Array, s: Shape, fp8: bool) -> jax.Array:
    """Dropless: every token reaches each of its top-k experts."""
    shape = h.shape
    x = h.reshape(-1, s.d)
    rows = x.shape[0]
    pad = (-rows) % MOE_ROWS
    x = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, MOE_ROWS, s.d)

    def block(xb):
        probs = jax.nn.softmax(_mm(xb, p["router"], fp8), -1)
        top_p, top_i = jax.lax.top_k(probs, s.top_k)
        if s.norm_topk:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        wts = jnp.sum(jax.nn.one_hot(top_i, s.experts) * top_p[..., None], 1)
        wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
        if fp8:
            xb_q = _quant(xb, -1)
            wg, wu, wd = _quant(wg, 1), _quant(wu, 1), _quant(wd, 1)
        else:
            xb_q = xb
        a = jax.nn.silu(jnp.einsum("td,edf->tef", xb_q, wg)) \
            * jnp.einsum("td,edf->tef", xb_q, wu)
        if fp8:
            a = _quant(a, -1)
        y = jnp.einsum("tef,efd->ted", a, wd)
        return jnp.einsum("ted,te->td", y, wts)

    out = jax.lax.map(block, x).reshape(-1, s.d)[:rows]
    return out.reshape(shape)


def _layer(p: dict, x: jax.Array, s: Shape, fp8: bool) -> jax.Array:
    x = x + _attention(p["attn"], _rms(x, p["attn"]["ln"], s.eps), s, fp8)
    h = _rms(x, p[s.ffn]["ln"], s.eps)
    if s.experts:
        return x + _moe(p["moe"], h, s, fp8)
    m = p["mlp"]
    return x + _swiglu(h, m["w_gate"], m["w_up"], m["w_down"], fp8)


class Reference:
    """The reference for one configuration file and one parameter layout
    (``specs``: (path, shape, dtype) of every leaf the program is given)."""

    def __init__(self, conf: dict, specs: Sequence[Tuple[str, tuple, object]]):
        self.s = Shape(conf)
        self.rules = conf.get("weights")
        self.specs = list(specs)
        prefix = f"segments/{self.s.segment}/"
        self.layer_leaves = [(path[len(prefix):], path, shape[1:], dtype)
                             for path, shape, dtype in self.specs
                             if path.startswith(prefix)]
        self.other = {path: (shape, dtype) for path, shape, dtype in self.specs
                      if not W.layered(path)}

    @functools.partial(jax.jit, static_argnums=0)
    def _draw_layer(self, key, layer):
        out: Dict[str, Dict[str, jax.Array]] = {}
        for name, path, shape, dtype in self.layer_leaves:
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
            h = _rms(x[rows[:, 0], rows[:, 1]], norm, self.s.eps)
            return _mm(h, head, fp8)

    def logits(self, seed: int, tokens: np.ndarray, rows: np.ndarray,
               precision: str = "float32") -> np.ndarray:
        """Logits (len(rows), vocab) at ``rows`` = [(sequence, position)]
        of ``tokens`` (N, T): every sequence is run causally over all T."""
        fp8 = precision == "fp8"
        key = W.base_key(seed)
        embed = self._draw("embed", key)
        x = embed[jnp.asarray(tokens)]
        for layer in range(self.s.layers):
            x = self._run_layer(self._draw_layer(key, layer), x, fp8)
        head = embed.T if self.s.tied else self._draw("lm_head", key)
        out = self._head(x, jnp.asarray(rows, jnp.int32),
                         self._draw("final_norm", key), head, fp8)
        return np.asarray(out, np.float32)


def served_gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best
    at its position."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), np.asarray(tokens)]


def check_rows(prompts: List[List[int]], served: List[List[int]],
               length: int) -> Tuple[np.ndarray, np.ndarray, List[int]]:
    """Token array (N, length) of prompt + served tokens (the last served
    token is only read, never fed), the rows whose logits chose each served
    token, and the served tokens in that order."""
    tokens = np.zeros((len(prompts), length), np.int32)
    rows, picked = [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = list(p) + list(s[:-1])
        tokens[i, :len(seq)] = seq
        for j, t in enumerate(s):
            rows.append((i, len(p) - 1 + j))
            picked.append(int(t))
    return tokens, np.asarray(rows, np.int32), picked

"""GQA attention: chunked (memory-bounded) prefill + single-token decode.

Variants (per ModelConfig): causal, bidirectional (encoder), sliding-window
(serving path for long-context decode of full-attention archs), qk-norm
(Qwen3), QKV bias (Qwen2.5).

The prefill path scans over query chunks so the score tensor never exceeds
(B, chunk, H, S) — the pure-JAX analogue of flash attention's tiling, and the
reference the Pallas kernels are validated against. The decode path attends
one new token against the (possibly sequence-sharded) KV cache; under pjit
the softmax reductions over the sharded S axis lower to all-reduces, which is
our TPU-native stand-in for the paper's DP-attention with UB-pooled KV.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_norm

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Per-segment stacked KV cache. k/v: (L, B, S, KV, hd).

    Whether the cache is a sliding-window ring buffer is a *static* property
    derived from (cfg, seq_len) via :func:`is_ring` — it is deliberately not a
    field so the cache stays a clean jit-able pytree.
    """
    k: jax.Array
    v: jax.Array
    length: jax.Array   # scalar int32: number of tokens written (global)

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


def is_ring(cfg: ModelConfig, seq_len: int) -> bool:
    return bool(cfg.sliding_window and seq_len > cfg.sliding_window)


def init_attention_params(key, cfg: ModelConfig, n_layers: int, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "ln": jnp.ones((n_layers, d), dtype),
        "wq": dense_init(ks[0], (n_layers, d, h * hd), dtype),
        "wk": dense_init(ks[1], (n_layers, d, kv * hd), dtype),
        "wv": dense_init(ks[2], (n_layers, d, kv * hd), dtype),
        "wo": dense_init(ks[3], (n_layers, h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_layers, h * hd), dtype)
        p["bk"] = jnp.zeros((n_layers, kv * hd), dtype)
        p["bv"] = jnp.zeros((n_layers, kv * hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n_layers, hd), dtype)
        p["k_norm"] = jnp.ones((n_layers, hd), dtype)
    return p


def _project_qkv(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KV,hd), with qk-norm + RoPE."""
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kv, hd)
    v = v.reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.attention_kind != "bidirectional" or True:
        # RoPE is applied for all archs in the zoo (hubert uses it in lieu of
        # its conv positional encoding — frontend carve-out, see DESIGN.md).
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, mask) -> jax.Array:
    """q: (B,Sq,H,hd); k/v: (B,Skv,KV,hd); mask: (B|1, Sq, Skv) bool."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    qg = q.reshape(b, sq, kvh, groups, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (hd ** 0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h * hd).astype(q.dtype)


def _pick_chunk(s: int, target: int = 512) -> int:
    if s <= target:
        return s
    c = target
    while s % c:
        c //= 2
    return max(c, 1)


def block_skip_enabled() -> bool:
    """Causal block-skipping (beyond-paper §Perf optimization): the flash-
    style prefill loop visits only kv blocks ≤ the query block (and within
    the sliding window), halving executed attention FLOPs vs the masked
    full-S baseline. Opt-in via REPRO_BLOCK_SKIP=1."""
    import os
    return os.environ.get("REPRO_BLOCK_SKIP", "0") == "1"


def _flash_causal(q, k, v, cfg: ModelConfig, chunk: int):
    """Block-skipped causal attention with an online-softmax kv-block loop.

    q: (B,S,H,hd); k/v: (B,S,KV,hd). Query chunk ci attends kv blocks
    [lo(ci), ci] only — lo respects the sliding window when configured.
    """
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    nc = s // chunk
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def one_chunk(ci):
        qc = jax.lax.dynamic_slice_in_dim(q, ci * chunk, chunk, axis=1)
        qg = qc.reshape(b, chunk, kvh, groups, hd).astype(jnp.float32)
        q_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)

        def kv_block(j, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kf, j * chunk, chunk, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(vf, j * chunk, chunk, axis=1)
            scores = jnp.einsum("bskgh,btkh->bkgst", qg, kb) / (hd ** 0.5)
            kv_pos = j * chunk + jnp.arange(chunk, dtype=jnp.int32)
            mask = kv_pos[None, :] <= q_pos[:, None]
            if cfg.sliding_window:
                mask &= kv_pos[None, :] > q_pos[:, None] - cfg.sliding_window
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bkgst,btkh->bkgsh", p, vb)
            return m_new, l_new, acc_new

        m0 = jnp.full((b, kvh, groups, chunk, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, groups, chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, kvh, groups, chunk, hd), jnp.float32)
        if cfg.sliding_window:
            # first kv block containing any in-window position for this chunk
            lo = jnp.maximum(0, (ci * chunk - (cfg.sliding_window - 1)) // chunk)
        else:
            lo = jnp.int32(0)
        m, l, acc = jax.lax.fori_loop(lo, ci + 1, kv_block, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30)
        # (b,kv,g,chunk,hd) -> (b,chunk,h*hd)
        return jnp.moveaxis(out, 3, 1).reshape(b, chunk, h * hd).astype(q.dtype)

    from repro.models.scan_util import chunk_map
    if nc == 1:
        return one_chunk(jnp.int32(0))
    outs = chunk_map(one_chunk, nc)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h * hd)


def attention_prefill(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention, chunked over queries. Returns (out, (k, v))."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q, k, v = _project_qkv(p, x, cfg, positions)
    chunk = _pick_chunk(s)
    n_chunks = s // chunk

    if cfg.attention_kind != "bidirectional" and block_skip_enabled():
        out = _flash_causal(q, k, v, cfg, chunk)
        out = jnp.einsum("bse,ed->bsd", out, p["wo"])
        return out, (k, v)

    kv_pos = jnp.arange(s, dtype=jnp.int32)

    def one_chunk(ci):
        q_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        qc = jax.lax.dynamic_slice_in_dim(q, ci * chunk, chunk, axis=1)
        if cfg.attention_kind == "bidirectional":
            mask = jnp.ones((1, chunk, s), bool)
        else:
            mask = (kv_pos[None, :] <= q_pos[:, None])[None]
            if cfg.sliding_window and s > cfg.sliding_window:
                mask &= (kv_pos[None, :] > q_pos[:, None] - cfg.sliding_window)[None]
        return _sdpa(qc, k, v, mask)

    if n_chunks == 1:
        out = one_chunk(jnp.int32(0))
    else:
        from repro.models.scan_util import chunk_map
        outs = chunk_map(one_chunk, n_chunks)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, -1)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return out, (k, v)


def _positions_of(cache_len: jax.Array, b: int) -> jax.Array:
    """cache_len: scalar or (B,) -> positions (B, 1)."""
    if cache_len.ndim == 0:
        return jnp.broadcast_to(cache_len[None], (b, 1)).astype(jnp.int32)
    return cache_len[:, None].astype(jnp.int32)


def decode_write_pos(cache_len: jax.Array, cap: int, ring: bool,
                     live: Optional[jax.Array] = None) -> jax.Array:
    """Where each request's new decode row goes: ``cache_len`` (mod ``cap``
    in a ring), and ``cap``, where :func:`write_row` drops it, for a slot
    that is not ``live`` (B,). Scalar or (B,)."""
    pos = (cache_len % cap if ring else cache_len).astype(jnp.int32)
    if live is not None:
        pos = jnp.where(live, pos, cap)
    return pos


def write_row(stack: jax.Array, layer: jax.Array, new: jax.Array,
              pos: jax.Array) -> jax.Array:
    """Write each request's row ``new`` (B, 1, ...) into the layer-stacked
    cache (L, B, S, ...) at (``layer``, b, ``pos[b]``), one row in place; a
    ``pos`` of S (the capacity) drops that request's write."""
    b = stack.shape[1]
    pos = jnp.broadcast_to(pos, (b,))
    return stack.at[layer, jnp.arange(b), pos].set(
        new[:, 0].astype(stack.dtype), mode="drop")


def decode_valid_mask(cache_len: jax.Array, cap: int, ring: bool) -> jax.Array:
    """(B|1, 1, S) boolean mask of attendable cache slots (incl. new token).

    MTP-aware: cache_len may be per-request (B,) — the paper's "varying
    effective sequence lengths within the same batch" (§4.2.2 issue 3).
    """
    kv_idx = jnp.arange(cap, dtype=jnp.int32)
    cl = cache_len[None] if cache_len.ndim == 0 else cache_len  # (B|1,)
    if ring:
        valid = kv_idx[None, :] <= jnp.minimum(cl[:, None], cap - 1)
    else:
        valid = kv_idx[None, :] <= cl[:, None]
    return valid[:, None, :]                                    # (B|1,1,S)


def attention_decode(
    p: dict,
    x: jax.Array,                 # (B, 1, D) — one new token per request
    cache_k: jax.Array,           # (L, B, S, KV, hd), the segment's stack
    cache_v: jax.Array,
    cache_len: jax.Array,         # int32: scalar or per-request (B,)
    cfg: ModelConfig,
    ring: bool,
    layer: jax.Array,             # int32 index into the stacks' L axis
    write_pos: jax.Array,         # (B,) or scalar, from decode_write_pos
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of layer ``layer``: writes the token's K/V row into
    the stacks in place at ``write_pos``, then attends over that layer of
    the stacks. Returns (out (B,1,D), cache_k, cache_v)."""
    b = x.shape[0]
    cap = cache_k.shape[2]
    positions = _positions_of(cache_len, b)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)
    cache_k = write_row(cache_k, layer, k_new, write_pos)
    cache_v = write_row(cache_v, layer, v_new, write_pos)
    mask = decode_valid_mask(cache_len, cap, ring)
    out = _sdpa(q, cache_k[layer], cache_v[layer], mask)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return out, cache_k, cache_v


def attention_extend(
    p: dict,
    x: jax.Array,                 # (B, S, D) — teacher-forced new tokens
    cache_k: jax.Array,           # (B, cap, KV, hd), first `offset` valid
    cache_v: jax.Array,
    offset: jax.Array,            # int32: scalar or per-request (B,) (traced)
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-token cached attention: process S known tokens at positions
    ``offset .. offset+S-1`` in one shot — the batched generalization of
    :func:`attention_decode` (S=1) used by the chunked suffix-prefill fast
    path. A per-request ``offset`` (B,) supports divergent sequence lengths
    within one batch — the MTP fused base+draft verification forward (paper
    §4.2.2 issue 3). No ring-buffer support (neither the EMS reuse path nor
    MTP verification ever sees rings).

    Returns (out (B,S,D), new_cache_k, new_cache_v)."""
    b, s, _ = x.shape
    cap = cache_k.shape[1]
    offset = jnp.asarray(offset, jnp.int32)
    if offset.ndim == 0:
        q_pos = offset + jnp.arange(s, dtype=jnp.int32)     # (S,)
        positions = jnp.broadcast_to(q_pos[None], (b, s))
        q, k_new, v_new = _project_qkv(p, x, cfg, positions)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new.astype(cache_k.dtype), offset, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new.astype(cache_v.dtype), offset, axis=1)
        kv_idx = jnp.arange(cap, dtype=jnp.int32)
        mask = (kv_idx[None, :] <= q_pos[:, None])[None]    # (1, S, cap)
    else:
        positions = offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        q, k_new, v_new = _project_qkv(p, x, cfg, positions)
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        # Out-of-bounds scatter rows are dropped (masked callers rely on it).
        cache_k = cache_k.at[rows, positions].set(k_new.astype(cache_k.dtype))
        cache_v = cache_v.at[rows, positions].set(v_new.astype(cache_v.dtype))
        kv_idx = jnp.arange(cap, dtype=jnp.int32)
        mask = kv_idx[None, None, :] <= positions[:, :, None]   # (B, S, cap)
    out = _sdpa(q, cache_k, cache_v, mask)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return out, cache_k, cache_v


def make_cache(cfg: ModelConfig, n_layers: int, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    cap = cfg.sliding_window if is_ring(cfg, seq_len) else seq_len
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    shape = (n_layers, batch, cap, kv, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))

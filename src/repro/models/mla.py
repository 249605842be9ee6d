"""Multi-head Latent Attention (DeepSeek-style), per paper §4.2.2 / §4.3.1.

Two execution forms, equivalence-tested against each other:

* ``mla_prefill`` — the *unabsorbed* form the paper uses for prefill (§4.3.1):
  latents are expanded to full per-head K/V and the layer behaves as standard
  MHA ("without certain weight matrix absorption to enhance raw computational
  efficiency"). Chunked over queries like models/attention.py.
* ``mla_decode`` — the *absorbed* form for decode: queries are pulled into
  latent space through W_UK so attention runs directly against the compressed
  (kv_lora_rank + rope) cache — the 93.3% KV-cache reduction the paper cites.
  The Pallas kernel in kernels/mla_attention implements this inner loop.

The latent KV cache is (B, S, kv_lora_rank + qk_rope_head_dim); under pjit it
is sequence-sharded over the ``model`` axis (our TPU analogue of the paper's
UB-pooled DP320 cache — see DESIGN.md §5).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import NEG_INF, _pick_chunk
from repro.models.layers import apply_rope, dense_init, rms_norm, yarn_mscale


def init_mla_params(key, cfg: ModelConfig, n_layers: int, dtype) -> dict:
    d, h = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.ones((n_layers, d), dtype),
        "wq_a": dense_init(ks[0], (n_layers, d, qr), dtype),
        "q_ln": jnp.ones((n_layers, qr), dtype),
        "wq_b": dense_init(ks[1], (n_layers, qr, h * (nope + rope)), dtype),
        "wkv_a": dense_init(ks[2], (n_layers, d, kvr + rope), dtype),
        "kv_ln": jnp.ones((n_layers, kvr), dtype),
        "wk_b": dense_init(ks[3], (n_layers, kvr, h * nope), dtype),
        "wv_b": dense_init(ks[4], (n_layers, kvr, h * vd), dtype),
        "wo": dense_init(ks[5], (n_layers, h * vd, d), dtype),
    }


def softmax_scale(cfg: ModelConfig) -> float:
    """``1/sqrt(nope + rope)``, times ``yarn_mscale(factor,
    mscale_all_dim)**2`` under YaRN, as DeepSeek-V3's attention has it."""
    scale = 1.0 / ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** 0.5)
    if cfg.yarn is not None:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_qkv_latent(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """Shared 'MLAProlog': projections + norms + RoPE (paper fuses these)."""
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rms_norm(q, p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,re->bse", q, p["wq_b"]).reshape(b, s, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.yarn)

    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv, k_rope = kv[..., : cfg.kv_lora_rank], kv[..., cfg.kv_lora_rank:]
    c_kv = rms_norm(c_kv, p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.yarn)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def _mla_flash_causal(q_nope, q_rope, k_nope, k_rope, vfull, scale: float,
                      chunk: int) -> jax.Array:
    """Block-skipped causal MLA attention (flash kv-block loop; the query
    chunk visits only kv blocks ≤ its own). Returns (B,S,H,vd) f32."""
    b, s, h, nope = q_nope.shape
    vd = vfull.shape[-1]
    nc = s // chunk
    knf = k_nope.astype(jnp.float32)
    krf = k_rope.astype(jnp.float32)
    vf = vfull.astype(jnp.float32)

    def one_chunk(ci):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, ci * chunk, chunk, 1
                                          ).astype(jnp.float32)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, ci * chunk, chunk, 1
                                          ).astype(jnp.float32)
        q_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)

        def kv_block(j, carry):
            m, l, acc = carry
            knb = jax.lax.dynamic_slice_in_dim(knf, j * chunk, chunk, 1)
            krb = jax.lax.dynamic_slice_in_dim(krf, j * chunk, chunk, 1)
            vb = jax.lax.dynamic_slice_in_dim(vf, j * chunk, chunk, 1)
            scores = (jnp.einsum("bshe,bthe->bhst", qn, knb)
                      + jnp.einsum("bshe,bte->bhst", qr, krb)) * scale
            kv_pos = j * chunk + jnp.arange(chunk, dtype=jnp.int32)
            mask = kv_pos[None, :] <= q_pos[:, None]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(scores - m_new)
            l_new = l * alpha + jnp.sum(pr, -1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhst,bthe->bhse", pr, vb)
            return m_new, l_new, acc_new

        m0 = jnp.full((b, h, chunk, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, h, chunk, vd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, ci + 1, kv_block, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30)                    # (b,h,chunk,vd)
        return jnp.moveaxis(out, 1, 2)                       # (b,chunk,h,vd)

    from repro.models.scan_util import chunk_map
    if nc == 1:
        return one_chunk(jnp.int32(0))
    outs = chunk_map(one_chunk, nc)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, h, vd)


def mla_prefill(p: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array | None = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Unabsorbed MHA-form prefill. Returns (out, latent_cache (B,S,kvr+rope))."""
    from repro.models.attention import block_skip_enabled

    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(p, x, cfg, positions)

    k_nope = jnp.einsum("bsr,re->bse", c_kv, p["wk_b"]).reshape(b, s, h, nope)
    vfull = jnp.einsum("bsr,re->bse", c_kv, p["wv_b"]).reshape(b, s, h, vd)
    scale = softmax_scale(cfg)

    chunk = _pick_chunk(s)
    n_chunks = s // chunk

    if block_skip_enabled():
        out = _mla_flash_causal(q_nope, q_rope, k_nope, k_rope, vfull,
                                scale, chunk)
        out = out.reshape(b, s, h * vd).astype(x.dtype)
        out = jnp.einsum("bse,ed->bsd", out, p["wo"])
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)
        return out, latent

    kv_pos = jnp.arange(s, dtype=jnp.int32)

    def one_chunk(ci):
        q_pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, ci * chunk, chunk, axis=1)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, ci * chunk, chunk, axis=1)
        scores = (
            jnp.einsum("bshe,bthe->bhst", qn.astype(jnp.float32), k_nope.astype(jnp.float32))
            + jnp.einsum("bshe,bte->bhst", qr.astype(jnp.float32), k_rope.astype(jnp.float32))
        ) * scale
        mask = kv_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhst,bthe->bshe", probs, vfull.astype(jnp.float32))

    if n_chunks == 1:
        out = one_chunk(jnp.int32(0))
    else:
        from repro.models.scan_util import chunk_map
        outs = chunk_map(one_chunk, n_chunks)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, vd)
    out = out.reshape(b, s, h * vd).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)
    return out, latent


def mla_decode(p: dict, x: jax.Array, cache: jax.Array, cache_len: jax.Array,
               cfg: ModelConfig, layer: jax.Array, write_pos: jax.Array,
               use_kernel: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Absorbed decode step of layer ``layer``.

    x: (B, 1, D); cache: the segment's stacked latents (L, B, S, kvr+rope).
    Writes the token's latent row in place at ``write_pos`` (see
    ``attention.decode_write_pos``) and attends over that layer. Returns
    (out (B,1,D), the stack).
    """
    from repro.models.attention import (_positions_of, decode_valid_mask,
                                        write_row)

    b = x.shape[0]
    h = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cap = cache.shape[2]
    positions = _positions_of(cache_len, b)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(p, x, cfg, positions)

    new_entry = jnp.concatenate([c_kv, k_rope], axis=-1)        # (B,1,kvr+rope)
    cache = write_row(cache, layer, new_entry, write_pos)
    lat = cache[layer]                                          # (B,S,kvr+rope)

    # Absorb W_UK into the query: q_lat (B,1,H,kvr)
    wk = p["wk_b"].reshape(kvr, h, nope)
    q_lat = jnp.einsum("bshe,rhe->bshr", q_nope.astype(jnp.float32),
                       wk.astype(jnp.float32))
    scale = softmax_scale(cfg)
    vmask = decode_valid_mask(cache_len, cap, ring=False)        # (B|1,1,S)

    if use_kernel and cache_len.ndim == 0:
        from repro.kernels.mla_attention.ops import mla_decode_attention
        valid = jnp.arange(cap, dtype=jnp.int32) <= cache_len
        o_lat = mla_decode_attention(
            q_lat[:, 0], q_rope[:, 0], lat.astype(jnp.float32), valid, scale, kvr)
        o_lat = o_lat[:, None]
    else:
        ck = lat[..., :kvr].astype(jnp.float32)                  # (B,S,kvr)
        kr = lat[..., kvr:].astype(jnp.float32)                  # (B,S,rope)
        scores = (
            jnp.einsum("bshr,btr->bhst", q_lat, ck)
            + jnp.einsum("bshe,bte->bhst", q_rope.astype(jnp.float32), kr)
        ) * scale
        scores = jnp.where(vmask[:, :, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, ck)          # (B,1,H,kvr)

    wv = p["wv_b"].reshape(kvr, h, vd)
    out = jnp.einsum("bshr,rhe->bshe", o_lat, wv.astype(jnp.float32))
    out = out.reshape(b, 1, h * vd).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return out, cache


def mla_extend(p: dict, x: jax.Array, cache: jax.Array, offset: jax.Array,
               cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Absorbed-form teacher-forced continuation — the S-token
    generalization of :func:`mla_decode` used by chunked suffix prefill.

    x: (B, S, D) at positions ``offset .. offset+S-1``; cache:
    (B, cap, kvr+rope) with the first ``offset`` rows valid. ``offset`` may
    be per-request (B,) — divergent in-batch lengths for the MTP fused
    verification forward. Returns (out (B,S,D), new cache)."""
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    cap = cache.shape[1]
    offset = jnp.asarray(offset, jnp.int32)
    if offset.ndim == 0:
        q_pos = offset + jnp.arange(s, dtype=jnp.int32)      # (S,)
        positions = jnp.broadcast_to(q_pos[None], (b, s))
    else:
        positions = offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(p, x, cfg, positions)

    new_entry = jnp.concatenate([c_kv, k_rope], axis=-1)     # (B,S,kvr+rope)
    if offset.ndim == 0:
        cache = jax.lax.dynamic_update_slice_in_dim(
            cache, new_entry.astype(cache.dtype), offset, axis=1)
    else:
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        # Out-of-bounds scatter rows are dropped (masked callers rely on it).
        cache = cache.at[rows, positions].set(new_entry.astype(cache.dtype))

    wk = p["wk_b"].reshape(kvr, h, nope)
    q_lat = jnp.einsum("bshe,rhe->bshr", q_nope.astype(jnp.float32),
                       wk.astype(jnp.float32))
    scale = softmax_scale(cfg)
    ck = cache[..., :kvr].astype(jnp.float32)                # (B,cap,kvr)
    kr = cache[..., kvr:].astype(jnp.float32)                # (B,cap,rope)
    scores = (
        jnp.einsum("bshr,btr->bhst", q_lat, ck)
        + jnp.einsum("bshe,bte->bhst", q_rope.astype(jnp.float32), kr)
    ) * scale
    kv_idx = jnp.arange(cap, dtype=jnp.int32)
    mask = kv_idx[None, None, :] <= positions[:, :, None]    # (B, S, cap)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", probs, ck)          # (B,S,H,kvr)
    wv = p["wv_b"].reshape(kvr, h, vd)
    out = jnp.einsum("bshr,rhe->bshe", o_lat, wv.astype(jnp.float32))
    out = out.reshape(b, s, h * vd).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return out, cache


def make_mla_cache(cfg: ModelConfig, n_layers: int, batch: int, seq_len: int,
                   dtype=jnp.bfloat16) -> jax.Array:
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return jnp.zeros((n_layers, batch, seq_len, width), dtype)

"""Pallas TPU kernel: absorbed-MLA decode attention (FlashMLA analogue).

One new query token per request attends against the compressed latent KV
cache (kv_lora_rank + rope dims). Flash-decoding style: the sequence axis is
tiled into VMEM-resident blocks with a running (max, sum, acc) softmax, so
the (B, S, R+Dr) cache streams HBM→VMEM once in 128-aligned tiles — the TPU
analogue of the paper's NZ-formatted KV cache (§4.2.2, DESIGN.md §5.3).

Grid: (batch, seq_blocks); seq dimension is "arbitrary" (sequential) so the
running-softmax scratch carries across blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_lat_ref, q_rope_ref, cache_ref, valid_ref,  # inputs
            out_ref,                                      # output
            m_ref, l_ref, acc_ref,                        # scratch
            *, scale: float, kvr: int, block_s: int):
    sb = pl.program_id(1)
    nsb = pl.num_programs(1)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lat = q_lat_ref[0]                     # (H, R)
    q_rope = q_rope_ref[0]                   # (H, Dr)
    cache = cache_ref[0]                     # (BS, R+Dr) f32
    ck = cache[:, :kvr]                      # (BS, R)
    kr = cache[:, kvr:]                      # (BS, Dr)
    valid = valid_ref[0]                     # (BS,) int32 (1 = attendable)

    scores = (
        jax.lax.dot_general(q_lat, ck, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(q_rope, kr, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ) * scale                                # (H, BS)
    scores = jnp.where(valid[None, :] > 0, scores, NEG_INF)

    m_prev = m_ref[...]                      # (H, 1)
    m_cur = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)              # (H, BS)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, ck, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(sb == nsb - 1)
    def _finalize():
        out_ref[0] = acc_ref[...] / l_ref[...]


def mla_decode_attention_pallas(q_lat, q_rope, cache, valid, scale: float,
                                kvr: int, block_s: int = 128,
                                interpret: bool = False):
    """q_lat: (B,H,R) f32; q_rope: (B,H,Dr) f32; cache: (B,S,R+Dr) f32;
    valid: (S,) bool. Returns (B,H,R) f32."""
    b, h, r = q_lat.shape
    s = cache.shape[1]
    bs = min(block_s, s)
    while s % bs:
        bs //= 2
    n_sb = s // bs
    valid_i = valid.astype(jnp.int32)[None, :]   # (1, S) — lane-aligned

    kernel = functools.partial(_kernel, scale=scale, kvr=kvr, block_s=bs)
    return pl.pallas_call(
        kernel,
        grid=(b, n_sb),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda bi, si: (bi, 0, 0)),
            pl.BlockSpec((1, h, q_rope.shape[-1]), lambda bi, si: (bi, 0, 0)),
            pl.BlockSpec((1, bs, cache.shape[-1]), lambda bi, si: (bi, si, 0)),
            pl.BlockSpec((1, bs), lambda bi, si: (0, si)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda bi, si: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # running sum
            pltpu.VMEM((h, r), jnp.float32),   # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q_lat, q_rope, cache, valid_i)

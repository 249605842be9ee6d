"""Pallas TPU kernel: Mamba2 SSD chunked scan (state-space duality).

Grid: (batch, heads, chunks) with the chunk axis sequential ("arbitrary") so
the (P, N) recurrent state lives in a VMEM scratch across chunks. Per chunk
the kernel computes the quadratic intra-chunk term (an attention-like
(Q,Q) matmul on the MXU), the inter-chunk term from the carried state, and
the state update — the exact SSD decomposition of arXiv:2405.21060 §6.

Heads are a parallel grid dimension laid out ahead of the sequence, so each
head's chunk tiles are (Q, P), (Q, 1) and (Q, N): with Q=chunk=128 every
block meets the TPU's (8, 128) tiling rule for any head count (mamba2-780m
has 48). The chunk's cumulative decay is one matmul against a
lower-triangular ones matrix, at highest precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref,                                    # SMEM (H,)
            x_ref, dt_ref, b_ref, c_ref,              # inputs
            y_ref, hout_ref,                          # outputs
            h_ref,                                    # scratch (P, N)
            *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)
    ncs = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)             # (Q, 1)
    a = a_ref[hi]                                     # scalar A_log for head
    bmat = b_ref[0].astype(jnp.float32)               # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)               # (Q, N)

    idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = idx >= jdx
    tri = causal.astype(jnp.float32)                  # tri[t, s] = s <= t

    # Inclusive cumsum of dt*A as a column and as a row, each one matmul
    # against the lower-triangular ones matrix.
    dta = dt * (-jnp.exp(a))                          # (Q, 1) <= 0
    hp = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(tri, dta, (((1,), (0,)), ((), ())),
                              precision=hp,
                              preferred_element_type=jnp.float32)   # (Q, 1)
    cum_row = jax.lax.dot_general(dta, tri, (((0,), (1,)), ((), ())),
                                  precision=hp,
                                  preferred_element_type=jnp.float32)  # (1, Q)
    total = jnp.sum(dta, axis=0, keepdims=True)       # (1, 1) == cum[-1]

    # inter-chunk: y_inter[t] = exp(cum[t]) * C_t · h
    y_inter = jax.lax.dot_general(
        cmat, h_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(cum)          # (Q, P)

    # intra-chunk: W[t,s] = (C_t·B_s) * exp(cum[t]-cum[s]), s <= t; the
    # dt[s] factor rides on the rows of x.
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (Q, Q)
    lmat = jnp.exp(jnp.where(causal, cum - cum_row, -jnp.inf))
    y_intra = jax.lax.dot_general(cb * lmat, x * dt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = y_inter + y_intra

    # state update: h = exp(cum[-1]) * h + sum_s exp(cum[-1]-cum[s]) dt_s x_s B_s^T
    decay_to_end = jnp.exp(total - cum) * dt                          # (Q, 1)
    contrib = jax.lax.dot_general(
        x * decay_to_end, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                           # (P, N)
    h_ref[...] = h_ref[...] * jnp.exp(total) + contrib

    @pl.when(ci == ncs - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


def ssd_scan_pallas(x, dt, a_log, bmat, cmat, chunk: int = 128,
                    interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); a_log: (H,); B/C: (B,S,N).

    Returns (y (B,S,H,P) f32, h_final (B,H,P,N) f32).

    Heads go ahead of the sequence axis inside the kernel, so every block's
    last two dims are a (chunk, P), (chunk, 1) or (chunk, N) tile: the TPU
    tiling rule holds for any head count. ``a_log`` sits whole in SMEM.
    """
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, s)
    while s % q:
        q //= 2
    ncs = s // q
    kernel = functools.partial(_kernel, chunk=q)
    x_h = jnp.moveaxis(x.astype(jnp.float32), 2, 1)               # (B,H,S,P)
    dt_h = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)[..., None]  # (B,H,S,1)
    y, hout = pl.pallas_call(
        kernel,
        grid=(b, h, ncs),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_log.astype(jnp.float32), x_h, dt_h,
      bmat.astype(jnp.float32), cmat.astype(jnp.float32))
    return jnp.moveaxis(y, 1, 2), hout

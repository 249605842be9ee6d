"""Pallas TPU kernel: INT8 GEMM with per-token × per-channel rescale.

Paper §4.5 "Efficient INT8 Matrix Multiplication Kernels": activations are
quantized per token (dynamic), weights per output channel (static); the MXU
runs int8×int8→int32 and a single fp32 rescale produces BF16 output. Tiling
is (BM, BN, BK) with an int32 VMEM accumulator carried over the sequential K
grid dimension — K-innermost so the accumulator tile stays resident (the
data-reuse property Table 10 attributes to the Ascend L1-resident tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _finalize():
        scaled = acc_ref[...].astype(jnp.float32) * xs_ref[...] * ws_ref[...]
        o_ref[...] = scaled.astype(o_ref.dtype)


def int8_matmul_pallas(x_q, w_q, x_scale, w_scale, out_dtype=jnp.bfloat16,
                       bm: int = 128, bn: int = 128, bk: int = 128,
                       interpret: bool = False):
    m, k = x_q.shape
    _, n = w_q.shape
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    while m % bm:
        bm //= 2
    while n % bn:
        bn //= 2
    while k % bk:
        bk //= 2
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale)

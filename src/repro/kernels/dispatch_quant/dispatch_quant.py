"""Pallas TPU kernel: fused per-token INT8 quantize+pack for FusedDispatch.

Paper §4.2.1 Opt-2 "Early Quantization": token hidden states are quantized to
INT8 (+ per-token fp32 scale) *before* the dispatch all-to-all, cutting the
collective payload ~2× vs BF16 (7.5 KB vs 14 KB per 7168-dim token). On
Ascend this runs on AIV cores inside the send pipeline; the TPU analogue is
this VPU row-wise kernel fused into the dispatch producer so the all_to_all
moves int8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # (BT, D)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (BT, 1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def dispatch_quantize_pallas(x, bt: int = 256, interpret: bool = False):
    """x: (T, D) -> (int8 (T,D), f32 scale (T,1))."""
    t, d = x.shape
    bt = min(bt, t)
    while t % bt:
        bt //= 2
    return pl.pallas_call(
        _kernel,
        grid=(t // bt,),
        in_specs=[pl.BlockSpec((bt, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, d), lambda i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), jnp.int8),
            jax.ShapeDtypeStruct((t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)

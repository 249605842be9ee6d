"""Pallas TPU kernels for the paper's compute hot-spots.

Four kernels, each a package with ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jit'd public wrapper) and ``ref.py`` (pure-jnp oracle):

* ``mla_attention``  — absorbed-MLA decode attention over the compressed
  latent KV cache (paper §4.2.2, FlashMLA analogue; Tables 8/9).
* ``int8_gemm``      — INT8×INT8→INT32 GEMM with per-token × per-channel
  rescale (paper §4.5; Table 10).
* ``ssd_scan``       — Mamba2 SSD chunked scan (assigned mamba2/zamba2 archs).
* ``dispatch_quant`` — fused per-token INT8 quantize+pack, the producer side
  of FusedDispatch's early quantization (paper §4.2.1).

On the CPU kernels run under ``interpret=True``; on a TPU the same
pallas_call lowers to Mosaic. Tests validate every kernel against its
``ref.py`` oracle in interpret mode, ``tests/test_chip_compile.py`` compiles
each for a described v5e at real widths, and ``chip_smoke.py`` runs each on
the chip.
"""

import jax

INTERPRET = jax.default_backend() == "cpu"

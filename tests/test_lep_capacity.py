"""Regression tests for core/lep.py capacity math (paper Eq. 2).

These pin the *behaviour* of the static-buffer sizing — zero-token edge
cases, capacity-factor rounding, sublane alignment, and the drop accounting
of capacity-bounded dispatch.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lep import _cdiv, lep_capacity
from repro.models.moe import dispatch_indices


# ---------------------------------------------------------------------------
# lep_capacity (Eq. 2): cap = ceil(int(t_loc·k·factor) / slots) + 1,
# rounded up to `align` with an `align` floor.
# ---------------------------------------------------------------------------


def test_zero_tokens_still_allocates_aligned_floor():
    # An empty local shard must still produce a valid (non-zero) static
    # buffer: the TPU sublane floor dominates.
    assert lep_capacity(0, 2, 8, 1.0) == 8           # default align=8
    assert lep_capacity(0, 2, 8, 1.0, align=1) == 1  # decode path floor
    assert lep_capacity(0, 8, 256, 4.0, align=4) == 4


def test_exact_values_and_alignment_rounding():
    # cdiv(16·1·1.0, 4) + 1 = 5 → padded to the next multiple of align
    assert lep_capacity(16, 1, 4, 1.0, align=1) == 5
    assert lep_capacity(16, 1, 4, 1.0, align=4) == 8
    assert lep_capacity(16, 1, 4, 1.0, align=8) == 8
    # paper-scale EP320-ish shape: 128 tokens/rank, k=8, 256 slots
    assert lep_capacity(128, 8, 256, 1.0, align=1) == 5
    assert lep_capacity(128, 8, 256, 1.0) == 8
    # decode single-token path: t_loc=1
    assert lep_capacity(1, 8, 256, 1.0, align=1) == 2


def test_capacity_factor_rounding_truncates_product_first():
    # 3·2·1.25 = 7.5 → int() truncation to 7 BEFORE cdiv: cdiv(7,4)+1 = 3.
    assert lep_capacity(3, 2, 4, 1.25, align=1) == 3
    # if the product were ceil'd first this would be cdiv(8,4)+1 = 3 too;
    # distinguish with a case where truncation changes the bucket count:
    # 5·1·1.5 = 7.5 → int → 7 → cdiv(7,8)+1 = 2 (ceil'd 8 would give 2 as
    # well, so use slots=7: trunc 7→cdiv=1+1=2; ceil 8→cdiv=2+1=3)
    assert lep_capacity(5, 1, 7, 1.5, align=1) == 2


def test_capacity_monotone_in_factor_and_tokens():
    caps_f = [lep_capacity(32, 4, 16, f, align=1)
              for f in (0.5, 1.0, 1.5, 2.0, 4.0)]
    assert caps_f == sorted(caps_f)
    caps_t = [lep_capacity(t, 4, 16, 1.0, align=1) for t in (0, 8, 64, 512)]
    assert caps_t == sorted(caps_t)


def test_alignment_is_respected_for_all_aligns():
    for align in (1, 2, 4, 8, 16):
        for t in (0, 1, 7, 33, 100):
            cap = lep_capacity(t, 2, 8, 1.0, align=align)
            assert cap % align == 0 and cap >= align
            # never below the unaligned requirement
            assert cap >= _cdiv(int(t * 2 * 1.0), 8) + 1 or t == 0


# ---------------------------------------------------------------------------
# Drop accounting: dispatch_indices valid-mask under capacity pressure
# ---------------------------------------------------------------------------


def test_dispatch_drops_exactly_the_overflow():
    top_i = jnp.zeros((8, 1), jnp.int32)            # all tokens → expert 0
    slot, valid = dispatch_indices(top_i, num_experts=4, capacity=8)
    np.testing.assert_array_equal(np.asarray(slot[:, 0]), np.arange(8))
    assert bool(valid.all())                        # capacity fits: no drops
    _, valid6 = dispatch_indices(top_i, num_experts=4, capacity=6)
    assert int(valid6.sum()) == 6                   # exactly 2 dropped
    # arrival order is preserved: the dropped ones are the LAST arrivals
    np.testing.assert_array_equal(np.asarray(valid6[:, 0]),
                                  [1, 1, 1, 1, 1, 1, 0, 0])


def test_lep_capacity_prevents_drops_under_uniform_routing():
    """cap from Eq. 2 with factor>=1 never drops uniformly-routed tokens."""
    t, k, slots = 24, 2, 8
    top_i = jnp.asarray(
        (np.arange(t * k) % slots).reshape(t, k), jnp.int32)
    cap = lep_capacity(t, k, slots, 1.0, align=1)
    _, valid = dispatch_indices(top_i, slots, cap)
    assert bool(valid.all())

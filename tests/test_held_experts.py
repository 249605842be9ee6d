"""DeepSeek-V3's MoE layer and rope in the program: the published sigmoid
group router (``noaux_tc``), an expert layer that holds some of the
router's experts, YaRN, and the routings counted onto held experts. The
softmax router and plain rope stay as they were, bit for bit."""
import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke
from repro.models import decode_loop, init_params, prefill
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, yarn_freqs

#: digests of the softmax router, ``moe_capacity`` and plain rope, computed
#: before the sigmoid router, the held experts and YaRN were added (the
#: inputs are those of ``_softmax_and_rope`` below)
BEFORE = {
    "route": "c1dc31ac9197589be931fb7e4fdd87446bb5c0bc3c9ce11b7ede6e95127cdb88",
    "moe_capacity":
        "acfd70514ffe7bab97ff4b56d99feea982c1c5b2eb1c99f5fb1d28a25ada7a02",
    "rope": "d79c43f9894b9a6eb8d2ee4a64a09932fc442a89b649167620304133b8b81309",
}


def _digest(*arrays):
    m = hashlib.sha256()
    for a in arrays:
        m.update(np.asarray(a).tobytes())
    return m.hexdigest()


def _softmax_and_rope():
    cfg = smoke("olmoe-1b-7b")
    p = jax.tree.map(lambda a: a[0], moe_mod.init_moe_params(
        jax.random.PRNGKey(0), cfg, 1, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, cfg.d_model))
    top_i, top_p, aux = moe_mod.route(p["router"], x, cfg)
    out, a = moe_mod.moe_capacity(p, x, cfg)
    xr = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(9) + 5, (2, 9))
    return {"route": _digest(top_i, top_p, aux),
            "moe_capacity": _digest(out, a["aux_loss"], a["dropped"]),
            "rope": _digest(apply_rope(xr, pos, 10000.0))}


def test_softmax_router_and_plain_rope_are_unchanged():
    assert _softmax_and_rope() == BEFORE


def test_yarn_at_factor_one_is_plain_rope():
    cfg = smoke("olmoe-1b-7b")
    assert cfg.yarn is None
    xr = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
    assert np.array_equal(apply_rope(xr, pos, 1e4, cfg.yarn),
                          apply_rope(xr, pos, 1e4))


def test_yarn_frequencies_published_values():
    """DeepSeek-V3's rope (dim 64, theta 1e4, factor 40 over 4096
    positions, beta 32/1): the correction range is dims 10-23, below it the
    frequencies are extrapolated, above it divided by 40, and the softmax
    scale carries mscale(40, 1)**2 = 1.874."""
    from repro.configs import get_config
    from repro.models.mla import softmax_scale
    cfg = get_config("deepseek-v3")
    f = np.asarray(yarn_freqs(64, 1e4, cfg.yarn))
    plain = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(f[11:23] < plain[11:23]) and np.all(f[11:23] > plain[11:23] / 40)
    m = 0.1 * math.log(40) + 1
    assert softmax_scale(cfg) == pytest.approx(m * m / math.sqrt(192))
    assert m * m == pytest.approx(1.874, abs=1e-3)


def noaux_tc(logits, bias, n_group, topk_group, k, scaling):
    """DeepSeek-V3's ``MoEGate`` (``noaux_tc``) in plain numpy."""
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = s + bias
    t, e = s.shape
    ids = np.zeros((t, k), np.int64)
    w = np.zeros((t, k))
    for i in range(t):
        groups = choice[i].reshape(n_group, -1)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(1)
        kept = np.argsort(-gscore, kind="stable")[:topk_group]
        masked = np.full(e, -np.inf)
        for g in kept:
            lo = g * (e // n_group)
            masked[lo: lo + e // n_group] = choice[i, lo: lo + e // n_group]
        ids[i] = np.argsort(-masked, kind="stable")[:k]
        w[i] = s[i, ids[i]] / s[i, ids[i]].sum() * scaling
    return ids, w


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sigmoid_router_matches_numpy_noaux_tc(seed):
    cfg = dataclasses.replace(smoke("deepseek-v3"), num_experts=16,
                              num_experts_per_tok=4, n_group=4, topk_group=2)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (32, cfg.d_model))
    w = jax.random.normal(ks[1], (cfg.d_model, 16)) / cfg.d_model ** 0.5
    bias = 0.1 * jax.random.normal(ks[2], (16,))
    top_i, top_p, aux = moe_mod.route(w, x, cfg, bias)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    ids, wts = noaux_tc(logits, np.asarray(bias, np.float64), 4, 2, 4, 2.5)
    assert np.array_equal(np.sort(np.asarray(top_i), 1), np.sort(ids, 1))
    order = np.argsort(np.asarray(top_i), 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(top_p), order, 1),
        np.take_along_axis(wts, np.argsort(ids, 1), 1), rtol=1e-5)
    assert float(aux) == 0.0


def _held_layer(seed=0, t=40):
    """The smoke DeepSeek layer widened to 8 experts, all held, with
    non-zero selection biases."""
    cfg = dataclasses.replace(smoke("deepseek-v3"), num_experts=8,
                              num_experts_per_tok=3, n_group=4, topk_group=2,
                              capacity_factor=8 / 3)
    p = jax.tree.map(lambda a: a[0], moe_mod.init_moe_params(
        jax.random.PRNGKey(seed), cfg, 1, jnp.float32))
    p["e_score_correction_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (8,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (t, cfg.d_model))
    return cfg, p, x


def _share(cfg, p, first, n):
    """The config and weights of a chip that holds experts
    ``first .. first + n - 1`` of the router's."""
    c = dataclasses.replace(cfg, num_experts=n, router_experts=cfg.num_experts,
                            first_held_expert=first, capacity_factor=n / 3)
    q = dict(p, **{w: p[w][first:first + n]
                   for w in ("w_gate", "w_up", "w_down")})
    return c, q


def test_held_shares_add_up_to_the_whole_layer():
    """Four chips of two experts each: their routed parts, with the shared
    expert (which every chip computes alike) counted once, are the layer
    that holds all eight. float32 on the CPU: only the order of the sums
    differs, so 1e-5."""
    cfg, p, x = _held_layer()
    whole, aux = moe_mod.moe_capacity(p, x, cfg)
    shared = moe_mod._shared_out(p, x)
    parts, held = [], []
    for first in (0, 2, 4, 6):
        c, q = _share(cfg, p, first, 2)
        out, a = moe_mod.moe_capacity(q, x, c)
        assert int(a["dropped"]) == 0
        parts.append(out - shared)
        held.append(a["held"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(sum(held)), np.asarray(aux["held"]))
    assert (np.asarray(aux["held"]) == 3).all()
    c, q = _share(cfg, p, 2, 2)
    out, _ = moe_mod.moe_capacity(q, x, c)
    ref, _ = moe_mod.moe_reference(q, x, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_param_count_is_the_leaves_of_init_params():
    cfg = smoke("deepseek-v3")
    assert cfg.d_ff != cfg.moe_d_ff and cfg.router_score == "sigmoid"
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert cfg.param_count() == sum(a.size for a in jax.tree.leaves(shapes))
    held = dataclasses.replace(cfg, router_experts=16, first_held_expert=4)
    shapes = jax.eval_shape(lambda k: init_params(k, held),
                            jax.random.PRNGKey(0))
    assert held.param_count() == sum(a.size for a in jax.tree.leaves(shapes))


def test_decode_loop_counts_routings_onto_held_experts():
    """Every live slot's routings onto held experts at every step, summed
    over the MoE layers: k a layer with every expert held, none where a
    slot is frozen; and fewer with a quarter of the experts held."""
    cfg = dataclasses.replace(smoke("deepseek-v3"), capacity_factor=8.0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    logits, caches = prefill(params, cfg, {"tokens": toks}, 24,
                             cache_dtype=jnp.float32)
    tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    out = decode_loop(params, cfg, tok0, caches, jnp.int32(12), 4,
                      steps_left=jnp.asarray([4, 2], jnp.int32))
    live, held = np.asarray(out[1]), np.asarray(out[3])
    moe_layers = cfg.num_layers - cfg.first_k_dense
    assert held.shape == live.shape
    assert np.array_equal(held, live * cfg.num_experts_per_tok * moe_layers)

    quarter = dataclasses.replace(cfg, num_experts=1, router_experts=4,
                                  first_held_expert=1)
    qp = jax.tree.map(lambda a: a, params)
    for w in ("w_gate", "w_up", "w_down"):
        qp["segments"]["moe"]["moe"][w] = \
            params["segments"]["moe"]["moe"][w][:, 1:2]
    logits, caches = prefill(qp, quarter, {"tokens": toks}, 24,
                             cache_dtype=jnp.float32)
    out = decode_loop(qp, quarter, tok0, caches, jnp.int32(12), 4)
    held = np.asarray(out[3])
    assert 0 < held.sum() and held.max() <= moe_layers

"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned family runs one forward + one train step on CPU, asserting output
shapes and no NaNs; decode-capable archs additionally run one serve_step."""
import jax
import jax.numpy as jnp
import pytest

from conftest import make_batch, smoke
from repro.configs import ASSIGNED_ARCHS, get_config, smoke_variant
from repro.models import decode_step, forward, init_params, prefill
from repro.train import OptConfig, make_train_step, init_opt_state

ALL_ARCHS = ASSIGNED_ARCHS + ["deepseek-r1", "deepseek-v3"]


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(name):
        if name not in cache:
            cfg = smoke(name)
            params = init_params(jax.random.PRNGKey(0), cfg)
            cache[name] = (cfg, params)
        return cache[name]

    return get


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_no_nan(arch, arch_state):
    cfg, params = arch_state(arch)
    b, s = 2, 32
    batch = make_batch(cfg, b, s)
    logits, aux = forward(params, cfg, batch)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits))), f"{arch}: NaN logits"
    assert not bool(jnp.isnan(aux["aux_loss"]))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step(arch, arch_state):
    cfg, params = arch_state(arch)
    batch = make_batch(cfg, 2, 32)
    step = make_train_step(cfg, OptConfig(total_steps=10, warmup_steps=2))
    opt = init_opt_state(params)
    new_params, opt, metrics = step(params, opt, batch)
    assert not bool(jnp.isnan(metrics["loss"])), f"{arch}: NaN loss"
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    changed = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert changed


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_serve_step(arch, arch_state):
    cfg, params = arch_state(arch)
    if not cfg.supports_decode:
        pytest.skip("encoder-only: decode shapes skipped (DESIGN.md §3)")
    b, s = 2, 32
    batch = make_batch(cfg, b, s)
    batch.pop("labels", None)
    logits, caches = prefill(params, cfg, batch, capacity=s + 8,
                             cache_dtype=jnp.float32)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    dl, caches = decode_step(params, cfg, tok, caches, jnp.int32(s))
    assert dl.shape == (b, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(dl))), f"{arch}: NaN decode logits"

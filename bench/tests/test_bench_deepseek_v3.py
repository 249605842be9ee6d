"""DeepSeek-V3 on one chip of an expert-parallel deployment
(``bench/configs/deepseek-v3.ep32.json``, its reference and counts), checked
on the CPU at a tiny size that keeps every mechanism: the sigmoid group
router with selection bias, 2 of 8 experts held from expert 2 on, a shared
expert, a dense width beside the expert width, and YaRN over a short
original context that the prompts run past."""
import copy
import dataclasses
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402
from bench import reference_deepseek_v3 as RD  # noqa: E402
from bench import weights as W  # noqa: E402
from bench.tests.test_bench_harness import (BENCH, TINY_LIMIT,  # noqa: E402
                                            TINY_TRAFFIC, alter_tokens)

DATA = os.path.join("bench", "tests", "data", "deepseek_v3")
TINY_FILE = os.path.join(ROOT, DATA, "tiny-deepseek-v3.json")
FULL_FILE = os.path.join(ROOT, "bench", "configs", "deepseek-v3.ep32.json")
CELL = "tiny-deepseek-v3.tiny"
PEAK = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))[
    "TPU v5 lite"]
SEED = 2**32 + 2**31 + 171


def tiny_conf():
    return harness.load_json(TINY_FILE)


@pytest.fixture(scope="module")
def ds(tmp_path_factory):
    """The tiny file added to a copy of ``bench/`` as the full-size one is
    added: a configuration file naming the new reference and counts, a
    mix, a limit, entries in ``BENCHMARK.json`` (the cell listed by
    ``expert_tokens_per_iter``). One sound run with the program's tracer on,
    whose Run is kept, and one with the tokens altered where they are
    produced."""
    from repro.serving import obs
    root = tmp_path_factory.mktemp("deepseek_v3")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(TINY_FILE, root / "bench/configs/tiny-deepseek-v3.json")
    (root / "bench/traffic/tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    (root / f"bench/limits/{CELL}.json").write_text(
        json.dumps({"max_logit_gap": {"limit": TINY_LIMIT}}))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "tiny-deepseek-v3", "source": "test",
                             "file": "bench/configs/tiny-deepseek-v3.json",
                             "reduced": ["n_routed_experts"], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-deepseek-v3",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] == "expert_tokens_per_iter":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell(CELL, root=str(root))
    runs = []

    class Kept(harness.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            runs.append(self)

    def run(after_build=None):
        return harness.run_cell(cell, SEED, 1.0, False, time.perf_counter(),
                                jax.devices(), root=str(root), peak=PEAK,
                                after_build=after_build)

    obs.reset()
    obs.enable(True)
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(harness, "Run", Kept)
            sound = run()
    finally:
        obs.enable(False)
    yield {"cell": cell, "sound": sound, "run": runs[0],
           "counts": list(obs.counters()), "altered": run(alter_tokens)}
    obs.reset()


def test_tiny_file_keeps_every_mechanism():
    cfg = harness.program_config(tiny_conf())
    assert (cfg.router_score, cfg.n_group, cfg.topk_group) == ("sigmoid", 4, 2)
    assert (cfg.num_experts, cfg.router_width, cfg.first_held_expert) \
        == (2, 8, 2)
    assert cfg.d_ff != cfg.expert_d_ff and cfg.yarn is not None
    assert cfg.yarn_original_max < 32      # TINY_TRAFFIC's documents alone


def test_full_file_is_the_published_math():
    """The program's fields are DeepSeek-V3's as the file states them."""
    conf = harness.load_json(FULL_FILE)
    cfg = harness.program_config(conf)
    rs = conf["rope_scaling"]
    assert (cfg.d_ff, cfg.expert_d_ff) == (conf["intermediate_size"],
                                           conf["moe_intermediate_size"])
    assert (cfg.router_score, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling) == (conf["scoring_func"], conf["n_group"],
                                    conf["topk_group"],
                                    conf["routed_scaling_factor"])
    assert cfg.yarn == (rs["factor"], rs["original_max_position_embeddings"],
                        rs["beta_fast"], rs["beta_slow"], rs["mscale"],
                        rs["mscale_all_dim"])
    assert (cfg.num_experts, cfg.router_width) \
        == (conf["n_routed_experts"], conf["published"]["n_routed_experts"])
    assert cfg.head_dim == conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]


def test_run_through_the_serve_path_is_correct(ds):
    out = ds["sound"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_run_fails_a_token_altered(ds):
    out = ds["altered"]
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_expert_tokens_per_iter_reads_the_programs_counter(ds):
    """Held routings of the window's decode calls over held experts, MoE
    layers and iterations; at most every token of the batch on each held
    expert."""
    cell, run = ds["cell"], ds["run"]
    reader = cell.readers["expert_tokens_per_iter"]
    value = reader.read(run)
    conf = cell.config
    lo, hi = run.window
    routed = sum(c.n for c in ds["counts"]
                 if c.name == "decode.held_routings" and lo <= c.t <= hi)
    iters = sum(c[2] for c in run.decode_calls)
    moe = conf["num_hidden_layers"] - conf["first_k_dense_replace"]
    assert routed > 0 and iters > 0
    assert value == pytest.approx(routed / (2 * moe * iters))
    assert 0 < value <= conf["deployment"]["decode_batch"]


def test_expert_tokens_per_iter_without_the_counter_is_none(ds, monkeypatch):
    """A program whose tracer keeps no counters (the parent's) gives None."""
    from repro.serving import obs
    monkeypatch.delattr(obs, "counters")
    assert ds["cell"].readers["expert_tokens_per_iter"].read(ds["run"]) is None


def test_prefill_then_decode_matches_the_reference():
    """Prefill of a prompt past YaRN's original context, then decode
    through the latent cache, against the reference's full forward, on
    logits. Both run float32 on the CPU; what differs is the order of sums
    and the decode's absorbed form, about 1e-5 on logits of magnitude 1, so
    1e-3 is rounding and a wrong mechanism (a router, a scale, a rope
    frequency) is far outside it."""
    from repro.models import decode_step, prefill
    conf = tiny_conf()
    cfg = harness.program_config(conf)
    shapes = harness.param_shapes(cfg)
    params = W.make_params(shapes, SEED, conf["weights"])
    ref = RD.Reference(conf, W.leaf_specs(shapes))
    s, n, cap = 20, 12, 40
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (2, s + n),
                                           0, conf["vocab_size"]), np.int32)
    rows = np.asarray([(i, p) for i in range(2) for p in range(s + n)],
                      np.int32)
    want = ref.logits(SEED, tokens, rows).reshape(2, s + n, -1)
    logits, caches = prefill(params, cfg, {"tokens": jnp.asarray(tokens[:, :s])},
                             cap, cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), want[:, :s], atol=1e-3,
                               rtol=1e-3)
    for i in range(n):
        step, caches = decode_step(params, cfg,
                                   jnp.asarray(tokens[:, s + i: s + i + 1]),
                                   caches, jnp.int32(s + i))
        np.testing.assert_allclose(np.asarray(step), want[:, s + i],
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"decode step {i}")


def test_reference_shares_add_up_to_the_whole_layer():
    """Four chips of two experts each of the tiny router: the reference's
    held parts add up to its layer that holds all eight (the shared expert
    is added by ``_layer`` once, outside them)."""
    conf = tiny_conf()
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    p = {"router": jax.random.normal(ks[0], (d, 8)) / d ** 0.5,
         "e_score_correction_bias": 0.1 * jax.random.normal(ks[1], (8,)),
         "w_gate": jax.random.normal(ks[2], (8, d, f)) / d ** 0.5,
         "w_up": jax.random.normal(ks[3], (8, d, f)) / d ** 0.5,
         "w_down": jax.random.normal(ks[4], (8, f, d)) / f ** 0.5}
    h = jax.random.normal(ks[5], (2, 30, d))

    def layer(first, held):
        c = dict(conf, n_routed_experts=held,
                 program={"fields": {"first_held_expert": first}})
        q = dict(p, **{w: p[w][first:first + held]
                       for w in ("w_gate", "w_up", "w_down")})
        with jax.default_matmul_precision("highest"):
            return RD._moe(q, h, RD.shape_of(c), False)

    whole = layer(0, 8)
    parts = sum(layer(first, 2) for first in (0, 2, 4, 6))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(layer(2, 2)))) > 0


def test_counts_read_the_held_experts_a_decode_iteration_can_reach():
    """With every held expert reachable, an iteration reads every weight
    but the embedding (only the live tokens' rows of it), at bf16: 8.42 of
    the 8.65 GB here. Each token's context adds its latent cache at bf16."""
    conf = harness.load_json(FULL_FILE)
    counts = harness.load_part(conf, "counts")
    d, v = conf["hidden_size"], conf["vocab_size"]
    _, one = counts.decode_iteration(conf, [100])
    assert one == 2 * (counts.param_count(conf) - v * d + d) \
        + counts.latent_bytes_per_token(conf) * 100
    assert 2 * counts.param_count(conf) == pytest.approx(8.65e9, rel=0.005)
    assert one == pytest.approx(8.42e9, rel=0.005)
    _, more = counts.decode_iteration(conf, [101])
    assert more - one == conf["num_hidden_layers"] * (512 + 64) * 2
    tiny = tiny_conf()
    _, b1 = counts.decode_iteration(tiny, [10])
    small = dict(tiny, num_experts_per_tok=1)
    _, b0 = counts.decode_iteration(small, [10])
    moe = tiny["num_hidden_layers"] - tiny["first_k_dense_replace"]
    assert b1 - b0 == moe * counts.expert_params(tiny) * 4

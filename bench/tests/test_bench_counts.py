"""Operations and bytes from shapes, and the table of peaks."""
import os
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import counts, harness  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
FILES = {c["name"]: c["file"] for c in BENCH["configs"]}
CONFIGS = tuple(FILES)
#: an MLA + MoE configuration with counts of its own, kept as test data
FILES["tiny-mla-moe"] = "bench/tests/data/mla_moe/tiny-mla-moe.json"


def conf(name):
    return harness.load_json(os.path.join(ROOT, FILES[name]))


def counts_of(c):
    """The configuration's own counts module."""
    return harness.load_part(c, "counts")


#: configurations counted by ``bench/counts.py`` (GQA), whose KV bytes the
#: test below checks
GQA = tuple(n for n in CONFIGS
            if conf(n).get("counts", harness.PARTS["counts"])
            == harness.PARTS["counts"])


@pytest.mark.parametrize("name", CONFIGS + ("tiny-mla-moe",))
def test_param_count_is_the_program_leaves(name):
    c = conf(name)
    shapes = harness.param_shapes(harness.program_config(c))
    assert counts_of(c).param_count(c) \
        == sum(a.size for a in jax.tree.leaves(shapes))


@pytest.mark.parametrize("name", GQA)
def test_decode_kv_bytes_at_the_configuration_dtype(name):
    """The program keeps its KV cache in float32; the roofline counts it at
    the configuration's bf16, 2 bytes a value."""
    c = conf(name)
    own = counts_of(c)
    assert c["torch_dtype"] == "bfloat16"
    per_token = (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
                 * (c["hidden_size"] // c["num_attention_heads"]) * 2)
    assert own.kv_bytes_per_token(c) == per_token
    _, b1 = own.decode_iteration(c, [100, 200])
    _, b2 = own.decode_iteration(c, [101, 200])
    assert b2 - b1 == per_token
    f32 = dict(c, torch_dtype="float32")
    assert own.kv_bytes_per_token(f32) == 2 * per_token


def test_moe_decode_reads_only_reachable_experts():
    c = conf("olmoe-1b-7b.half")
    _, one = counts.decode_iteration(c, [64])          # 8 experts reachable
    _, eight = counts.decode_iteration(c, [64] * 8)    # all 64
    _, nine = counts.decode_iteration(c, [64] * 9)     # still 64
    expert = counts.expert_params(c) * 2 * c["num_hidden_layers"]
    kv = counts.kv_bytes_per_token(c) * 64
    assert eight - one == 56 * expert + 7 * kv + 7 * c["hidden_size"] * 2
    assert nine - eight == kv + c["hidden_size"] * 2


def test_prefill_counts_no_reused_token():
    c = conf("granite-3-2b")
    reused = counts.prefill_flops(c, 512, 640)
    fresh_tail = sum(counts.token_flops(c, p + 1, head=False)
                     for p in range(512, 640)) + counts.head_flops(c)
    assert reused == fresh_tail
    assert counts.prefill_flops(c, 640, 640) == 0
    assert counts.prefill_flops(c, 0, 640) > 4 * reused


def test_mfu_reader_skips_reused_tokens():
    c = conf("granite-3-2b")
    mfu = harness.load_module(os.path.join(ROOT, "bench", "metrics",
                                           "mfu.py"), "bench_metric_mfu")
    peak = {"bf16_flops_per_s": 197e12}

    def run(reused):
        r = harness.Req(1, 640, 32, 0.0, 0, reused=reused, first=0.5)
        return harness.Run(conf=c, peak=peak, reqs=[r], window=(0.0, 1.0),
                           seconds=1.0, chips=1, counts=counts,
                           decode_calls=[])

    assert mfu.read(run(512)) == pytest.approx(
        100 * counts.prefill_flops(c, 512, 640) / 197e12)
    assert mfu.read(run(0)) > 4 * mfu.read(run(512))


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]


def test_least_seconds_is_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000, 50, peak) == 10.0
    assert counts.least_seconds(100, 50, peak) == 5.0

"""The harness: found by name from files, refuses a machine without a chip,
and its check fails a run whose timed path was broken."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time
import types

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

BENCH = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_found_by_name_from_files(name):
    cell = harness.find_cell(name)
    w = cell.workload
    assert cell.config["name"] == w["config"]
    assert hasattr(cell.generator, "Generator")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.readers[m["name"]].read)
    assert cell.limits and all(v["limit"] > 0 for v in cell.limits.values())
    assert set(cell.limits) <= {"max_logit_gap", "mean_logit_gap"}
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert sorted(conf["reduced"]) == sorted(cell.config["reduced"])
    assert conf["source"] == cell.config["source"]


def test_adding_a_cell_edits_no_file(tmp_path):
    """A new configuration, mix, metric and workload are new files and new
    entries: nothing that is there changes."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: open(p, "rb").read() for p in
              map(str, (tmp_path / "bench").rglob("*")) if os.path.isfile(p)}
    conf = harness.load_json(os.path.join(ROOT, "bench", "configs",
                                          "granite-3-2b.json"))
    (tmp_path / "bench/configs/new-model.json").write_text(
        json.dumps(dict(conf, name="new-model")))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"generator": "waves", "clients": 4,
         "suffix_tokens": {"min": 32, "max": 64, "step": 32},
         "output_tokens": {"min": 4, "max": 8}}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (tmp_path / "bench/limits/new-model.new-mix.json").write_text(
        json.dumps({"max_logit_gap": {"limit": 1.0}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="new-model",
                                 file="bench/configs/new-model.json"))
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("new-model.new-mix", root=str(tmp_path))
    assert cell.config["name"] == "new-model"
    assert cell.traffic["clients"] == 4
    assert cell.readers["new_metric"].read(None) == 42.0
    assert len(cell.generator.Generator(cell.traffic, 1, 100).wave(0)) == 4
    old = harness.find_cell("granite.rag-prefix", root=str(tmp_path))
    assert "new_metric" not in old.readers
    for p, data in before.items():
        assert open(p, "rb").read() == data


MLA_MOE = os.path.join("bench", "tests", "data", "mla_moe")
PEAK = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))[
    "TPU v5 lite"]
#: the per-layer metrics that read a cell's own counts module
COUNTED = ("decode_roofline", "decode_mfu", "mfu")


@pytest.fixture(scope="module")
def mla_moe(tmp_path_factory):
    """A tiny MLA + MoE configuration (the program's ``deepseek-r1`` with
    one dense and one MoE layer, one shared expert, softmax top-k) added to
    a copy of ``bench/`` as new files only: its configuration file with
    program ``fields`` and ``weights`` rules and its own reference and
    counts modules (``bench/tests/data/mla_moe``, where the file names
    them), a mix, a limit and entries in ``BENCHMARK.json``. One sound run,
    whose Recorder is kept, and one with the tokens altered where they are
    produced."""
    root = tmp_path_factory.mktemp("mla_moe")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: open(p, "rb").read() for p in
              map(str, (root / "bench").rglob("*")) if os.path.isfile(p)}
    shutil.copytree(os.path.join(ROOT, MLA_MOE), root / MLA_MOE,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(root / MLA_MOE / "tiny-mla-moe.json",
                root / "bench/configs/tiny-mla-moe.json")
    (root / "bench/traffic/tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    (root / "bench/limits/tiny-mla-moe.tiny.json").write_text(
        json.dumps({"max_logit_gap": {"limit": TINY_LIMIT}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny-mla-moe", "source": "test",
                             "file": "bench/configs/tiny-mla-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-mla-moe.tiny",
                               "config": "tiny-mla-moe", "traffic": "tiny",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("tiny-mla-moe.tiny", root=str(root))
    recorders = []

    class Kept(harness.Recorder):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            recorders.append(self)

    def run(after_build=None):
        return harness.run_cell(cell, 2**31 + 77, 1.0, False,
                                time.perf_counter(), jax.devices(),
                                root=str(root), peak=PEAK,
                                after_build=after_build)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Recorder", Kept)
        sound = run()
    return {"root": root, "before": before, "cell": cell, "sound": sound,
            "recorder": recorders[0], "altered": run(alter_tokens)}


def test_new_architecture_arrives_as_new_files_only(mla_moe):
    cell = mla_moe["cell"]
    cfg = harness.program_config(cell.config)
    assert (cfg.attention_kind, cfg.first_k_dense, cfg.num_experts,
            cfg.num_shared_experts, cfg.head_dim, cfg.kv_lora_rank) \
        == ("mla", 1, 8, 1, 48, 32)
    counts = harness.load_part(cell.config, "counts", str(mla_moe["root"]))
    shapes = harness.param_shapes(cfg)
    assert counts.param_count(cell.config) \
        == sum(a.size for a in jax.tree.leaves(shapes))
    for p, data in mla_moe["before"].items():
        assert open(p, "rb").read() == data


def test_new_architecture_reads_the_model_step_through_its_counts(mla_moe):
    """The model-step metrics join the new cell with no entry edited, and
    read its run's decode calls and prefills through its own counts."""
    cell, rec = mla_moe["cell"], mla_moe["recorder"]
    assert set(COUNTED) <= set(cell.readers)
    own = os.path.realpath(mla_moe["root"] / cell.config["counts"])
    assert rec.counts.__file__ == own
    from bench import counts as gqa
    assert rec.counts.decode_iteration(cell.config, [100, 200]) \
        != gqa.decode_iteration(cell.config, [100, 200])
    calls = rec.decode_calls
    t0, t1 = calls[0][0], calls[-1][1]
    run = harness.Run(
        conf=cell.config, peak=PEAK, rec=rec, reqs=list(rec.reqs.values()),
        window=(t0, t1), seconds=t1 - t0, chips=1, counts=rec.counts,
        decode_calls=calls, traced_calls=calls, traced=(t0, t1),
        trace=types.SimpleNamespace(device_s_in={"decode": rec.busy_s(
            t0, t1, ("decode",))}))
    values = {m: cell.readers[m].read(run) for m in COUNTED}
    assert all(v is not None and 0 < v <= 100 for v in values.values()), \
        values
    prefill = sum(rec.counts.prefill_flops(cell.config, r.reused,
                                           r.prompt_len)
                  for r in run.reqs if t0 <= r.first <= t1)
    decode = sum(c[3] for c in calls)
    assert values["mfu"] == pytest.approx(
        100 * (prefill + decode) / (run.seconds * PEAK["bf16_flops_per_s"]))


def test_new_architecture_run_is_correct(mla_moe):
    out = mla_moe["sound"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_new_architecture_fails_a_token_altered(mla_moe):
    out = mla_moe["altered"]
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


def test_weight_rules_cover_leaves_by_name():
    from bench import weights as W
    key = W.base_key(3)
    x = jax.random.normal(key, (16,))
    path = "segments/moe/attn/q_ln"
    with pytest.raises(ValueError, match=path):
        W.draw(key, path, (16,), "float32")
    assert (W.draw(key, path, (16,), "float32", {"q_ln": "gain"})
            == 1.0 + 0.1 * x).all()
    assert (W.draw(key, "segments/moe/moe/bias", (16,), "float32",
                   {"bias": 0.5}) == 0.5 * x).all()
    with pytest.raises(ValueError, match="gainz"):
        W.draw(key, path, (16,), "float32", {"q_ln": "gainz"})


def test_program_fields_name_a_missing_field():
    conf = dict(TINY, program={"arch": "granite-3-2b",
                               "fields": {"num_dragons": 3}})
    with pytest.raises(ValueError, match="num_dragons"):
        harness.program_config(conf)


@pytest.mark.parametrize("field,key", [("num_layers", "num_hidden_layers"),
                                       ("dtype", "torch_dtype"),
                                       ("d_ff", "intermediate_size")])
def test_program_fields_refuse_a_size_the_file_states(field, key):
    """A field may not set what a key of the file states: the reference,
    the counts and the audit of ``reduced`` read the key."""
    conf = dict(TINY, program={"arch": "granite-3-2b",
                               "fields": {field: TINY[key]}})
    with pytest.raises(ValueError, match=key):
        harness.program_config(conf)
    head = dict(TINY, program={"arch": "granite-3-2b",
                               "fields": {"head_dim": 48}})
    assert harness.program_config(head).head_dim == 48


@pytest.mark.parametrize("rel", ["/etc/counts.py", "bench/../../counts.py"])
def test_parts_lie_inside_the_checkout(rel):
    with pytest.raises(ValueError, match="outside the checkout"):
        harness.load_part(dict(TINY, counts=rel), "counts")


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite.rag-prefix",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run_cli(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# --- the output check, driven at a size a test run holds ------------------

TINY = {"name": "tiny", "hidden_size": 128, "intermediate_size": 256,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 512, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
        "torch_dtype": "float32", "program": {"arch": "granite-3-2b"},
        "deployment": {"prefill_engines": 2, "decode_engines": 1,
                       "decode_batch": 4, "decode_chunk": 4,
                       "continuous_batching": True, "prefill_chunk": 8,
                       "ems_block_tokens": 8, "ems_nodes": 2}}
TINY_TRAFFIC = {"generator": "waves", "clients": 6,
                "documents": {"count": 2, "tokens": 32, "zipf_s": 1.0},
                "suffix_tokens": {"min": 8, "max": 24, "step": 8},
                "output_tokens": {"min": 3, "max": 9}}
#: this size's own limit: in float32 on the CPU the program reads 0 to
#: rounding against the reference (0.0), the fp8 control 0.005-0.02
TINY_LIMIT = 0.001


def tiny_run(after_build=None, control=False):
    cell = dataclasses.replace(
        harness.find_cell("granite.rag-prefix"), config=TINY,
        traffic=TINY_TRAFFIC, limits={"max_logit_gap": {"limit": TINY_LIMIT}})
    return harness.run_cell(cell, 2**31 + 99, 1.0, False, time.perf_counter(),
                            jax.devices(), after_build=after_build,
                            control=control)


def alter_tokens(system):
    """Break the timed path: every decode call's first emitted token of each
    slot is replaced as the device loop returns it."""
    for eng in system.pool.engines:
        get = eng._get_loop

        def broken(width, get=get):
            loop = get(width)

            def run(*a):
                em, *rest = loop(*a)
                return ((em.at[:, 0].add(1)) % TINY["vocab_size"], *rest)
            return run
        eng._get_loop = broken


def test_check_passes_a_sound_run_and_fails_the_control():
    out = tiny_run(control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["control"]["max_logit_gap"] > TINY_LIMIT \
        >= out["checks"]["max_logit_gap"]["value"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                   "output_tok_s", "setup_s"}


def test_check_fails_a_token_altered_where_it_is_produced():
    out = tiny_run(after_build=alter_tokens)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > TINY_LIMIT

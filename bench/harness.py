"""One run of one cell: set-up, the measured window, the output check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  sizes as run, deployment, source;
* ``bench/traffic/<traffic>.json`` parameters of a mix; its ``generator``
  names the code ``bench/traffic/<generator>.py`` that reads them;
* ``bench/metrics/<metric>.py``   a reader, ``read(run) -> float | None``;
* ``bench/limits/<workload>.json`` the limit of each number compared.

A configuration file names the parts that depend on its architecture, so
that a new architecture arrives as new files only: ``program.fields``
(``ModelConfig`` fields set verbatim after the key mapping of
:func:`program_config`, for sizes that no key of the file states),
``reference`` and ``counts`` (modules, as paths inside the checkout from
its root; ``PARTS`` gives the defaults) and ``weights``
(rules for leaves the default draws do not cover, ``bench/weights.py``).

Times are host clock (``time.perf_counter``) around calls that end on the
device: ``PrefillEngine.run`` ends in a ``device_get`` of the first token,
``DecodeEngine.step_chunk`` in one of the emitted tokens. A token is stamped
when that call returns.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import re
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: requests compared with the reference after the window: the one with the
#: most served tokens and the rest drawn from the seed
CHECK_REQUESTS = 4
#: span kinds of the benchmark's host spans (and trace annotations)
SPANS = ("prefill", "handoff", "decode")
#: modules a configuration file may name, and the one each defaults to
PARTS = {"reference": "bench/reference.py", "counts": "bench/counts.py"}


# ---------------------------------------------------------------------------
# Files, found by name
# ---------------------------------------------------------------------------


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_part(conf: dict, part: str, root: str = ROOT):
    """The module that the configuration names for ``part``, a path from
    the checkout's root, or the default of ``PARTS``."""
    rel = conf.get(part, PARTS[part])
    top = os.path.realpath(root)
    path = os.path.realpath(os.path.join(top, rel))
    if os.path.commonpath([top, path]) != top:
        raise ValueError(f"{conf['name']}: {part} {rel!r} lies outside the "
                         f"checkout")
    return load_module(path, f"bench_{part}_" + re.sub(r"\W", "_", rel))


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with every file it names."""
    workload: dict
    config: dict
    traffic: dict
    generator: Any
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Any]
    limits: dict


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, confs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    generator = load_module(os.path.join(root, "bench", "traffic",
                                         traffic["generator"] + ".py"),
                            "bench_traffic_" + traffic["generator"])

    def here(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if here(m)]
    per_layer = [m for m in bench["per_layer"] if here(m)]
    readers = {m["name"]: load_module(
        os.path.join(root, "bench", "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_")) for m in per_layer}
    limits = load_json(os.path.join(root, "bench", "limits", name + ".json"))
    return Cell(w, config, traffic, generator, e2e, per_layer, readers,
                limits)


# ---------------------------------------------------------------------------
# The program, built from a configuration file
# ---------------------------------------------------------------------------


#: DeepSeek's keys that set a ModelConfig field of their own, where stated
DEEPSEEK_KEYS = {"n_routed_experts": "num_experts",
                 "n_shared_experts": "num_shared_experts",
                 "first_k_dense_replace": "first_k_dense",
                 "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
                 "qk_nope_head_dim": "qk_nope_head_dim",
                 "qk_rope_head_dim": "qk_rope_head_dim",
                 "v_head_dim": "v_head_dim"}


def program_config(conf: dict):
    """The program's registered config of the file's architecture, with
    every size the file states put in: the file is what is run. The keys
    below and ``DEEPSEEK_KEYS`` are mapped first; then ``program.fields``
    sets ``ModelConfig`` fields verbatim, for sizes that no key of the file
    states. A field that a stated key sets is refused: change the key."""
    from repro.configs import get_config
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    base = get_config(conf["program"]["arch"])
    # field: (the file's key that states it, the value the program takes)
    mapped = {
        "num_layers": ("num_hidden_layers", conf["num_hidden_layers"]),
        "d_model": ("hidden_size", d),
        "d_ff": ("intermediate_size", conf["intermediate_size"]),
        "num_heads": ("num_attention_heads", h),
        "num_kv_heads": ("num_key_value_heads",
                         conf["num_key_value_heads"]),
        "head_dim": ("head_dim", conf.get("head_dim") or d // h),
        "vocab_size": ("vocab_size", conf["vocab_size"]),
        "rope_theta": ("rope_theta", conf["rope_theta"]),
        "norm_eps": ("rms_norm_eps", conf["rms_norm_eps"]),
        "tie_embeddings": ("tie_word_embeddings",
                           conf["tie_word_embeddings"]),
        "num_experts": ("num_experts", conf.get("num_experts", 0)),
        "num_experts_per_tok": ("num_experts_per_tok",
                                conf.get("num_experts_per_tok", 0)),
        "qk_norm": ("qk_norm", conf.get("qk_norm") == "per_head"),
        "dtype": ("torch_dtype", conf["torch_dtype"]),
        "capacity_factor": ("capacity_factor", conf.get(
            "capacity_factor", base.capacity_factor)),
    }
    mapped.update({f: (k, conf[k]) for k, f in DEEPSEEK_KEYS.items()
                   if k in conf})
    cfg = dataclasses.replace(base, name=conf["name"],
                              **{f: v for f, (_, v) in mapped.items()})
    fields = conf["program"].get("fields", {})
    known = {f.name for f in dataclasses.fields(cfg)}
    for name in fields:
        if name not in known:
            raise ValueError(f"{conf['name']}: program.fields names "
                             f"{name!r}, which ModelConfig lacks")
        key = mapped.get(name, (None,))[0]
        if key in conf:
            raise ValueError(f"{conf['name']}: program.fields sets {name!r}, "
                             f"which the file's key {key!r} states")
    return dataclasses.replace(cfg, **fields)


def param_shapes(cfg):
    import jax
    from repro.models import init_params
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))


def build_system(params, cfg, deployment: dict, capacity: int):
    """The ServingSystem as ``launch/serve.build`` wires it."""
    from repro.mempool import EMSService, MemoryPool
    from repro.serving import ServingSystem
    cc = EMSService(MemoryPool(n_nodes=deployment["ems_nodes"]),
                    block_tokens=deployment["ems_block_tokens"],
                    model_tag=cfg.name)
    system = ServingSystem(
        params, cfg, prefill_engines=deployment["prefill_engines"],
        decode_batch=deployment["decode_batch"], capacity=capacity,
        decode_engines=deployment["decode_engines"], context_cache=cc,
        decode_chunk=deployment["decode_chunk"],
        continuous_batching=deployment["continuous_batching"] or None,
        prefill_chunk=deployment["prefill_chunk"])
    return system, cc


def capacity_of(gen) -> int:
    """KV slot capacity as ``launch/serve.capacity_for`` derives it: the
    longest prompt plus the most new tokens, plus 8."""
    return gen.max_prompt + gen.max_new + 8


# ---------------------------------------------------------------------------
# Host spans and token stamps
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    rid: int
    prompt_len: int
    max_new: int
    sent: float
    wave: int
    first: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    reused: int = 0
    prefill_s: float = 0.0
    handoff_s: float = 0.0


class Recorder:
    """Wraps the engine calls of one ServingSystem, from outside it."""

    def __init__(self, conf: dict, peak: Optional[dict], root: str = ROOT):
        self.counts = load_part(conf, "counts", root)
        self.conf = conf
        self.peak = peak
        self.reqs: Dict[int, Req] = {}
        self.spans: List[tuple] = []        # (kind, t0, t1)
        # per decode call: (t0, t1, iterations, operations, roofline s)
        self.decode_calls: List[tuple] = []
        self.annotate = False               # trace annotations on

    def _span(self, kind: str, fn: Callable, *a, **kw):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation("bench." + kind):
                t0 = time.perf_counter()
                out = fn(*a, **kw)
                t1 = time.perf_counter()
        else:
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            t1 = time.perf_counter()
        self.spans.append((kind, t0, t1))
        return out, t0, t1

    def attach(self, system) -> None:
        for eng in system.prefills:
            eng.run = self._prefill(eng.run)
        for eng in system.pool.engines:
            eng.step_chunk = self._decode(eng.step_chunk)
        system.transfer.transfer = self._handoff(system.transfer.transfer,
                                                 lambda a, kw: kw.get("rid"))
        system.pool.add = self._handoff(system.pool.add,
                                        lambda a, kw: a[5].rid)

    def _prefill(self, run):
        def wrapped(req):
            (first, caches, res), t0, t1 = self._span("prefill", run, req)
            r = self.reqs.get(req.rid)
            if r is not None:
                r.first = t1
                r.stamps.append(t1)
                r.reused = res.reused_tokens
                r.prefill_s += t1 - t0
            return first, caches, res
        return wrapped

    def _handoff(self, fn, rid_of):
        def wrapped(*a, **kw):
            out, t0, t1 = self._span("handoff", fn, *a, **kw)
            r = self.reqs.get(rid_of(a, kw))
            if r is not None:
                r.handoff_s += t1 - t0
            return out
        return wrapped

    def _decode(self, step_chunk):
        def wrapped(*a, **kw):
            (finished, iter_log), t0, t1 = self._span(
                "decode", step_chunk, *a, **kw)
            ops_sum, least = 0.0, 0.0
            for live, _done, tokens_by_rid, _masked in iter_log:
                ctx = []
                for rid in live:
                    r = self.reqs.get(rid)
                    if r is not None:
                        ctx.append(r.prompt_len + len(r.stamps))
                for rid, n in tokens_by_rid.items():
                    r = self.reqs.get(rid)
                    if r is not None:
                        r.stamps.extend([t1] * n)
                ops, nbytes = self.counts.decode_iteration(self.conf, ctx)
                ops_sum += ops
                if self.peak is not None:
                    least += self.counts.least_seconds(ops, nbytes, self.peak)
            self.decode_calls.append((t0, t1, len(iter_log), ops_sum, least))
            return finished, iter_log
        return wrapped

    def busy_s(self, lo: float, hi: float, kinds=SPANS) -> float:
        """Union of the spans of ``kinds`` inside [lo, hi]."""
        ivs = sorted((max(t0, lo), min(t1, hi)) for k, t0, t1 in self.spans
                     if k in kinds and t1 > lo and t0 < hi)
        total, end = 0.0, lo
        for a, b in ivs:
            if b > end:
                total += b - max(a, end)
                end = b
        return total


# ---------------------------------------------------------------------------
# Statistics
# ---------------------------------------------------------------------------


def p90(values: List[float]) -> float:
    """90th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 90))


def tpot(r: Req) -> Optional[float]:
    if len(r.stamps) < 2:
        return None
    return (r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


class Run:
    """What the metric readers read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _device_info(devices, chips: int) -> dict:
    d = devices[0]
    peaks = []
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": max(peaks) if peaks else None}


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else one fixed directory inside the checkout. Every program
    is kept, however short its compile, so a second run compiles none."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """Backend compilations seen in this process (a cache hit included)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, root: str = ROOT,
             peak: Optional[dict] = None,
             after_build: Optional[Callable] = None,
             control: bool = False) -> dict:
    """Set up, measure for ``seconds``, check. Returns the result line.
    ``after_build(system)`` may break the system under test (the tests of
    the check do); ``control`` also reads the fp8 control's gap."""
    import jax
    from bench import weights as W

    conf = cell.config
    chips = cell.workload["chips"]
    compiles = CompileCount()
    cfg = program_config(conf)
    shapes = param_shapes(cfg)
    params = W.make_params(shapes, seed, conf.get("weights"))
    jax.block_until_ready(params)
    log(f"weights: {cfg.name}, {sum(a.size for a in jax.tree.leaves(params))} "
        f"parameters from seed {seed}, "
        f"{time.perf_counter() - t_start:.3f} s since start")

    gen = cell.generator.Generator(cell.traffic, seed, conf["vocab_size"])
    capacity = capacity_of(gen)
    system, ems = build_system(params, cfg, conf["deployment"], capacity)
    if after_build is not None:
        after_build(system)
    rec = Recorder(conf, peak, root)
    rec.attach(system)
    from repro.serving import Request

    # Set-up: the shared documents into EMS, then one wave of every shape.
    for i, doc in enumerate(gen.documents):
        system.prefills[i % len(system.prefills)].run(
            Request(-(i + 1), doc, 1))
    warm = gen.warmup()
    results = system.serve([Request(r.rid, r.prompt, r.max_new)
                            for r in warm])
    if len(results) != len(warm):
        raise RuntimeError("warm-up lost a request")
    compiled_in_setup = compiles.n
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s; capacity {capacity}; "
        f"{cell.generator.describe(gen)}; {compiled_in_setup} compiles")

    # The window: closed-loop waves until the clock passes ``seconds``; the
    # wave running then finishes, and the window closes with it, so every
    # rate is over whole waves: all their requests, tokens and time.
    trace_dir = os.path.join(root, ".bench_trace", cell.workload["name"])
    t0 = time.perf_counter()
    end = t0 + seconds
    wave = 0
    finished: Dict[int, Any] = {}
    traced = None
    while time.perf_counter() < end:
        for res in serve_wave(system, rec, gen.wave(wave), wave):
            finished[res.rid] = res
        wave += 1
    t_done = time.perf_counter()
    compiled_in_window = compiles.n - compiled_in_setup
    # With --trace 1, one more wave under the profiler, after the window, so
    # that the tracer's cost stays out of every host-clock number.
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.annotate = True
        jax.profiler.start_trace(trace_dir)
        t_tr = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.traced_wave"):
            serve_wave(system, rec, gen.wave(wave), wave)
        traced = (t_tr, time.perf_counter())
        jax.profiler.stop_trace()
        rec.annotate = False
    device = _device_info(devices, chips)
    prompts = {r.rid: r for w in range(wave) for r in gen.wave(w)}

    # Requests and tokens of the window.
    reqs = [r for r in rec.reqs.values() if r.wave < wave]
    for r in reqs:
        finished.setdefault(r.rid, None)
    failed = 0
    for r in reqs:
        res = finished.get(r.rid)
        ok = (res is not None and not res.shed
              and len(res.tokens) == r.max_new
              and all(0 <= t < conf["vocab_size"] for t in res.tokens)
              and res.nonfinite_logits == 0 and r.first is not None)
        failed += not ok
    ttft = [r.first - r.sent for r in reqs if r.first is not None]
    tpots = [x for x in (tpot(r) for r in reqs) if x is not None]
    window_s = t_done - t0
    tokens = sum(len(r.stamps) for r in reqs)
    log(f"window: {window_s:.3f} s ({seconds} s, closed at the end of the "
        f"wave then running), {wave} waves, {len(reqs)} requests sent, "
        f"{failed} failed; ttft tail over {len(ttft)} requests, tpot tail "
        f"over {len(tpots)}; {tokens} output tokens; "
        f"{compiled_in_window} backend compiles inside the window")

    run = Run(conf=conf, peak=peak, rec=rec, reqs=reqs, window=(t0, t_done),
              seconds=window_s, chips=chips, trace=None, traced=traced,
              counts=rec.counts,
              decode_calls=[c for c in rec.decode_calls
                            if t0 <= c[0] and c[1] <= t_done],
              traced_calls=[c for c in rec.decode_calls
                            if traced and traced[0] <= c[0]
                            and c[1] <= traced[1]])
    if trace:
        from bench import trace_reduce
        run.trace = trace_reduce.reduce_dir(trace_dir)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    metrics: Dict[str, dict] = {}
    if not trace:
        values = {
            "ttft_p90_ms": 1e3 * p90(ttft) if ttft else None,
            "tpot_p90_ms": 1e3 * p90(tpots) if tpots else None,
            "output_tok_s": tokens / window_s / chips,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # The output check, once the program's state is freed.
    served = {rid: res for rid, res in finished.items()
              if res is not None and not res.shed and res.tokens}
    del system, ems, params
    gc.collect()
    ref = load_part(conf, "reference", root).Reference(
        conf, W.leaf_specs(shapes))
    sample = pick_sample(served, prompts, seed)
    gaps, control_gaps = compare(ref, seed, sample, prompts, served,
                                 capacity, control)
    checks = {name: {"value": gaps.get(name), "limit": lim["limit"]}
              for name, lim in cell.limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    checks["window_compiles"] = {"value": compiled_in_window, "limit": 0}
    correct = len(reqs) > 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    for name, v in gaps.items():
        if name not in checks:
            log(f"reading {name}: {v} (not compared)")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out_line = {"correct": bool(correct), "attempted": len(reqs),
                "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        out_line["breakdown"] = run.trace.breakdown
    if control:
        out_line["readings"] = gaps
        out_line["control"] = control_gaps
    out_line["checks"] = checks
    return out_line


def serve_wave(system, rec: Recorder, batch, wave: int):
    """Send one wave, all its requests at once; returns when all finished."""
    from repro.serving import Request
    sent = time.perf_counter()
    for r in batch:
        rec.reqs[r.rid] = Req(r.rid, len(r.prompt), r.max_new, sent, wave)
    return system.serve([Request(r.rid, r.prompt, r.max_new) for r in batch])


def pick_sample(served: dict, prompts: dict, seed: int) -> List[int]:
    """The request with the most served tokens, then others drawn from the
    seed, CHECK_REQUESTS in all."""
    rids = sorted(served)
    if not rids:
        return []
    longest = max(rids, key=lambda r: (len(served[r].tokens),
                                       len(prompts[r].prompt), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed), 3])
    k = min(CHECK_REQUESTS - 1, len(rest))
    return [longest] + sorted(rng.choice(rest, k, replace=False).tolist())


def compare(ref, seed: int, sample: List[int], prompts: dict, served: dict,
            capacity: int, control: bool):
    """How far the served tokens' reference logits lie below the
    reference's best: the widest gap and the mean over the served tokens.
    With ``control``, the same for the tokens the fp8 control would have
    picked at the same positions."""
    from bench import reference as R
    if not sample:
        return {}, None
    tokens, rows, picked = R.check_rows(
        [prompts[r].prompt for r in sample],
        [served[r].tokens for r in sample], capacity)
    t = time.perf_counter()
    logits = ref.logits(seed, tokens, rows)

    def numbers(gaps):
        return {"max_logit_gap": float(gaps.max()),
                "mean_logit_gap": float(gaps.mean())}

    gaps = numbers(R.served_gaps(logits, picked))
    control_gaps = None
    if control:
        low = ref.logits(seed, tokens, rows, precision="fp8")
        control_gaps = numbers(R.served_gaps(logits, low.argmax(-1)))
    log(f"reference: {len(sample)} requests, {len(picked)} served tokens, "
        f"{time.perf_counter() - t:.3f} s")
    return gaps, control_gaps

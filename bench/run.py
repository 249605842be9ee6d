"""Run one cell of the benchmark once, on the chips of this machine.

  python bench/run.py --workload granite.rag-prefix --seed 7 \
      --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are looked up by name
from ``BENCHMARK.json`` (see ``bench/harness.py``). The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the reference, beside its limit. The
same numbers are the last lines of stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    import jax
    devices = jax.devices()
    chips = cell.workload["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        harness.log(f"bench: {args.workload} needs {chips} TPU chip(s); "
                    f"JAX found {len(devices)} {devices[0].platform} "
                    f"device(s)")
        return 3
    peaks = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks:
        harness.log(f"bench: no peaks for device kind {kind!r} in "
                    f"bench/peaks.json")
        return 3
    harness.log(f"device: {len(devices)}x {devices[0].platform} {kind}; "
                f"compile cache {harness.enable_cache(ROOT)}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START, devices,
                              peak=peaks[kind])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

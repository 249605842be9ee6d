"""Readings that set a cell's limit: the program's gap and the control's.

  python bench/control.py --workload olmoe.chat-decode --seconds 5 \
      --seeds 11 12 13

For each seed, in one process: a run of the cell as ``bench/run.py`` makes
it (short window, same load), then the widest gap of the served tokens below
the float32 reference's best (the program's reading) and the widest gap of
the tokens that the fp8 control would have picked at the same positions
(the control's reading). Both come from the reference module that the
cell's configuration names (``bench/harness.py``, ``load_part``). One JSON line per seed on stdout. The benchmark's
own runs never run the control.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log("control: needs a TPU")
        return 3
    peaks = harness.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    harness.enable_cache(ROOT)
    for seed in args.seeds:
        t = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t, devices,
                               peak=peaks[devices[0].device_kind],
                               control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": out["readings"],
                          "control": out["control"],
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "metrics": out["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

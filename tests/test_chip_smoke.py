"""chip_smoke.py on the CPU: its serve phase passes its own checks on the
smoke config, and its entry point refuses to run without a TPU."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402

SMOKE_ARGV = ("--arch", "granite-3-2b",          # smoke_variant: no --full
              "--n-requests", "4", "--prompt-len", "64",
              "--shared-prefix", "32", "--max-new", "8",
              "--prefill-engines", "2", "--decode-engines", "1",
              "--decode-batch", "2", "--decode-chunk", "4")


def test_serve_phase_passes_its_checks_on_smoke_config():
    out = chip_smoke.serve_phase(SMOKE_ARGV)
    assert [w["tokens"] for w in out["waves"]] == [4 * 8, 4 * 8]
    # float32 smoke weights: the reused prefill, a fresh prefill and
    # forward agree to rounding.
    for name in ("logit_err", "fresh_err", "reuse_err"):
        assert set(out[name]) == {0, 1}
        assert max(out[name].values()) < 1e-4


def test_main_without_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--four-chips"]) != 0
    assert '"ok"' not in capsys.readouterr().out

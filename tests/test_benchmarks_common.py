"""benchmarks/common.py helpers that guard how benchmarks reach devices."""
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common  # noqa: E402


def test_ensure_dryrun_raises_when_the_child_fails(monkeypatch, tmp_path):
    """A failed dry-run child is an error, not a silent fallback to the
    placeholder decode costs."""
    monkeypatch.setattr(common, "DRYRUN_DIR", str(tmp_path))
    monkeypatch.setattr(subprocess, "run", lambda cmd, **kw:
                        subprocess.CompletedProcess(cmd, 3, "", "no device"))
    with pytest.raises(RuntimeError, match="exited 3"):
        common.ensure_dryrun("granite-3-2b", "decode_32k")

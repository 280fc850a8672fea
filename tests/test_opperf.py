"""opperf harness smoke (reference: upstream's opperf package, SURVEY.md §6)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_opperf_smoke(tmp_path):
    out = tmp_path / "r.json"
    env = dict(os.environ, PYTHONPATH=REPO)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "opperf.py"),
         "--cpu", "--ops", "relu,softmax,FullyConnected",
         "--json", str(out)],
        capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    rows = json.loads(out.read_text())
    assert len(rows) == 3
    for r in rows:
        assert r["eager_ms"] > 0 and r["fused_ms"] >= 0

"""The benchmark cells PR 28 adds, rehearsed end to end through
``chipbench/run.py --rehearse`` on the CPU (tiny sizes, every value null);
``bert_base.pretrain_dp4``'s files wait in the tree for a ``benchmark`` PR
(PERF.md section 7.1) and are rehearsed with it."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("cell,trace", [("deepseek_v32.decode_long", 1),
                                        ("deepseek_v32.decode_long", 0),
                                        ("bert_base.pretrain_dp4", 1)])
def test_run_py_rehearses_the_cell(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "2147485999", "--seconds", "3", "--trace", str(trace),
         "--rehearse"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    names = set(result["metrics"])
    if cell.startswith("deepseek") and trace:
        assert {"routed_held_share.dsv32", "index_selected_share.dsv32",
                "experts_touched.dsv32", "expert_load_max.dsv32",
                "batch_occupancy.dsv32", "compile_s.dsv32",
                "loop_offcpu_us.dsv32", "emit_to_wire_us.dsv32",
                "wire_write_us.dsv32", "writer_batch_tokens.dsv32"} <= names
    elif cell.startswith("deepseek"):
        assert names == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    else:
        assert "compile_s" in names and result["device"]["count"] == 4

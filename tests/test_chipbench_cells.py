"""The benchmark's serving cells of the MoE models, rehearsed end to end through
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
                                        ("bert_base.pretrain_dp4", 1),
                                        ("lfm2_24b.decode_rollout", 1),
                                        ("lfm2_24b.decode_rollout", 0),
                                        ("keye_vl2.decode_doc", 1),
                                        ("keye_vl2.decode_doc", 0),
                                        ("solar_open2.decode_rollout", 1),
                                        ("solar_open2.decode_rollout", 0)])
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
                "expert_rows_computed.dsv32",
                "batch_occupancy.dsv32", "compile_s.dsv32",
                "loop_offcpu_us.dsv32", "emit_to_wire_us.dsv32",
                "wire_write_us.dsv32", "writer_batch_tokens.dsv32"} <= names
    elif cell.startswith("lfm2") and trace:
        assert {"experts_touched.lfm2", "expert_load_max.lfm2",
                "expert_rows_computed.lfm2", "kv_rows_read.lfm2",
                "kv_context_mean.lfm2", "batch_occupancy.lfm2",
                "compile_s.lfm2", "overlap_share.lfm2",
                "loop_offcpu_us.lfm2", "emit_to_wire_us.lfm2",
                "wire_write_us.lfm2", "writer_batch_tokens.lfm2"} <= names
    elif cell.startswith("keye") and trace:
        assert {"index_selected_share.keye", "kv_rows_read.keye",
                "kv_context_mean.keye", "experts_touched.keye",
                "expert_rows_computed.keye",
                "expert_load_max.keye", "batch_occupancy.keye",
                "compile_s.keye", "overlap_share.keye",
                "loop_offcpu_us.keye", "emit_to_wire_us.keye",
                "wire_write_us.keye", "writer_batch_tokens.keye"} <= names
    elif cell.startswith("solar") and trace:
        assert {"experts_touched.solar", "expert_rows_computed.solar",
                "kv_rows_read.solar", "batch_occupancy.solar",
                "compile_s.solar"} <= names
    elif cell.startswith(("deepseek", "lfm2", "keye", "solar")):
        assert names == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    else:
        assert "compile_s" in names and result["device"]["count"] == 4

"""chipbench's own checks; none needs a chip.

    python -m pytest chipbench/tests -q

Kept with the benchmark (``paths`` of BENCHMARK.json), so that a PR that
claims a gain cannot change what they pin: the trace reduction on a
recorded trace, the required-operation arithmetic on hand-worked values,
the data-driven look-up, and the shape of the result line.
"""
import gzip
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)

from chipbench import required, trace_reduce as tr  # noqa: E402
from chipbench.generators import sessions  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(args, root=REPO, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               **(env_extra or {}))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# -- every cell's command, end to end at the rehearsal size ------------------
@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(
    f[:-len(".json")] for f in os.listdir(os.path.join(BENCH, "workloads"))))
def test_cell_rehearses(cell, trace):
    """Every cell file, those of BENCHMARK.json and those that wait under
    Open questions in PERF.md for their proof on the chip."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        chips = json.load(f)["chips"]
    out = last_line(run_cell(["--workload", cell, "--seed", "2147483999",
                              "--seconds", "2", "--trace", str(trace),
                              "--rehearse"]))
    assert set(out) - {"breakdown"} == RESULT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"]) and out["device"]["count"] == chips
    # a CPU's timing is never printed under a device metric's name
    assert out["metrics"] and all(m["value"] is None
                                  for m in out["metrics"].values())
    if not trace:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2
        assert ("ttft_p95_ms" in out["metrics"]) == (cell == "gpt1.chat_open")
    else:
        assert {"busy_s", "window_s"} <= set(out["device"])


def test_refuses_to_run_without_the_chip():
    cell = bench_json()["workloads"][0]["name"]
    proc = run_cell(["--workload", cell, "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


# -- data-driven: new files, no edits ------------------------------------------
def test_new_cell_mix_and_metric_are_found_as_files(tmp_path):
    """A four-chip cell, a traffic mix and a per-layer metric added to a
    copy as new files: the harness runs them (the mesh from the cell's
    ``chips``, on four virtual CPU devices) with no file edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _s, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()

    def add(kind, name, body):
        with open(os.path.join(root, "chipbench", kind, name + ".json"),
                  "w") as f:
            json.dump(body, f)
    with open(os.path.join(BENCH, "traffic", "pretrain.json")) as f:
        mix = json.load(f)
    mix["distinct_batches"] = 3
    add("traffic", "pretrain_three", mix)
    add("workloads", "bert_base.added_dp4",
        {"config": "bert_base_pretrain", "traffic": "pretrain_three",
         "chips": 4, "why": "test"})
    add("metrics", "step_period_ms.added",
        {"layer": "model step, training", "unit": "ms", "better": "lower",
         "source": "device_trace", "moves": "train_tokens_per_s",
         "jobs": ["train_bert"], "reader": "module_time",
         "params": {"role": "step", "stat": "period"}})
    add("metrics", "lower_s.added",
        {"layer": "compile", "unit": "s", "better": "lower",
         "source": "host_clock", "moves": "setup_s", "jobs": ["train_bert"],
         "reader": "setup_phase", "params": {"keys": ["lower_s"]}})
    out = last_line(run_cell(["--workload", "bert_base.added_dp4", "--seed",
                              "7", "--seconds", "2", "--trace", "1",
                              "--rehearse"], root=root))
    assert out["correct"] is True and out["device"]["count"] == 4
    assert "lower_s.added" in out["metrics"]        # the added metric, read
    assert "compile_s" in out["metrics"]
    # the added trace metric has no device plane to read on a CPU: left out
    assert "step_period_ms.added" not in out["metrics"]
    for p, body in before.items():
        assert open(p, "rb").read() == body, f"{p} was edited"


# -- BENCHMARK.json against the contract and against chipbench/ ---------------
def test_benchmark_json_names_units_and_files():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"]
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            mine = json.load(f)
        assert {k: w[k] for k in ("config", "traffic", "chips", "why")} == mine
        assert len(w["why"]) <= 200
    for c in b["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(REPO, c["file"]))
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            mine = json.load(f)
        assert {k: m[k] for k in ("layer", "unit", "better", "source",
                                  "moves")} == \
            {k: mine[k] for k in ("layer", "unit", "better", "source",
                                  "moves")}


def test_metric_files_use_permitted_names_and_known_readers():
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        name = f[:-len(".json")]
        with open(os.path.join(BENCH, "metrics", f)) as fh:
            m = json.load(fh)
        assert NAME.match(name) and UNIT.match(m["unit"]), name
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           m["reader"] + ".py")), name
        assert all(os.path.isfile(os.path.join(BENCH, "jobs", j + ".py"))
                   for j in m["jobs"])


# -- the trace reduction on a recorded trace -----------------------------------
def recorded(name):
    with gzip.open(os.path.join(BENCH, "testdata", name), "rt") as f:
        return json.load(f)


SERVE_ROLES = {"decode": {"prefix": "jit_pure", "pick": "most_frequent"},
               "prefill": {"prefix": "jit_pure", "pick": "rest"}}


def test_reduction_of_a_recorded_decode_trace():
    """decode x4, prefill x3, decode x2, cut from PR 23's trace of 128
    slots on a v5e (chiprun_out/r2).  The first and the last module are
    left out as possibly cut short, which leaves 3 decode steps, 3
    prefills, 1 decode step.  Expected values are worked from the module
    rows here, by a loop and not by the code under test."""
    trace = recorded("trace_gpt1_decode.json.gz")
    dev = trace["devices"][0]
    roles = tr.modules_by_role(dev, SERVE_ROLES)
    assert [len(roles["decode"]), len(roles["prefill"])] == [4, 3]
    mods = sorted(dev["modules"], key=lambda m: m[1])
    assert len(mods) == 9
    decode_name = mods[0][0]
    dec = [m for m in mods[1:-1] if m[0] == decode_name]
    pre = [m for m in mods[1:-1] if m[0] != decode_name]
    assert roles["decode"] == dec and roles["prefill"] == pre
    assert tr.mean_duration_ms(roles["decode"]) == pytest.approx(
        sum(m[2] for m in dec) / 4 / 1e6)
    assert 15.3 < tr.mean_duration_ms(roles["prefill"]) < 15.5
    raw_gaps = [b[1] - (a[1] + a[2]) for a, b in zip(dec, dec[1:])]
    assert tr.mean_gap_ms(roles["decode"]) == pytest.approx(
        sum(raw_gaps) / 3 / 1e6)
    # all three prefills sit in the last gap: taken out of it
    assert tr.mean_gap_ms(roles["decode"], roles["prefill"]) == \
        pytest.approx((sum(raw_gaps) - sum(m[2] for m in pre)) / 3 / 1e6)
    busy, window = tr.busy_and_window_s(trace)
    # the window is the device plane's own extent, not the host's
    assert window == pytest.approx(
        (mods[-1][1] + mods[-1][2] - mods[0][1]) / 1e9, rel=1e-3)
    # ops run only inside modules, so busy is at most the modules' time
    assert 0.98 * sum(m[2] for m in mods) / 1e9 < busy \
        <= sum(m[2] for m in mods) / 1e9 + 1e-9
    ops = tr.top_device_ops(trace)
    assert len(ops) <= 10 and ops[0][1] >= ops[-1][1]
    # twelve per-layer fusions over the ring a decode step: one entry, x72
    assert any(o[0].startswith("multiply_reduce_fusion f32[128,12,512]")
               and o[0].endswith("x72") for o in ops), ops
    gaps = tr.top_idle_gaps(trace)
    assert gaps and sum(g[1] for g in gaps) <= window - busy + 1e-9


def test_reduction_of_a_recorded_training_trace():
    """Four BERT-base steps (batch 32 x 512, one v5e) from the same run;
    the two in the middle are read."""
    trace = recorded("trace_bert_step.json.gz")
    dev = trace["devices"][0]
    roles = tr.modules_by_role(dev, {"step": {"prefix": "jit_step"}})
    assert len(dev["modules"]) == 4 and len(roles["step"]) == 2
    assert 88.0 < tr.mean_duration_ms(roles["step"]) < 89.5
    a, b = roles["step"]
    assert tr.mean_period_ms(roles["step"]) == pytest.approx(
        (b[1] - a[1]) / 1e6)
    assert tr.mean_gap_ms(roles["step"]) == pytest.approx(
        (b[1] - a[1] - a[2]) / 1e6)
    mosaic = tr.ops_ms_per_module(dev, roles["step"], lambda o: o[4])
    inside = [o for o in dev["ops"]
              if o[4] and a[1] <= o[1] < b[1] + b[2]]
    assert mosaic == pytest.approx(sum(o[2] for o in inside) / 2 / 1e6)
    assert 55 < mosaic < 65                 # fused FFN, attention, res-LN
    assert tr.exposed_collective_ms(dev, roles["step"]) == 0.0   # one chip


def test_union_covered_and_exposed_collectives_by_hand():
    assert tr.union([(0, 5), (3, 4), (10, 1)]) == [[0, 7], [10, 11]]
    assert tr.covered([[0, 7], [10, 11]], 5, 10.5) == 2.5
    dev = {"modules": [["jit_step(1)", 0, 100]],
           "ops": [["f", 0, 40, "fusion", 0],
                   ["ar", 30, 30, "all-reduce-start", 0],
                   ["g", 70, 30, "fusion", 0]]}
    mods = dev["modules"]
    # the all-reduce runs 30..60; compute covers 30..40 of it: 20 exposed
    assert tr.exposed_collective_ms(dev, mods) == pytest.approx(20 / 1e6)
    assert tr.op_label('%fusion.12 = bf16[4,8]{1,0} fusion(bf16[4,8] %p)') \
        == ("fusion bf16[4,8]", "fusion", 0)


# -- required operations and bytes, by hand ------------------------------------
BERT = {"units": 768, "hidden_size": 3072, "num_layers": 12, "num_heads": 12,
        "vocab_size": 30522, "max_length": 512, "seq_length": 512,
        "max_predictions": 80, "storage_bytes": 2}
GPT1 = {"units": 768, "hidden_size": 3072, "num_layers": 12, "num_heads": 12,
        "vocab_size": 40478, "max_length": 512, "weight_bytes": 4,
        "kv_bytes": 4}


def test_bert_step_flops_by_hand():
    # a layer: 4*768^2 + 2*768*3072 = 7,077,888 MACs a token in products
    # with weights, 2*512*768 = 786,432 in attention: 7,864,320
    assert required.encoder_layer_macs_per_token(BERT, 512) == 7_864_320
    # a sequence: 512 tokens x 12 layers x 7,864,320 = 48,318,382,080;
    # heads: 80 x (768^2 + 768*30522) = 1,922,457,600; pooler + NSP 591,360
    per_seq = 48_318_382_080 + 1_922_457_600 + 591_360
    assert required.bert_step_flops(BERT, 32) == 6 * per_seq * 32
    # "2 x 110 M x tokens plus attention": 110 M parameters, of which the
    # 23.8 M of the embedding tables do no product with most tokens
    assert 109e6 < required.bert_param_count(BERT) < 111e6
    per_token_fwd = required.bert_step_flops(BERT, 1) / 3 / 512
    assert 1.9e8 < per_token_fwd < 2.0e8     # 2 x 85 M + attention + heads


def test_lm_decode_bytes_and_flops_by_hand():
    layer = 4 * 768 * 768 + 4 * 768 + 2 * 768 * 3072 + 3072 + 768 + 4 * 768
    weights = (12 * layer + 40478 * 768 + 40478) * 4
    assert required.lm_decode_weight_bytes(GPT1) == weights
    assert 464e6 < weights < 466e6
    # 128 slots at 200 valid positions each: 25,600 x 2 x 12 x 768 x 4 B
    assert required.lm_decode_step_bytes(GPT1, 25_600) == \
        weights + 25_600 * 73_728
    least, bound = required.roofline_ms(
        required.lm_decode_step_flops(GPT1, 128, 25_600),
        required.lm_decode_step_bytes(GPT1, 25_600),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and 2.8 < least < 2.9


# -- traffic from a seed ---------------------------------------------------------
def test_sessions_same_work_for_every_seed_and_same_seed_same_inputs():
    with open(os.path.join(BENCH, "traffic", "decode_full.json")) as f:
        mix = json.load(f)
    a, b, c = (sessions.sessions(mix, s) for s in (1, 1, 2 ** 31 - 5))
    assert a == b and a != c

    def prompts(plan):
        return sorted(r[0] for s in plan for r in s["requests"])
    assert prompts(a) == prompts(c)     # dealt out in another order
    assert len(a) == 128
    for s in a:
        for plen, new, _seed in s["requests"]:
            assert 8 <= plen <= 128 and 1 <= new <= 384 and plen + new <= 512
    assert sessions.prompt_tokens(100, 5, 7) == sessions.prompt_tokens(100, 5, 7)


# -- the readers, on the recorded traces -----------------------------------------
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_every_reader_reads_the_recorded_traces():
    """No CPU run has a device plane, so the readers that need one are
    driven here from the recorded traces and hand-made readings."""
    from chipbench.run import per_layer_metrics
    ctx = 128 * 200
    judged = ["serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"]
    serve = per_layer_metrics("serve_lm", judged, {
        "trace": recorded("trace_gpt1_decode.json.gz"), "chips": 1,
        "peaks": V5E, "memory_peak_bytes": 10_859_566_592,
        "readings": {
            "roles": SERVE_ROLES, "phases": {"engine_s": 6.4},
            "compile_keys": ["engine_s"], "open_loop": False,
            "counters": {"tokens_generated": 1270, "decode_steps": 10},
            "client_ttft_ms": [50.0, 58.0, 70.0],
            "engine_ttft_ms": [48.0, 55.5, 66.0], "late_ms": [0.1],
            "required": {"decode": {
                "flops": required.lm_decode_step_flops(GPT1, 128, ctx),
                "bytes": required.lm_decode_step_bytes(GPT1, ctx)}}}})
    got = {k: v["value"] for k, v in serve.items()}
    assert set(got) == {
        "batch_occupancy", "compile_s", "decode_step_ms",
        "decode_step_roofline", "device_idle_share.serve",
        "host_gap_ms.decode", "peak_hbm_gb.serve", "prefill_device_ms",
        "wire_ttft_ms"}                     # closed loop: no generator_late
    assert got["batch_occupancy"] == 127.0 and got["wire_ttft_ms"] == 2.5
    assert 15.50 < got["decode_step_ms"] < 15.53
    # (465 MB of weights + 25,600 x 73,728 B of KV) / 819 GB/s = 2.87 ms
    assert got["decode_step_roofline"] == pytest.approx(
        100 * 2.8727 / got["decode_step_ms"], rel=1e-3)
    assert 0 < got["device_idle_share.serve"] < 100
    # a mix that does not judge TTFT gets no metric that moves it
    assert "wire_ttft_ms" not in per_layer_metrics(
        "serve_lm", ["serve_tokens_per_s", "itl_p95_ms", "setup_s"],
        {"trace": None, "chips": 1, "peaks": V5E, "memory_peak_bytes": 0,
         "readings": {"client_ttft_ms": [5.0], "engine_ttft_ms": [4.0],
                      "phases": {}, "roles": SERVE_ROLES}})
    train = per_layer_metrics("train_bert", ["train_tokens_per_s", "setup_s"], {
        "trace": recorded("trace_bert_step.json.gz"), "chips": 1,
        "peaks": V5E, "memory_peak_bytes": 5_449_000_000,
        "readings": {
            "roles": {"step": {"prefix": "jit_step"}},
            "phases": {"lower_s": 21.0, "compile_s": 6.0},
            "compile_keys": ["lower_s", "compile_s"],
            "required": {"step": {
                "flops": required.bert_step_flops(BERT, 32),
                "bytes": required.bert_step_bytes(BERT, 32)}}}})
    got = {k: v["value"] for k, v in train.items()}
    assert set(got) == {
        "compile_s", "device_idle_share.train", "host_gap_ms.train",
        "mfu.train", "mosaic_ms_per_step", "peak_hbm_gb.train",
        "step_device_ms", "train_step_roofline"}    # one chip: no collectives
    assert got["compile_s"] == 27.0 and got["peak_hbm_gb.train"] == 5.449
    # 9.646e12 required operations in an 88.7 ms step: 55 % of 197 TFLOP/s
    assert 54.5 < got["mfu.train"] < 56.0
    assert got["mfu.train"] < got["train_step_roofline"] < 57.0
    assert got["host_gap_ms.train"] < 0.1

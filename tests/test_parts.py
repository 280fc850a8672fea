"""The parts a device trace names the model's work by (``telemetry.part``),
and the reader that turns a trace's rows into milliseconds a part
(``chipbench/readers/part_time.py``).  No chip: the programs are compiled
on the CPU and read as text, the reader is driven from rows.

* coverage as a contract: of every benchmark model's step programs at a
  tiny size, at least 95 % of the instructions that came from traced code
  carry a part of the vocabulary on their ``op_name``, and each part the
  model has is there.  Code added to a model's step goes under a part.
* the reader's arithmetic on hand-made rows and on rows cut from a chip
  trace of ``deepseek_v32.decode_long``.
* the guards: no raw trace that matches, no number; a compile cache from
  before the parts' layout changed is not found.
"""
import glob
import gzip
import json
import os
import re
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile as mx_compile
from mxnet_tpu import parallel, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import common, trace_reduce as tr  # noqa: E402
from chipbench.readers import part_time  # noqa: E402

# instructions that do no work of their own
SKIP = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([a-z][a-z0-9\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')

PARTS_OF = {
    "dsv32": {"embed", "attention", "indexer", "ffn", "experts", "head"},
    "lfm2": {"embed", "attention", "conv", "ffn", "experts", "head"},
    "keye": {"embed", "attention", "indexer", "experts", "head"},
    "solar": {"embed", "attention", "experts", "head"},
    "lm": {"embed", "attention", "ffn", "head"},
}
SUB_PARTS_OF = {
    ("dsv32", "decode"): {"attention/ring_write", "attention/project",
                          "attention/attend", "indexer/ring_write",
                          "indexer/scores", "indexer/top_k",
                          "experts/router", "experts/sort",
                          "experts/product", "experts/combine",
                          "experts/shared"},
    ("dsv32", "prefill"): {"attention/attend", "attention/ring_write",
                           "indexer/scores", "indexer/top_k",
                           "indexer/mask", "experts/product"},
    ("lfm2", "decode"): {"attention/ring_write", "attention/attend",
                         "experts/router", "experts/product"},
    ("lfm2", "prefill"): {"attention/attend", "attention/ring_write",
                          "conv/ring_write", "experts/product"},
    ("keye", "decode"): {"attention/ring_write", "attention/attend",
                         "indexer/ring_write", "indexer/scores",
                         "indexer/top_k", "indexer/mask",
                         "experts/product"},
    ("keye", "prefill"): {"attention/attend", "indexer/scores",
                          "indexer/top_k", "indexer/mask",
                          "indexer/ring_write", "experts/product"},
    ("solar", "decode"): {"attention/project", "attention/short_conv",
                          "attention/scan", "attention/gate",
                          "attention/ring_write", "attention/attend",
                          "experts/router", "experts/product",
                          "experts/shared"},
    ("solar", "prefill"): {"attention/project", "attention/short_conv",
                           "attention/scan", "attention/gate",
                           "attention/attend", "attention/ring_write",
                           "conv/ring_write", "experts/product"},
    ("lm", "decode"): {"attention/ring_write", "attention/project",
                       "attention/attend"},
    ("lm", "prefill"): {"attention/project", "attention/attend",
                        "attention/ring_write"},
}


def test_the_two_vocabularies_agree():
    """The reader keeps its own copy: it also reads a program that has no
    ``telemetry.PARTS`` (the parent of the PR that added them)."""
    assert part_time.PARTS == telemetry.PARTS
    assert not set(telemetry.PARTS) & set(telemetry.SUB_PARTS)


def coverage(text):
    """(instructions from traced code, those that carry a part, the parts
    and part/sub-parts seen) of one compiled program's text."""
    total, scoped, seen = 0, 0, set()
    for line in text.splitlines():
        m, name = INSTRUCTION.match(line), OP_NAME.search(line)
        if not m or m.group(1) in SKIP or not name:
            continue            # no op_name: the compiler's own
        total += 1
        part, sub, _direction = part_time.classify(name.group(1))
        if part != part_time.UNSCOPED:
            scoped += 1
            seen.add(part)
            if sub:
                assert sub in telemetry.SUB_PARTS, name.group(1)
                seen.add(part + "/" + sub)
    return total, scoped, seen


@pytest.fixture(scope="module")
def serving_programs():
    """{(model, role): compiled text}, an engine a model, built when first
    asked for."""
    from mxnet_tpu import models
    from mxnet_tpu.serving import GenerationEngine
    makers = {"dsv32": models.tiny_v32, "lfm2": models.tiny_lfm2,
              "keye": models.tiny_keye, "solar": models.tiny_solar,
              "lm": models.tiny_lm}
    texts = {}

    def get(model, role):
        if (model, role) not in texts:
            net = makers[model]()
            net.initialize()
            eng = GenerationEngine(net, slots=4, max_len=64,
                                   prefill_buckets=(32,))
            try:
                eng.precompile()
                texts[model, "decode"] = eng._decode_prog[0].as_text()
                texts[model, "prefill"] = eng._prefill_progs[32][0].as_text()
            finally:
                eng.close()
        return texts[model, role]
    return get


@pytest.mark.parametrize("model,role", sorted(SUB_PARTS_OF))
def test_a_serving_program_names_its_parts(serving_programs, model, role):
    total, scoped, seen = coverage(serving_programs(model, role))
    assert total > 100
    assert scoped >= 0.95 * total, (scoped, total)
    assert PARTS_OF[model] <= seen, PARTS_OF[model] - seen
    assert SUB_PARTS_OF[model, role] <= seen, SUB_PARTS_OF[model, role] - seen


def test_the_training_step_names_its_parts_and_its_directions():
    from mxnet_tpu.models import BERTModel, BERTPretrainingLoss
    net = BERTModel(vocab_size=64, num_layers=2, units=32, hidden_size=64,
                    num_heads=4, max_length=16, dropout=0.1)
    net.initialize()
    loss_core = BERTPretrainingLoss()

    def loss_fn(out, labels):
        _seq, _pooled, nsp, mlm = out
        return loss_core(mlm, nsp, *labels)
    import jax
    mesh = parallel.make_mesh({"data": 1}, devices=jax.devices()[:1])
    trainer = parallel.SPMDTrainer(
        net, loss_fn, mx.optimizer.create("lamb", learning_rate=1e-3), mesh,
        skip_nonfinite=True)
    B, L, M = 4, 16, 3
    rng = onp.random.RandomState(0)

    def ints(hi, *shape):
        return mx.nd.array(rng.randint(0, hi, shape).astype("int32"))
    data = (ints(64, B, L), ints(2, B, L),
            mx.nd.array(onp.full((B,), L, "int32")), ints(L, B, M))
    label = (ints(64, B, M), mx.nd.array(onp.ones((B, M), "float32")),
             ints(2, B))
    text = trainer.precompile(data, label)["compiled"].as_text()
    total, scoped, seen = coverage(text)
    assert scoped >= 0.95 * total, (scoped, total)
    assert {"embed", "attention", "ffn", "head", "loss", "optimizer",
            "attention/project", "attention/attend",
            "optimizer/health"} <= seen
    directions = {"forward": set(), "backward": set(), None: set()}
    for name in re.findall(r'op_name="([^"]*)"', text):
        part, _sub, direction = part_time.classify(name)
        directions[direction].add(part)
    assert {"attention", "ffn", "head", "embed"} <= directions["backward"]
    assert {"attention", "ffn", "loss"} <= directions["forward"]
    assert directions[None] == {"optimizer", part_time.UNSCOPED}


# -- the reader's arithmetic --------------------------------------------------
ROLES = {"decode": {"prefix": "jit_pure", "pick": "most_frequent"},
         "prefill": {"prefix": "jit_pure", "pick": "rest"}}
MS = 1_000_000


def by_hand_rows():
    """Two whole decode runs of 20 ms between two cut ones.  In each: a
    ``while`` of 10 ms that attention owns, holding a body op of 3 ms of
    the indexer's and one of 4 ms of attention's; an expert product of 5
    ms; an op of 1 ms nobody named.  One op lies between two runs."""
    def run(t0):
        w = "jit(pure_decode)/mx.attention/mx.attend/while"
        return [
            ["while", t0, 10 * MS, w],
            ["fusion f32[4]", t0 + 1 * MS, 3 * MS,
             w + "/body/closed_call/mx.indexer/mx.scores/dot_general"],
            ["fusion f32[8]", t0 + 5 * MS, 4 * MS,
             w + "/body/closed_call/mx.attention/mx.attend/dot_general"],
            ["gmm", t0 + 11 * MS, 5 * MS,
             "jit(pure_decode)/mx.experts/jit(_experts)/mx.product/gmm"],
            ["copy", t0 + 17 * MS, 1 * MS, "jit(pure_decode)/copy"]]
    mods = [["jit_pure_decode(1)", t, 20 * MS]
            for t in (0, 30 * MS, 60 * MS, 90 * MS)]
    ops = run(30 * MS) + run(60 * MS) + [
        ["fusion", 52 * MS, 2 * MS, "jit(other)/mx.head/dot_general"],
        ["gmm", 91 * MS, 5 * MS, "jit(pure_decode)/mx.experts/gmm"]]
    return [{"name": "/device:TPU:0", "modules": mods, "ops": ops}]


def test_a_loop_counts_what_its_body_leaves():
    rows = by_hand_rows()

    def ms(**params):
        return part_time.read_rows(rows, ROLES, dict(role="decode", **params))
    # the while of 10 holds 3 and 4: it keeps 3, with attention's 4
    assert ms(part="attention") == pytest.approx(7.0)
    assert ms(part="attention/attend") == pytest.approx(7.0)
    assert ms(part="indexer") == pytest.approx(3.0)
    assert ms(part="indexer/scores") == pytest.approx(3.0)
    assert ms(part="experts") == pytest.approx(5.0)
    assert ms(part="experts/product") == pytest.approx(5.0)
    assert ms(part="unscoped") == pytest.approx(1.0)
    # the op between two runs and the one in the cut run count nowhere
    assert ms(part="head") == 0.0
    assert ms() == pytest.approx(15.0)
    record = part_time.summary(part_time.reduce_device(
        rows[0], tr.modules_by_role(rows[0], ROLES)["decode"]))
    assert record["runs"] == 2 and record["module_ms"] == 20.0
    # the parts and what no part owns add up to the busy time
    assert record["busy_ms"] == pytest.approx(16.0)
    assert sum(v for k, v in record["ms_per_run"].items() if "/" not in k) \
        + record["unscoped_ms"] == pytest.approx(record["busy_ms"])
    assert record["top_unscoped"] == [["copy", 1.0]]
    # no whole prefill in the slice: nothing to read
    assert part_time.read_rows(rows, ROLES, {"role": "prefill",
                                             "part": "attention"}) is None


def test_forward_backward_and_optimizer_of_a_step():
    roles = {"step": {"prefix": "jit_step"}}
    mods = [["jit_step(7)", t * MS, 90 * MS] for t in (0, 100, 200, 300)]

    def run(t0):
        return [
            ["fusion", t0, 10 * MS, "jit(step)/jvp(mx.attention)/mul"],
            ["custom-call", t0 + 10 * MS, 20 * MS,
             "jit(step)/jvp(mx.ffn)/pallas_call"],
            ["custom-call", t0 + 30 * MS, 35 * MS,
             "jit(step)/transpose(jvp(mx.ffn))/pallas_call"],
            ["fusion", t0 + 65 * MS, 5 * MS,
             "jit(step)/transpose(jvp(mx.attention))/mx.project/dot_general"],
            ["fusion", t0 + 70 * MS, 6 * MS,
             "jit(step)/mx.optimizer/mul"],
            ["fusion", t0 + 76 * MS, 2 * MS,
             "jit(step)/mx.optimizer/mx.health/reduce_sum"],
            ["fusion", t0 + 80 * MS, 1 * MS,
             "jit(step)/jit(_threefry_fold_in)/xor"]]
    rows = [{"name": "/device:TPU:0", "modules": mods,
             "ops": run(100 * MS) + run(200 * MS)}]

    def ms(**params):
        return part_time.read_rows(rows, roles, dict(role="step", **params))
    assert ms(direction="forward") == pytest.approx(30.0)
    assert ms(direction="backward") == pytest.approx(40.0)
    assert ms(part="optimizer") == pytest.approx(8.0)
    assert ms(part="optimizer/health") == pytest.approx(2.0)
    assert ms(part="unscoped") == pytest.approx(1.0)
    assert ms(part="ffn", direction="backward") == pytest.approx(35.0)
    assert ms(part="attention/project",
              direction="backward") == pytest.approx(5.0)


def test_a_program_without_parts_reads_nothing():
    rows = by_hand_rows()
    for op in rows[0]["ops"]:
        op[3] = re.sub(r"mx\.\w+/", "", op[3])
    assert not part_time.has_parts(
        part_time.reduce_role(rows, ROLES, "decode"))
    assert part_time.read_rows(rows, ROLES, {"role": "decode",
                                             "part": "unscoped"}) is None


def test_rows_cut_from_a_chip_trace():
    """Three decode steps and one prefill of ``deepseek_v32.decode_long``
    on a v5e (my chip run, PR 37), between two runs that stand for the
    ones the tracer cut."""
    with gzip.open(os.path.join(REPO, "chipbench", "testdata",
                                "parts_dsv32_rows.json.gz"), "rt") as f:
        rows = json.load(f)
    mods = tr.modules_by_role(rows[0], ROLES)
    assert len(mods["decode"]) == 3 and len(mods["prefill"]) == 1
    for role in ("decode", "prefill"):
        reduced = part_time.reduce_device(rows[0], mods[role])
        record = part_time.summary(reduced)
        parts = {k: v for k, v in record["ms_per_run"].items()
                 if "/" not in k}
        assert {"attention", "indexer", "experts", "ffn", "head",
                "embed"} <= set(parts)
        # every op's self time is in exactly one part, or in none
        assert sum(parts.values()) + record["unscoped_ms"] == \
            pytest.approx(record["busy_ms"], rel=1e-9)
        # the device is busy nearly all of a run, and never more
        assert 0.97 * record["module_ms"] < record["busy_ms"] \
            <= record["module_ms"] * (1 + 1e-9)
        # what no part owns is mostly the compiler's own nameless moves
        assert record["unscoped_ms"] < 0.05 * record["module_ms"]
        assert record["unscoped_ms"] - record["unnamed_ms"] \
            < 0.02 * record["module_ms"]
        for metric in ("attention", "indexer", "experts"):
            assert part_time.read_rows(
                rows, ROLES, {"role": role, "part": metric}) == \
                pytest.approx(parts[metric])
    # what the cell's PERF.md section reads (ms a decode step)
    step = part_time.summary(part_time.reduce_device(rows[0], mods["decode"]))
    assert step["ms_per_run"]["experts"] == pytest.approx(7.88, abs=0.1)
    assert step["ms_per_run"]["attention"] == pytest.approx(5.63, abs=0.1)
    assert step["ms_per_run"]["indexer"] == pytest.approx(3.50, abs=0.1)
    assert step["ms_per_run"]["experts/product"] == \
        pytest.approx(6.63, abs=0.1)
    # a loop's own time is what its body leaves
    whiles = [(op, own) for op, own in part_time.self_times(rows[0]["ops"])
              if op[0].startswith("while")]
    assert whiles and all(own < 0.05 * op[2] for op, own in whiles)


# -- the guards ---------------------------------------------------------------
def new_metrics():
    return [dict(common.load("metrics", n), name=n)
            for n in common.names("metrics")
            if common.load("metrics", n)["reader"] == "part_time"]


def test_ten_metrics_and_their_benchmark_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell_of = {}
    for w in bench["workloads"]:
        cell_of[common.load("configs", w["config"])["job"]] = w["name"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    metrics = new_metrics()
    assert len(metrics) == 14
    for m in metrics:
        assert entries[m["name"]]["workloads"] == \
            [cell_of[j] for j in m["jobs"]]
        part = m["params"].get("part", "").split("/")[0]
        assert part in telemetry.PARTS + ("", part_time.UNSCOPED)


def test_without_the_raw_trace_every_new_metric_reads_nothing(
        tmp_path, monkeypatch):
    """The recorded reduced traces have no raw trace: no number, whether
    the trace directory is empty or holds another run's."""
    def recorded(name):
        with gzip.open(os.path.join(REPO, "chipbench", "testdata", name),
                       "rt") as f:
            return json.load(f)
    serve = {"trace": recorded("trace_gpt1_decode.json.gz"),
             "readings": {"roles": ROLES}}
    train = {"trace": recorded("trace_bert_step.json.gz"),
             "readings": {"roles": {"step": {"prefix": "jit_step"}}}}
    monkeypatch.setattr(common, "OUT_DIR", str(tmp_path))

    def all_none():
        for m in new_metrics():
            obs = train if m["jobs"] == ["train_bert"] else serve
            assert part_time.read(m, obs) is None, m["name"]
    all_none()
    # another run's trace (a CPU's: no device plane) is not taken for it
    import jax
    import jax.numpy as jnp
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace" / "some.cell"),
                             profiler_options=options)
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert part_time.newest_xplane() is not None
    all_none()


def test_a_compile_cache_from_before_the_parts_moved_is_not_found(
        monkeypatch):
    import jax
    import jax.numpy as jnp
    cache_dir = mx_compile.enable_persistent_cache()
    if cache_dir is None:
        pytest.skip("MXNET_COMPILE_CACHE=0")
    from jax._src import cache_key
    assert cache_key.custom_hook() == f"mx.parts={telemetry.PARTS_VERSION}"
    assert mx_compile.version_stamp()["parts"] == telemetry.PARTS_VERSION

    def lowered():
        # a new function each time: jax keeps what it compiled for one
        def fn_for_parts_cache_test(x):
            with telemetry.part("ffn"):
                return jnp.tanh(x) * 3.0
        return jax.jit(fn_for_parts_cache_test).lower(jnp.ones((4, 4)))

    def entries():
        return len(glob.glob(os.path.join(
            cache_dir, "jit_fn_for_parts_cache_test-*")))
    first = lowered()
    key = mx_compile.fingerprint_lowered(first)
    first.compile()
    assert entries() == 1
    lowered().compile()                                     # a warm hit
    assert entries() == 1
    monkeypatch.setattr(telemetry, "PARTS_VERSION",
                        telemetry.PARTS_VERSION + 1)
    moved = lowered()
    assert mx_compile.fingerprint_lowered(moved) != key
    moved.compile()
    assert entries() == 2

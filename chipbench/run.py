"""chipbench: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads/<cell>.json``) names a configuration
(``configs/``) and a traffic mix (``traffic/``); the configuration names
its job kind (``jobs/``), the mix its generator (``generators/``), and
each per-layer metric (``metrics/``) its reader (``readers/``).  A new
cell, mix, configuration of a known job kind or metric is new files and an
entry in ``BENCHMARK.json``; nothing here is edited for it.

Set-up is everything from process start to the window's start.  The last
line printed is the result; ``--trace 0`` gives the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a traced slice of the
window.  Without a TPU that ``peaks.json`` knows, or with fewer chips than
the cell asks for, the run fails and prints no result.  ``--rehearse``
(tests only) runs the configuration's tiny ``rehearse`` sizes on whatever
jax finds and prints every value as null: a CPU's timing is never written
under a device metric's name.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import common  # noqa: E402
from chipbench.tracing import Tracer  # noqa: E402


def merge(base, override):
    out = dict(base)
    for k, v in (override or {}).items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def per_layer_metrics(job_kind, judged, obs):
    """{name: {"value", "unit"}} from every metric file that applies to
    this job kind, moves an end-to-end metric this cell is judged by, and
    whose reader found something to read."""
    out = {}
    for name in common.names("metrics"):
        metric = dict(common.load("metrics", name), name=name)
        if job_kind not in metric["jobs"] or metric["moves"] not in judged:
            continue
        value = common.plugin("readers", metric["reader"]).read(metric, obs)
        if value is not None:
            out[name] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = common.load("workloads", args.workload)
    cfg = common.load("configs", cell["config"])
    traffic = common.load("traffic", cell["traffic"])
    if args.rehearse:
        cfg = merge(cfg, cfg.get("rehearse"))
        traffic = merge(traffic, traffic.get("rehearse"))
        if cell["chips"] > 1:       # virtual CPU devices for the mesh
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={cell['chips']}")
    job = common.plugin("jobs", cfg["job"])
    dev, peaks, devs = common.device_record(cell["chips"], args.rehearse)
    tracer = Tracer(args.trace, args.workload,
                    traffic["trace_start_share"] * args.seconds,
                    traffic["trace_seconds"])
    obs = job.run(cell, cfg, traffic, args, devs, tracer)
    # a mix may leave out an end-to-end metric it cannot judge (a tail that
    # sits on a step of its distribution); it is still on the window line
    judged = traffic.get("end_to_end", list(job.END_TO_END)) + ["setup_s"]

    if args.trace:
        obs.update(trace=tracer.reduced(), chips=cell["chips"],
                   peaks=None if args.rehearse else peaks)
        metrics = per_layer_metrics(cfg["job"], judged, obs)
    else:
        metrics = {k: {"value": v, "unit": job.END_TO_END[k]}
                   for k, v in obs["end_to_end"].items() if k in judged}
        metrics["setup_s"] = {"value": obs["setup_s"], "unit": "s"}
    if args.rehearse:
        common.say(rehearsal_readings=metrics)
        metrics = {k: {"value": None, "unit": m["unit"]}
                   for k, m in metrics.items()}
    dev["memory_peak_bytes"] = obs["memory_peak_bytes"]
    result = {"correct": obs["correct"], "attempted": obs["attempted"],
              "failed": obs["failed"], "metrics": metrics, "device": dev}
    if args.trace and obs["trace"] is not None:
        from chipbench import trace_reduce
        busy, window = trace_reduce.busy_and_window_s(obs["trace"])
        dev["busy_s"], dev["window_s"] = busy, window
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(obs["trace"]),
            "idle_gaps": trace_reduce.top_idle_gaps(obs["trace"])}
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

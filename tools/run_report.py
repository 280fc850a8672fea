#!/usr/bin/env python
"""Render a training-dynamics report from a run ledger.

Answers "how did the learning go — and did it go the same way as the
baseline" from the per-run JSONL ledger ``mxnet_tpu.health`` writes
(``MXNET_RUN_LEDGER_DIR``; docs/OBSERVABILITY.md "Training-dynamics
observability").  Deliberately stdlib-only, like its memory/cost/trace
siblings: forensics on a dead run must not need a working jax install.

Default output:

* **summary** — run id, step span, first/best/final loss, mean
  throughput, nonfinite step count, anomaly count by kind, contiguity
  check (duplicated / missing steps — the elastic-restart referee);
* **curve table** — sampled step rows (loss, grad/param norms, update
  ratio, lr, steps/s, MFU);
* **anomaly timeline** — every ``event: "anomaly"`` row in step order;
* **per-block table** (``--blocks``) — final-row per-block grad norm /
  update ratio, largest grad norm first.

**Baseline mode** (``--baseline other.jsonl``): aligns the two runs by
step and reports noise-aware loss deltas — the mean |delta| over the
common steps judged against the baseline's own step-to-step loss
volatility — plus the step where the curves first diverge beyond it and
the anomaly-count diff.  The referee a perf/memory PR cites to prove it
did not change convergence.

Usage:
    python tools/run_report.py runs/run_myrun.jsonl
    python tools/run_report.py runs/run_a.jsonl --baseline runs/run_b.jsonl
    python tools/run_report.py runs/run_myrun.jsonl --every 10 --json
"""
from __future__ import annotations

import argparse
import json
import sys


def load_rows(path):
    """Parse one ledger JSONL file (torn/corrupt lines skipped — the
    crash-interrupted tail is expected damage)."""
    rows = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except ValueError:
                continue
    return rows


def split_rows(rows):
    steps = [r for r in rows if r.get("event", "step") == "step"
             and isinstance(r.get("step"), int)]
    steps.sort(key=lambda r: r["step"])
    anomalies = [r for r in rows if r.get("event") == "anomaly"]
    anomalies.sort(key=lambda r: (r.get("step") or 0))
    return steps, anomalies


def contiguity(steps):
    """(duplicated, missing) step counts over the run's step span — the
    elastic-restart resume referee (both must be 0)."""
    seen = {}
    for r in steps:
        seen[r["step"]] = seen.get(r["step"], 0) + 1
    dup = sum(c - 1 for c in seen.values())
    if not seen:
        return dup, 0
    lo, hi = min(seen), max(seen)
    missing = sum(1 for s in range(lo, hi + 1) if s not in seen)
    return dup, missing


def _finite(vals):
    return [v for v in vals if isinstance(v, (int, float))
            and v == v and abs(v) != float("inf")]


def _fmt(v, prec=6):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{prec}g}"
    return str(v)


def summarize(steps, anomalies):
    losses = _finite([r.get("loss") for r in steps])
    thr = _finite([r.get("steps_per_s") for r in steps])
    dup, missing = contiguity(steps)
    kinds = {}
    for a in anomalies:
        kinds[a.get("kind", "?")] = kinds.get(a.get("kind", "?"), 0) + 1
    return {
        "run": steps[0].get("run") if steps else None,
        "steps": len(steps),
        "step_span": [steps[0]["step"], steps[-1]["step"]] if steps
        else None,
        "first_loss": losses[0] if losses else None,
        "best_loss": min(losses) if losses else None,
        "final_loss": losses[-1] if losses else None,
        "mean_steps_per_s": sum(thr) / len(thr) if thr else None,
        "nonfinite_steps": sum(1 for r in steps
                               if (r.get("nonfinite") or 0) > 0),
        "anomalies": kinds,
        "duplicated_steps": dup,
        "missing_steps": missing,
    }


def format_summary(s):
    lines = [f"run {s['run']}: {s['steps']} steps "
             f"{s['step_span']}, loss {_fmt(s['first_loss'])} -> "
             f"{_fmt(s['final_loss'])} (best {_fmt(s['best_loss'])})"]
    lines.append(f"  throughput {_fmt(s['mean_steps_per_s'], 4)} steps/s  "
                 f"nonfinite steps {s['nonfinite_steps']}  "
                 f"duplicated {s['duplicated_steps']}  "
                 f"missing {s['missing_steps']}")
    if s["anomalies"]:
        lines.append("  anomalies: " + ", ".join(
            f"{k} x{v}" for k, v in sorted(s["anomalies"].items())))
    else:
        lines.append("  anomalies: none")
    return "\n".join(lines)


def format_curve(steps, every=1, max_rows=40):
    """The sampled curve table."""
    if not steps:
        return "(no step rows)"
    sel = steps[::max(1, int(every))]
    if len(sel) > max_rows:
        stride = (len(sel) + max_rows - 1) // max_rows
        sel = sel[::stride]
    if sel[-1] is not steps[-1]:
        sel.append(steps[-1])
    head = (f"{'step':>8} {'loss':>12} {'grad_norm':>12} "
            f"{'param_norm':>12} {'upd_ratio':>10} {'lr':>10} "
            f"{'steps/s':>8} {'mfu':>7} {'nf':>3}")
    lines = [head, "-" * len(head)]
    for r in sel:
        lines.append(
            f"{r['step']:>8} {_fmt(r.get('loss')):>12} "
            f"{_fmt(r.get('grad_norm'), 5):>12} "
            f"{_fmt(r.get('param_norm'), 5):>12} "
            f"{_fmt(r.get('update_ratio'), 3):>10} "
            f"{_fmt(r.get('lr'), 4):>10} "
            f"{_fmt(r.get('steps_per_s'), 4):>8} "
            f"{_fmt(r.get('mfu'), 3):>7} "
            f"{r.get('nonfinite') or 0:>3}")
    return "\n".join(lines)


def format_anomalies(anomalies):
    if not anomalies:
        return "(no anomalies)"
    lines = [f"{'step':>8} {'kind':<18} {'value':>12} {'threshold':>12}  "
             "message"]
    lines.append("-" * 78)
    for a in anomalies:
        lines.append(
            f"{a.get('step', '?'):>8} {a.get('kind', '?'):<18} "
            f"{_fmt(a.get('value'), 5):>12} "
            f"{_fmt(a.get('threshold'), 5):>12}  "
            f"{a.get('message', '')}")
    return "\n".join(lines)


def format_blocks(steps):
    last = None
    for r in reversed(steps):
        if r.get("blocks"):
            last = r
            break
    if last is None:
        return "(no per-block rows — MXNET_STEP_DIAGNOSTICS off, or an "\
               "eager path without block scoping)"
    head = (f"{'block':<40} {'grad_norm':>12} {'param_norm':>12} "
            f"{'upd_ratio':>10}")
    lines = [f"per-block norms at step {last['step']}:", head,
             "-" * len(head)]
    blocks = sorted(last["blocks"].items(),
                    key=lambda kv: -(kv[1].get("grad_norm") or 0))
    for name, b in blocks:
        lines.append(f"{name:<40} {_fmt(b.get('grad_norm'), 5):>12} "
                     f"{_fmt(b.get('param_norm'), 5):>12} "
                     f"{_fmt(b.get('update_ratio'), 3):>10}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# baseline comparison
# ---------------------------------------------------------------------------
def compare(steps, base_steps, anomalies, base_anomalies):
    """Noise-aware two-run comparison over the common step range.

    The noise floor is the baseline's own step-to-step loss volatility
    (mean |delta loss| between consecutive baseline steps): a fresh
    run whose mean |loss delta vs baseline| sits under ~2x that floor
    is ``consistent``; above it, ``diverged`` with the first step
    where the per-step delta crossed the floor."""
    by_step = {r["step"]: r for r in steps}
    base_by = {r["step"]: r for r in base_steps}
    common = sorted(set(by_step) & set(base_by))
    if len(common) < 2:
        return {"verdict": "incomparable", "common_steps": len(common)}
    deltas = []
    for s in common:
        a, b = by_step[s].get("loss"), base_by[s].get("loss")
        if a is None or b is None or a != a or b != b:
            deltas.append((s, None))
        else:
            deltas.append((s, a - b))
    base_losses = [base_by[s].get("loss") for s in common]
    base_losses = [v for v in base_losses if v is not None and v == v]
    noise = (sum(abs(b - a) for a, b in zip(base_losses, base_losses[1:]))
             / max(1, len(base_losses) - 1))
    valid = [(s, d) for s, d in deltas if d is not None]
    mean_abs = sum(abs(d) for _s, d in valid) / max(1, len(valid))
    bar = max(2.0 * noise, 1e-12)
    first_div = None
    for s, d in valid:
        if abs(d) > bar:
            first_div = s
            break
    kinds = lambda rows: {a.get("kind") for a in rows}  # noqa: E731
    return {
        "verdict": "diverged" if mean_abs > bar or first_div is not None
        else "consistent",
        "common_steps": len(common),
        "mean_abs_loss_delta": mean_abs,
        "noise_floor": noise,
        "bar": bar,
        "first_divergent_step": first_div,
        "final_loss_delta": valid[-1][1] if valid else None,
        "anomaly_kinds_only_in_run":
            sorted(k for k in kinds(anomalies) - kinds(base_anomalies)
                   if k),
        "anomaly_kinds_only_in_baseline":
            sorted(k for k in kinds(base_anomalies) - kinds(anomalies)
                   if k),
    }


def format_compare(c):
    if c.get("verdict") == "incomparable":
        return (f"baseline comparison: incomparable "
                f"({c['common_steps']} common steps)")
    lines = [f"baseline comparison over {c['common_steps']} common steps: "
             f"{c['verdict'].upper()}"]
    lines.append(
        f"  mean |loss delta| {_fmt(c['mean_abs_loss_delta'], 5)} vs "
        f"noise-aware bar {_fmt(c['bar'], 5)} "
        f"(baseline step-to-step volatility {_fmt(c['noise_floor'], 5)})")
    if c["first_divergent_step"] is not None:
        lines.append(f"  first divergent step: "
                     f"{c['first_divergent_step']}")
    lines.append(f"  final loss delta: {_fmt(c['final_loss_delta'], 5)}")
    if c["anomaly_kinds_only_in_run"]:
        lines.append("  anomalies only in run: "
                     + ", ".join(c["anomaly_kinds_only_in_run"]))
    if c["anomaly_kinds_only_in_baseline"]:
        lines.append("  anomalies only in baseline: "
                     + ", ".join(c["anomaly_kinds_only_in_baseline"]))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="training-dynamics report from a mxnet_tpu.health "
                    "run ledger (JSONL)")
    ap.add_argument("ledger", help="run_<id>.jsonl ledger file")
    ap.add_argument("--baseline", default=None, metavar="LEDGER",
                    help="second ledger to compare against (noise-aware "
                         "loss deltas over the common steps)")
    ap.add_argument("--every", type=int, default=1,
                    help="curve table sampling stride")
    ap.add_argument("--blocks", action="store_true",
                    help="print the final per-block norm table")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    args = ap.parse_args(argv)

    steps, anomalies = split_rows(load_rows(args.ledger))
    out = {"summary": summarize(steps, anomalies)}
    if args.baseline:
        b_steps, b_anoms = split_rows(load_rows(args.baseline))
        out["baseline"] = summarize(b_steps, b_anoms)
        out["comparison"] = compare(steps, b_steps, anomalies, b_anoms)
    if args.json:
        if args.baseline:
            # one compact machine-parseable line: the full payload plus
            # the verdict fields hoisted to the top level, so a harness
            # (a CI gate) can json.loads a single stdout line and branch on
            # .verdict without digging into the comparison object
            c = out["comparison"]
            out["verdict"] = c.get("verdict")
            out["first_divergent_step"] = c.get("first_divergent_step")
            out["anomaly_kind_diff"] = {
                "only_in_run": c.get("anomaly_kinds_only_in_run", []),
                "only_in_baseline":
                    c.get("anomaly_kinds_only_in_baseline", []),
            }
            json.dump(out, sys.stdout, separators=(",", ":"),
                      default=str)
        else:
            json.dump(out, sys.stdout, indent=1, default=str)
        print()
        return 0
    print(format_summary(out["summary"]))
    print()
    print(format_curve(steps, every=args.every))
    print()
    print("anomaly timeline:")
    print(format_anomalies(anomalies))
    if args.blocks:
        print()
        print(format_blocks(steps))
    if args.baseline:
        print()
        print(format_compare(out["comparison"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())

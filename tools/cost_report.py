#!/usr/bin/env python
"""Render a compute-cost report: per-program flops/MFU tables, the
per-block cost table of a captured step, and a roofline verdict.

Answers "where do the FLOPs go, which block owns them, and is this
program compute- or byte-bound" from the ``costs`` section
``mxnet_tpu.costs`` attaches to crash reports (schema v4,
docs/RESILIENCE.md) — or from a full ``costs.report_payload()`` dump
written out as JSON.
Deliberately stdlib-only, like trace_report/memory_report: forensics on
a dead job's report must not need a working jax install.

Default output, four tables:

* **programs** — the hottest ledger entries: ProgramCache key, kind,
  GFLOPs, MB accessed, arithmetic intensity (flops/byte), analysis
  freshness, executions and last/best MFU — "which executable owns the
  compute and how close to peak did it run";
* **blocks** — the per-block attribution of a captured segment (default:
  the attributed program with the most flops; ``--program`` picks by key
  prefix): flops per originating HybridBlock, forward + backward folded
  to the block that recorded the forward, coverage vs the program's
  ``cost_analysis()`` total;
* **roofline** — per program: intensity vs the machine ridge
  (peak FLOP/s ÷ peak bytes/s from the payload's resolved peak table,
  ``MXNET_PEAK_FLOPS``/``MXNET_PEAK_BYTES_PER_S`` overrides) and the
  verdict: ``compute-bound`` (intensity ≥ ridge) or ``byte-bound`` —
  byte-bound glue is where fusion/layout passes pay (ROADMAP pass-layer
  item);
* **rewrite candidates** — the byte-bound subset as machine-readable
  rows with ``suggested_passes`` for :mod:`mxnet_tpu.compile.passes`
  (``--json`` carries the same rows under ``rewrite_candidates``; the
  pass tests consume them as fixtures via ``candidate_specs``).

Usage:
    python tools/cost_report.py cost_payload.json
    python tools/cost_report.py crash_report_123_0001.json
    python tools/cost_report.py payload.json --program pc:6c1d8f --ops
    python tools/cost_report.py payload.json --json
"""
from __future__ import annotations

import argparse
import json
import sys


def load_payload(obj):
    """Accept a crash report (uses its ``costs`` section) or a bare
    ``costs.crash_report_payload()`` / ``costs.report_payload()`` dict."""
    if not isinstance(obj, dict):
        raise ValueError(f"unsupported container {type(obj).__name__}")
    if "costs" in obj and isinstance(obj["costs"], dict):
        return obj["costs"]
    if any(k in obj for k in ("ledger", "executions", "attributions")):
        return obj
    raise ValueError("no costs section found (crash report schema < 4, "
                     "or not a costs payload)")


def _gf(x):
    return f"{(x or 0) / 1e9:10.3f}"


def _mb(x):
    return f"{(x or 0) / 2 ** 20:9.2f}"


# ---------------------------------------------------------------------------
# tables
# ---------------------------------------------------------------------------
def format_programs(payload, top_k=10):
    led = payload.get("ledger") or {}
    hot = led.get("hottest") or []
    peak = payload.get("peak") or {}
    lines = [f"ledger: {led.get('programs', 0)} programs, "
             f"{led.get('upgrades', 0)} warm upgrades; peak "
             f"{(peak.get('flops') or 0) / 1e12:.1f} TFLOP/s "
             f"({peak.get('source', 'unresolved')})"]
    if not hot:
        lines.append("(no ledger entries — nothing compiled yet, or "
                     "MXNET_COSTS=0)")
        return "\n".join(lines)
    hdr = (f"{'key':<14} {'kind':<13} {'gflops':>10} {'mb_acc':>9} "
           f"{'fl/byte':>8} {'anl':>5} {'exec':>5} {'last_mfu':>9} "
           f"{'best_mfu':>9}  label")
    lines += [hdr, "-" * len(hdr)]
    for e in hot[:top_k]:
        byts = e.get("bytes_accessed") or 0
        inten = (e.get("flops") or 0) / byts if byts else 0.0
        lines.append(
            f"{str(e.get('key', ''))[:12]:<14} "
            f"{str(e.get('kind', ''))[:11]:<13} "
            f"{_gf(e.get('flops'))} {_mb(byts)} {inten:>8.1f} "
            f"{str(e.get('analysis', ''))[:4]:>5} "
            f"{e.get('executions', 0):>5} "
            f"{str(e.get('last_mfu', '-')):>9} "
            f"{str(e.get('best_mfu', '-')):>9}  {e.get('label', '')}")
    ex = payload.get("executions") or {}
    last = ex.get("last")
    if last:
        lines.append(
            f"last execution: {str(last.get('key', ''))[:12]} "
            f"{(last.get('flops') or 0) / 1e9:.3f} GFLOP in "
            f"{(last.get('dur_us') or 0) / 1000:.2f} ms -> "
            f"MFU {last.get('mfu', '-')}")
    return "\n".join(lines)


def pick_attribution(payload, program=None):
    """The attribution table to render: by key prefix when ``--program``
    is given, else the attributed program with the most flops."""
    ats = payload.get("attributions") or []
    if program:
        p = program[3:] if program.startswith("pc:") else program
        for t in ats:
            if str(t.get("key", "")).startswith(p):
                return t
        return None
    return max(ats, key=lambda t: t.get("attributed_flops") or 0) \
        if ats else None


def format_blocks(table, top_k=12, ops=False):
    if not table:
        return ("(no attribution tables in payload — captured segments "
                "only; MXNET_COST_ATTRIBUTION=0 disables them, and bare "
                "crash payloads carry none: use costs.report_payload())")
    total = table.get("total_flops")
    cov = table.get("coverage")
    lines = [f"program {str(table.get('key', ''))[:12]} "
             f"[{table.get('kind', '')}]: attributed "
             f"{(table.get('attributed_flops') or 0) / 1e9:.3f} GFLOP"
             + (f" = {100.0 * cov:.1f}% of cost_analysis total "
                f"{total / 1e9:.3f} GFLOP" if cov and total else
                " (no cost_analysis total to referee against)")]
    hdr = f"{'block':<40} {'gflops':>10} {'%prog':>7} {'ops':>5}"
    lines += [hdr, "-" * len(hdr)]
    denom = total or table.get("attributed_flops") or 1
    for b in (table.get("blocks") or [])[:top_k]:
        lines.append(f"{str(b['block'])[:38]:<40} {_gf(b['flops'])} "
                     f"{100.0 * b['flops'] / denom:>7.1f} {b['ops']:>5}")
    rest = (table.get("blocks") or [])[top_k:]
    if rest:
        rf = sum(b["flops"] for b in rest)
        lines.append(f"{'(+%d more blocks)' % len(rest):<40} {_gf(rf)} "
                     f"{100.0 * rf / denom:>7.1f} "
                     f"{sum(b['ops'] for b in rest):>5}")
    if ops:
        hdr2 = (f"{'block':<34} {'op':<24} {'dir':<9} {'gflops':>10} "
                f"{'count':>6}")
        lines += ["", hdr2, "-" * len(hdr2)]
        for r in (table.get("rows") or [])[:4 * top_k]:
            lines.append(
                f"{str(r['block'])[:32]:<34} {str(r['op'])[:22]:<24} "
                f"{r.get('direction', ''):<9} {_gf(r['flops'])} "
                f"{r['count']:>6}")
    return "\n".join(lines)


def roofline(payload, top_k=8):
    """Per-program roofline rows + verdicts from ledger flops/bytes and
    the resolved peak pair."""
    peak = payload.get("peak") or {}
    pf, pb = peak.get("flops"), peak.get("bytes_per_s")
    ridge = (pf / pb) if pf and pb else None
    rows = []
    for e in (payload.get("ledger") or {}).get("hottest") or []:
        byts = e.get("bytes_accessed") or 0
        if not byts:
            continue
        inten = (e.get("flops") or 0) / byts
        verdict = None
        if ridge is not None:
            verdict = "compute-bound" if inten >= ridge else "byte-bound"
        rows.append({"key": e.get("key"), "kind": e.get("kind"),
                     "label": e.get("label"),
                     "intensity_flops_per_byte": round(inten, 2),
                     "ridge_flops_per_byte":
                         round(ridge, 2) if ridge else None,
                     "verdict": verdict,
                     "bound_roof_flops":
                         round(min(pf, inten * pb), 1)
                         if pf and pb else None})
    return {"peak": peak, "ridge_flops_per_byte":
            round(ridge, 2) if ridge else None, "programs": rows[:top_k]}


def rewrite_candidates(payload, top_k=16):
    """Machine-readable rewrite-pass candidates from the roofline rows.

    Byte-bound programs are where graph-rewrite passes pay (a rewrite
    that trims bytes moves them toward the ridge); compute-bound
    programs are excluded — a pass can only shave the part that is not
    the bottleneck.  The output is a stable fixture contract consumed by
    the pass tests (``tests/test_compile_passes.py``) and by
    ``mxnet_tpu.compile.passes.candidate_specs``, which turns the rows
    into per-program ``MXNET_COMPILE_PASSES``-style specs:

    ``{"schema": 1, "ridge_flops_per_byte": float|None,
       "candidates": [{"key", "label", "kind",
                       "intensity_flops_per_byte", "verdict",
                       "suggested_passes": [name, ...]}, ...]}``
    """
    rep = roofline(payload, top_k=top_k)
    cands = []
    for r in rep["programs"]:
        if r["verdict"] == "compute-bound":
            continue
        # dce is always safe to suggest; int8 residency only pays where
        # there is a quantized serving path to propagate through —
        # candidate_specs() filters to passes actually registered, and
        # the pipeline validates before anything is served, so an
        # over-eager suggestion degrades to "no change", never to a
        # wrong answer
        passes = ["dce"]
        if str(r.get("kind") or "") in ("block", "serving", "infer"):
            passes.append("int8_residency")
        cands.append({"key": r["key"], "label": r.get("label"),
                      "kind": r.get("kind"),
                      "intensity_flops_per_byte":
                          r["intensity_flops_per_byte"],
                      "verdict": r["verdict"] or "unknown",
                      "suggested_passes": passes})
    return {"schema": 1,
            "ridge_flops_per_byte": rep["ridge_flops_per_byte"],
            "candidates": cands}


def format_rewrite_candidates(rc):
    if not rc["candidates"]:
        return ("(no byte-bound programs — nothing for the pass layer "
                "to chase, or no byte figures in the ledger)")
    hdr = (f"{'key':<14} {'kind':<13} {'fl/byte':>8} "
           f"{'suggested_passes':<24} label")
    lines = [hdr, "-" * len(hdr)]
    for c in rc["candidates"]:
        lines.append(f"{str(c['key'])[:12]:<14} "
                     f"{str(c['kind'])[:11]:<13} "
                     f"{c['intensity_flops_per_byte']:>8.1f} "
                     f"{','.join(c['suggested_passes']):<24} "
                     f"{c.get('label') or ''}")
    return "\n".join(lines)


def format_roofline(rep):
    ridge = rep.get("ridge_flops_per_byte")
    peak = rep.get("peak") or {}
    lines = [f"ridge = peak_flops/peak_bw = {ridge if ridge else '?'} "
             f"flops/byte "
             f"({(peak.get('flops') or 0) / 1e12:.1f} TFLOP/s / "
             f"{(peak.get('bytes_per_s') or 0) / 1e9:.0f} GB/s, "
             f"source {peak.get('source', 'unresolved')})"]
    if not rep["programs"]:
        lines.append("(no byte figures in the ledger)")
        return "\n".join(lines)
    hdr = f"{'key':<14} {'kind':<13} {'fl/byte':>8} {'verdict':<14} label"
    lines += [hdr, "-" * len(hdr)]
    for r in rep["programs"]:
        lines.append(f"{str(r['key'])[:12]:<14} "
                     f"{str(r['kind'])[:11]:<13} "
                     f"{r['intensity_flops_per_byte']:>8.1f} "
                     f"{str(r['verdict'] or '?'):<14} "
                     f"{r.get('label') or ''}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# cli
# ---------------------------------------------------------------------------
def render(payload, program=None, ops=False):
    return "\n\n".join([
        "== programs ==\n" + format_programs(payload),
        "== blocks ==\n" + format_blocks(
            pick_attribution(payload, program), ops=ops),
        "== roofline ==\n" + format_roofline(roofline(payload)),
        "== rewrite candidates ==\n"
        + format_rewrite_candidates(rewrite_candidates(payload)),
    ])


def main():
    ap = argparse.ArgumentParser(
        description="per-program flops/MFU, per-block cost table of a "
                    "captured step, and a roofline verdict from a costs "
                    "payload or crash report")
    ap.add_argument("report", help="costs payload or crash report (JSON)")
    ap.add_argument("--program", default=None,
                    help="render the block table of this program "
                         "(key prefix or pc:<key12>)")
    ap.add_argument("--ops", action="store_true",
                    help="also print the per-(block, op) rows")
    ap.add_argument("--json", action="store_true",
                    help="emit the structured payload (+ roofline) "
                         "instead of tables")
    args = ap.parse_args()
    with open(args.report) as f:
        payload = load_payload(json.load(f))
    if args.json:
        out = dict(payload, roofline=roofline(payload),
                   rewrite_candidates=rewrite_candidates(payload))
        json.dump(out, sys.stdout, indent=1)
        print()
        return
    print(render(payload, program=args.program, ops=args.ops))


if __name__ == "__main__":
    main()

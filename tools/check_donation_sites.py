#!/usr/bin/env python
"""Lint: every buffer-donation site names its snapshot/recovery test.

Donating a buffer into an executable makes failure recovery a
correctness feature: a dispatch that dies after the runtime consumed its
inputs cannot be retried in-process, so every place the code ARMS
donation must point at the test that proves the recovery path
(restore-from-checkpoint, refuse-to-retry, or re-dispatch) actually
works — the same discipline ``check_fault_points.py`` enforces for fault
points.

A **donation site** is a source line under ``mxnet_tpu/`` that either

* passes ``donate_argnums=`` into a jit/compile wrapper, or
* passes ``donate=`` into an ``engine.record_lazy`` call;

each must be preceded (within ``LOOKBACK`` lines) by a marker comment::

    # donation-recovery: tests/test_donation.py::test_name

naming an existing test function in an existing test file.  Stale
markers (pointing at tests that no longer exist) are violations too.

Run directly (exit 1 on violations) or from the fast test in
``tests/test_donation.py`` — same wiring as the other tools/ lints.
"""
from __future__ import annotations

import os
import re
import sys

LOOKBACK = 40
_MARK_RE = re.compile(r"#\s*donation-recovery:\s*(tests/\S+?\.py)::(\w+)")
_SITE_RE = re.compile(r"donate_argnums\s*=")
_LAZY_RE = re.compile(r"donate\s*=\s*(?!\(\)|None\b|frozenset)")


def find_sites(repo_root):
    """(relpath, lineno, line) for every donation site under mxnet_tpu/."""
    out = []
    pkg = os.path.join(repo_root, "mxnet_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, repo_root)
            with open(path, encoding="utf-8") as fh:
                lines = fh.readlines()
            for i, line in enumerate(lines, 1):
                stripped = line.split("#", 1)[0]
                if _SITE_RE.search(stripped):
                    out.append((rel, i, lines))
                elif "record_lazy" in stripped and \
                        _LAZY_RE.search(stripped):
                    out.append((rel, i, lines))
                elif re.search(r"\bdonate=donate\b", stripped) or \
                        re.search(r"\bdonate=\s*tuple\(", stripped):
                    out.append((rel, i, lines))
    return out


def marker_for(lines, lineno):
    """The closest donation-recovery marker within LOOKBACK lines above."""
    lo = max(0, lineno - 1 - LOOKBACK)
    for j in range(lineno - 1, lo - 1, -1):
        m = _MARK_RE.search(lines[j])
        if m:
            return m.group(1), m.group(2)
    return None


def all_markers(repo_root):
    """Every donation-recovery marker in the repo (for staleness)."""
    out = []
    for base in ("mxnet_tpu", "tools"):
        root = os.path.join(repo_root, base)
        if not os.path.isdir(root):
            continue
        for dirpath, _dirs, files in os.walk(root):
            for fn in sorted(files):
                if not fn.endswith(".py") or \
                        fn == "check_donation_sites.py":
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, repo_root)
                with open(path, encoding="utf-8") as fh:
                    for i, line in enumerate(fh, 1):
                        m = _MARK_RE.search(line)
                        if m:
                            out.append((rel, i, m.group(1), m.group(2)))
    return out


def test_exists(repo_root, test_file, test_name):
    path = os.path.join(repo_root, test_file)
    if not os.path.isfile(path):
        return False
    with open(path, encoding="utf-8") as fh:
        return f"def {test_name}(" in fh.read()


def check(repo_root=None):
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
    violations = []
    sites = find_sites(repo_root)
    if not sites:
        return ["no donation sites found under mxnet_tpu/ — did the "
                "donate_argnums call sites move?"]
    seen = set()
    for rel, lineno, lines in sites:
        if (rel, lineno) in seen:
            continue
        seen.add((rel, lineno))
        mark = marker_for(lines, lineno)
        if mark is None:
            violations.append(
                f"{rel}:{lineno}: donation site has no "
                f"'# donation-recovery: tests/...::test' marker within "
                f"{LOOKBACK} lines — every donation site must name the "
                "test that proves its failure-recovery path")
            continue
        tf, tn = mark
        if not test_exists(repo_root, tf, tn):
            violations.append(
                f"{rel}:{lineno}: donation-recovery marker names "
                f"{tf}::{tn}, which does not exist")
    for rel, lineno, tf, tn in all_markers(repo_root):
        if not test_exists(repo_root, tf, tn):
            v = (f"{rel}:{lineno}: stale donation-recovery marker "
                 f"{tf}::{tn} — test not found")
            if v not in violations:
                violations.append(v)
    return violations


def main():
    violations = check()
    for v in violations:
        print(f"check_donation_sites: {v}", file=sys.stderr)
    if violations:
        sys.exit(1)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    n = len({(r, l) for r, l, _ in find_sites(repo_root)})
    print(f"check_donation_sites: OK ({n} donation sites, every one "
          "names an existing recovery test)")


if __name__ == "__main__":
    main()

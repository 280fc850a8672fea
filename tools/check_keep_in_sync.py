#!/usr/bin/env python
"""Lint: KEEP-IN-SYNC marked blocks are actually identical.

Some logic is deliberately duplicated across the repo — the canonical
case is the span-union / waterfall rendering shared between
``mxnet_tpu/telemetry.py`` and the stdlib-only ``tools/trace_report.py``
(the tool must fold trace spools without importing jax, so it cannot
import the telemetry module).  A prose "keep in sync" comment rots the
first time one side is edited; this checker makes the contract
mechanical.

Structured markers fence each shared body:

    # >>> KEEP-IN-SYNC(<name>) <free-form note>
    ...shared code...
    # <<< KEEP-IN-SYNC(<name>)

Rules enforced over every ``*.py`` under ``mxnet_tpu/`` and ``tools/``:

* every opened block is closed (same name, same file, no nesting);
* every block name appears in **at least two files** (a block with one
  copy has nothing to be in sync with — either add the twin or drop the
  markers);
* all copies of a name are **textually identical** (exact line match,
  whitespace included — the blocks live at module level on both sides
  precisely so a plain diff is the contract).

Run directly (exit 1 on violations) or from the fast test in
``tests/test_memory.py`` — the same wiring as ``check_sync_free.py`` /
``check_metric_names.py``.
"""
from __future__ import annotations

import os
import re
import sys

_OPEN_RE = re.compile(r"^\s*#\s*>>>\s*KEEP-IN-SYNC\(([^)]+)\)")
_CLOSE_RE = re.compile(r"^\s*#\s*<<<\s*KEEP-IN-SYNC\(([^)]+)\)")
_SCAN_DIRS = ("mxnet_tpu", "tools")


def find_blocks(repo_root):
    """``{name: [(relpath, lineno, body_text), ...]}`` for every marked
    block, plus a list of marker violations (unclosed/unopened/nested)."""
    blocks: dict = {}
    violations = []
    for d in _SCAN_DIRS:
        base = os.path.join(repo_root, d)
        if not os.path.isdir(base):
            continue
        for dirpath, _dirs, files in os.walk(base):
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, repo_root)
                if os.path.basename(rel) == "check_keep_in_sync.py":
                    continue        # the docstring's marker example

                with open(path, encoding="utf-8") as fh:
                    lines = fh.readlines()
                open_name = None
                open_line = 0
                body: list = []
                for i, line in enumerate(lines, 1):
                    m = _OPEN_RE.match(line)
                    if m:
                        if open_name is not None:
                            violations.append(
                                f"{rel}:{i}: KEEP-IN-SYNC({m.group(1)}) "
                                f"opened inside still-open block "
                                f"{open_name!r} (line {open_line}) — "
                                "blocks cannot nest")
                        open_name = m.group(1).strip()
                        open_line = i
                        body = []
                        continue
                    m = _CLOSE_RE.match(line)
                    if m:
                        name = m.group(1).strip()
                        if open_name is None:
                            violations.append(
                                f"{rel}:{i}: close marker for "
                                f"KEEP-IN-SYNC({name}) with no open block")
                        elif name != open_name:
                            violations.append(
                                f"{rel}:{i}: close marker names {name!r} "
                                f"but the open block (line {open_line}) "
                                f"is {open_name!r}")
                        else:
                            blocks.setdefault(name, []).append(
                                (rel, open_line, "".join(body)))
                        open_name = None
                        body = []
                        continue
                    if open_name is not None:
                        body.append(line)
                if open_name is not None:
                    violations.append(
                        f"{rel}:{open_line}: KEEP-IN-SYNC({open_name}) "
                        "never closed")
    return blocks, violations


def check(repo_root=None):
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
    blocks, violations = find_blocks(repo_root)
    if not blocks and not violations:
        return ["no KEEP-IN-SYNC blocks found anywhere — did the markers "
                "move or get renamed?"]
    for name, copies in sorted(blocks.items()):
        files = {rel for rel, _l, _b in copies}
        if len(files) < 2:
            rel, lineno, _b = copies[0]
            violations.append(
                f"{rel}:{lineno}: KEEP-IN-SYNC({name}) exists in only one "
                "file — nothing to be in sync with (add the twin or drop "
                "the markers)")
            continue
        canon_rel, canon_line, canon_body = copies[0]
        for rel, lineno, body in copies[1:]:
            if body != canon_body:
                # name the first diverging line so the fix is a one-look
                a = canon_body.splitlines()
                b = body.splitlines()
                diverge = next(
                    (j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                    min(len(a), len(b)))
                theirs = b[diverge].strip() if diverge < len(b) \
                    else "<missing>"
                ours = a[diverge].strip() if diverge < len(a) \
                    else "<missing>"
                violations.append(
                    f"KEEP-IN-SYNC({name}) diverged: {rel}:{lineno} != "
                    f"{canon_rel}:{canon_line} (first difference at block "
                    f"line {diverge + 1}: {theirs!r} vs {ours!r})")
    return violations


def main():
    violations = check()
    for v in violations:
        print(f"check_keep_in_sync: {v}", file=sys.stderr)
    if violations:
        sys.exit(1)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    blocks, _v = find_blocks(repo_root)
    n_copies = sum(len(c) for c in blocks.values())
    print(f"check_keep_in_sync: OK ({len(blocks)} blocks, "
          f"{n_copies} copies verified identical)")


if __name__ == "__main__":
    main()

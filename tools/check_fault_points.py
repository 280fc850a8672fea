#!/usr/bin/env python
"""Lint: the fault-point registry stays coherent.

``mxnet_tpu.faults`` turns failure into a deterministically testable code
path by compiling named fault points into the hot paths
(``faults.point("trainer.step")``).  That only works while the registry
stays disciplined; this checker enforces, over every literal
``*.point("...")`` call under ``mxnet_tpu/``:

* names match the ``subsystem.site`` grammar (lowercase, dot-separated) —
  no free-form strings;
* wire-level call sites (``faults.wire_point("net....")``, the HTTP
  client/server boundaries that apply ``delay``/``reset``/``torn``/
  ``blackhole`` at the byte level) are first-class registrations under
  the same rules, and the ``net.*`` family may ONLY be registered
  through ``wire_point`` — a plain ``point()`` cannot tear bytes, so a
  ``net.*`` name on it would be a fault point that cannot express its
  own documented kinds;
* every name is **unique** per call site *module* (the same conceptual
  point may be shared across implementations of the same surface, e.g.
  ``trainer.step`` in both ``gluon.Trainer`` and ``SPMDTrainer``, but a
  module must not hit one name from two places);
* every name is **documented** in the registry table of
  ``docs/RESILIENCE.md``;
* the RESILIENCE.md table lists no phantom points that exist nowhere in
  the code;
* every name is **exercised** by at least one test (appears literally
  somewhere under ``tests/``) — an untested fault point is a recovery
  path nobody has ever run.

Run directly (exit 1 on violations) or from the fast test in
``tests/test_faults.py`` — the same wiring as ``check_sync_free.py`` /
``check_metric_names.py``.
"""
from __future__ import annotations

import ast
import os
import re
import sys

_NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
_DOC = os.path.join("docs", "RESILIENCE.md")


def find_points(repo_root):
    """(name, relpath, lineno, fn) for every literal fault-point call
    under mxnet_tpu/ — ``faults.point("...")`` / ``_faults.point("...")``
    and the wire-level ``faults.wire_point("...")`` sites."""
    out = []
    pkg = os.path.join(repo_root, "mxnet_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, repo_root)
            with open(path, encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read(), filename=path)
                except SyntaxError:
                    continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if not (isinstance(f, ast.Attribute) and
                        f.attr in ("point", "wire_point")):
                    continue
                if not (isinstance(f.value, ast.Name) and
                        "faults" in f.value.id):
                    continue
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    out.append((node.args[0].value, rel, node.lineno,
                                f.attr))
    return out


def documented_points(repo_root):
    """Point names listed in the RESILIENCE.md registry table (the
    backtick-quoted first column of the fault-point table)."""
    path = os.path.join(repo_root, _DOC)
    if not os.path.isfile(path):
        return None
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    names = set()
    for m in re.finditer(r"^\|\s*`([a-z0-9_.]+)`", src, re.M):
        if _NAME_RE.match(m.group(1)):
            names.add(m.group(1))
    return names


def tested_points(repo_root, names):
    """Subset of ``names`` appearing literally in some tests/*.py file."""
    tdir = os.path.join(repo_root, "tests")
    blob = []
    for fn in sorted(os.listdir(tdir)):
        if fn.endswith(".py"):
            with open(os.path.join(tdir, fn), encoding="utf-8") as fh:
                blob.append(fh.read())
    blob = "\n".join(blob)
    return {n for n in names if n in blob}


def check(repo_root=None):
    if repo_root is None:
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
    points = find_points(repo_root)
    violations = []
    if not points:
        return [f"no fault points found under mxnet_tpu/ — did the "
                "faults.point call sites move?"]

    names = {}
    per_module = {}
    for name, rel, lineno, fn in points:
        names.setdefault(name, []).append((rel, lineno))
        key = (name, rel)
        per_module.setdefault(key, []).append(lineno)
        if not _NAME_RE.match(name):
            violations.append(
                f"{rel}:{lineno}: fault point {name!r} does not match the "
                "subsystem.site grammar (lowercase dot-separated)")
        if name.startswith("net.") and fn != "wire_point":
            violations.append(
                f"{rel}:{lineno}: wire-level fault point {name!r} must "
                "register through faults.wire_point (a plain point() "
                "cannot apply torn/reset/blackhole at the byte level)")
        if fn == "wire_point" and not name.startswith("net."):
            violations.append(
                f"{rel}:{lineno}: wire_point registration {name!r} is "
                "outside the net.* family — wire semantics belong to "
                "wire-level points")
    for (name, rel), linenos in sorted(per_module.items()):
        if len(linenos) > 1:
            violations.append(
                f"{rel}: fault point {name!r} registered at {len(linenos)} "
                f"call sites in one module (lines {linenos}) — one name, "
                "one site; split the names or hoist the point")

    docset = documented_points(repo_root)
    if docset is None:
        violations.append(f"{_DOC} missing — the fault-point registry "
                          "must be documented")
        docset = set()
    for name in sorted(names):
        if name not in docset:
            sites = ", ".join(f"{r}:{l}" for r, l in names[name])
            violations.append(
                f"fault point {name!r} ({sites}) is not documented in the "
                f"{_DOC} registry table")
    for name in sorted(docset - set(names)):
        violations.append(
            f"{_DOC} documents fault point {name!r} but no "
            "faults.point call site exists — stale registry entry")

    tested = tested_points(repo_root, set(names))
    for name in sorted(set(names) - tested):
        violations.append(
            f"fault point {name!r} is not exercised by any test under "
            "tests/ — an untested fault point is a recovery path nobody "
            "has ever run")
    return violations


def main():
    violations = check()
    for v in violations:
        print(f"check_fault_points: {v}", file=sys.stderr)
    if violations:
        sys.exit(1)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    n = len({name for name, _r, _l, _f in find_points(repo_root)})
    print(f"check_fault_points: OK ({n} fault points registered, "
          "documented and tested)")


if __name__ == "__main__":
    main()

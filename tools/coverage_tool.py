#!/usr/bin/env python
"""Line coverage for the test suite without pytest-cov (absent in this
environment).

Uses Python 3.12 ``sys.monitoring``: a LINE callback records each
(file, line) once and then returns ``DISABLE`` for that location, so
steady-state overhead is near zero.  Executable-line denominators come from
the AST (statement linenos), the same notion gcov-style tools report.

Usage:  python tools/coverage_tool.py [pytest args...]
Writes: build/coverage.txt (per-module table + total) and prints the total.
"""

import ast
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "nnstreamer_tpu")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # `python tools/coverage_tool.py` from anywhere
TOOL_ID = 5  # sys.monitoring tool slot (0-5 free for apps)

_hit = {}  # filename -> set[lineno]


def _on_line(code, lineno):
    fn = code.co_filename
    if fn.startswith(PKG):
        s = _hit.get(fn)
        if s is None:
            _hit[fn] = s = set()
        s.add(lineno)
    return sys.monitoring.DISABLE  # one hit per location is enough


def executable_lines(path):
    """Line numbers of executable statements (AST), minus docstrings."""
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return set()
    lines = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.stmt):
            # skip bare docstring expressions
            if isinstance(node, ast.Expr) and isinstance(
                node.value, ast.Constant
            ) and isinstance(node.value.value, str):
                continue
            lines.add(node.lineno)
    return lines


def main():
    sys.monitoring.use_tool_id(TOOL_ID, "nns-cov")
    sys.monitoring.register_callback(
        TOOL_ID, sys.monitoring.events.LINE, _on_line
    )
    sys.monitoring.set_events(TOOL_ID, sys.monitoring.events.LINE)

    import pytest

    rc = pytest.main(sys.argv[1:] or ["tests/", "-q"])

    sys.monitoring.set_events(TOOL_ID, 0)

    rows = []
    tot_exec = tot_hit = 0
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            ex = executable_lines(path)
            if not ex:
                continue
            hit = _hit.get(path, set()) & ex
            tot_exec += len(ex)
            tot_hit += len(hit)
            rel = os.path.relpath(path, ROOT)
            rows.append((rel, len(hit), len(ex),
                         100.0 * len(hit) / len(ex)))
    total_pct = 100.0 * tot_hit / max(1, tot_exec)

    lines = [
        "# Test-suite line coverage (tools/coverage_tool.py, sys.monitoring)",
        f"# pytest exit code: {rc}",
        "",
        f"{'module':58s} {'hit':>6s} {'exec':>6s} {'pct':>7s}",
    ]
    for rel, h, e, pct in rows:
        lines.append(f"{rel:58s} {h:6d} {e:6d} {pct:6.1f}%")
    lines.append("-" * 80)
    lines.append(f"{'TOTAL':58s} {tot_hit:6d} {tot_exec:6d} {total_pct:6.1f}%")
    out = "\n".join(lines) + "\n"
    os.makedirs(os.path.join(ROOT, "build"), exist_ok=True)
    with open(os.path.join(ROOT, "build", "coverage.txt"), "w") as f:
        f.write(out)
    print(out.splitlines()[-1])
    return rc


if __name__ == "__main__":
    sys.exit(main())

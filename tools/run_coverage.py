"""Stdlib-only line coverage for the test suite (PEP 669 sys.monitoring).

coverage.py / pytest-cov have no installable distribution in the zero-egress
build environment (docs/operations.md), so this uses Python 3.12's
monitoring API directly: a LINE callback that returns
``sys.monitoring.DISABLE`` after the first hit of each code location -
steady-state overhead is near zero (the same mechanism coverage.py's
``sysmon`` core uses).

Usage::

    python tools/run_coverage.py                # full suite + report
    python tools/run_coverage.py tests/test_schema.py   # subset
    COV=1 ./ci.sh                               # CI entry

Reference analog: the reference tracks line coverage via codecov
(/root/reference/README.rst:4-12).

Caveats (stated in the report): subprocess children (spawn-based process
pools, the multi-process selfcheck workers, bench train children) execute
outside this process, so lines only they reach count as uncovered here.
"""

from __future__ import annotations

import os
import sys
from typing import Dict, Set


class LineCoverage:
    """Record executed lines of files under ``root`` via sys.monitoring."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root) + os.sep
        self.hits: Dict[str, Set[int]] = {}
        self._tool = sys.monitoring.COVERAGE_ID

    def _on_line(self, code, line):
        fn = code.co_filename
        if fn.startswith(self.root):
            self.hits.setdefault(fn, set()).add(line)
        # one hit per location is all line coverage needs: disabling the
        # event at this location makes the steady state almost free
        return sys.monitoring.DISABLE

    def start(self) -> None:
        sys.monitoring.use_tool_id(self._tool, "petastorm-tpu-linecov")
        sys.monitoring.register_callback(
            self._tool, sys.monitoring.events.LINE, self._on_line)
        sys.monitoring.set_events(self._tool, sys.monitoring.events.LINE)

    def stop(self) -> None:
        sys.monitoring.set_events(self._tool, 0)
        sys.monitoring.register_callback(
            self._tool, sys.monitoring.events.LINE, None)
        sys.monitoring.free_tool_id(self._tool)


def executable_lines(path: str) -> Set[int]:
    """The interpreter's own notion of executable lines: compile the file
    and walk every code object's ``co_lines`` - the honest denominator
    (comments/blank lines never appear; docstring loads do)."""
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        top = compile(source, path, "exec")
    except SyntaxError:
        return set()
    lines: Set[int] = set()
    stack = [top]
    while stack:
        code = stack.pop()
        for _, _, line in code.co_lines():
            if line is not None:
                lines.add(line)
        for const in code.co_consts:
            if hasattr(const, "co_lines"):
                stack.append(const)
    return lines


def report(cov: LineCoverage, pkg_root: str) -> float:
    rows = []
    total_exec = total_hit = 0
    for dirpath, _, files in os.walk(pkg_root):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            exe = executable_lines(path)
            if not exe:
                continue
            hit = len(cov.hits.get(path, set()) & exe)
            total_exec += len(exe)
            total_hit += hit
            rows.append((os.path.relpath(path, pkg_root), hit, len(exe)))
    rows.sort(key=lambda r: r[1] / r[2])
    print("\n== line coverage (sys.monitoring; in-process only - spawn-pool"
          " workers, selfcheck processes and bench children run elsewhere) ==")
    for rel, hit, exe in rows:
        print(f"  {100.0 * hit / exe:5.1f}%  {hit:5d}/{exe:<5d}  {rel}")
    pct = 100.0 * total_hit / max(total_exec, 1)
    print(f"COVERAGE_TOTAL {pct:.1f}% ({total_hit}/{total_exec} lines)")
    return pct


def main() -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import pytest

    # resolve the package by PATH, not import: importing it here would run
    # every module-level line BEFORE the monitor starts, permanently
    # undercounting them (import happens once per process)
    pkg_root = os.path.join(repo, "petastorm_tpu")
    cov = LineCoverage(pkg_root)
    cov.start()
    try:
        rc = pytest.main(["tests/", "-q"] + sys.argv[1:])
    finally:
        cov.stop()
        report(cov, pkg_root)
    return int(rc)


if __name__ == "__main__":
    sys.exit(main())

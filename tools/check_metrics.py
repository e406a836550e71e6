#!/usr/bin/env python3
"""Metric-name documentation lint (CI docs job).

DESIGN.md §12 documents the telemetry plane's canonical metric names in
a table; the single source of truth for those names is
``src/repro/obs/names.py``. This lint holds the two together, both
ways, statically (ast — no jax import needed):

* every ``serve.*`` / ``cim.*`` / ``jit.*`` / ``resnet.*`` / ``vit.*`` name and
  every ``cim_*`` kernel name appearing in DESIGN.md §12 must be the
  value of a constant in ``repro/obs/names.py``;
* every constant in ``names.py`` must appear in the §12 table.

  python tools/check_metrics.py
"""
from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
NAMES_PY = REPO / "src" / "repro" / "obs" / "names.py"
DESIGN = REPO / "DESIGN.md"

#: backticked names in §12: dotted metric and scope names such as
#: `serve.queue.depth` or `resnet.s{stage}b{block}`, and kernel names
#: such as `cim_matmul`
NAME_RE = re.compile(
    r"`((?:serve|cim|jit|resnet|vit)\.[a-z0-9_.{}]+|cim_[a-z0-9_]+)`")


def declared_names() -> set[str]:
    tree = ast.parse(NAMES_PY.read_text(encoding="utf-8"),
                     filename=str(NAMES_PY))
    names = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            names.add(node.value.value)
    if not names:
        raise SystemExit(f"{NAMES_PY}: no string constants found")
    return names


def documented_names() -> set[str]:
    text = DESIGN.read_text(encoding="utf-8")
    marker = "§12"
    at = text.find(f"## {marker}")
    if at < 0:
        raise SystemExit(f"{DESIGN}: no §12 section found")
    return set(NAME_RE.findall(text[at:]))


def main() -> int:
    declared = declared_names()
    documented = documented_names()
    undeclared = sorted(documented - declared)
    undocumented = sorted(declared - documented)
    if undeclared:
        print("DESIGN.md §12 documents metric names that do not exist in "
              "src/repro/obs/names.py:")
        for n in undeclared:
            print(f"  {n}")
    if undocumented:
        print("src/repro/obs/names.py declares metric names missing from "
              "the DESIGN.md §12 table:")
        for n in undocumented:
            print(f"  {n}")
    if undeclared or undocumented:
        return 1
    print(f"ok: {len(declared)} metric names consistent between "
          "DESIGN.md §12 and repro/obs/names.py")
    return 0


if __name__ == "__main__":
    sys.exit(main())

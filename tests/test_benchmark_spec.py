"""``benchmark/test_benchmark_spec.py`` lives beside the harness (a PR that
adds a cell may add files only there); its cases are collected here by
path so that tier-1 counts them."""
from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_path = os.path.join(ROOT, "benchmark", "test_benchmark_spec.py")
_spec = importlib.util.spec_from_file_location("benchmark_spec_selftests",
                                               _path)
_mod = importlib.util.module_from_spec(_spec)
sys.modules["benchmark_spec_selftests"] = _mod
_spec.loader.exec_module(_mod)
bench, config = _mod.bench, _mod.config           # the module's fixtures
globals().update({k: v for k, v in vars(_mod).items()
                  if k.startswith("test_")})


def test_cell_is_listed_where_its_readers_find_something(bench):  # noqa: F811
    """That test holds its cell to be the benchmark's LAST and its new
    metrics to list it alone; a later ``model_config`` PR appends a cell,
    appends its name to lists, and may not edit that file.  So the test is
    handed the benchmark without the later cells' names, and nothing else
    cut (ROADMAP B0 (ix) asks the ``benchmark`` issue to loosen the two
    lines there)."""
    names = [w["name"] for w in bench["workloads"]]
    cut = names.index(_mod.CELL) + 1
    later = set(names[cut:])
    _mod.test_cell_is_listed_where_its_readers_find_something(dict(
        bench, workloads=bench["workloads"][:cut],
        per_layer=[dict(m, workloads=[w for w in m["workloads"]
                                      if w not in later])
                   if "workloads" in m else m for m in bench["per_layer"]]))

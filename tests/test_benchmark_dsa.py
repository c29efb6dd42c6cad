"""``benchmark/test_benchmark_dsa.py`` lives beside the harness (a PR that
adds a cell may add files only there); its cases are collected here by
path so that tier-1 counts them."""
from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_path = os.path.join(ROOT, "benchmark", "test_benchmark_dsa.py")
_spec = importlib.util.spec_from_file_location("benchmark_dsa_selftests",
                                               _path)
_mod = importlib.util.module_from_spec(_spec)
sys.modules["benchmark_dsa_selftests"] = _mod
_spec.loader.exec_module(_mod)
bench, config = _mod.bench, _mod.config           # the module's fixtures
globals().update({k: v for k, v in vars(_mod).items()
                  if k.startswith("test_")})


def test_cell_is_listed_where_its_readers_find_something(bench):
    """The benchmark-side case pins the cell's nine metrics as the LAST
    nine of ``per_layer``, as they were when it was written; entries
    appended since (which a PR may not put anywhere else, nor edit that
    file), the cell's own later ones among them
    (``index_key_pages_per_grid_step.replay``, PR 49), are not its
    business: the list is cut behind the ninth that names the cell alone.
    Nor is a cell appended since that joined one of those nine's lists
    (``dev_mlp_dense_share.replay``, PR 53): the later cells are taken out
    of every list first."""
    cells = [w["name"] for w in bench["workloads"]]
    later = set(cells[cells.index(_mod.CELL) + 1:])
    per_layer = [dict(m, workloads=[c for c in m["workloads"]
                                    if c not in later])
                 for m in bench["per_layer"]]
    own = [i for i, m in enumerate(per_layer)
           if m["workloads"] == [_mod.CELL]]
    _mod.test_cell_is_listed_where_its_readers_find_something(
        dict(bench, per_layer=per_layer[:own[8] + 1]))

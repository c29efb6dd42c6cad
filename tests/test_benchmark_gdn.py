"""``benchmark/test_benchmark_gdn.py`` lives beside the harness (a PR
that adds a cell may add files only there); its cases are collected here
by path so that tier-1 counts them."""
from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_path = os.path.join(ROOT, "benchmark", "test_benchmark_gdn.py")
_spec = importlib.util.spec_from_file_location("benchmark_gdn_selftests",
                                               _path)
_mod = importlib.util.module_from_spec(_spec)
sys.modules["benchmark_gdn_selftests"] = _mod
_spec.loader.exec_module(_mod)
bench, config = _mod.bench, _mod.config           # the module's fixtures
globals().update({k: v for k, v in vars(_mod).items()
                  if k.startswith("test_")})

"""End-to-end example scripts must run and self-check on the virtual
mesh (reference examples/{gpt,hydraulis,malleus} smoke coverage)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(script, *argv, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *argv],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, \
        f"{script} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    return proc.stdout


@pytest.mark.slow
class TestExamples:
    def test_train_gpt_dp_tp(self):
        out = _run_example(
            "train_gpt.py", "--dp", "2", "--tp", "2", "--steps", "4",
            "--hidden", "64", "--layers", "2", "--heads", "4",
            "--seq-len", "32", "--vocab-size", "128",
            "--global-batch", "8", "--log-every", "2")
        assert "step" in out

    def test_train_gpt_pp_from_ds_config(self, tmp_path):
        import json
        sys.path.insert(0, REPO)
        from hetu_tpu.utils.ds_config import generate_gpt_3d_config
        cfg = generate_gpt_3d_config(num_layers=4, dp=2, tp=2, pp=2,
                                     zero=True)
        p = str(tmp_path / "pp2.json")
        json.dump(cfg, open(p, "w"))
        out = _run_example(
            "train_gpt.py", "--ds-config", p, "--steps", "4",
            "--hidden", "64", "--layers", "4", "--heads", "4",
            "--seq-len", "32", "--vocab-size", "128",
            "--global-batch", "8", "--log-every", "2")
        assert "step" in out

    def test_train_gpt_auto_parallel(self):
        """--auto-parallel: the planner picks (dp, tp, pp, zero,
        micro-batch) for the visible 8 devices and training runs under
        the selected plan (the closed Galvatron loop)."""
        out = _run_example(
            "train_gpt.py", "--auto-parallel", "--steps", "4",
            "--hidden", "64", "--layers", "2", "--heads", "4",
            "--seq-len", "32", "--vocab-size", "128",
            "--global-batch", "8", "--log-every", "2")
        assert "step" in out

    def test_train_hydraulis(self):
        out = _run_example("train_hydraulis.py", "--steps", "5")
        assert "hydraulis e2e OK" in out

    def test_train_malleus(self):
        out = _run_example("train_malleus.py", "--steps", "12")
        assert "malleus e2e OK" in out

    def test_train_malleus_calibrated(self):
        out = _run_example("train_malleus.py", "--steps", "12",
                           "--calibrate")
        assert "calibrated:" in out and "malleus e2e OK" in out

    def test_generate_gpt(self):
        out = _run_example("generate_gpt.py", "--steps", "120",
                           "--hidden", "48")
        assert "self-check OK" in out

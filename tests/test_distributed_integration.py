"""Real multi-process jax.distributed integration.

The reference CI launches N actual worker processes through pssh + gRPC
and trains (`tests/ci_test/scripts/pssh_train_hetu.sh`,
`python/hetu/rpc/pssh_start.py:19`).  Counterpart here: the Launcher
spawns REAL python processes; each bootstraps ``jax.distributed`` through
the coordinator (rendezvous + KV address exchange in
``rpc.coordinator.distributed_init``), forms a global dp mesh (one CPU
device per process, gloo collectives), and trains a tiny data-parallel
model.  The loss trajectory must equal the single-process oracle, and a
worker crash before init must be healed by the launcher restart budget.

Workers are pinned to the CPU (``JAX_PLATFORMS=cpu``): a chip belongs to
one process, and N workers inheriting the environment would each claim
every chip of the host.
"""
import json
import os
import sys

import numpy as np
import pytest

from hetu_tpu.rpc.launcher import Launcher

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {repo!r})
import numpy as np

rank_env = os.environ["HETU_TPU_WORKER_RANK"]
crash_marker = os.environ.get("ITEST_CRASH_MARKER", "")
if crash_marker and rank_env == "1" and not os.path.exists(crash_marker):
    # simulate a worker lost before distributed init; the launcher's
    # restart budget must revive it and the job must still complete
    open(crash_marker, "w").close()
    sys.exit(1)

from hetu_tpu.rpc.coordinator import distributed_init
addr = os.environ["HETU_TPU_COORDINATOR"]
n = int(os.environ["HETU_TPU_NUM_WORKERS"])
client = distributed_init(addr, num_hosts=n, uid=f"worker-{{rank_env}}")

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

assert jax.process_count() == n, jax.process_count()
assert jax.process_index() == client.rank, (jax.process_index(), client.rank)
devs = jax.devices()
assert len(devs) == n, devs  # one CPU device per process, globally visible

mesh = Mesh(np.array(devs), ("dp",))
rank = client.rank
per = 4
rng = np.random.RandomState(0)
X = rng.randn(per * n, 8).astype(np.float32)
Y = rng.randn(per * n, 1).astype(np.float32)
W0 = rng.randn(8, 1).astype(np.float32)

dsh = NamedSharding(mesh, P("dp"))
Xg = jax.make_array_from_process_local_data(dsh, X[rank * per:(rank + 1) * per])
Yg = jax.make_array_from_process_local_data(dsh, Y[rank * per:(rank + 1) * per])
W = jax.device_put(W0, NamedSharding(mesh, P()))

@jax.jit
def step(W, X, Y):
    l, g = jax.value_and_grad(lambda W: jnp.mean((X @ W - Y) ** 2))(W)
    return l, W - 0.1 * g

losses = []
for _ in range(4):
    l, W = step(W, Xg, Yg)
    losses.append(float(l))   # replicated scalar; grad psum rode gloo

out_dir = os.environ["ITEST_OUT_DIR"]
with open(os.path.join(out_dir, f"losses_{{rank}}.json"), "w") as f:
    json.dump(losses, f)
client.barrier("done", world_size=n, timeout=120)
client.exit()
"""


def _oracle_losses(n, per=4, steps=4):
    rng = np.random.RandomState(0)
    X = rng.randn(per * n, 8).astype(np.float32)
    Y = rng.randn(per * n, 1).astype(np.float32)
    W = rng.randn(8, 1).astype(np.float32)
    losses = []
    for _ in range(steps):
        E = X @ W - Y
        losses.append(float(np.mean(E ** 2)))
        W = W - 0.1 * (2.0 / X.shape[0]) * (X.T @ E)
    return losses


def _run(tmp_path, n, crash=False, max_restarts=0):
    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO))
    env = {
        "JAX_PLATFORMS": "cpu",
        # override conftest's 8-device flag the pytest process exported:
        # each worker contributes exactly ONE device to the global mesh
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "ITEST_OUT_DIR": str(tmp_path),
    }
    if crash:
        env["ITEST_CRASH_MARKER"] = str(tmp_path / "crashed")
    with Launcher([sys.executable, str(script)], num_workers=n,
                  max_restart_times=max_restarts, env=env) as l:
        ok = l.monitor(poll=0.2, timeout=300)
    losses = []
    for r in range(n):
        p = tmp_path / f"losses_{r}.json"
        assert p.exists(), f"rank {r} left no losses"
        losses.append(json.loads(p.read_text()))
    return ok, losses, l.events


class TestMultiProcessTraining:
    def test_dp_training_matches_single_process(self, tmp_path):
        """4 real processes bootstrap jax.distributed via the coordinator
        and train; every rank's (replicated) loss trajectory equals the
        single-process oracle."""
        n = 4
        ok, losses, _ = _run(tmp_path, n)
        assert ok == n
        oracle = _oracle_losses(n)
        for r in range(n):
            np.testing.assert_allclose(losses[r], oracle, rtol=1e-5,
                                       atol=1e-6)
        assert losses[0][-1] < losses[0][0]   # actually trained

    def test_worker_crash_is_restarted_and_job_completes(self, tmp_path):
        """Rank 1 dies before distributed init; the launcher restarts it
        (uid-keyed rank recycling) and the whole job still trains to the
        oracle trajectory."""
        n = 2
        ok, losses, events = _run(tmp_path, n, crash=True, max_restarts=1)
        assert ok == n
        assert any(e["event"] == "restart" and e["rank"] == 1
                   for e in events), events
        oracle = _oracle_losses(n)
        for r in range(n):
            np.testing.assert_allclose(losses[r], oracle, rtol=1e-5,
                                       atol=1e-6)

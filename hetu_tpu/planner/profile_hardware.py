"""Measured hardware profiling feeding the planner / elastic cost models.

TPU-native counterpart of the reference's profiling pass
(``tools/Galvatron/galvatron/profile_hardware/profile_hardware.py``, which
shells out to nccl-tests + matmul benchmarks and writes the fitted
constants consumed by ``galvatron/core/profiler.py``).  Here the same
measurements run through jax on the live backend:

- ``profile_matmul``     — achievable matmul FLOP/s (MXU roofline point)
- ``profile_hbm``        — HBM read+write bandwidth (elementwise saxpy)
- ``profile_collectives``— alpha-beta (latency, 1/bw) fits per collective
                           over a mesh axis, via least squares on message
                           -size sweeps
- ``calibrate``          — folds the measurements into a ``ChipSpec`` /
                           ``ClusterSpec`` (replacing the datasheet
                           constants) and into the elastic
                           ``StrategyModel`` constants
                           (``layer_comm_cost``, ``pipeline_p2p_cost``)
- ``validate_step_prediction`` — predicted-vs-measured wall time of a
                           real training step (the reference validates its
                           cost model the same way before trusting the
                           search)

Results serialize to JSON so a one-off profile feeds later planner runs.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cost_model import CHIPS, ChipSpec, ClusterSpec


def _time_fn(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    """Median wall time of fn(*args) (jitted by the caller)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# ---------------------------------------------------------------------------
# compute / memory
# ---------------------------------------------------------------------------

def profile_matmul(sizes: Sequence[int] = (1024, 2048, 4096),
                   dtype: str = "bfloat16",
                   reps: int = 5) -> Dict[int, float]:
    """Measured FLOP/s of square matmuls (datasheet check of
    peak_flops * mxu_efficiency)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for n in sizes:
        a = jnp.asarray(np.random.RandomState(0).randn(n, n), dtype)
        b = jnp.asarray(np.random.RandomState(1).randn(n, n), dtype)
        f = jax.jit(lambda a, b: a @ b)
        t = _time_fn(f, a, b, reps=reps)
        out[int(n)] = 2.0 * n ** 3 / t
    return out


def profile_hbm(nbytes: int = 1 << 28, dtype: str = "float32",
                reps: int = 5) -> float:
    """Measured HBM bandwidth (bytes/s) via y = 2*x + 1 (read + write)."""
    import jax
    import jax.numpy as jnp
    n = nbytes // np.dtype(np.float32).itemsize
    x = jnp.arange(n, dtype=dtype)
    f = jax.jit(lambda x: 2.0 * x + 1.0)
    t = _time_fn(f, x, reps=reps)
    itemsize = np.dtype(dtype).itemsize if dtype != "bfloat16" else 2
    return 2.0 * n * itemsize / t   # one read + one write


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def _fit_alpha_beta(sizes_bytes: Sequence[float],
                    times: Sequence[float]) -> Tuple[float, float]:
    """Least-squares fit t = alpha + beta * bytes; clamped to >= 0."""
    A = np.stack([np.ones(len(sizes_bytes)), np.asarray(sizes_bytes)], 1)
    (alpha, beta), *_ = np.linalg.lstsq(A, np.asarray(times), rcond=None)
    return max(0.0, float(alpha)), max(0.0, float(beta))


def profile_collectives(mesh, axis: str,
                        sizes: Sequence[int] = (1 << 16, 1 << 20, 1 << 23),
                        dtype: str = "float32",
                        reps: int = 5) -> Dict[str, Tuple[float, float]]:
    """(alpha, beta) per collective over ``axis`` of ``mesh``:
    't = alpha + beta * message_bytes'.  Keys: all_reduce, all_gather,
    reduce_scatter, p2p (ring ppermute).  ``beta`` is seconds/byte —
    1/beta is the achieved bus bandwidth the planner's
    ``ClusterSpec.bw_for_group`` should report."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.comm import shard_map

    n = mesh.shape[axis]
    itemsize = 2 if dtype == "bfloat16" else np.dtype(dtype).itemsize

    def timed(make_fn, elems) -> float:
        x = jnp.asarray(np.random.RandomState(0).randn(n * elems)
                        .reshape(n, elems), dtype)
        f = jax.jit(shard_map(make_fn, mesh, (P(axis, None),), P(axis, None)))
        return _time_fn(f, x, reps=reps)

    perm = [(i, (i + 1) % n) for i in range(n)]
    builders = {
        "all_reduce": lambda v: lax.psum(v, axis),
        "all_gather": lambda v: lax.all_gather(
            v, axis, axis=1, tiled=True)[:, :v.shape[1]],
        "reduce_scatter": lambda v: jnp.tile(
            lax.psum_scatter(v, axis, scatter_dimension=1, tiled=True),
            (1, n)) if v.shape[1] % n == 0 else v,
        "p2p": lambda v: lax.ppermute(v, axis, perm),
    }
    out = {}
    for name, builder in builders.items():
        ts, szs = [], []
        for nb in sizes:
            elems = max(n, nb // itemsize // max(1, n) * max(1, n))
            ts.append(timed(builder, elems))
            szs.append(elems * itemsize)
        out[name] = _fit_alpha_beta(szs, ts)
    return out


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Calibration:
    """Everything the cost models consume, measured on the live backend."""
    matmul_flops: Dict[int, float] = dataclasses.field(default_factory=dict)
    hbm_bw: float = 0.0
    collectives: Dict[str, Tuple[float, float]] = \
        dataclasses.field(default_factory=dict)
    device_kind: str = "?"
    platform: str = "?"

    @property
    def best_matmul_flops(self) -> float:
        return max(self.matmul_flops.values()) if self.matmul_flops else 0.0

    def to_chip_spec(self, base: Optional[ChipSpec] = None) -> ChipSpec:
        """Fold measurements into a ChipSpec: measured matmul throughput
        replaces peak*efficiency, measured HBM bandwidth replaces the
        datasheet number, collective beta-fit replaces ici_bw."""
        base = base or chip_for(self.platform, self.device_kind)
        kw: Dict = {}
        if self.best_matmul_flops:
            # keep nominal peak when it is plausible; fold the measurement
            # into mxu_efficiency (the planner multiplies them)
            if self.best_matmul_flops <= base.peak_flops:
                kw["mxu_efficiency"] = \
                    self.best_matmul_flops / base.peak_flops
            else:
                kw["peak_flops"] = self.best_matmul_flops
                kw["mxu_efficiency"] = 1.0
        if self.hbm_bw:
            kw["hbm_bw"] = self.hbm_bw
        ar = self.collectives.get("all_reduce")
        if ar:
            alpha, beta = ar
            if beta > 0:
                kw["ici_bw"] = 1.0 / beta
            kw["ici_latency"] = max(alpha, 1e-9)
        return dataclasses.replace(base, **kw)

    def to_cluster_spec(self, num_chips: int = 8, num_slices: int = 1,
                        base: Optional[ChipSpec] = None) -> ClusterSpec:
        """Fold the measurements into a full :class:`ClusterSpec`: the
        calibrated chip (:meth:`to_chip_spec`) PLUS the per-collective
        ``(alpha, beta)`` link fits, fed straight into the shared
        alpha-beta formulas (``cost_model.collective_time``) — so the
        planner's DP solver and the analysis step-time linter price
        every collective from the same measured link speeds instead of
        the datasheet ring model."""
        return ClusterSpec(
            chip=self.to_chip_spec(base),
            num_chips=max(1, int(num_chips)),
            num_slices=max(1, int(num_slices)),
            link_alpha_beta={k: (float(a), float(b))
                             for k, (a, b) in self.collectives.items()}
            if self.collectives else None)

    def elastic_constants(self, batch: int, seq: int, hidden: int,
                          ffn: int, tp: int = 2,
                          dtype_bytes: int = 2) -> Dict[str, float]:
        """Measured replacements for StrategyModel's invented
        layer_comm_cost / pipeline_p2p_cost: per-layer TP-collective and
        stage-boundary p2p time expressed in units of per-layer compute
        time at tp=1 (the solver's layer unit)."""
        from .cost_model import transformer_layer_spec
        spec = transformer_layer_spec(batch, seq, hidden, ffn, dtype_bytes)
        flops = self.best_matmul_flops or ChipSpec().peak_flops * 0.5
        layer_t = 3.0 * spec.flops / flops
        ar = self.collectives.get("all_reduce", (1e-6, 1e-11))
        p2p = self.collectives.get("p2p", (1e-6, 1e-11))
        ar_t = 4 * (ar[0] + ar[1] * spec.boundary_bytes)  # Megatron 2f+2b
        p2p_t = p2p[0] + p2p[1] * spec.boundary_bytes
        return {
            "layer_comm_cost": ar_t / max(layer_t, 1e-12),
            "pipeline_p2p_cost": p2p_t / max(layer_t, 1e-12),
        }

    def save(self, path: str) -> None:
        d = dataclasses.asdict(self)
        d["matmul_flops"] = {str(k): v for k, v in d["matmul_flops"].items()}
        with open(path, "w") as f:
            json.dump(d, f, indent=2)

    @classmethod
    def load(cls, path: str) -> "Calibration":
        with open(path) as f:
            d = json.load(f)
        d["matmul_flops"] = {int(k): v for k, v in d["matmul_flops"].items()}
        d["collectives"] = {k: tuple(v) for k, v in d["collectives"].items()}
        return cls(**d)


# the chip a process WITHOUT a TPU plans for (tier-1, dry runs on a
# host): named here, not inherited from a fall-through
PLANNING_CHIP = "v5p"


def _kind_key(device_kind: str) -> str:
    """``CHIPS`` key of a TPU ``device_kind``; an unknown one is an error
    (a peak or a bandwidth must never come from a default)."""
    k = device_kind.lower()
    if "v5 lite" in k or "v5e" in k:
        return "v5e"
    if "v5p" in k or "v5" in k:
        return "v5p"
    if "v4" in k:
        return "v4"
    if "v6" in k or "trillium" in k:
        return "v6e"
    raise ValueError(f"unknown TPU device_kind {device_kind!r}: add it to "
                     f"planner/cost_model.py CHIPS with its source")


def chip_for(platform: str, device_kind: str) -> ChipSpec:
    """Datasheet :class:`ChipSpec` of the chip a process runs on
    (platform ``tpu``), else of the named planning target."""
    if platform == "tpu":
        return CHIPS[_kind_key(device_kind)]
    return CHIPS[PLANNING_CHIP]


def local_chip() -> ChipSpec:
    import jax
    d = jax.devices()[0]
    return chip_for(d.platform, d.device_kind)


def profile_and_calibrate(mesh=None, axis: Optional[str] = None,
                          matmul_sizes: Sequence[int] = (512, 1024, 2048),
                          hbm_bytes: int = 1 << 26,
                          coll_sizes: Sequence[int] = (1 << 14, 1 << 17,
                                                       1 << 20),
                          reps: int = 5) -> Calibration:
    """One-shot profiling pass (the profile_hardware entry point)."""
    import jax
    d = jax.devices()[0]
    cal = Calibration(
        matmul_flops=profile_matmul(matmul_sizes, reps=reps),
        hbm_bw=profile_hbm(hbm_bytes, reps=reps),
        device_kind=getattr(d, "device_kind", "?"),
        platform=d.platform,
    )
    if mesh is not None:
        ax = axis or mesh.axis_names[0]
        if mesh.shape[ax] > 1:
            cal.collectives = profile_collectives(mesh, ax, coll_sizes,
                                                  reps=reps)
    return cal


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def validate_step_prediction(cal: Calibration, batch: int = 4,
                             seq: int = 128, hidden: int = 128,
                             ffn: Optional[int] = None,
                             num_layers: int = 2,
                             vocab: int = 256) -> Dict[str, float]:
    """Predict a small GPT train step with the calibrated cost model, then
    measure it; returns {"predicted_s", "measured_s", "ratio"}.  The
    reference runs the same closed loop before trusting its search."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu import optim
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    from .cost_model import (Strategy, layer_time, transformer_layer_spec)

    ffn = ffn or 4 * hidden
    chip = cal.to_chip_spec()
    cluster = ClusterSpec(chip=chip, num_chips=1)
    spec = transformer_layer_spec(batch, seq, hidden, ffn, dtype_bytes=4)
    pred = num_layers * layer_time(spec, Strategy(), cluster) \
        + 3.0 * (2.0 * batch * seq * hidden * vocab) \
        / (chip.peak_flops * chip.mxu_efficiency)

    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=num_layers, num_heads=max(1, hidden // 64),
                    max_seq_len=seq, sp=False, dtype="float32")
    with ht.graph("define_and_run", create_new=True) as g:
        ids = ht.placeholder("int32", (batch, seq), name="ids")
        lbl = ht.placeholder("int32", (batch, seq), name="lbl")
        model = GPTLMHeadModel(cfg)
        loss = model(ids, lbl)
        op = optim.AdamOptimizer(lr=1e-3).minimize(loss)
        rng = np.random.RandomState(0)
        I = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
        L = np.roll(I, -1, 1)

        def step():
            out = g.run(loss, [loss, op], {ids: I, lbl: L})
            return out[0]

        step()  # compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            v = step()
            np.asarray(v)
            ts.append(time.perf_counter() - t0)
    measured = float(np.median(ts))
    return {"predicted_s": float(pred), "measured_s": measured,
            "ratio": float(pred / measured) if measured else float("inf")}

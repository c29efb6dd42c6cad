"""The benchmark's own peaks table and work functions (operations and
bytes an algorithm NEEDS, computed from shapes).  Nothing here is read
from the program: ``hetu_tpu.planner.profile_hardware`` has its own
table, and a PR that changes it cannot move these numbers.
"""
from __future__ import annotations

# Published peaks of ONE chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
# A device kind that is not in the table is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to benchmark/work.py "
                       f"with its source")
    return PEAKS[device_kind]


def _sizes(model: dict):
    h, layers = model["n_embd"], model["n_layer"]
    ffn = model.get("n_inner") or 4 * h
    return h, layers, ffn, model["vocab_size"], model["n_head"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Model FLOPs one trained token needs, forward + backward, no
    recomputed operation.  Per layer the matmul parameters are
    qkv 3h^2 + out h^2 + up h*ffn + down ffn*h; the tied LM head is
    h*vocab.  A matmul costs 2 FLOPs per parameter per token forward and
    twice that backward: 6 * params.  Causal attention forward is QK^T
    and PV, 2 * 2 * s * h FLOPs per token per layer over the full square,
    half of it under the causal mask: 2*s*h; backward is twice the
    forward: 6*s*h per layer in all.  Embedding gathers, norms, gelu and
    softmax are not counted (MFU convention)."""
    h, layers, ffn, vocab, _ = _sizes(model)
    matmul_params = layers * (4 * h * h + 2 * h * ffn) + h * vocab
    return 6.0 * matmul_params + 6.0 * layers * seq_len * h


def flash_flops(model: dict, batch: int, seq_len: int, backward: bool) -> float:
    """FLOPs of ONE causal flash-attention call over [batch, seq, heads,
    head_dim]: forward 2 matmuls (QK^T, PV) = 4*b*s^2*h, halved by the
    causal mask = 2*b*s^2*h.  The fused backward needs 4 matmuls (dV,
    dP, dQ, dK) plus the recomputation of QK^T that flash attention
    cannot avoid; only the 4 needed ones are counted (a recomputed
    operation is not work the algorithm needs) = 4*b*s^2*h under the
    mask."""
    h = model["n_embd"]
    fwd = 2.0 * batch * seq_len * seq_len * h
    return 2.0 * fwd if backward else fwd


def flash_bytes(model: dict, batch: int, seq_len: int, backward: bool) -> float:
    """HBM bytes one flash call must move in bf16: forward reads q, k, v
    and writes o (4 tensors of b*s*h); backward reads q, k, v, o, do and
    writes dq, dk, dv (8 tensors).  The log-sum-exp rows are small and
    left out."""
    n = batch * seq_len * model["n_embd"] * 2
    return (8.0 if backward else 4.0) * n


def ragged_attention_bytes(model: dict, context_tokens: float,
                           query_tokens: float) -> float:
    """HBM bytes ONE ragged paged attention call (one layer) must move
    in bf16: every attended key and value once (context_tokens = sum of
    the rows' context lengths, 2 tensors of h each) plus q in and out
    written for the query tokens."""
    h = model["n_embd"]
    return (2.0 * context_tokens * h + 2.0 * query_tokens * h) * 2


def ragged_attention_flops(model: dict, attended_pairs: float) -> float:
    """FLOPs of one ragged paged attention call: QK^T and PV over every
    (query token, attended key) pair, 2 * 2 * h each."""
    return 4.0 * attended_pairs * model["n_embd"]


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


WORK_FNS = {
    "train_flops_per_token": train_flops_per_token,
    "flash_flops": flash_flops,
    "flash_bytes": flash_bytes,
    "ragged_attention_bytes": ragged_attention_bytes,
    "ragged_attention_flops": ragged_attention_flops,
}

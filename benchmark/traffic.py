"""The one general traffic generator.  A traffic mix is a data file
``traffic/<name>.json``; nothing here knows any mix by name.

Steadiness rule: a mix's SCHEDULE — prompt and output lengths, document
choices, inter-arrival gaps, and their order — is drawn from the mix's own
``shape_seed`` and is the same in every run; ``--seed`` draws the token
ids (and, for training, the corpus and the loader's shuffle).  So every
seed offers the same work at the same instants, and a difference between
two runs is the system's, not the load's.  Measured on the chip before
this rule (PR 24, PERF.md): with the seed rotating or permuting the same
sizes, two runs of ONE seed agreed within 0.2 % on ``tbt_p95_ms`` and
0.05 % on ``serve_tokens_per_s`` while runs of different seeds spread by
8.7 % and 1.4 % — the order alone was changing the work (which requests
meet in a batch, which finish inside the window).

Serving mixes (``driver`` serve_open_loop / serve_replay):
  arrivals   {"process": "gamma", "rate_per_s": r, "cv": c}  open loop;
             cv 1 is Poisson, cv > 1 is bursty (BurstGPT).  round(r *
             seconds) requests, gaps rescaled to sum to the window.
             {"process": "at_zero", "count": n}  everything due at t = 0.
  classes    [{"weight": w, "prompt": LEN, "output": LEN}, ...]
  LEN        {"dist": "lognormal", "median": m, "sigma": s, "min": a,
              "max": b}  or  {"dist": "uniform", "min": a, "max": b}
  shared_prefix  optional {"documents": d, "tokens": t, "zipf_a": a}:
             each prompt = one of d fixed documents (Zipf-popular) + its
             own ``prompt`` tokens as a unique suffix.
  max_total  prompt + output is clipped to this (the position table).
Training mixes (``driver`` train_steps) are read by the driver itself;
``token_stream`` below makes their corpus.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
MAX_SEED = 2 ** 32            # numpy's RandomState takes 32 unsigned bits


def _rng(seed: int, stream: int) -> np.random.RandomState:
    """--seed is any whole number a little over 2**31: fold it into the
    32 unsigned bits RandomState takes, one stream per use."""
    return np.random.RandomState((int(seed) * 1000003 + stream) % MAX_SEED)


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class ServeRequest:
    due_s: float                  # offset from the window's start
    prompt: list
    max_new_tokens: int
    document: int = -1            # shared-prefix document, -1 for none


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int):
    """The window's requests, ordered by due time."""
    shape = np.random.RandomState(int(mix["shape_seed"]))
    arr = mix["arrivals"]
    if arr["process"] == "at_zero":
        n = int(arr["count"])
        due = np.zeros(n)
    elif arr["process"] == "gamma":
        n = max(1, int(round(arr["rate_per_s"] * seconds)))
        k = 1.0 / float(arr.get("cv", 1.0)) ** 2
        gaps = shape.gamma(k, 1.0 / k, n)
        # rescaled so the last request is due inside the window
        due = np.cumsum(gaps) * (seconds / gaps.sum()) * (n / (n + 1.0))
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    weights = np.asarray([c["weight"] for c in mix["classes"]], float)
    counts = np.floor(weights / weights.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    prompt_len, out_len = [], []
    for c, k in zip(mix["classes"], counts):
        prompt_len.append(_lengths(c["prompt"], k, shape))
        out_len.append(_lengths(c["output"], k, shape))
    prompt_len, out_len = np.concatenate(prompt_len), np.concatenate(out_len)
    sp = mix.get("shared_prefix")
    if sp:
        ranks = np.arange(1, sp["documents"] + 1, dtype=float)
        pop = ranks ** -float(sp["zipf_a"])
        doc = shape.choice(sp["documents"], n, p=pop / pop.sum())
        doc_len = int(sp["tokens"])
    else:
        doc, doc_len = np.full(n, -1), 0
    room = int(mix["max_total"]) - doc_len
    prompt_len = np.minimum(prompt_len, room - out_len)
    if (prompt_len < 1).any():
        raise ValueError("a request has no room for its prompt")
    tok = _rng(seed, 3)
    docs = [tok.randint(0, vocab, doc_len).tolist()
            for _ in range(sp["documents"])] if sp else []
    out = []
    for i in range(n):
        body = tok.randint(0, vocab, int(prompt_len[i])).tolist()
        d = int(doc[i])
        out.append(ServeRequest(float(due[i]),
                                (docs[d] if d >= 0 else []) + body,
                                int(out_len[i]), d))
    return out, docs


def token_stream(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """Synthetic corpus for training: Zipf-distributed ids (the unigram
    statistics of text, so the loss falls within a few steps)."""
    n = int(mix["global_batch"]) * int(mix["seq_len"]) * \
        int(mix["corpus_batches"]) + 1
    return ((_rng(seed, 4).zipf(float(mix["zipf_a"]), n) - 1)
            % vocab).astype(np.int32)

"""Serving request lifecycle + class-then-arrival admission queue."""
from __future__ import annotations

import heapq
from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional

from .slo.classes import SLO_CLASSES, class_rank

WAITING = "waiting"
RUNNING = "running"
FINISHED = "finished"


UNMASK_RULES = ("low_confidence_dynamic", "low_confidence_static",
                "sequential")


@dataclass(frozen=True)
class DenoiseRule:
    """How a block-wise model's open block is denoised (the ENGINE'S
    choice, ``Engine(denoise=)``; the block length and the mask id are the
    model's, ``GPTConfig.diffusion_block`` / ``mask_token_id``): ``steps``
    passes would unmask a whole block of B masks by the family's schedule
    — ``B // steps`` positions a pass, the first ``B % steps`` passes one
    more — under one of the three published rules.  The defaults are the
    family's (``generate.py``)."""
    steps: int = 4
    rule: str = "low_confidence_dynamic"
    tau: float = 0.9

    def check(self, block: int) -> "DenoiseRule":
        if self.rule not in UNMASK_RULES:
            raise ValueError(f"unknown unmask rule {self.rule!r}; one of "
                             f"{UNMASK_RULES}")
        if not 1 <= self.steps <= block:
            raise ValueError(
                f"denoising steps {self.steps} must lie in 1..{block} (the "
                "block length): a pass unmasks at least one position")
        return self

    def unmask(self, block: int, pass_index: int):
        """``(unmask_k, unmask_tau)`` of denoise pass ``pass_index`` of a
        block, the two numbers a row the step's one selection reads: of
        the masked positions the ``|k|`` first by rank — of confidence,
        or, ``k < 0``, of position (the sequential rule) — and every one
        whose confidence passes ``tau`` (2.0: none, the static rules)."""
        base, more = divmod(block, self.steps)
        k = base + (pass_index < more)
        if self.rule == "sequential":
            return -k, 2.0
        return k, self.tau if self.rule == "low_confidence_dynamic" else 2.0


@dataclass
class Request:
    """One generation request flowing through the engine.

    ``tokens`` accumulates prompt + generated tokens; preemption resets
    only the KV state (``pages``/``pos``), so a re-prefill over
    ``tokens`` resumes the sequence with an identical continuation at
    temperature 0.
    """
    req_id: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0           # 0 (or >= 1) disables nucleus cut
    seed: int = 0
    eos_token_id: Optional[int] = None
    arrival_time: float = 0.0
    stream_cb: Optional[Callable] = None
    # SLO class (serving/slo/classes.py): pure POLICY — decides who
    # waits/sheds/preempts, never what a surviving request computes
    slo_class: str = "standard"

    # runtime state
    tokens: List[int] = field(default_factory=list)
    out_tokens: List[int] = field(default_factory=list)
    pages: List[int] = field(default_factory=list)
    # the first shared_pages entries of ``pages`` are READ-ONLY prefix-
    # cache pages (refcounted, never in the KV write plan); the rest are
    # exclusively owned.  cached_tokens = prefill tokens skipped via the
    # cache on the most recent start (metrics / tests).
    shared_pages: int = 0
    cached_tokens: int = 0
    # (the ``pages`` list it was made from, that list as int32): the
    # engine's packing keeps a long page table between steps
    page_array: Optional[tuple] = field(default=None, repr=False,
                                        compare=False)
    # speculative decoding (serving/spec.py): greedy draft proposals
    # staged for the next packed step.  Non-empty only while the engine
    # runs a spec scheduler mode AND the request is decode-ready; the
    # scheduler packs ``1 + len(spec_drafts)`` tokens as a verify row
    # (a chunk slot), and the engine clears the list after the verify
    # commits (or when the drafts are dropped: preemption, a step with
    # no free chunk slot, a page squeeze).
    spec_drafts: List[int] = field(default_factory=list)
    # every verify row this request rode while the engine's analysis tap
    # is on: (index in ``tokens`` of the first draft's place, the drafts
    # fed, how many the accept head kept)
    verify_log: List[tuple] = field(default_factory=list, repr=False)
    # from a preemption until the re-prefill reaches the tip again: the
    # row that gets there is a prefill row whatever its width, and no draft
    # can have been staged for it
    resuming: bool = False
    # block-wise generation (``GPTConfig.diffusion_block``, DESIGN.md §29):
    # the request's tip is its OPEN BLOCK — the B ids at positions ``pos ..
    # pos + B`` (``pos`` a multiple of B: the committed K/V), the mask id
    # where a position is not yet known — and the denoise passes it has
    # had.  None until the prompt's whole blocks are prefilled; ``tokens``
    # holds the prompt and the COMMITTED blocks' tokens only, so a
    # preemption loses the open block and nothing else
    block: Optional[List[int]] = None
    block_pass: int = 0
    # every pass of every block while the engine's analysis tap is on:
    # (the block's first position, the state going in, the positions the
    # pass unmasked, their tokens, the served confidences of the positions
    # masked going in); a commit pass unmasks nothing
    denoise_log: List[tuple] = field(default_factory=list, repr=False)
    # the recurrent-state slot of a hybrid stack (kv_pool.StateSlotStore),
    # held from admission to finish / preemption
    state_slot: Optional[int] = None
    # window layers (kv_pool.WindowPages): the window-space pages of the
    # logical pages ``win_first ..`` of the sequence, a reference each —
    # only those the coming queries' windows reach
    win_pages: List[int] = field(default_factory=list)
    win_first: int = 0
    pos: int = 0                 # KV entries committed (next write index)
    state: str = WAITING
    # start of the CURRENT lifecycle segment (queued/running) for the
    # trace plane: the engine closes a state span over
    # [trace_t0, transition] at every admit/preempt/finish, so the
    # per-request segments tile [submit, finish] gaplessly (asserted by
    # the timeline gate in tests/test_obs.py)
    trace_t0: float = 0.0
    n_preemptions: int = 0
    peak_pages: int = 0
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None
    finish_time: Optional[float] = None

    def __post_init__(self):
        if not self.tokens:
            self.tokens = list(self.prompt)
        class_rank(self.slo_class)   # validate eagerly (raises on typo)

    @property
    def rank(self) -> int:
        """Priority rank (0 = most urgent) — the leading sort key of
        every scheduler ordering decision."""
        return class_rank(self.slo_class)

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def n_generated(self) -> int:
        return len(self.out_tokens)

    @property
    def done(self) -> bool:
        if self.n_generated >= self.max_new_tokens:
            return True
        return (self.eos_token_id is not None and self.out_tokens and
                self.out_tokens[-1] == self.eos_token_id)


class RequestQueue:
    """Class-ranked, arrival-time-ordered waiting queue.

    One arrival-ordered heap PER SLO class; ``pop_ready(now)`` scans
    classes in rank order and releases the first request whose
    ``arrival_time`` has passed — an interactive request that has
    arrived always pops before any standard/batch one, but a FUTURE
    interactive arrival never blocks an already-arrived lower class
    (the gate is per heap, not global).  Within a class, ties break on
    ``req_id`` (submission order), NOT insertion order, so a request
    pushed BACK (didn't fit / preempted) keeps its place ahead of
    same-arrival-time peers — no overtaking, starvation-free within
    the class.
    """

    def __init__(self):
        self._heaps = {c: [] for c in SLO_CLASSES}

    def push(self, req: Request) -> None:
        heapq.heappush(self._heaps[req.slo_class],
                       (req.arrival_time, req.req_id, req))

    def pop_ready(self, now: float) -> Optional[Request]:
        for c in SLO_CLASSES:        # rank order: interactive first
            heap = self._heaps[c]
            if heap and heap[0][0] <= now:
                return heapq.heappop(heap)[2]
        return None

    def next_arrival(self) -> Optional[float]:
        heads = [h[0][0] for h in self._heaps.values() if h]
        return min(heads) if heads else None

    def requests(self) -> Iterator[Request]:
        """All queued requests, rank-major (heap order within a class
        — NOT sorted by arrival; callers that care must sort)."""
        for c in SLO_CLASSES:
            for _, _, req in self._heaps[c]:
                yield req

    def clear(self) -> None:
        for heap in self._heaps.values():
            heap.clear()

    def due(self, now: float) -> int:
        """Queued requests whose ``arrival_time`` has passed: the ones
        admission left waiting.  O(queue) — for the trace plane, not
        the untraced loop."""
        return sum(1 for heap in self._heaps.values()
                   for arrival, _, _ in heap if arrival <= now)

    def depth_by_class(self) -> dict:
        """Queue depth per class — an autoscaler/router signal."""
        return {c: len(h) for c, h in self._heaps.items()}

    def __len__(self) -> int:
        return sum(len(h) for h in self._heaps.values())

    def __bool__(self) -> bool:
        return any(self._heaps.values())

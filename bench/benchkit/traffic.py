"""The one traffic generator. A mix file (``bench/traffic/<mix>.json``)
names, per tenant class, an arrival kind and a length kind for prompts and
for outputs; the kinds are the modules ``bench/traffic/<kind>.py``.

Every seed gets the same work in another order. Lengths are the stratified
quantiles ``F^-1((i + 0.5) / n)`` of their distribution, and exponential
gaps likewise, so the multiset of prompt lengths, output lengths and gaps
in a window depends only on the mix and the window's length; the seed
shuffles them and draws the prompts' token ids. Runs with different seeds
then differ by ordering alone, which keeps the spread between runs close
to that of one seed run twice. A closed-loop backlog is drawn from in
order and a window uses only its first requests, so its lengths follow a
low-discrepancy sequence instead, whose start the seed sets: every seed's
first few requests then cover the distribution alike.

A stream ``{"arrivals": {"kind": "poisson", ...}, "prompt": {...},
"output": {...}}`` is open-loop: its requests fall due on a schedule over
the window and, past it, over a tail that keeps the load on while the
window's own requests finish. ``{"arrivals": {"kind": "backlog", "depth":
d}}`` is closed-loop: the harness keeps ``d`` requests waiting.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Sent:
    """One request as the load generator made it, and what the client saw
    of it (host clock, seconds)."""
    cls: str                     # "LS" | "BE"
    index: int                   # position in its stream
    prompt_len: int
    max_new: int
    due: Optional[float] = None  # offset from the window start (open loop)
    in_window: bool = False      # due inside the measured window
    t_due: Optional[float] = None
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None   # the program's admission time
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    n_seen: int = 0              # output tokens observed so far
    gaps: List[float] = field(default_factory=list)
    req: object = None           # the engine's Request, while it runs
    finished: bool = False
    failed: bool = False
    output: Optional[list] = None
    tokens: Optional[np.ndarray] = None


def detach(s: Sent):
    """Copy what the metrics need from the engine's request and drop it."""
    r = s.req
    if r is not None:
        s.t_admit, s.output = r.t_admit, list(r.output or [])
        s.finished = r.t_done is not None and not r.failed
        s.failed = bool(r.failed)
        s.req = None


def _grid(x, lo, hi, grid=None, grid_offset=0):
    x = min(max(x, lo), hi)
    if grid:
        x = grid * math.ceil((x - grid_offset) / grid) + grid_offset
    return int(math.ceil(x))


# additive recurrences (golden ratio, silver ratio) for the prompt and the
# output lengths of a backlog
KRONECKER = ((math.sqrt(5) - 1) / 2, math.sqrt(2) - 1)


def _kronecker(n: int, alpha: float, rng) -> np.ndarray:
    """``frac(start + i * alpha)``, i < n: any run of consecutive terms
    spreads evenly over [0, 1); the seed's ``rng`` picks the start."""
    return (rng.random() + np.arange(n) * alpha) % 1.0


def _seq(seed: int, *parts) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63] + [
        zlib.crc32(str(p).encode()) for p in parts])


class Stream:
    """Sizes and due times of one tenant class's requests."""

    def __init__(self, spec, cls: str, conf: dict, seed: int,
                 seconds: float, tail_s: float):
        self.cls = cls
        self.conf = conf
        self.seed = seed
        arr = dict(conf["arrivals"])
        self.kind = arr.pop("kind")
        self.depth = None
        rng = _seq(seed, cls, "order")

        def strat(n):
            return rng.permutation((np.arange(n) + 0.5) / n)

        if self.kind == "backlog":
            self.depth = int(arr["depth"])
            n = spec.kind("backlog").pool_size(**arr)
            self.due = [None] * n
            self.in_window = [False] * n
            # the harness takes a backlog's requests in order, and a window
            # uses only the first few: a low-discrepancy sequence spreads
            # every run of consecutive requests over the whole distribution
            self.prompt = self._lengths(spec, conf["prompt"],
                                        _kronecker(n, KRONECKER[0], rng))
            self.output = self._lengths(spec, conf["output"],
                                        _kronecker(n, KRONECKER[1], rng))
            return
        sched = spec.kind(self.kind).schedule
        win = list(sched(strat, seconds, **arr))
        tail = [seconds + t for t in sched(strat, tail_s, **arr)]
        self.due = win + tail
        self.in_window = [True] * len(win) + [False] * len(tail)
        blocks = [len(win), len(tail)]
        # the window's sizes are a block of their own, so that every seed
        # puts the same sizes in the window
        self.prompt, self.output = [], []
        for n in blocks:
            self.prompt += self._lengths(spec, conf["prompt"], strat(n))
            self.output += self._lengths(spec, conf["output"], strat(n))

    def _lengths(self, spec, conf, u):
        c = dict(conf)
        kind = spec.kind(c.pop("kind"))
        grid = {k: c[k] for k in ("grid", "grid_offset") if k in c}
        return [_grid(x, c["min"], c["max"], **grid)
                for x in kind.quantiles(list(u), **c)]

    def make(self, i: int, vocab: int) -> Sent:
        """Request ``i`` of the stream (backlog pools are cycled)."""
        j = i % len(self.prompt)
        s = Sent(self.cls, i, self.prompt[j], self.output[j],
                 due=self.due[j] if self.depth is None else None,
                 in_window=self.in_window[j] if self.depth is None else False)
        s.tokens = _seq(self.seed, self.cls, "tokens", i).integers(
            0, vocab, s.prompt_len).astype(np.int32)
        return s

    def __len__(self):
        return len(self.prompt)


def streams(spec, mix: dict, seed: int, seconds: float,
            tail_s: float) -> dict:
    """{class: Stream} for the classes the mix names ("ls", "be")."""
    return {cls.upper(): Stream(spec, cls.upper(), conf, seed, seconds,
                                tail_s)
            for cls, conf in mix.items() if cls in ("ls", "be")}


def chunk_lengths(prompt_len: int, chunk: int) -> list:
    """The prefill chunk lengths the engine's scheduler issues for one
    prompt when a request advances ``chunk`` tokens per quantum: the body
    of L - 1 tokens in pieces of ``chunk``, then the last position alone
    (``serving.scheduler.TokenBudgetScheduler.prefill_chunks``). Used to
    warm up exactly the chunk shapes a mix will use."""
    body = prompt_len - 1
    out = [chunk] * (body // chunk)
    if body % chunk:
        out.append(body % chunk)
    return out + [1]

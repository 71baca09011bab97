"""The engine's own host spans in a profiler trace, and what they say about
the device's idle time.

The engine marks its host work with ``jax.profiler.TraceAnnotation`` spans
named ``engine.*`` (``repro.obs.span``), on the same clock as the device's
operations. They nest on the engine's one host thread:

* ``engine.step``      one ``ServingEngine.step()`` (arg ``step``);
* ``engine.control``   a control tick, when one runs;
* ``engine.sched``     the scheduler's passes inside a quantum (``tenant``);
* ``engine.dispatch``  one decode or chunk call: building its inputs and
  calling the step program (``tenant``, ``kind``, ``sq``, ``slots`` rows
  computed, ``live`` rows, ``tokens`` real tokens, ``rids`` their request
  ids joined by spaces);
* ``engine.pages``     a page-table upload or a copy-on-write page fork;
* ``engine.sync``      the host waiting for a step's argmax (``tenant``);
* ``engine.emit``      token bookkeeping after the sync (``tenant``,
  ``tokens``, ``finished``).

:func:`benchkit.tracing.load` reads them (``Trace.program``), and the
traced run's reduction keeps those of its slice with the slice's idle
intervals (:func:`benchkit.cell.reduce_trace`), for the readers below.

Each instant of a traced slice belongs to the innermost engine span open
then (its self-time), or to no engine span. Intersected exactly with the
intervals in which the device runs no operation, that splits the device's
idle time by the host work behind it.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional

from . import tracing as tr

PREFIX = tr.ENGINE
# the split's labels, in the order it is printed
SEAMS = ("sched", "dispatch", "pages", "sync", "emit", "control", "step")
OUTSIDE = "outside"


def segments(program: List[tr.Event], lo: int, hi: int) -> list:
    """[(start, end, label)] covering [lo, hi) in order: the innermost
    engine span open in each piece, or ``OUTSIDE``."""
    out, stack, t = [], [], lo

    def upto(end):
        nonlocal t
        end = min(end, hi)
        if end > t:
            out.append((t, end, stack[-1].name[len(PREFIX):] if stack
                        else OUTSIDE))
            t = end

    for e in sorted(program, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= e.start:
            upto(stack[-1].end)
            stack.pop()
        upto(e.start)
        stack.append(e)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return out


def idle_intervals(ops: List[tr.Event], lo: int, hi: int) -> list:
    """[start, end) pieces of [lo, hi) in which no device operation runs."""
    out, t = [], lo
    for s, e in tr._union([(o.start, o.end) for o in ops], lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_split(program: List[tr.Event], idle: list, lo: int,
               hi: int) -> Dict[str, int]:
    """Idle ns inside [lo, hi) by the innermost engine span open then."""
    out = defaultdict(int)
    segs = segments(program, lo, hi)
    i = 0
    for s, e in idle:
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < e:
            a, b, lab = segs[j]
            out[lab] += min(b, e) - max(a, s)
            j += 1
    return dict(out)


def idle_engine_pct(program: List[tr.Event], idle: list, lo: int,
                    hi: int) -> Optional[float]:
    """Share of [lo, hi) in which the device is idle while the host's
    innermost open span is an engine span other than ``engine.sync``;
    ``None`` when the slice holds no engine span."""
    if hi <= lo or not any(e.start < hi and e.end > lo for e in program):
        return None
    split = idle_split(program, idle, lo, hi)
    host = sum(v for k, v in split.items() if k not in (OUTSIDE, "sync"))
    return 100.0 * host / (hi - lo)


def step_host_ms(program: List[tr.Event], lo: int, hi: int) -> List[float]:
    """For each ``engine.step`` that starts in [lo, hi): its length minus
    the time its ``engine.sync`` spans cover (ms), the host work of one
    quantum."""
    syncs = [e for e in program if e.name == PREFIX + "sync"]
    starts = [e.start for e in syncs]
    out = []
    for st in program:
        if st.name != PREFIX + "step" or not lo <= st.start < hi:
            continue
        i = bisect.bisect_left(starts, st.start)
        waited = 0
        while i < len(syncs) and syncs[i].start < st.end:
            waited += min(syncs[i].end, st.end) - syncs[i].start
            i += 1
        out.append((st.end - st.start - waited) / 1e6)
    return out


def step_host_ms_p50(program: List[tr.Event], lo: int,
                     hi: int) -> Optional[float]:
    vals = step_host_ms(program, lo, hi)
    return statistics.median(vals) if vals else None


def describe_split(split: Dict[str, int], lo: int, hi: int,
                   idle_ns: int) -> str:
    """The one-line idle split a traced run reports."""
    parts = [f"{k} {split.get(k, 0) / 1e9:.6f}" for k in SEAMS + (OUTSIDE,)]
    return (f"info: device idle {idle_ns / 1e9:.6f} s of a "
            f"{(hi - lo) / 1e9:.6f} s slice, by innermost engine span (s): "
            + ", ".join(parts))

"""Host spans written by the harness, and the reduction of a profiler trace
(``.xplane.pb``) to what the per-layer metrics read.

Spans (``jax.profiler.TraceAnnotation``, named ``bench.*``) sit on the
profiler's own clock, beside the device's operations:

* ``bench.traced``  the traced slice of the window (its last seconds);
* ``bench.submit``  the load generator handing requests to the engine;
* ``bench.step``    one ``ServingEngine.step()`` (scheduler, page tables,
  dispatch, the argmax sync);
* ``bench.call``    one dispatch of a tenant's decode or chunk program,
  with ``tenant`` and ``kind`` (``decode`` / ``chunk<Sq>``);
* ``bench.client``  the harness reading new tokens after a step;
* ``bench.idle``    waiting for the next request to fall due.

On the device, a call is one execution of its step program (``XLA
Modules``), which encloses that step's operations (``XLA Ops``). Both
tenants' steps come from the same jitted closures, so a program's name
does not say which tenant it served: the reduction pairs the step
programs, in the order the device ran them, with the calls the harness
recorded in the order it made them, and assigns every operation to the
step program whose execution encloses it. Both attention kernels are
Pallas kernels named ``_paged_kernel``; the trace names each custom call
after the jitted wrapper around it, which the kernel matcher reads.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import List, Optional

STEP_PROGRAMS = ("_decode_paged", "_chunk_paged")
# a Pallas kernel is a custom call that the TPU trace names after the
# jitted wrapper around it (``decode_attention_paged.3 = ... custom-call``)
KERNEL_MARKERS = ("attention_paged",)


@dataclass
class Event:
    name: str
    start: int          # ns, profiler clock
    end: int
    args: dict = field(default_factory=dict)


@dataclass
class Trace:
    host: List[Event]      # the harness's bench.* spans
    modules: List[Event]   # device program executions (one device)
    ops: List[Event]       # device operations (one device)
    n_devices: int = 1


def _stats(e) -> dict:
    return dict(e.stats)


def load(path) -> Trace:
    """Read the harness spans and the first accelerator's programs and
    operations from an ``.xplane.pb`` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    host, modules, ops = [], [], []
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")
               and p.name[len("/device:TPU:"):].isdigit()]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    host.append(Event(e.name, int(e.start_ns),
                                      int(e.end_ns), _stats(e)))
    if devices:
        for line in devices[0].lines:
            dest = {"XLA Modules": modules, "XLA Ops": ops}.get(line.name)
            if dest is None:
                continue
            for e in line.events:
                dest.append(Event(e.name, int(e.start_ns), int(e.end_ns)))
    for lst in (host, modules, ops):
        lst.sort(key=lambda e: e.start)
    return Trace(host, modules, ops, n_devices=len(devices))


def window(trace: Trace) -> Optional[Event]:
    spans = [e for e in trace.host if e.name == "bench.traced"]
    return spans[0] if spans else None


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(trace: Trace, lo: int, hi: int) -> int:
    return sum(e - s for s, e in
               _union([(o.start, o.end) for o in trace.ops], lo, hi))


def _label(span: Event) -> str:
    if span.name == "bench.call":
        a = span.args
        return f"call {a.get('tenant', '?')}/{a.get('kind', '?')}"
    return span.name[len("bench."):]


def host_label_at(trace: Trace, t: int) -> str:
    """What the harness was doing at ``t``: its innermost span there."""
    best = None
    for e in trace.host:
        if e.start > t:
            break
        if e.end > t and e.name != "bench.traced" and (
                best is None or e.start >= best.start):
            best = e
    return _label(best) if best is not None else "outside harness spans"


def idle_gaps(trace: Trace, lo: int, hi: int) -> dict:
    """Idle device time inside [lo, hi), in ns, by what the host was doing
    at the middle of each gap."""
    busy = _union([(o.start, o.end) for o in trace.ops], lo, hi)
    out = defaultdict(int)
    t = lo
    for s, e in busy + [[hi, hi]]:
        if s > t:
            out[host_label_at(trace, (t + s) // 2)] += s - t
        t = max(t, e)
    return dict(out)


def step_modules(trace: Trace, lo: int, hi: int) -> List[Event]:
    """Executions of the tenants' decode and chunk programs inside the
    window, in the order the device ran them."""
    return [m for m in trace.modules
            if lo <= m.start < hi and any(p in m.name for p in STEP_PROGRAMS)]


def short_name(name: str) -> str:
    """An operation's HLO instruction name, without its text."""
    return name.split(" = ")[0].lstrip("%")


def is_kernel(op: Event) -> bool:
    return any(k in short_name(op.name) for k in KERNEL_MARKERS) and (
        "custom-call" in op.name or "custom_call" in op.name)


def per_module(trace: Trace, mods: List[Event]) -> List[dict]:
    """For each step-program execution: its device time, and the device
    time of the attention kernels inside it (ns)."""
    starts = [o.start for o in trace.ops]
    out = []
    for m in mods:
        i = bisect.bisect_left(starts, m.start)
        kern = 0
        while i < len(trace.ops) and trace.ops[i].start < m.end:
            if is_kernel(trace.ops[i]):
                kern += trace.ops[i].end - trace.ops[i].start
            i += 1
        out.append({"device_ns": m.end - m.start, "kernel_ns": kern,
                    "name": m.name})
    return out


def top_ops(trace: Trace, mods: List[Event], labels: List[str], lo: int,
            hi: int, n: int = 10) -> list:
    """The device operations that took most time in the window, summed by
    (the call that ran them, operation name). A control-flow operation
    that encloses others (the layer scan's ``while``) is left out: its
    time is theirs."""
    bounds = [(m.start, m.end, lab) for m, lab in zip(mods, labels)]
    starts = [b[0] for b in bounds]
    tot = defaultdict(int)
    ops = trace.ops
    for i, o in enumerate(ops):
        if not lo <= o.start < hi:
            continue
        if i + 1 < len(ops) and ops[i + 1].start < o.end:
            continue     # a loop around the operations that follow it
        j = bisect.bisect_right(starts, o.start) - 1
        lab = (bounds[j][2] if j >= 0 and o.start < bounds[j][1]
               else "other programs")
        tot[f"{lab}/{short_name(o.name)}"] += o.end - o.start
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in best]


def describe(path, n: int = 8) -> str:
    """Planes, lines, event counts and a few events with their stats: what
    to look at before trusting the reduction on a new chip or JAX."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:n]:
                out.append(f"    {e.name[:240]!r} {int(e.start_ns)}+"
                           f"{int(e.duration_ns)} "
                           f"{str(_stats(e))[:240]}")
    return "\n".join(out)

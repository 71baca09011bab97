"""Shared by the per-layer readers of step and kernel metrics: the traced
calls of one kind, each with its step program's and its kernels' device
time, and the work that the call's tenant's architecture
(``bench/arch/<arch>.py``) counts for it."""
from . import workcount


def traced(rec, kind):
    t = rec.traced or {}
    if not t.get("aligned") or rec.peak is None:
        return []
    return [(c, d) for c, d in t["aligned"] if c.kind == kind and c.rows]


def step_mfu(rec, kind):
    pairs = traced(rec, kind)
    dev = sum(d["device_ns"] for _, d in pairs) * 1e-9
    if not dev:
        return None
    flops = sum(rec.archs[c.tenant].step_flops(
        rec.models[c.tenant], c.rows, c.kind, c.logit_rows)
        for c, _ in pairs)
    return 100.0 * flops / (dev * rec.peak["flops"])


def kernel_roofline(rec, kind, marker):
    """The least time the chip needs for the work of the kernel named by
    ``marker`` (its architecture's count, over every layer that runs it)
    over that kernel's device time, in the traced calls of ``kind`` whose
    tenant's architecture lists it."""
    pairs = [(c, d) for c, d in traced(rec, kind)
             if marker in rec.archs[c.tenant].kernels]
    kern = sum(d["kernels"].get(marker, 0) for _, d in pairs) * 1e-9
    if not kern:
        return None
    need = 0.0
    for c, _ in pairs:
        m = rec.models[c.tenant]
        flops, nbytes, layers = rec.archs[c.tenant].kernels[marker]
        need += layers(m) * workcount.roofline_s(
            flops(m, c.rows, c.kind), nbytes(m, c.rows, c.kind), rec.peak)
    return 100.0 * need / kern

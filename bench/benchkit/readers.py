"""Shared by the per-layer readers of step and kernel metrics: the traced
calls of one kind, each with its step program's and its attention
kernels' device time."""
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
    flops = sum(workcount.step_flops(rec.models[c.tenant], c.rows, c.kind,
                                     c.logit_rows) for c, _ in pairs)
    return 100.0 * flops / (dev * rec.peak["flops"])


def attn_roofline(rec, kind):
    pairs = traced(rec, kind)
    kern = sum(d["kernel_ns"] for _, d in pairs) * 1e-9
    if not kern:
        return None
    need = 0.0
    for c, _ in pairs:
        m = rec.models[c.tenant]
        need += m.layers * workcount.roofline_s(
            workcount.attn_flops(m, c.rows, c.kind),
            workcount.attn_bytes(m, c.rows, c.kind), rec.peak)
    return 100.0 * need / kern

"""Kernels: the least time the chip needs for the paged decode attention
kernel's work (the larger of FLOPs / peak and bytes / bandwidth, from live
rows and real contexts, per layer) over the kernel's device time in the
traced decode steps."""
from benchkit.readers import attn_roofline


def read(rec):
    return attn_roofline(rec, "decode")

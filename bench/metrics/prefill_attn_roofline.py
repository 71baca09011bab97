"""Kernels: the least time the chip needs for the paged prefill attention
kernel's work (the larger of FLOPs / peak and bytes / bandwidth, from
live rows and real contexts, per layer, as the tenant's architecture
counts them) over the kernel's device time in the traced chunk steps."""
from benchkit.readers import kernel_roofline


def read(rec):
    return kernel_roofline(rec, "chunk", "attention_paged")

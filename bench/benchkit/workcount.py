"""Operations and bytes that the algorithm needs, counted from the work the
traffic really asked for: live rows, their real context lengths and real
chunk tokens -- never the padded ``n_slots x Sq`` shape a step computes,
so the counts stay right when a later change packs rows. The counts of a
step and of each kernel belong to the architecture (``bench/arch/<arch>.py``:
``step_flops`` and ``kernels``); this module keeps the chip's peaks, the
roofline and the helpers the counts share.

``rows`` of a decode call are the live rows' positions (the new token at
``pos`` attends to ``pos + 1`` keys). ``rows`` of a chunk call are (start,
length) pairs (query ``start + i`` attends to ``start + i + 1`` keys).
Bytes are those a kernel must move at least once: its inputs and output,
each once; weights are counted at the served type's width.
"""
from __future__ import annotations

# the TPU v5e peaks, by ``device.device_kind`` as JAX reports it. Source:
# Google Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 819 GB/s HBM bandwidth, 16 GiB HBM per chip
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud docs, TPU v5e"},
    "TPU v5e": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                "source": "Google Cloud docs, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


def itemsize(m) -> int:
    """Bytes per element of the model's served type."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m.dtype]


def queries(rows, kind):
    """[(start, n_queries)] per live row."""
    return [(p, 1) for p in rows] if kind == "decode" else list(rows)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])

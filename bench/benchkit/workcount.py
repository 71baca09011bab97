"""Operations and bytes that the algorithm needs, counted from the work the
traffic really asked for: live rows, their real context lengths and real
chunk tokens -- never the padded ``n_slots x Sq`` shape a step computes,
so the counts stay right when a later change packs rows.

``rows`` of a decode call are the live rows' positions (the new token at
``pos`` attends to ``pos + 1`` keys). ``rows`` of a chunk call are (start,
length) pairs (query ``start + i`` attends to ``start + i + 1`` keys).
Bytes are those a kernel must move at least once: its inputs and output,
each once; weights are counted at the served type's width.
"""
from __future__ import annotations

from .model import Model

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


def _itemsize(m: Model) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m.dtype]


def _queries(rows, kind):
    """[(start, n_queries)] per live row."""
    return [(p, 1) for p in rows] if kind == "decode" else list(rows)


def attn_flops(m: Model, rows, kind: str) -> float:
    """One layer's attention kernel: QK^T and PV over the causal context,
    2 * 2 * heads * head_dim per (query, key) pair."""
    pairs = sum(n * s + n * (n + 1) // 2 for s, n in _queries(rows, kind))
    return 4.0 * m.heads * m.head_dim * pairs


def attn_bytes(m: Model, rows, kind: str) -> float:
    """One layer's attention kernel: each row's keys and values over its
    context read once, its queries read and its outputs written once."""
    b = _itemsize(m)
    total = 0
    for s, n in _queries(rows, kind):
        total += 2 * m.kv_heads * m.head_dim * (s + n)   # K and V
        total += 2 * n * m.heads * m.head_dim            # Q in, O out
    return float(total * b)


def step_flops(m: Model, rows, kind: str, logit_rows: int) -> float:
    """A whole decode or chunk step: every layer's matmuls for each real
    token, every layer's attention, and the output matrix for the
    ``logit_rows`` rows whose logits are used (a decode row's next token;
    a chunk row that completes its prompt)."""
    tokens = sum(n for _, n in _queries(rows, kind))
    return (2.0 * m.layer_matmul_params * m.layers * tokens
            + m.layers * attn_flops(m, rows, kind)
            + 2.0 * m.d * m.vocab * logit_rows)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])

"""Work counts against hand-worked shapes of both served models, and the
peak table."""
import json
import os

import pytest

import benchtest
from benchkit import workcount
from benchkit.model import Model


def _model(cls, config="colo-qwen3-1.7b-stablelm-1.6b"):
    with open(os.path.join(benchtest.BENCH, "configs", f"{config}.json")) as f:
        conf = json.load(f)
    return Model.from_conf(conf[cls], conf["dtype"])


QWEN3, STABLELM = _model("ls"), _model("be")


def test_models_as_published():
    assert (QWEN3.d, QWEN3.layers, QWEN3.heads, QWEN3.kv_heads,
            QWEN3.head_dim, QWEN3.ff, QWEN3.vocab) == (
        2048, 28, 16, 8, 128, 6144, 151936)
    assert (STABLELM.d, STABLELM.layers, STABLELM.heads, STABLELM.kv_heads,
            STABLELM.head_dim, STABLELM.ff, STABLELM.vocab) == (
        2048, 24, 32, 32, 64, 5632, 100352)
    # 1.41B layer weights + 0.31B tied embedding = qwen3's 1.72B
    assert QWEN3.layer_matmul_params * 28 == 1_409_286_144


def test_decode_attention_qwen3():
    rows = [99, 511]             # live rows' positions: 100 and 512 keys
    assert workcount.attn_flops(QWEN3, rows, "decode") == 4 * 16 * 128 * 612
    # K and V of 612 tokens at 8 x 128, plus q and o of 16 x 128, bf16
    assert workcount.attn_bytes(QWEN3, rows, "decode") == 2 * (
        2 * 8 * 128 * 612 + 2 * 2 * 16 * 128)


def test_chunk_attention_stablelm():
    rows = [(0, 256), (256, 64)]
    pairs = 256 * 257 // 2 + 64 * 256 + 64 * 65 // 2
    assert workcount.attn_flops(STABLELM, rows, "chunk") == \
        4 * 32 * 64 * pairs == 420_741_120
    assert workcount.attn_bytes(STABLELM, rows, "chunk") == 2 * (
        2 * 32 * 64 * (256 + 320) + 2 * (256 + 64) * 32 * 64) == 7_340_032


def test_step_flops_counts_real_tokens_only():
    rows = [99, 511]
    want = (2 * 50_331_648 * 28 * 2 + 28 * 4 * 16 * 128 * 612
            + 2 * 2048 * 151936 * 2)
    assert workcount.step_flops(QWEN3, rows, "decode", 2) == want \
        == 7_022_182_400
    # a chunk whose logits are unused pays no output matrix
    c = workcount.step_flops(STABLELM, [(0, 256)], "chunk", 0)
    assert c == (2 * STABLELM.layer_matmul_params * 24 * 256
                 + 24 * 4 * 32 * 64 * (256 * 257 // 2))


def test_roofline_takes_the_larger_bound():
    peak = workcount.peaks("TPU v5 lite")
    assert peak["flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert workcount.roofline_s(197e12, 1.0, peak) == pytest.approx(1.0)
    assert workcount.roofline_s(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        workcount.peaks("cpu")

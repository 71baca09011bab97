"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU), plus hypothesis property tests."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (1, 128, 4, 4, 64), (2, 256, 8, 2, 64), (1, 128, 4, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, Hkv, D, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = _rand(ks[0], (B, S, H, D), dtype)
    k = _rand(ks[1], (B, S, Hkv, D), dtype)
    v = _rand(ks[2], (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    want = ref.ref_attention(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window,softcap", [(64, None), (None, 30.0),
                                            (32, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    B, S, H, D = 1, 128, 2, 64
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (_rand(ks[i], (B, S, H, D), jnp.float32) for i in range(3))
    out = ops.flash_attention(q, k, v, window=window, softcap=softcap,
                              block_q=32, block_k=32)
    want = ref.ref_attention(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    B, S, H, D = 1, 64, 2, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (_rand(ks[i], (B, S, H, D), jnp.float32) for i in range(3))
    out = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    want = ref.ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Smax,H,Hkv,D,pos", [
    (2, 256, 8, 2, 64, 0), (2, 256, 8, 2, 64, 100), (1, 512, 4, 4, 128, 511),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, Smax, H, Hkv, D, pos, dtype):
    ks = jax.random.split(jax.random.key(3), 3)
    q = _rand(ks[0], (B, H, D), dtype)
    kc = _rand(ks[1], (B, Smax, Hkv, D), dtype)
    vc = _rand(ks[2], (B, Smax, Hkv, D), dtype)
    out = ops.decode_attention(q, kc, vc, jnp.asarray(pos, jnp.int32),
                               block_k=64)
    want = ref.ref_decode_attention(q, kc, vc, pos)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_ragged_pos(dtype):
    """Vector per-row positions (continuous batching): each row attends to
    its own valid window only."""
    B, Smax, H, Hkv, D = 4, 256, 8, 2, 64
    ks = jax.random.split(jax.random.key(6), 3)
    q = _rand(ks[0], (B, H, D), dtype)
    kc = _rand(ks[1], (B, Smax, Hkv, D), dtype)
    vc = _rand(ks[2], (B, Smax, Hkv, D), dtype)
    pos = jnp.asarray([0, 17, 128, 255], jnp.int32)
    out = ops.decode_attention(q, kc, vc, pos, block_k=64)
    want = ref.ref_decode_attention(q, kc, vc, pos)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("Smax,block_k", [(192, 128), (100, 64)])
def test_decode_attention_nondividing_window(Smax, block_k):
    """Cache windows that block_k doesn't divide (e.g. an engine max_seq of
    prompt+max_new+slack) lower via the largest dividing block."""
    B, H, Hkv, D = 2, 4, 2, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Smax, Hkv, D), jnp.float32)
    vc = _rand(ks[2], (B, Smax, Hkv, D), jnp.float32)
    pos = jnp.asarray([7, Smax - 1], jnp.int32)
    out = ops.decode_attention(q, kc, vc, pos, block_k=block_k)
    want = ref.ref_decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_kv_major_layout():
    """The KV-major serving layout ([B,Hkv,S,D]) gives the same result as
    the default [B,S,Hkv,D] without the wrapper transpose."""
    B, Smax, H, Hkv, D = 2, 128, 4, 2, 64
    ks = jax.random.split(jax.random.key(7), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Smax, Hkv, D), jnp.float32)
    vc = _rand(ks[2], (B, Smax, Hkv, D), jnp.float32)
    pos = jnp.asarray([3, 100], jnp.int32)
    a = ops.decode_attention(q, kc, vc, pos, block_k=32)
    b = ops.decode_attention(q, kc.transpose(0, 2, 1, 3),
                             vc.transpose(0, 2, 1, 3), pos, block_k=32,
                             kv_layout="bhsd")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_attention_paged_matches_dense():
    """Paged flash-decode through a shuffled page table equals dense decode
    over the same logical KV, including rows with partially-mapped tables."""
    B, Smax, H, Hkv, D, ps = 3, 128, 8, 2, 64, 16
    P = Smax // ps
    n_pages = 32
    ks = jax.random.split(jax.random.key(8), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Smax, Hkv, D), jnp.float32)
    vc = _rand(ks[2], (B, Smax, Hkv, D), jnp.float32)
    pos = jnp.asarray([5, 63, 127], jnp.int32)
    rng = np.random.default_rng(0)
    pages = rng.permutation(n_pages)[:B * P].reshape(B, P)
    kp = np.zeros((n_pages, Hkv, ps, D), np.float32)
    vp = np.zeros((n_pages, Hkv, ps, D), np.float32)
    for b in range(B):
        for j in range(P):
            kp[pages[b, j]] = np.asarray(kc)[b, j * ps:(j + 1) * ps] \
                .transpose(1, 0, 2)
            vp[pages[b, j]] = np.asarray(vc)[b, j * ps:(j + 1) * ps] \
                .transpose(1, 0, 2)
    pt = pages.astype(np.int32)
    pt[0, 1:] = n_pages            # row 0 (pos 5 < ps): rest unmapped
    out = ops.decode_attention_paged(jnp.asarray(q), jnp.asarray(kp),
                                     jnp.asarray(vp), jnp.asarray(pt), pos)
    want = ref.ref_decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    wantp = ref.ref_decode_attention_paged(jnp.asarray(q), jnp.asarray(kp),
                                           jnp.asarray(vp), jnp.asarray(pt),
                                           pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(wantp),
                               rtol=2e-5, atol=2e-5)


# the served tenants' (H, Hkv, D): qwen3-1.7b, stablelm-1.6b
SERVED_WIDTHS = [(16, 8, 128), (32, 32, 64)]


def _paged_case(key, widths, ps, P, B, L=1):
    """q, stacked pools [L, n_pages, Hkv, ps, D] and a shuffled page table
    [B, P] with one unmapped entry past row 0's first page."""
    H, Hkv, D = widths
    n_pages = B * P + 1
    ks = jax.random.split(jax.random.key(key), 3)
    q = _rand(ks[0], (B, H, D), jnp.float32)
    kp = _rand(ks[1], (L, n_pages, Hkv, ps, D), jnp.float32)
    vp = _rand(ks[2], (L, n_pages, Hkv, ps, D), jnp.float32)
    pt = np.random.default_rng(key).permutation(n_pages)[:B * P]
    pt = pt.reshape(B, P).astype(np.int32)
    pt[0, 1:] = n_pages            # row 0 stays on its first page
    return q, kp, vp, jnp.asarray(pt)


@pytest.mark.parametrize("widths", SERVED_WIDTHS, ids=["qwen3", "stablelm"])
@pytest.mark.parametrize("ps", [16, 128], ids=["p16", "p128"])
def test_decode_attention_paged_served_widths(widths, ps):
    """At both served tenants' widths, with pages of 16 and 128 (D 64 at 128
    takes the D-major view), the kernel matches the paged oracle for ragged
    positions: the first token, either side of a page boundary, and a row
    on its table's last page up to the window's last token; every layer of
    a stacked pool."""
    P, L = 3, 2
    pos = jnp.asarray([ps - 1, 0, ps, 2 * ps - 1, 2 * ps + 1, P * ps - 1],
                      jnp.int32)
    q, kp, vp, pt = _paged_case(41, widths, ps, P, len(pos), L)
    for layer in range(L):
        out = ops.decode_attention_paged(q, kp, vp, pt, pos,
                                         layer=jnp.asarray(layer))
        want = ref.ref_decode_attention_paged(q, kp[layer], vp[layer], pt,
                                              pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("widths", SERVED_WIDTHS, ids=["qwen3", "stablelm"])
@pytest.mark.parametrize("ps", [16, 128], ids=["p16", "p128"])
def test_decode_attention_paged_row_past_window(widths, ps):
    """A row at ``pos = P * page_size`` (the engine's write sentinel) reads
    nothing and returns zeros, and the other rows' outputs are those of the
    same call without that row."""
    P = 2
    q, kp, vp, pt = _paged_case(43, widths, ps, P, 3)
    pos = jnp.asarray([ps - 3, P * ps, ps + 1], jnp.int32)
    out = np.asarray(ops.decode_attention_paged(q, kp, vp, pt, pos, layer=0))
    keep = np.asarray([0, 2])
    alone = ops.decode_attention_paged(q[keep], kp, vp, pt[keep], pos[keep],
                                       layer=0)
    np.testing.assert_array_equal(out[keep], np.asarray(alone))
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))


@pytest.mark.parametrize("shape,groups", [
    ((8, 128, 128, 2), 1),         # qwen3-1.7b, bf16
    ((32, 128, 64, 2), 1),         # stablelm-1.6b, bf16
    ((64, 128, 256, 2), 4),        # 4 MB of heads: four groups of 1 MB
    ((12, 128, 512, 4), 3),        # 3 MB in float32: halves do not fit
    ((2, 1024, 512, 4), 2),        # one head past the budget alone
], ids=["qwen3", "stablelm", "hkv64-d256", "hkv12-thirds", "head-past-budget"])
def test_paged_decode_head_groups(shape, groups):
    """The paged decode kernel reads one page of every KV head a step; only
    a block past the budget splits the heads, into the fewest equal groups
    that fit (one head a group where none does)."""
    from repro.kernels.decode_attention import PAGE_BLOCK_BYTES, head_groups
    Hkv, ps, D, itemsize = shape
    assert head_groups(Hkv, ps, D, itemsize) == groups
    assert Hkv % groups == 0
    per_group = Hkv // groups * ps * D * itemsize
    assert per_group <= PAGE_BLOCK_BYTES or groups == Hkv


def test_decode_attention_paged_split_heads(monkeypatch):
    """With the block budget shrunk so the heads split into groups (a grid
    of rows x head groups x pages), the kernel still matches the oracle,
    a row past the window still returns zeros, and the result equals the
    one-group call."""
    # the package exports the function ``decode_attention`` under the
    # module's name
    da = importlib.import_module("repro.kernels.decode_attention")
    H, Hkv, D, ps, P = 8, 4, 64, 16, 3
    q, kp, vp, pt = _paged_case(47, (H, Hkv, D), ps, P, 3)
    pos = jnp.asarray([2, 2 * ps, P * ps], jnp.int32)
    whole = da.decode_attention_paged(q, kp, vp, pt, pos, layer=0,
                                      interpret=True)
    monkeypatch.setattr(da, "PAGE_BLOCK_BYTES", 2 * ps * D * 4)
    assert da.head_groups(Hkv, ps, D, 4) == 2
    out = np.asarray(da.decode_attention_paged(q, kp, vp, pt, pos, layer=0,
                                               interpret=True))
    np.testing.assert_array_equal(out, np.asarray(whole))
    want = ref.ref_decode_attention_paged(q, kp[0], vp[0], pt, pos)
    np.testing.assert_allclose(out[:2], np.asarray(want)[:2], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(out[2], np.zeros_like(out[2]))


# ---------------------------------------------------------------------------
# SPT gather / scatter
# ---------------------------------------------------------------------------

@given(n_pages=st.integers(1, 32), seed=st.integers(0, 100))
@settings(max_examples=12, deadline=None)
def test_spt_gather_property(n_pages, seed):
    rng = np.random.default_rng(seed)
    n_arena = n_pages + int(rng.integers(0, 16))
    arena = jnp.asarray(rng.normal(size=(n_arena, 256)).astype(np.float32))
    spt = jnp.asarray(rng.choice(n_arena, n_pages, replace=False)
                      .astype(np.int32))
    out = ops.spt_gather(arena, spt)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.ref_spt_gather(arena, spt)))


def test_spt_roundtrip():
    """scatter(gather(x)) restores the arena pages the SPT references."""
    rng = np.random.default_rng(0)
    n_arena, n_pages = 24, 16
    arena = jnp.asarray(rng.normal(size=(n_arena, 128)).astype(np.float32))
    spt = jnp.asarray(rng.choice(n_arena, n_pages, replace=False)
                      .astype(np.int32))
    logical = ops.spt_gather(arena, spt)
    back = ops.spt_scatter(logical, spt, n_arena)
    np.testing.assert_array_equal(np.asarray(back)[np.asarray(spt)],
                                  np.asarray(arena)[np.asarray(spt)])


# ---------------------------------------------------------------------------
# dual-tenant matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m_ls,m_be,K,N,sm_be", [
    (128, 256, 128, 128, 0.3), (256, 128, 256, 256, 0.5),
])
def test_dual_tenant_matmul(m_ls, m_be, K, N, sm_be):
    ks = jax.random.split(jax.random.key(4), 4)
    a_ls = _rand(ks[0], (m_ls, K), jnp.float32)
    b_ls = _rand(ks[1], (K, N), jnp.float32)
    a_be = _rand(ks[2], (m_be, K), jnp.float32)
    b_be = _rand(ks[3], (K, N), jnp.float32)
    o_ls, o_be = ops.dual_tenant_matmul(a_ls, b_ls, a_be, b_be, sm_be=sm_be,
                                        block_m=64, block_n=64, block_k=64)
    w_ls, w_be = ref.ref_dual_tenant_matmul(a_ls, b_ls, a_be, b_be)
    np.testing.assert_allclose(np.asarray(o_ls), np.asarray(w_ls), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_be), np.asarray(w_be), rtol=1e-5,
                               atol=1e-4)


def test_dual_tenant_schedule_quota():
    """In every scheduling round while both tenants have tiles, BE holds at
    most floor(sm_be * round) tiles (the SM_BE quota)."""
    from repro.kernels.dual_tenant_matmul import _schedule
    order = _schedule(n_ls=16, n_be=64, sm_be=0.25, round_tiles=8)
    assert [o for o, _ in order].count(0) == 16
    assert [o for o, _ in order].count(1) == 64
    # while LS tiles remain, each window of 8 has <= 2 BE tiles
    upto = max(i for i, (o, _) in enumerate(order) if o == 0)
    for s in range(0, upto - 8, 8):
        window = [o for o, _ in order[s:s + 8]]
        assert window.count(1) <= 2, (s, window)


def test_dual_tenant_schedule_no_starvation():
    """A fractional quota below one tile per round (sm_be * round_tiles < 1)
    accumulates as credit: BE tiles interleave before LS drains instead of
    starving until the tail, and every tile is scheduled exactly once."""
    from repro.kernels.dual_tenant_matmul import _schedule
    order = _schedule(n_ls=40, n_be=6, sm_be=0.05, round_tiles=8)
    owners = [o for o, _ in order]
    assert owners.count(0) == 40 and owners.count(1) == 6
    # sm_be=0.05 earns 0.4 credit per 8-tile round -> first BE tile by
    # round 3 (credit 1.2), well before the 40 LS tiles drain
    first_be = owners.index(1)
    assert first_be < 40, f"BE starved until LS drained (index {first_be})"
    # per-tenant tile ids stay in order and complete
    assert [r for o, r in order if o == 0] == list(range(40))
    assert [r for o, r in order if o == 1] == list(range(6))
    # quota still respected while both run
    upto = max(i for i, o in enumerate(owners) if o == 0)
    for s in range(0, upto - 8, 8):
        assert owners[s:s + 8].count(1) <= 1, (s, owners[s:s + 8])


# ---------------------------------------------------------------------------
# dual-tenant fused attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B_ls,B_be,S,H,Hkv,D,sm_be", [
    (2, 3, 256, 4, 4, 64, 0.3), (1, 2, 128, 4, 2, 64, 0.5),
])
def test_dual_tenant_attention(B_ls, B_be, S, H, Hkv, D, sm_be):
    """Both tenants of the fused grid match the single-tenant causal flash
    kernel bit-for-bit — the quota interleave only permutes placement."""
    ks = jax.random.split(jax.random.key(21), 6)
    q1 = _rand(ks[0], (B_ls, S, H, D), jnp.float32)
    k1 = _rand(ks[1], (B_ls, S, Hkv, D), jnp.float32)
    v1 = _rand(ks[2], (B_ls, S, Hkv, D), jnp.float32)
    q2 = _rand(ks[3], (B_be, S, H, D), jnp.float32)
    k2 = _rand(ks[4], (B_be, S, Hkv, D), jnp.float32)
    v2 = _rand(ks[5], (B_be, S, Hkv, D), jnp.float32)
    o1, o2 = ops.dual_tenant_attention(q1, k1, v1, q2, k2, v2, sm_be=sm_be,
                                       block_q=64, block_k=64)
    w1 = ops.flash_attention(q1, k1, v1, causal=True, block_q=64, block_k=64)
    w2 = ops.flash_attention(q2, k2, v2, causal=True, block_q=64, block_k=64)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(o2), np.asarray(w2))


def test_dual_tenant_attention_quota_invariant():
    """sm_be permutes only the schedule: outputs are bit-identical across
    quota settings."""
    ks = jax.random.split(jax.random.key(22), 3)
    q = _rand(ks[0], (2, 128, 4, 64), jnp.float32)
    k = _rand(ks[1], (2, 128, 4, 64), jnp.float32)
    v = _rand(ks[2], (2, 128, 4, 64), jnp.float32)
    outs = [ops.dual_tenant_attention(q, k, v, q, k, v, sm_be=s,
                                      block_q=64, block_k=64)
            for s in (0.1, 0.5, 0.9)]
    for o_ls, o_be in outs[1:]:
        np.testing.assert_array_equal(np.asarray(o_ls),
                                      np.asarray(outs[0][0]))
        np.testing.assert_array_equal(np.asarray(o_be),
                                      np.asarray(outs[0][1]))


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,K,P,chunk", [
    (1, 128, 2, 16, 32, 32), (2, 64, 4, 8, 8, 16), (1, 256, 1, 64, 64, 64),
])
def test_ssd_scan_sweep(B, T, H, K, P, chunk):
    ks = jax.random.split(jax.random.key(5), 4)
    q = _rand(ks[0], (B, T, H, K), jnp.float32)
    k = _rand(ks[1], (B, T, H, K), jnp.float32)
    v = _rand(ks[2], (B, T, H, P), jnp.float32)
    log_w = -jnp.abs(_rand(ks[3], (B, T, H, K), jnp.float32)) * 0.2
    out = ops.ssd_scan(q, k, v, log_w, chunk=chunk)
    want = ref.ref_ssd_scan(q, k, v, log_w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@given(seed=st.integers(0, 1000))
@settings(max_examples=8, deadline=None)
def test_ssd_scan_property_decay_extremes(seed):
    """With decay ~ 0 (log_w very negative) the scan reduces to per-token
    kv outer products; with decay = 1 (log_w = 0) it is a running sum."""
    rng = np.random.default_rng(seed)
    B, T, H, K, P = 1, 32, 1, 8, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, K)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, K)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, P)).astype(np.float32))
    zero = jnp.zeros((B, T, H, K), jnp.float32)
    out = ops.ssd_scan(q, k, v, zero, chunk=8)
    want = ref.ref_ssd_scan(q, k, v, zero)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# chunked-prefill attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Smax,Sq,H,Hkv,D", [
    (2, 256, 8, 8, 2, 64), (1, 128, 16, 4, 4, 64), (2, 128, 1, 4, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefill_attention_sweep(B, Smax, Sq, H, Hkv, D, dtype):
    """Sq-token query chunks at per-row start positions attend to their
    cached-context window (kernel vs jnp oracle); Sq == 1 covers the
    scheduler's one-token seeding chunk."""
    ks = jax.random.split(jax.random.key(11), 3)
    q = _rand(ks[0], (B, Sq, H, D), dtype)
    kc = _rand(ks[1], (B, Smax, Hkv, D), dtype)
    vc = _rand(ks[2], (B, Smax, Hkv, D), dtype)
    pos = jnp.asarray(list(range(0, B * 37, 37))[:B], jnp.int32)
    out = ops.prefill_attention(q, kc.transpose(0, 2, 1, 3),
                                vc.transpose(0, 2, 1, 3), pos, block_k=64)
    want = ref.ref_prefill_attention(q, kc, vc, pos)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_prefill_attention_paged_matches_dense():
    """Paged chunked-prefill through a shuffled page table equals the dense
    chunk over the same logical KV, including partially-mapped rows."""
    B, Smax, Sq, H, Hkv, D, ps = 3, 128, 8, 8, 2, 64, 16
    P = Smax // ps
    n_pages = 32
    ks = jax.random.split(jax.random.key(12), 3)
    q = _rand(ks[0], (B, Sq, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Smax, Hkv, D), jnp.float32)
    vc = _rand(ks[2], (B, Smax, Hkv, D), jnp.float32)
    pos = jnp.asarray([0, 40, 120], jnp.int32)     # chunk ends at pos+Sq-1
    rng = np.random.default_rng(1)
    pages = rng.permutation(n_pages)[:B * P].reshape(B, P)
    kp = np.zeros((n_pages, Hkv, ps, D), np.float32)
    vp = np.zeros((n_pages, Hkv, ps, D), np.float32)
    for b in range(B):
        for j in range(P):
            kp[pages[b, j]] = np.asarray(kc)[b, j * ps:(j + 1) * ps] \
                .transpose(1, 0, 2)
            vp[pages[b, j]] = np.asarray(vc)[b, j * ps:(j + 1) * ps] \
                .transpose(1, 0, 2)
    pt = pages.astype(np.int32)
    pt[0, 1:] = n_pages            # row 0 (chunk within page 0): unmapped
    out = ops.prefill_attention_paged(jnp.asarray(q), jnp.asarray(kp),
                                      jnp.asarray(vp), jnp.asarray(pt), pos)
    want = ref.ref_prefill_attention(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    wantp = ref.ref_prefill_attention_paged(jnp.asarray(q), jnp.asarray(kp),
                                            jnp.asarray(vp), jnp.asarray(pt),
                                            pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(wantp),
                               rtol=2e-5, atol=2e-5)


def test_prefill_attention_abort_progress():
    """The sub-chunk abort protocol: with a per-row position cap, the first
    ``abort`` rows are bit-equal to running a chunk of exactly ``abort``
    tokens, and ``progress`` reports min(abort, Sq) per row."""
    B, Sq, H, Hkv, Smax, D = 3, 8, 4, 2, 128, 64
    ks = jax.random.split(jax.random.key(23), 3)
    q = _rand(ks[0], (B, Sq, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Hkv, Smax, D), jnp.float32)
    vc = _rand(ks[2], (B, Hkv, Smax, D), jnp.float32)
    pos = jnp.asarray([0, 13, 77], jnp.int32)
    full = ops.prefill_attention(q, kc, vc, pos, block_k=32)
    abort = jnp.asarray([3, 8, 0], jnp.int32)
    out, prog = ops.prefill_attention(q, kc, vc, pos, block_k=32,
                                      abort=abort)
    np.testing.assert_array_equal(np.asarray(prog), [3, 8, 0])
    np.testing.assert_array_equal(np.asarray(out)[0, :3],
                                  np.asarray(full)[0, :3])
    np.testing.assert_array_equal(np.asarray(out)[1], np.asarray(full)[1])
    # an aborted prefix equals a genuinely smaller chunk (the resume
    # contract: a resumed chunk is just a smaller chunk)
    small = ops.prefill_attention(q[:, :3], kc, vc, pos, block_k=32)
    np.testing.assert_array_equal(np.asarray(out)[0, :3],
                                  np.asarray(small)[0])


def test_prefill_attention_paged_abort_progress():
    """Same protocol through the paged entry point: abort caps agree with
    the dense kernel and unmapped pages past the cap stay untouched."""
    B, Smax, Sq, H, Hkv, D, ps = 2, 128, 8, 4, 2, 64, 16
    P = Smax // ps
    n_pages = 24
    ks = jax.random.split(jax.random.key(24), 3)
    q = _rand(ks[0], (B, Sq, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Smax, Hkv, D), jnp.float32)
    vc = _rand(ks[2], (B, Smax, Hkv, D), jnp.float32)
    pos = jnp.asarray([0, 40], jnp.int32)
    rng = np.random.default_rng(3)
    pages = rng.permutation(n_pages)[:B * P].reshape(B, P)
    kp = np.zeros((n_pages, Hkv, ps, D), np.float32)
    vp = np.zeros((n_pages, Hkv, ps, D), np.float32)
    for b in range(B):
        for j in range(P):
            kp[pages[b, j]] = np.asarray(kc)[b, j * ps:(j + 1) * ps] \
                .transpose(1, 0, 2)
            vp[pages[b, j]] = np.asarray(vc)[b, j * ps:(j + 1) * ps] \
                .transpose(1, 0, 2)
    abort = jnp.asarray([5, 2], jnp.int32)
    out, prog = ops.prefill_attention_paged(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(pages.astype(np.int32)), pos, abort=abort)
    dense, dprog = ops.prefill_attention(
        q, jnp.asarray(kc).transpose(0, 2, 1, 3),
        jnp.asarray(vc).transpose(0, 2, 1, 3), pos, block_k=ps, abort=abort)
    np.testing.assert_array_equal(np.asarray(prog), np.asarray(dprog))
    np.testing.assert_allclose(np.asarray(out)[0, :5],
                               np.asarray(dense)[0, :5], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(out)[1, :2],
                               np.asarray(dense)[1, :2], rtol=2e-6,
                               atol=2e-6)


@pytest.mark.parametrize("kernel", ["decode", "prefill", "prefill-abort"])
@pytest.mark.parametrize("ps,D", [(16, 64), (128, 64)],
                         ids=["p16", "p128-dmajor"])
def test_paged_kernels_read_one_layer_of_a_stack(kernel, ps, D):
    """A stacked pool [L, n_pages, Hkv, ps, D] read at ``layer`` gives what
    the 4-D call on ``pool[layer]`` gives, for every layer of the stack; a
    128-token page of D 64 takes the D-major view (the TPU's own layout for
    that tile) and still matches the oracle."""
    L, B, H, Hkv, Sq = 3, 2, 4, 2, 5
    P, n_pages = 2, 5
    ks = jax.random.split(jax.random.key(31), 3)
    kp = _rand(ks[0], (L, n_pages, Hkv, ps, D), jnp.float32)
    vp = _rand(ks[1], (L, n_pages, Hkv, ps, D), jnp.float32)
    pt = jnp.asarray([[3, 1], [0, n_pages]], jnp.int32)
    pos = jnp.asarray([ps + 2, ps - 3], jnp.int32)
    if kernel == "decode":
        q = _rand(ks[2], (B, H, D), jnp.float32)
        call = ops.decode_attention_paged
        oracle = ref.ref_decode_attention_paged
        kw = {}
    else:
        q = _rand(ks[2], (B, Sq, H, D), jnp.float32)
        call = ops.prefill_attention_paged
        oracle = ref.ref_prefill_attention_paged
        kw = ({"abort": jnp.asarray([2, 0], jnp.int32)}
              if kernel == "prefill-abort" else {})
    for layer in range(L):
        got = call(q, kp, vp, pt, pos, layer=jnp.asarray(layer), **kw)
        one = call(q, kp[layer], vp[layer], pt, pos, **kw)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if not kw:
            want = oracle(q, kp[layer], vp[layer], pt, pos)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)


def test_interpret_autodetect():
    """``interpret=None`` resolves from the backend (CPU hosts interpret)
    and matches an explicit ``interpret=True`` bit-for-bit."""
    from repro.kernels.pallas_compat import interpret_default
    assert interpret_default() == (jax.default_backend() != "tpu")
    B, Smax, H, Hkv, D = 2, 64, 4, 2, 64
    ks = jax.random.split(jax.random.key(25), 3)
    q = _rand(ks[0], (B, 4, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Hkv, Smax, D), jnp.float32)
    vc = _rand(ks[2], (B, Hkv, Smax, D), jnp.float32)
    pos = jnp.asarray([0, 9], jnp.int32)
    auto = ops.prefill_attention(q, kc, vc, pos, block_k=32)
    explicit = ops.prefill_attention(q, kc, vc, pos, block_k=32,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))
    d_auto = ops.decode_attention(q[:, 0], kc, vc, pos, block_k=32,
                                  kv_layout="bhsd")
    d_explicit = ops.decode_attention(q[:, 0], kc, vc, pos, block_k=32,
                                      kv_layout="bhsd", interpret=True)
    np.testing.assert_array_equal(np.asarray(d_auto), np.asarray(d_explicit))


def test_prefill_attention_reduces_to_decode():
    """An Sq == 1 prefill chunk is exactly a decode step (the bit-stable
    seeding-chunk contract)."""
    B, Smax, H, Hkv, D = 2, 128, 4, 2, 64
    ks = jax.random.split(jax.random.key(13), 3)
    q = _rand(ks[0], (B, 1, H, D), jnp.float32)
    kc = _rand(ks[1], (B, Hkv, Smax, D), jnp.float32)
    vc = _rand(ks[2], (B, Hkv, Smax, D), jnp.float32)
    pos = jnp.asarray([3, 90], jnp.int32)
    a = ops.prefill_attention(q, kc, vc, pos, block_k=32)
    b = ops.decode_attention(q[:, 0], kc, vc, pos, block_k=32,
                             kv_layout="bhsd")
    np.testing.assert_allclose(np.asarray(a[:, 0]), np.asarray(b),
                               rtol=2e-6, atol=2e-6)

"""The traffic generator: determinism by seed, the same work for every
seed, length grids and clips, and the arrival schedules."""
import collections
import json
import os

import numpy as np
import pytest

import benchtest
from benchkit import spec, traffic

SPEC = spec.Spec()


def _mix(name):
    with open(os.path.join(benchtest.BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_same_seed_same_requests():
    a = traffic.streams(SPEC, _mix("chat-colo"), 2**31 + 5, 20, 10)
    b = traffic.streams(SPEC, _mix("chat-colo"), 2**31 + 5, 20, 10)
    for cls in ("LS", "BE"):
        assert a[cls].prompt == b[cls].prompt
        assert a[cls].output == b[cls].output
        assert a[cls].due == b[cls].due
        ra, rb = a[cls].make(3, 1000), b[cls].make(3, 1000)
        assert np.array_equal(ra.tokens, rb.tokens)


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.streams(SPEC, _mix("chat-colo"), 1, 51, 10)["LS"]
    b = traffic.streams(SPEC, _mix("chat-colo"), 2**33 + 1, 51, 10)["LS"]
    n = sum(a.in_window)
    assert n == sum(b.in_window) > 10
    # the same sizes inside the window, and the same in the tail
    for lo, hi in ((0, n), (n, len(a))):
        assert sorted(a.prompt[lo:hi]) == sorted(b.prompt[lo:hi])
        assert sorted(a.output[lo:hi]) == sorted(b.output[lo:hi])
    assert a.prompt != b.prompt
    ga, gb = np.diff(a.due[:sum(a.in_window)]), np.diff(
        b.due[:sum(b.in_window)])
    assert np.allclose(sorted(ga), sorted(gb), atol=0.5)
    assert not np.array_equal(
        a.make(0, 5000).tokens[:8], b.make(0, 5000).tokens[:8])


def test_lengths_on_the_grid_and_within_the_clips():
    st = traffic.streams(SPEC, _mix("chat-colo"), 7, 40, 0)
    ls, be = st["LS"], st["BE"]
    assert all((p - 1) % 64 == 0 and 65 <= p <= 1537 for p in ls.prompt)
    assert all(8 <= o <= 448 for o in ls.output)
    assert all((p - 1) % 64 == 0 and 1025 <= p <= 1793 for p in be.prompt)
    assert all(128 <= o <= 256 for o in be.output)
    assert max(p + o for p, o in zip(be.prompt, be.output)) <= 2049
    # the lognormal median sits near its stated value
    assert 449 <= sorted(ls.prompt)[len(ls.prompt) // 2] <= 577


def test_poisson_schedule_count_and_range():
    st = traffic.streams(SPEC, _mix("chat-solo"), 3, 25, 10)["LS"]
    rate = _mix("chat-solo")["ls"]["arrivals"]["rate_per_s"]
    win = [d for d, w in zip(st.due, st.in_window) if w]
    tail = [d for d, w in zip(st.due, st.in_window) if not w]
    assert len(win) == round(rate * 25)
    assert len(tail) == round(rate * 10)
    assert all(0 <= d < 25 for d in win) and all(25 <= d < 35 for d in tail)
    assert win == sorted(win) and win[0] == 0.0


def test_square_wave_arrivals_fall_in_the_on_halves():
    kind = SPEC.kind("square_wave")
    rng = np.random.default_rng(0)

    def u(n):
        return rng.permutation((np.arange(n) + 0.5) / n)
    due = kind.schedule(u, 40, period_s=10, on_rate_per_s=4)
    assert len(due) == 4 * 20
    assert all((d % 10) < 5 for d in due)
    per = collections.Counter(int(d // 10) for d in due)
    assert set(per.values()) == {20}


def test_backlog_pool_is_cycled():
    be = traffic.streams(SPEC, _mix("chat-colo"), 1, 10, 0)["BE"]
    assert be.depth == 8 and len(be) == 64
    assert be.make(64 + 3, 100).prompt_len == be.make(3, 100).prompt_len


@pytest.mark.parametrize("n", [8, 16])
def test_backlog_prefix_covers_the_distribution_for_every_seed(n):
    """A window takes only a backlog's first requests; for every seed they
    spread over the whole range of lengths (at most one to each n-th of
    the range, give or take one), so no seed gets a window of long or
    short requests only."""
    mix = _mix("chat-colo")["be"]
    for seed in (1, 2**31 + 7, 2**33 + 5):
        be = traffic.streams(SPEC, _mix("chat-colo"), seed, 10, 0)["BE"]
        for key, vals in (("output", be.output[:n]),
                          ("prompt", be.prompt[:n])):
            lo, hi = mix[key]["min"], mix[key]["max"]
            bins = collections.Counter(
                min(int((v - lo) / (hi - lo + 1) * n), n - 1) for v in vals)
            assert max(bins.values()) <= 2 + (key == "prompt"), (seed, key)
            assert len(bins) >= n // 2, (seed, key)


@pytest.mark.parametrize("L,chunk,want", [
    (513, 256, [256, 256, 1]), (577, 256, [256, 256, 64, 1]),
    (65, 256, [64, 1]), (1537, 256, [256] * 6 + [1]),
    (1409, 256, [256] * 5 + [128, 1])])
def test_chunk_lengths(L, chunk, want):
    assert traffic.chunk_lengths(L, chunk) == want


def test_grid_prompts_use_only_five_chunk_lengths():
    st = traffic.streams(SPEC, _mix("chat-colo"), 1, 40, 40)
    lens = {c for s in st.values() for L in s.prompt
            for c in traffic.chunk_lengths(L, 256)}
    assert lens <= {256, 192, 128, 64, 1}

"""Paged KV pools carried through the layer loop.

``decode_step`` and ``prefill_step`` hand a paged cache's stacked pools
([L, n_pages, ...] per leaf) through the layer loop as carried state and
update each layer's entries in place. They must return logits and pools
bit-equal to the per-layer path that carried them before: each layer's
pools sliced out of the stack as a loop input, the new tokens appended by
one scatter per pool, and the pools stacked back as a loop output. That path
is kept here, as the reference, and nowhere in ``src``.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import attention as attn
from repro.models import transformer as tf

PS, P, N_PAGES, B = 4, 4, 20, 4
MAX_SEQ = P * PS


def _scatter_append(pools, layer, news, page_table, pos, *, seq_axis):
    """The per-layer path's append: one scatter per pool of every token's
    (page, offset) entry; unmapped pages and positions past the table
    drop."""
    Sq = news[0].shape[1]
    n_pages, ps = pools[0].shape[1], pools[0].shape[2 + seq_axis]
    positions = pos[:, None] + jnp.arange(Sq)[None, :]
    logical = positions // ps
    phys = jnp.take_along_axis(
        page_table, jnp.clip(logical, 0, page_table.shape[1] - 1), axis=1)
    phys = jnp.where((phys < 0) | (logical >= page_table.shape[1]), n_pages,
                     phys)
    off = positions % ps
    idx = ((layer, phys, slice(None), off) if seq_axis == 1
           else (layer, phys, off))
    return [pool.at[idx].set(new.astype(pool.dtype), mode="drop")
            for pool, new in zip(pools, news)]


def _per_layer_step(params, cfg, tokens, cache, pos, page_table, *, chunk,
                    use_flash):
    """``decode_step`` (``chunk`` False) or ``prefill_step`` as the layer
    loop ran them with per-layer pools as scan inputs and outputs."""
    Sq = tokens.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))
    positions = pos[:, None] + jnp.arange(Sq)[None, :]
    x = tf._embed_tokens(params, cfg, tokens, positions=positions)
    ctx = {"positions": positions, "page_table": page_table, "chunk": chunk,
           "use_flash": use_flash}
    aux = tf._aux0(cfg)
    n_prefix, prefix_kind, period, n_periods = tf._pattern_segments(cfg)
    new_cache = {}
    for i, p in enumerate(params.get("prefix", [])):
        x, aux, nc = tf._apply_one(p, x, cfg, prefix_kind, ctx, aux,
                                   cache["prefix"][i], pos, 0, -1 - i)
        new_cache.setdefault("prefix", []).append(nc)

    def body(carry, inp):
        x, aux = carry
        p_period, c_period, idx = inp
        out = {}
        for j, kind in enumerate(period):
            x, aux, out[f"s{j}"] = tf._apply_one(
                p_period[f"s{j}"], x, cfg, kind, ctx, aux,
                c_period[f"s{j}"], pos, idx, j)
        return (x, aux), out

    xs = (params["layers"], cache["layers"], jnp.arange(n_periods))
    if cfg.scan_layers:
        (x, aux), new_cache["layers"] = jax.lax.scan(body, (x, aux), xs)
    else:
        outs = []
        for i in range(n_periods):
            (x, aux), o = body((x, aux), jax.tree.map(lambda a: a[i], xs))
            outs.append(o)
        new_cache["layers"] = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    if chunk:
        x = x[:, -1:]
    return tf._logits(params, cfg, x), new_cache


@functools.lru_cache(maxsize=None)
def _model(name, unrolled):
    cfg = smoke_config(name)
    if unrolled:
        cfg = cfg.replace(scan_layers=False)
    return cfg, tf.init_params(jax.random.key(3), cfg)


def _state(cfg, seed):
    """Random pools and a page table with every row kind the engine makes:
    row 0 owns its pages; row 1 shares row 0's first page (as after a
    copy-on-write fork) and writes only past it, into its own pages, as the
    engine's rows do; row 2 is at the write sentinel with nothing mapped;
    row 3 has its last logical page unmapped, so a chunk running into it
    writes only what is mapped."""
    cache = tf.init_paged_cache(cfg, N_PAGES, PS)
    leaves, tree = jax.tree.flatten(cache)
    ks = jax.random.split(jax.random.key(seed), len(leaves))
    cache = jax.tree.unflatten(tree, [jax.random.normal(k, a.shape, a.dtype)
                                      for k, a in zip(ks, leaves)])
    pages = np.random.default_rng(seed).permutation(N_PAGES)
    pt = np.full((B, P), N_PAGES, np.int32)
    pt[0] = pages[0:4]
    pt[1, 0], pt[1, 1:] = pt[0, 0], pages[4:7]
    pt[3, :3] = pages[7:10]
    return cache, jnp.asarray(pt)


CASES = {
    # name: (arch, scan_layers off, use_flash)
    "qwen3": ("qwen3-1.7b", False, True),
    "stablelm": ("stablelm-1.6b", False, True),
    "stablelm-jnp": ("stablelm-1.6b", False, False),
    "gemma2-softcap-local": ("gemma2-9b", False, True),
    "mla": ("deepseek-v2-236b", False, True),
    "qwen3-unrolled": ("qwen3-1.7b", True, True),
}
# (Sq, chunk starts of rows 0, 1 and 3; row 2 sits at the write sentinel)
STEPS = {
    "decode": (1, (5, 6, 2 * PS + 3)),
    "chunk1": (1, (0, PS + 3, 3 * PS - 1)),
    "chunk3-offsets": (3, (1, PS + 2, 2 * PS + 3)),
    "chunk6-pages": (6, (0, PS + 1, 3 * PS - 2)),
}


@pytest.mark.parametrize("step", list(STEPS))
@pytest.mark.parametrize("case", list(CASES))
def test_carried_pools_match_per_layer_path(case, step, monkeypatch):
    arch, unrolled, use_flash = CASES[case]
    cfg, params = _model(arch, unrolled)
    Sq, (p0, p1, p3) = STEPS[step]
    chunk = step != "decode"
    cache, pt = _state(cfg, seed=len(case) + Sq)
    pos = jnp.asarray([p0, p1, MAX_SEQ, p3], jnp.int32)
    tokens = jax.random.randint(jax.random.key(Sq), (B, Sq), 0,
                                cfg.vocab_size)
    if chunk:
        got = tf.prefill_step(params, cfg, tokens, cache, pos,
                              ctx_extra={"page_table": pt},
                              use_flash=use_flash)
    else:
        got = tf.decode_step(params, cfg, tokens, cache, pos,
                             ctx_extra={"page_table": pt},
                             use_flash=use_flash)
    monkeypatch.setattr(attn, "_paged_append", _scatter_append)
    want = _per_layer_step(params, cfg, tokens, cache, pos, pt, chunk=chunk,
                           use_flash=use_flash)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    got_leaves = jax.tree.leaves(got[1])
    want_leaves = jax.tree.leaves(want[1])
    assert len(got_leaves) == len(want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the step wrote something: a check that passes on untouched pools
    # would pass on a step that drops every write
    before = jax.tree.leaves(cache)
    assert any(not np.array_equal(np.asarray(g), np.asarray(b))
               for g, b in zip(got_leaves, before))

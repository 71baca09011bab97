"""Jitted public wrappers for the Pallas kernels. On CPU hosts (tests, this
container) kernels execute in interpret mode; on TPU they compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention as _flash
from .decode_attention import (decode_attention as _decode,
                               decode_attention_paged as _decode_paged)
from .prefill_attention import (prefill_attention as _prefill,
                                prefill_attention_paged as _prefill_paged)
from .spt_gather import spt_gather as _gather, spt_scatter as _scatter
from .dual_tenant_matmul import dual_tenant_matmul as _dtm
from .dual_tenant_attention import dual_tenant_attention as _dta
from .pallas_compat import interpret_default as _interpret_default
from .ssd_scan import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    block_q=128, block_k=128, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret",
                                             "kv_layout"))
def decode_attention(q, k_cache, v_cache, pos, *, block_k=128,
                     interpret=None, kv_layout="bshd"):
    interpret = _interpret_default() if interpret is None else interpret
    return _decode(q, k_cache, v_cache, pos, block_k=block_k,
                   interpret=interpret, kv_layout=kv_layout)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_paged(q, k_pages, v_pages, page_table, pos, *,
                           layer=None, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _decode_paged(q, k_pages, v_pages, page_table, pos, layer=layer,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def prefill_attention(q, k_cache, v_cache, pos, *, block_k=128,
                      interpret=None, abort=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _prefill(q, k_cache, v_cache, pos, block_k=block_k,
                    interpret=interpret, abort=abort)


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefill_attention_paged(q, k_pages, v_pages, page_table, pos, *,
                            layer=None, interpret=None, abort=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _prefill_paged(q, k_pages, v_pages, page_table, pos, layer=layer,
                          interpret=interpret, abort=abort)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spt_gather(arena, spt, *, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _gather(arena, spt, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_arena_pages", "interpret"))
def spt_scatter(x, spt, n_arena_pages, *, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _scatter(x, spt, n_arena_pages, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sm_be", "block_m", "block_n",
                                             "block_k", "interpret"))
def dual_tenant_matmul(a_ls, b_ls, a_be, b_be, *, sm_be=0.3, block_m=128,
                       block_n=128, block_k=128, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _dtm(a_ls, b_ls, a_be, b_be, sm_be=sm_be, block_m=block_m,
                block_n=block_n, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sm_be", "block_q", "block_k",
                                             "round_tiles", "interpret"))
def dual_tenant_attention(q_ls, k_ls, v_ls, q_be, k_be, v_be, *, sm_be=0.3,
                          block_q=128, block_k=128, round_tiles=8,
                          interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _dta(q_ls, k_ls, v_ls, q_be, k_be, v_be, sm_be=sm_be,
                block_q=block_q, block_k=block_k, round_tiles=round_tiles,
                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(q, k, v, log_w, *, chunk=64, interpret=None):
    interpret = _interpret_default() if interpret is None else interpret
    return _ssd(q, k, v, log_w, chunk=chunk, interpret=interpret)

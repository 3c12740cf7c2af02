"""Pallas TPU speculative-decoding verification: score a whole in-flight
window of ``[next_token, draft_1..draft_k]`` tokens per decoding slot against
the paged KV pool in ONE launch.

Decode is memory-bound: a one-token step streams the request's entire live
KV working set to emit a single token.  Scoring ``k + 1`` positions per
request in one launch costs nearly the same HBM traffic (the pages stream
once; only the tiny q block grows), which is the classic speculative-
decoding win.  The caller has ALREADY scattered the window's K/V into the
request's pages at positions ``[lengths[b], lengths[b] + window_lens[b])``
— window starts are NOT page-aligned (they sit wherever decode left off),
so per-query causal masking is on *absolute* positions: query ``w`` of row
``b`` sits at ``lengths[b] + w`` and attends every position ``<= lengths[b]
+ w`` (committed context plus the causal prefix of its own window).

Grid = (batch, kv_pages) with the page dimension innermost and sequential
so the online-softmax state (one row per window position and query head)
lives in VMEM scratch — the same flash-decode layout as
:mod:`.paged_attention`: each program streams one page with every kv head,
``(page_size, kvh, d)``, and a static loop over the kv heads scores it
against that group's ``(W * rep, d)`` query rows (the window laid out
group-major by the wrapper, row ``w * rep + r``).  The page table,
committed ``lengths`` and per-row ``window_lens`` arrive as scalar
prefetch: the k/v BlockSpec index maps dereference the page table so only
pages holding live-or-in-flight tokens stream HBM->VMEM; trailing dead
blocks clamp to the last live page (a revisit — no new DMA).  ``W`` is
static (one jit variant per draft depth k), rows with fewer real drafts
mask the tail and emit exact zeros there.

Quantized pools (``k_scales``/``v_scales`` given): the float32 per-row
per-kv-head scale blocks stream through the same page-table index map as
their K/V pages and dequantization is fused right after the block load,
exactly as in :mod:`.paged_attention`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(
    pt_ref,                    # scalar prefetch: (b, max_pages) int32 page table
    lens_ref,                  # scalar prefetch: (b,) committed tokens
    wlens_ref,                 # scalar prefetch: (b,) real window tokens
    w_ref,                     # scalar prefetch: (1,) int32 window (0 = none)
    q_ref,                     # (1, kvh, W * rep, d)
    k_ref, v_ref,              # (1, page_size, kvh, d) — one page, every kv head
    *rest,                     # [ks_ref, vs_ref (1, page_size, kvh)], o_ref, scratch
    softcap: float,
    page_size: int,
    rep: int,                  # query heads per kv head
    scale: float,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    bi = pl.program_id(0)
    pj = pl.program_id(1)
    np_ = pl.num_programs(1)
    _, kvh, rows, d = q_ref.shape

    @pl.when(pj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    L = lens_ref[bi]
    wl = wlens_ref[bi]
    # positions are *logical*: page pj of this request covers
    # [pj*page_size, (pj+1)*page_size) regardless of the physical page the
    # index map streamed in.  Query row w*rep + r sits at position L + w.
    k_pos = pj * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1
    )
    w_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) // rep
    q_pos = L + w_idx
    w = w_ref[0]
    # (w <= 0) | ...: Mosaic cannot select between boolean vectors
    valid = (k_pos <= q_pos) & (w_idx < wl) & ((w <= 0) | (q_pos - k_pos < w))
    # zero V rows no query may read: dead pages hold undefined memory and
    # fully-masked q rows accumulate p=0 * garbage — 0-valued V keeps them
    # inert.  Row t is readable iff some window query sees it, i.e. it is
    # committed or in flight and inside the sliding window of the last query
    row_pos = pj * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, d), 0
    )
    last_q = L + wl - 1
    row_valid = (row_pos <= last_q) & ((w <= 0) | (L - row_pos < w))
    for g in range(kvh):                      # static: one MXU pass per group
        q = q_ref[0, g]                                     # (W * rep, d)
        k = k_ref[0, :, g, :]                               # (page_size, d)
        v = v_ref[0, :, g, :]
        if quantized:
            # fused dequant: one f32 scale per page row for this kv head
            q = q.astype(jnp.float32)
            k = k.astype(jnp.float32) * ks_ref[0, :, g:g + 1]
            v = v.astype(jnp.float32) * vs_ref[0, :, g:g + 1]
        v = jnp.where(row_valid, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                           # (W*rep, page_size)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[g]                                   # (W*rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit p mask: a fully-masked q row (window pad / idle slot) has
        # every score at NEG_INF, so exp(s - m) would be 1 everywhere; masked
        # p keeps l at 0 -> output exactly 0 for those rows
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[g] = m_new
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pj == np_ - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def spec_verify(
    q: jnp.ndarray,            # (b, W, h, d) in-flight windows
    k_pages: jnp.ndarray,      # (num_pages, page_size, kvh, d) global pool
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # (b, max_pages) int32 page ids per request
    lengths: jnp.ndarray,      # (b,) committed tokens BEFORE the window
    window_lens: jnp.ndarray,  # (b,) real window tokens per row (0..W)
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    pages_bound: Optional[int] = None,
    interpret: Optional[bool] = None,
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, page_size, kvh) f32
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    b, W, h, d = q.shape
    page_size, kvh = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    rep = h // kvh
    quantized = k_scales is not None
    scale = scale if scale is not None else d ** -0.5
    # static bound on pages per request INCLUDING the in-flight window (the
    # window may straddle into a freshly-opened page)
    ns = max_pages if pages_bound is None else min(pages_bound, max_pages)
    ns = max(ns, 1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wval = jnp.asarray([0], jnp.int32) if window is None else jnp.asarray(
        [window], jnp.int32
    ).reshape((1,))

    def _page(pj, pt, lens, wlens, bi):
        # clamp dead trailing blocks to the row's last live-or-in-flight
        # page: the index map returns the same block as the previous step,
        # so Pallas skips the DMA instead of streaming an arbitrary page
        total = lens[bi] + wlens[bi]
        last = jnp.maximum((total + page_size - 1) // page_size - 1, 0)
        return pt[bi, jnp.minimum(pj, last)]

    kernel = functools.partial(
        _kernel, softcap=float(softcap), page_size=page_size, rep=rep,
        scale=float(scale), quantized=quantized,
    )
    page_spec = pl.BlockSpec(
        (1, page_size, kvh, d),
        lambda bi, pj, pt, lens, wlens, w: (
            _page(pj, pt, lens, wlens, bi), 0, 0, 0
        ),
    )
    group_spec = pl.BlockSpec(
        (1, kvh, W * rep, d), lambda bi, pj, pt, lens, wlens, w: (bi, 0, 0, 0)
    )
    in_specs = [group_spec, page_spec, page_spec]
    # group-major window: q heads are kv-group-major (head = g*rep + r), so
    # (b, W, kvh, rep, d) -> (b, kvh, W*rep, d) puts each group's window
    # rows in one contiguous (W*rep, d) tile
    qg = q.reshape(b, W, kvh, rep, d).transpose(0, 2, 1, 3, 4)
    operands = [qg.reshape(b, kvh, W * rep, d), k_pages, v_pages]
    if quantized:
        # scale blocks ride the same page-table index map as their pages
        scale_spec = pl.BlockSpec(
            (1, page_size, kvh),
            lambda bi, pj, pt, lens, wlens, w: (
                _page(pj, pt, lens, wlens, bi), 0, 0
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, ns),
        in_specs=in_specs,
        out_specs=group_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, W * rep, 1), jnp.float32),
            pltpu.VMEM((kvh, W * rep, 1), jnp.float32),
            pltpu.VMEM((kvh, W * rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, W * rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="spec_verify",
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(window_lens, jnp.int32),
        wval,
        *operands,
    )
    out = out.reshape(b, kvh, W, rep, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, W, h, d)

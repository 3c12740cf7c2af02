"""Pallas TPU packed varlen prefill attention over a paged KV pool.

One launch runs flash attention for prompt chunks from *many* requests at
once: queries (and the chunks' own K/V) live in a single token-packed
buffer, and each chunk additionally attends its request's already-committed
context pages in the global page pool — no per-request pow2 padding, no
cross-request attention leakage, one compile for a fixed packed-buffer size
regardless of how lengths mix.

Packing contract (shared with ``ref.varlen_prefill`` / ``ops.varlen_prefill``
and the serving engine):

* chunk ``c`` occupies packed rows ``[cu_seqlens[c], cu_seqlens[c+1])``; the
  first ``chunk_lens[c]`` rows are real tokens, the rest pad.  Chunk spans
  are ``block``-aligned (the engine pads each chunk to a page multiple and
  the kernel block equals ``page_size``), so every q block belongs to
  exactly one chunk.
* ``chunk_pos0[c]`` is the absolute position of the chunk's first token
  (page-aligned); the request's committed context is exactly positions
  ``[0, chunk_pos0[c])``, held in the first ``chunk_pos0[c]/page_size``
  entries of ``page_tables[c]``.

A q block's stages are its context pages and its own packed K/V blocks:
stage ``s < ctx_bound`` streams context page ``page_tables[c, s]`` from the
pool; stage ``s >= ctx_bound`` streams the chunk's own packed K/V block
``start_blk[c] + (s - ctx_bound)``.  Only the live (q block, stage) pairs
are iterated: a q block's items are the context stages below its chunk's
committed context and the intra stages up to its causal diagonal, or one
item (which skips the body and writes zeros) for a block with no real
token.  The wrapper counts each block's items on the device and prefetches
where each block's items start, ``nqb`` words whatever the context bound;
the 1-D grid's dynamic bound is the items in all, and the kernel and its
index maps find item ``i``'s q block by a binary search over those starts.
Every stage left out contributed exactly nothing (its ``p`` is masked to 0
and ``alpha`` is 1), so the live steps' arithmetic, in its order, is the
whole computation.  The grid is sequential (the online-softmax state of a
q block lives in VMEM scratch across its items); init runs on a block's
first item and the finish on its last.  Every block carries every head:
the wrapper lays the queries out group-major, ``(kvh, T * rep, d)`` with
row ``t * rep + r``, so a q block is ``(kvh, block * rep, d)``, and K/V
blocks are ``(block, kvh, d)`` (packed chunk) or ``(1, page_size, kvh, d)``
(context page).  A static loop over the kv heads runs one ``(block * rep,
d) x (d, block)`` matmul per group, and each context page streams once per
q block instead of once per query head.  All per-chunk metadata arrives
via scalar prefetch so the BlockSpec index maps dereference only live
pages/blocks, clamped to an already-streamed block where a stage runs past
them (Pallas recognises a revisit: no new DMA).

Quantized pools (``k_scales``/``v_scales`` given): only the CONTEXT page
stages dequantize — the packed chunk K/V (current activations) stay full
precision.  The float32 per-row per-kv-head scale blocks stream through the
same context-page index map as their K/V pages and dequantization is fused
right after the block load.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _block_items(cu_seqlens, chunk_lens, chunk_pos0, *, nqb: int, block: int,
                 ctx_bound: int, xp=jnp):
    """The chunks' first q blocks, and per q block: its owning chunk, its
    count of live context stages, whether it holds a real token, and its
    work items (its live stages, or one for a block with no real token).
    ``xp`` is ``jnp`` on the device or ``np`` on the host."""
    start_blk = cu_seqlens[:-1] // block
    blk = xp.arange(nqb, dtype=start_blk.dtype)
    # q block -> owning chunk: the last chunk whose start is <= the block
    # (trailing buffer pad maps to the last chunk and is masked by lens)
    c = xp.clip(xp.searchsorted(start_blk, blk, side="right") - 1,
                0, len(chunk_lens) - 1).astype(start_blk.dtype)
    local = blk - start_blk[c]
    real = local * block < chunk_lens[c]
    nctx = xp.minimum(-(-chunk_pos0[c] // block), ctx_bound)
    return start_blk, c, nctx, real, xp.where(real, nctx + local + 1, 1)


def work_items(cu_seqlens, chunk_lens, chunk_pos0, *, t_pack: int, block: int,
               pages_bound: int):
    """(live, rect) of one launch, on the host: the work items the kernel
    iterates, and ``nqb * (bound + nqb)``, the whole (q block, stage) grid.
    ``pages_bound`` is the launch's context bound (the table's width at
    most, as the wrapper clamps it)."""
    nqb, ctx_bound = t_pack // block, max(pages_bound, 1)
    *_, counts = _block_items(
        np.asarray(cu_seqlens, np.int64), np.asarray(chunk_lens, np.int64),
        np.asarray(chunk_pos0, np.int64), nqb=nqb, block=block,
        ctx_bound=ctx_bound, xp=np,
    )
    return int(counts.sum()), nqb * (ctx_bound + nqb)


def _item_starts(cu_seqlens, chunk_lens, chunk_pos0, *, nqb: int, block: int,
                 ctx_bound: int):
    """On the device: ``(starts, count, blk_chunk, start_blk)``.
    ``starts[j]`` is q block ``j``'s first work item, padded with int32's
    largest value to a power-of-two length for ``_find``; ``count`` is the
    items in all, the grid's bound."""
    start_blk, blk_chunk, _, _, counts = _block_items(
        cu_seqlens, chunk_lens, chunk_pos0, nqb=nqb, block=block,
        ctx_bound=ctx_bound,
    )
    ends = jnp.cumsum(counts)
    starts = jnp.full((_search_span(nqb),), jnp.iinfo(jnp.int32).max, jnp.int32)
    return starts.at[:nqb].set(ends - counts), ends[-1], blk_chunk, start_blk


def _search_span(nqb: int) -> int:
    return 1 << (nqb - 1).bit_length()


def _find(i, starts, span: int):
    """Item ``i``'s q block: the last ``j`` with ``starts[j] <= i``
    (``starts[0]`` is 0), by a branch-free binary search of log2(span)
    scalar reads."""
    qj = 0
    step = span // 2
    while step:
        qj = jnp.where(starts[qj + step] <= i, qj + step, qj)
        step //= 2
    return qj


def _item(i, starts, blkc, sblk, pos0, lens, *, span: int, block: int,
          ctx_bound: int):
    """Item ``i``'s q block, chunk and stage, its index within its block,
    the block's item count, and whether the block holds a real token.  A
    block with no real token has one item, whose stage reads as 0."""
    qj = _find(i, starts, span)
    c = blkc[qj]
    local = qj - sblk[c]
    real = local * block < lens[c]
    nctx = jnp.minimum(-(-pos0[c] // block), ctx_bound)
    k = i - starts[qj]
    # context stages 0..nctx-1 first, then intra stages from ctx_bound on
    s = jnp.where(real, jnp.where(k < nctx, k, ctx_bound + k - nctx), 0)
    return qj, c, s, k, jnp.where(real, nctx + local + 1, 1), real


def _kernel(
    starts_ref,                # scalar prefetch: (span,) q blocks' first items
    blk_chunk_ref,             # scalar prefetch: (nqb,) chunk id per q block
    start_blk_ref,             # scalar prefetch: (C,) first packed block
    pos0_ref,                  # scalar prefetch: (C,) absolute chunk start
    lens_ref,                  # scalar prefetch: (C,) real tokens per chunk
    pt_ref,                    # scalar prefetch: (C, max_pages) page tables
    w_ref,                     # scalar prefetch: (1,) window (0 = none)
    q_ref,                     # (kvh, block * rep, d) group-major queries
    kc_ref, vc_ref,            # (block, kvh, d) — packed chunk K/V block
    kp_ref, vp_ref,            # (1, block, kvh, d) — one context page
    *rest,                     # [kps_ref, vps_ref (1, block, kvh)], o_ref, scratch
    softcap: float,
    block: int,
    rep: int,                  # query heads per kv head
    ctx_bound: int,
    span: int,                 # length of starts_ref, a power of two
    scale: float,
    quantized: bool,
):
    if quantized:
        kps_ref, vps_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qj, c, s, idx, n_items, real = _item(
        pl.program_id(0), starts_ref, blk_chunk_ref, start_blk_ref, pos0_ref,
        lens_ref, span=span, block=block, ctx_bound=ctx_bound,
    )
    kvh, rows, d = q_ref.shape

    @pl.when(idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(real)
    def _step():
        seq_len = lens_ref[c]
        pos0 = pos0_ref[c]
        # chunk-local offset / absolute position of each q row in this block
        # (row t*rep + r is token t of the block)
        off_q = (qj - start_blk_ref[c]) * block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 0
        ) // rep
        q_pos = pos0 + off_q
        q_valid = off_q < seq_len

        is_ctx = s < ctx_bound
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
        # context stage: page s covers logical positions [s*block, (s+1)*block)
        ctx_pos = s * block + col
        ctx_valid = ctx_pos < pos0
        # intra stage: packed block t of this chunk covers chunk-local offsets
        # [t*block, (t+1)*block) at absolute positions pos0 + those offsets
        t = s - ctx_bound
        off_k = t * block + col
        k_pos_in = pos0 + off_k
        intra_valid = (off_k < seq_len) & (q_pos >= k_pos_in)

        k_pos = jnp.where(is_ctx, ctx_pos, k_pos_in)
        # boolean algebra, not where(): Mosaic cannot select between i1 vectors
        valid = q_valid & ((is_ctx & ctx_valid) | (~is_ctx & intra_valid))
        w = w_ref[0]
        valid &= (w <= 0) | ((q_pos - k_pos) < w)
        # zero V rows that hold no token (a page's tail holds undefined
        # memory): masked p is exactly 0, but 0 * NaN is not
        row = jax.lax.broadcasted_iota(jnp.int32, (block, d), 0)
        row_valid = (is_ctx & (s * block + row < pos0)) | (
            ~is_ctx & (t * block + row < seq_len)
        )

        for g in range(kvh):                  # static: one MXU pass per group
            q = q_ref[g]                                    # (block*rep, d)
            if quantized:
                # fused dequant of the CONTEXT page only (packed chunk K/V
                # are the current activations and stay full precision)
                q = q.astype(jnp.float32)
                kp = kp_ref[0, :, g, :].astype(jnp.float32) * kps_ref[0, :, g:g + 1]
                vp = vp_ref[0, :, g, :].astype(jnp.float32) * vps_ref[0, :, g:g + 1]
                k = jnp.where(is_ctx, kp, kc_ref[:, g, :].astype(jnp.float32))
                v = jnp.where(is_ctx, vp, vc_ref[:, g, :].astype(jnp.float32))
            else:
                k = jnp.where(is_ctx, kp_ref[0, :, g, :], kc_ref[:, g, :])
                v = jnp.where(is_ctx, vp_ref[0, :, g, :], vc_ref[:, g, :])
            v = jnp.where(row_valid, v, jnp.zeros_like(v))
            s_qk = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale                                       # (block*rep, block)
            if softcap > 0:
                s_qk = softcap * jnp.tanh(s_qk / softcap)
            s_qk = jnp.where(valid, s_qk, NEG_INF)
            m_prev = m_ref[g]                               # (block*rep, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s_qk, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # explicit p mask: a fully-masked q row (chunk pad) has every
            # score at NEG_INF, so exp(s - m) would be 1 everywhere and
            # accumulate the OTHER rows' valid V columns; masked p keeps l
            # at 0 -> output 0
            p = jnp.where(valid, jnp.exp(s_qk - m_new), 0.0)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[g] = m_new
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(idx == n_items - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def varlen_prefill(
    q: jnp.ndarray,            # (T, h, d)   packed queries
    k: jnp.ndarray,            # (T, kvh, d) packed chunk K
    v: jnp.ndarray,            # (T, kvh, d)
    k_pages: jnp.ndarray,      # (num_pages, page_size, kvh, d) global pool
    v_pages: jnp.ndarray,
    cu_seqlens: jnp.ndarray,   # (C+1,) int32 packed chunk boundaries
    chunk_lens: jnp.ndarray,   # (C,) int32 real tokens per chunk
    chunk_pos0: jnp.ndarray,   # (C,) int32 absolute chunk starts (page-aligned)
    page_tables: jnp.ndarray,  # (C, max_pages) int32
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    pages_bound: Optional[int] = None,
    interpret: Optional[bool] = None,
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, page_size, kvh) f32
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    T, h, d = q.shape
    page_size, kvh = k_pages.shape[1], k_pages.shape[2]
    C, max_pages = page_tables.shape
    rep = h // kvh
    quantized = k_scales is not None
    block = page_size                  # chunk spans are page multiples
    if T % block:
        raise ValueError(f"packed length {T} not a multiple of page {block}")
    nqb = T // block
    scale = scale if scale is not None else d ** -0.5
    # static bound on context pages per chunk (>=1 so the clamping in the
    # index maps never indexes the table at -1)
    ctx_bound = max_pages if pages_bound is None else min(pages_bound, max_pages)
    ctx_bound = max(ctx_bound, 1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wval = jnp.asarray([0], jnp.int32) if window is None else jnp.asarray(
        [window], jnp.int32
    ).reshape((1,))

    pos0_c = jnp.asarray(chunk_pos0, jnp.int32)
    lens_c = jnp.asarray(chunk_lens, jnp.int32)
    starts, count, blk_chunk, start_blk = _item_starts(
        jnp.asarray(cu_seqlens, jnp.int32), lens_c, pos0_c,
        nqb=nqb, block=block, ctx_bound=ctx_bound,
    )
    span = _search_span(nqb)
    item = functools.partial(_item, span=span, block=block, ctx_bound=ctx_bound)

    def _ctx_page(i, st, blkc, sblk, pos0, lens, pt):
        # intra stages clamp to the chunk's last live page, which the
        # block's last context item streamed, so Pallas sees a revisit (no
        # new DMA); chunks with no context clamp to the table's first entry
        # (the engine points it at the scratch page), as does a block with
        # no real token (stage 0), so a run of them revisits
        _, c, s, *_ = item(i, st, blkc, sblk, pos0, lens)
        last = jnp.maximum(pos0[c] // block - 1, 0)
        return pt[c, jnp.minimum(jnp.minimum(s, ctx_bound - 1), last)]

    def _intra_blk(i, st, blkc, sblk, pos0, lens):
        # context stages clamp to the chunk's first packed block, which the
        # q block's first intra item streams next
        qj, c, s, *_ = item(i, st, blkc, sblk, pos0, lens)
        return sblk[c] + jnp.clip(s - ctx_bound, 0, qj - sblk[c])

    def _q_blk(i, st, blkc, sblk, pos0, lens):
        # a block with no real token reads no query: it maps to the first
        # block, which a run of them revisits
        qj, *_, real = item(i, st, blkc, sblk, pos0, lens)
        return jnp.where(real, qj, 0)

    kernel = functools.partial(
        _kernel, softcap=float(softcap), block=block, rep=rep,
        ctx_bound=ctx_bound, span=span, scale=float(scale),
        quantized=quantized,
    )
    q_spec = pl.BlockSpec(
        (kvh, block * rep, d),
        lambda i, st, blkc, sblk, pos0, lens, pt, w: (
            0, _q_blk(i, st, blkc, sblk, pos0, lens), 0
        ),
    )
    out_spec = pl.BlockSpec(
        (kvh, block * rep, d),
        lambda i, st, blkc, sblk, pos0, lens, pt, w: (0, _find(i, st, span), 0),
    )
    intra_spec = pl.BlockSpec(
        (block, kvh, d),
        lambda i, st, blkc, sblk, pos0, lens, pt, w: (
            _intra_blk(i, st, blkc, sblk, pos0, lens), 0, 0
        ),
    )
    ctx_spec = pl.BlockSpec(
        (1, block, kvh, d),
        lambda i, st, blkc, sblk, pos0, lens, pt, w: (
            _ctx_page(i, st, blkc, sblk, pos0, lens, pt), 0, 0, 0
        ),
    )
    in_specs = [q_spec, intra_spec, intra_spec, ctx_spec, ctx_spec]
    # group-major queries: q heads are kv-group-major (head = g*rep + r), so
    # (T, kvh, rep, d) -> (kvh, T*rep, d) puts each group's block rows in one
    # contiguous (block*rep, d) tile
    qg = q.reshape(T, kvh, rep, d).transpose(1, 0, 2, 3).reshape(kvh, T * rep, d)
    operands = [qg, k, v, k_pages, v_pages]
    if quantized:
        # scale blocks ride the same context-page index map as their pages
        scale_spec = pl.BlockSpec(
            (1, block, kvh),
            lambda i, st, blkc, sblk, pos0, lens, pt, w: (
                _ctx_page(i, st, blkc, sblk, pos0, lens, pt), 0, 0
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(count,),                 # dynamic: the items in all
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, block * rep, 1), jnp.float32),
            pltpu.VMEM((kvh, block * rep, 1), jnp.float32),
            pltpu.VMEM((kvh, block * rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, T * rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="varlen_prefill",
    )(
        starts,
        blk_chunk,
        start_blk,
        pos0_c,
        lens_c,
        jnp.asarray(page_tables, jnp.int32),
        wval,
        *operands,
    )
    return out.reshape(kvh, T, rep, d).transpose(1, 0, 2, 3).reshape(T, h, d)

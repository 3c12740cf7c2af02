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

Grid = (q_blocks, stages) with the stage dimension innermost and
sequential so the online-softmax state lives in VMEM scratch.  Every block
carries every head: the wrapper lays the queries out group-major, ``(kvh,
T * rep, d)`` with row ``t * rep + r``, so a q block is ``(kvh, block *
rep, d)``, and K/V blocks are ``(block, kvh, d)`` (packed chunk) or
``(1, page_size, kvh, d)`` (context page).  A static loop over the kv heads
runs one ``(block * rep, d) x (d, block)`` matmul per group, and each
context page streams once per q block instead of once per query head.
Stage ``s < ctx_bound`` streams context page ``page_tables[c, s]`` from the
pool; stage ``s >= ctx_bound`` streams the chunk's own packed K/V block
``start_blk[c] + (s - ctx_bound)``.  All per-chunk metadata arrives via
scalar prefetch so the BlockSpec index maps dereference only live
pages/blocks — dead stages clamp to the previously streamed block, which
Pallas recognises as a revisit (no new DMA).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(
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
    scale: float,
    quantized: bool,
):
    if quantized:
        kps_ref, vps_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qj = pl.program_id(0)
    s = pl.program_id(1)
    ns = pl.num_programs(1)
    kvh, rows, d = q_ref.shape

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = blk_chunk_ref[qj]
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
    # zero V rows that hold no token (dead blocks hold undefined memory):
    # masked p is exactly 0, but 0 * NaN is not
    row = jax.lax.broadcasted_iota(jnp.int32, (block, d), 0)
    row_valid = (is_ctx & (s * block + row < pos0)) | (
        ~is_ctx & (t * block + row < seq_len)
    )

    for g in range(kvh):                      # static: one MXU pass per group
        q = q_ref[g]                                        # (block*rep, d)
        if quantized:
            # fused dequant of the CONTEXT page only (packed chunk K/V are
            # the current activations and stay full precision)
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
        ) * scale                                           # (block*rep, block)
        if softcap > 0:
            s_qk = softcap * jnp.tanh(s_qk / softcap)
        s_qk = jnp.where(valid, s_qk, NEG_INF)
        m_prev = m_ref[g]                                   # (block*rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s_qk, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit p mask: a fully-masked q row (chunk/buffer pad) has every
        # score at NEG_INF, so exp(s - m) would be 1 everywhere and
        # accumulate the OTHER rows' valid V columns; masked p keeps l at 0
        # -> output 0
        p = jnp.where(valid, jnp.exp(s_qk - m_new), 0.0)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[g] = m_new
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s == ns - 1)
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
    # static bound on context pages per chunk (>=1 so dead-stage clamping in
    # the index maps never indexes the table at -1)
    ctx_bound = max_pages if pages_bound is None else min(pages_bound, max_pages)
    ctx_bound = max(ctx_bound, 1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wval = jnp.asarray([0], jnp.int32) if window is None else jnp.asarray(
        [window], jnp.int32
    ).reshape((1,))

    cu = jnp.asarray(cu_seqlens, jnp.int32)
    start_blk = cu[:-1] // block
    # q block -> owning chunk: the last chunk whose start is <= the block
    # (trailing buffer pad maps to the last chunk and is masked by lens)
    blk_chunk = jnp.clip(
        jnp.searchsorted(start_blk, jnp.arange(nqb, dtype=jnp.int32),
                         side="right").astype(jnp.int32) - 1,
        0, C - 1,
    )

    def _ctx_page(qj, s, blkc, sblk, pos0, lens, pt):
        # clamp dead context stages to the chunk's last live page so Pallas
        # sees a revisit (no new DMA); chunks with no context clamp to the
        # table's first entry (the engine points it at the scratch page)
        c = blkc[qj]
        last = jnp.maximum(pos0[c] // block - 1, 0)
        return pt[c, jnp.minimum(jnp.minimum(s, ctx_bound - 1), last)]

    def _intra_blk(qj, s, blkc, sblk):
        # context stages and post-causal stages clamp to an already-streamed
        # packed block of the same chunk
        c = blkc[qj]
        return sblk[c] + jnp.clip(s - ctx_bound, 0, qj - sblk[c])

    kernel = functools.partial(
        _kernel, softcap=float(softcap), block=block, rep=rep,
        ctx_bound=ctx_bound, scale=float(scale), quantized=quantized,
    )
    group_spec = pl.BlockSpec(
        (kvh, block * rep, d),
        lambda qj, s, blkc, sblk, pos0, lens, pt, w: (0, qj, 0),
    )
    intra_spec = pl.BlockSpec(
        (block, kvh, d),
        lambda qj, s, blkc, sblk, pos0, lens, pt, w: (
            _intra_blk(qj, s, blkc, sblk), 0, 0
        ),
    )
    ctx_spec = pl.BlockSpec(
        (1, block, kvh, d),
        lambda qj, s, blkc, sblk, pos0, lens, pt, w: (
            _ctx_page(qj, s, blkc, sblk, pos0, lens, pt), 0, 0, 0
        ),
    )
    in_specs = [group_spec, intra_spec, intra_spec, ctx_spec, ctx_spec]
    # group-major queries: q heads are kv-group-major (head = g*rep + r), so
    # (T, kvh, rep, d) -> (kvh, T*rep, d) puts each group's block rows in one
    # contiguous (block*rep, d) tile
    qg = q.reshape(T, kvh, rep, d).transpose(1, 0, 2, 3).reshape(kvh, T * rep, d)
    operands = [qg, k, v, k_pages, v_pages]
    if quantized:
        # scale blocks ride the same context-page index map as their pages
        scale_spec = pl.BlockSpec(
            (1, block, kvh),
            lambda qj, s, blkc, sblk, pos0, lens, pt, w: (
                _ctx_page(qj, s, blkc, sblk, pos0, lens, pt), 0, 0
            ),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nqb, ctx_bound + nqb),
        in_specs=in_specs,
        out_specs=group_spec,
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
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="varlen_prefill",
    )(
        blk_chunk,
        start_blk,
        jnp.asarray(chunk_pos0, jnp.int32),
        jnp.asarray(chunk_lens, jnp.int32),
        jnp.asarray(page_tables, jnp.int32),
        wval,
        *operands,
    )
    return out.reshape(kvh, T, rep, d).transpose(1, 0, 2, 3).reshape(T, h, d)

"""Pallas TPU paged decode attention: one new token vs a paged KV cache.

The KV cache is a global pool of ``page_size``-token pages shared by every
request; each request owns a list of pages recorded in a per-request page
table.  Grid = (batch, kv_pages) with the page dimension innermost and
sequential so the flash-decode online-softmax state lives in VMEM scratch.
Each program streams one page with EVERY kv head in it, ``(page_size, kvh,
d)``, and scores it against every query head of the request: the query
arrives grouped as ``(kvh, rep, d)`` and a static loop over the kv heads
runs one ``(rep, d) x (d, page_size)`` matmul per group, so each page is
read once per request, not once per query head.  Both block shapes end in
whole array dims (``(kvh, d)`` and ``(rep, d)``), which is what the TPU
compiler requires of a block whose last two dims are not multiples of
``(8, 128)``.

The page table and per-request ``lengths`` arrive as scalar-prefetch
operands: the k/v BlockSpec index maps dereference the page table so only a
request's *live* pages stream HBM->VMEM — pages beyond
``ceil(len/page_size)`` are clamped to the request's last live page, which
Pallas recognises as a revisit (no new DMA).  The caller additionally bounds
the grid with ``pages_bound`` (host-known max live pages, bucketed), so the
kernel never iterates the padded page-table width.

Quantized pools (``k_scales``/``v_scales`` given): pages hold int8/fp8 K/V
and a parallel ``(num_pages, page_size, kvh)`` float32 scale pool carries
one scale per row per kv head.  The scale blocks stream through the same
page-table index map as their K/V pages and dequantization (``q * scale``)
is fused right after the block load — quantized K/V never materializes in
full precision outside the kernel.
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
    lens_ref,                  # scalar prefetch: (b,) int32 valid lengths
    w_ref,                     # scalar prefetch: (1,) int32 window (0 = none)
    q_ref,                     # (1, kvh, rep, d)
    k_ref, v_ref,              # (1, page_size, kvh, d) — one page, every kv head
    *rest,                     # [ks_ref, vs_ref (1, page_size, kvh)], o_ref, scratch
    softcap: float,
    page_size: int,
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
    _, kvh, rep, d = q_ref.shape

    @pl.when(pj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[bi]
    w = w_ref[0]
    # positions are *logical*: page pj of this request covers
    # [pj*page_size, (pj+1)*page_size) regardless of which physical page
    # the index map streamed in
    k_pos = pj * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rep, page_size), 1
    )
    # (w <= 0) | ...: Mosaic cannot select between boolean vectors
    valid = (k_pos < length) & ((w <= 0) | (k_pos >= length - w))
    row_pos = pj * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, d), 0
    )
    row_valid = (row_pos < length) & ((w <= 0) | (row_pos >= length - w))
    for g in range(kvh):                      # static: one MXU pass per group
        q = q_ref[0, g]                                     # (rep, d)
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
        ) * scale                                           # (rep, page_size)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[g]                                   # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
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


def paged_attention(
    q: jnp.ndarray,            # (b, 1, h, d)
    k_pages: jnp.ndarray,      # (num_pages, page_size, kvh, d) global pool
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # (b, max_pages) int32 page ids per request
    lengths: jnp.ndarray,      # (b,) int32 live tokens per request
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    pages_bound: Optional[int] = None,
    interpret: Optional[bool] = None,
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, page_size, kvh) f32
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    b, _, h, d = q.shape
    page_size, kvh = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    rep = h // kvh
    quantized = k_scales is not None
    scale = scale if scale is not None else d ** -0.5
    ns = max_pages if pages_bound is None else min(pages_bound, max_pages)
    ns = max(ns, 1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wval = jnp.asarray([0], jnp.int32) if window is None else jnp.asarray(
        [window], jnp.int32
    ).reshape((1,))

    def _page(pj, pt, lens, bi):
        # clamp dead trailing blocks to the request's last live page: the
        # index map returns the same block as the previous step, so Pallas
        # skips the DMA instead of streaming an arbitrary page
        last = jnp.maximum((lens[bi] + page_size - 1) // page_size - 1, 0)
        return pt[bi, jnp.minimum(pj, last)]

    kernel = functools.partial(
        _kernel, softcap=float(softcap), page_size=page_size,
        scale=float(scale), quantized=quantized,
    )
    page_spec = pl.BlockSpec(
        (1, page_size, kvh, d),
        lambda bi, pj, pt, lens, w: (_page(pj, pt, lens, bi), 0, 0, 0),
    )
    group_spec = pl.BlockSpec(
        (1, kvh, rep, d), lambda bi, pj, pt, lens, w: (bi, 0, 0, 0)
    )
    in_specs = [group_spec, page_spec, page_spec]
    # q heads are kv-group-major (head = g*rep + r): grouping is a reshape
    operands = [q.reshape(b, kvh, rep, d), k_pages, v_pages]
    if quantized:
        # scale blocks ride the same page-table index map as their pages
        scale_spec = pl.BlockSpec(
            (1, page_size, kvh),
            lambda bi, pj, pt, lens, w: (_page(pj, pt, lens, bi), 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, ns),
        in_specs=in_specs,
        out_specs=group_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_attention",
    )(
        jnp.asarray(page_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        wval,
        *operands,
    )
    return out.reshape(b, 1, h, d)

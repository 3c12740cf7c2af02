"""Kernel dispatch layer.

Models call these ops with a ``backend`` string:

* ``ref``    — the naive oracles in :mod:`.ref` (correct, memory-hungry).
* ``flash``  — chunked/online pure-JAX implementations (memory-efficient,
               lowers on any backend; the dry-run default — mirrors the
               Pallas kernels' blocking so the compiled memory behaviour is
               representative of the TPU target).
* ``pallas`` — the Pallas TPU kernels (``interpret=True`` on CPU for tests).

``backend=None`` means :func:`default_backend`: the Pallas kernels on a
TPU, ``flash`` elsewhere.  ``flash`` and ``ref`` run on a TPU only when a
caller names them (as oracles).

All ops are shape/dtype-polymorphic and jit-friendly.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref

NEG_INF = ref.NEG_INF


def default_backend() -> str:
    """The kernels this platform runs: Pallas on a TPU; elsewhere the
    chunked pure-JAX path (interpret-mode Pallas is a test oracle there,
    not a runtime).  Asked at call time, so importing never touches the
    device."""
    return "pallas" if jax.default_backend() == "tpu" else "flash"


def _soft_cap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    return cap * jnp.tanh(x / cap) if cap > 0 else x


# ---------------------------------------------------------------------------
# Tensor-parallel head-split wrapping of the serving kernels
# ---------------------------------------------------------------------------
def _heads_shard_info(heads: int, kv_heads: int):
    """(mesh, axis) when the active sharding rules head-split the serving
    kernels, else None (no rules, or the replication fallback)."""
    # lazy: sharding.specs pulls in the model param helpers; importing it at
    # kernel-import time would cycle through models/__init__
    from ..sharding.specs import heads_shard_axis

    return heads_shard_axis(heads, kv_heads)


def _shard_heads(body, mesh, axis, in_specs, out_specs):
    """shard_map a serving-kernel body with heads-split blocks.

    Every rank runs the identical attention program on its own head slice —
    attention never mixes heads, so per-shard outputs are bit-exact slices
    of the unsharded result and no collective is needed until the o-proj
    contraction outside the kernel.  ``check_vma=False``: the replicated
    page tables/lengths feed gathers whose replication the checker can't
    prove."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# Attention (training / prefill)
# ---------------------------------------------------------------------------
def _kv_blocks(t: jnp.ndarray, block_k: int):
    """(b, sk, kvh, d) -> (nblk, b, block_k, kvh, d) with zero padding."""
    b, sk, kvh, d = t.shape
    nblk = (sk + block_k - 1) // block_k
    pad = nblk * block_k - sk
    if pad:
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return jnp.moveaxis(t.reshape(b, nblk, block_k, kvh, d), 1, 0)


def _block_mask(j, block_k, sk, q_pos, causal, window):
    k_pos = j * block_k + jnp.arange(block_k)
    mask = k_pos[None, :] < sk
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return mask                                             # (sq, block_k)


def _flash_fwd_core(q, k, v, causal, window, softcap, q_offset, block_k, scale):
    """Returns (out (b,sq,h,d), m, l with shape (b,kvh,rep,sq) fp32)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    kb = _kv_blocks(k, block_k)
    vb = _kv_blocks(v, block_k)
    nblk = kb.shape[0]
    qr = q.reshape(b, sq, kvh, rep, d)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inputs):
        m, l, acc = carry
        j, k_j, v_j = inputs
        s = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qr, k_j, preferred_element_type=jnp.float32
        ) * scale
        s = _soft_cap(s, softcap)
        mask = _block_mask(j, block_k, sk, q_pos, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_j = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_j)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bgrqk,bkgd->bqgrd", p.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * jnp.moveaxis(alpha, 3, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, kvh, rep, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (jnp.arange(nblk), kb, vb))
    l = jnp.maximum(l, 1e-37)
    out = (acc / jnp.moveaxis(l, 3, 1)[..., None]).reshape(b, sq, h, d)
    return out.astype(q.dtype), m, l


def _flash_bwd_core(
    q, k, v, o, m, l, do, causal, window, softcap, q_offset, block_k, scale
):
    """True flash backward: recompute P per KV block (no saved scores)."""
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    kb = _kv_blocks(k, block_k)
    vb = _kv_blocks(v, block_k)
    nblk = kb.shape[0]
    qr = q.reshape(b, sq, kvh, rep, d)
    dor = do.reshape(b, sq, kvh, rep, d)
    q_pos = q_offset + jnp.arange(sq)
    # D = rowsum(dO * O): (b, kvh, rep, sq)
    D = jnp.moveaxis(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        .reshape(b, sq, kvh, rep),
        1, 3,
    )

    def step(dq, inputs):
        j, k_j, v_j = inputs
        s = jnp.einsum(
            "bqgrd,bkgd->bgrqk", qr, k_j, preferred_element_type=jnp.float32
        ) * scale
        sc = _soft_cap(s, softcap)
        mask = _block_mask(j, block_k, sk, q_pos, causal, window)
        sc_masked = jnp.where(mask[None, None, None], sc, NEG_INF)
        p = jnp.exp(sc_masked - m[..., None]) / l[..., None]    # (b,g,r,sq,bk)
        dv_j = jnp.einsum(
            "bgrqk,bqgrd->bkgd", p.astype(do.dtype), dor,
            preferred_element_type=jnp.float32,
        )
        dp = jnp.einsum(
            "bqgrd,bkgd->bgrqk", dor, v_j, preferred_element_type=jnp.float32
        )
        ds = p * (dp - D[..., None])
        if softcap > 0:
            ds = ds * (1.0 - jnp.square(sc / softcap))
        ds = jnp.where(mask[None, None, None], ds, 0.0) * scale
        dsl = ds.astype(q.dtype)
        dq = dq + jnp.einsum(
            "bgrqk,bkgd->bqgrd", dsl, k_j, preferred_element_type=jnp.float32
        )
        dk_j = jnp.einsum(
            "bgrqk,bqgrd->bkgd", dsl, qr.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, sq, kvh, rep, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, (jnp.arange(nblk), kb, vb))
    dq = dq.reshape(b, sq, h, d).astype(q.dtype)

    def unblock(t):  # (nblk, b, block_k, kvh, d) -> (b, sk, kvh, d)
        t = jnp.moveaxis(t, 0, 1).reshape(b, nblk * block_k, kvh, d)
        return t[:, :sk]

    dk = unblock(dks).astype(k.dtype)
    dv = unblock(dvs).astype(v.dtype)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal, windowed, softcap, q_offset, block_k, scale_key):
    """custom_vjp instance per static-option set (window passed as operand)."""

    @jax.custom_vjp
    def fa(q, k, v, window):
        out, _, _ = _flash_fwd_core(
            q, k, v, causal, window if windowed else None, softcap,
            q_offset, block_k, scale_key,
        )
        return out

    def fwd(q, k, v, window):
        out, m, l = _flash_fwd_core(
            q, k, v, causal, window if windowed else None, softcap,
            q_offset, block_k, scale_key,
        )
        return out, (q, k, v, window, out, m, l)

    def bwd(res, do):
        q, k, v, window, out, m, l = res
        dq, dk, dv = _flash_bwd_core(
            q, k, v, out, m, l, do, causal, window if windowed else None,
            softcap, q_offset, block_k, scale_key,
        )
        return dq, dk, dv, None

    fa.defvjp(fwd, bwd)
    return fa


def flash_attention_jnp(
    q: jnp.ndarray,          # (b, sq, h, d)
    k: jnp.ndarray,          # (b, sk, kvh, d)
    v: jnp.ndarray,          # (b, sk, kvh, d)
    *,
    causal: bool = True,
    window=None,
    softcap: float = 0.0,
    q_offset: int = 0,
    block_k: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Online-softmax attention, scanned over KV blocks, O(sq) memory, with a
    true flash ``custom_vjp`` (backward recomputes scores blockwise — nothing
    quadratic is ever saved)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    block_k = min(block_k, sk)
    windowed = window is not None
    fa = _flash_vjp(causal, windowed, float(softcap), int(q_offset), int(block_k), float(scale))
    wval = jnp.asarray(window, jnp.int32) if windowed else jnp.int32(0)
    return fa(q, k, v, wval)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,
    softcap: float = 0.0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    block_k: int = 512,
) -> jnp.ndarray:
    backend = backend or default_backend()
    if backend == "ref":
        return ref.attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, scale=scale,
        )
    if backend == "flash":
        return flash_attention_jnp(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, block_k=block_k, scale=scale,
        )
    if backend == "pallas":
        from . import flash_attention as fa  # lazy: pallas import cost

        return fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, scale=scale,
        )
    raise ValueError(f"unknown attention backend {backend!r}")


# ---------------------------------------------------------------------------
# Decode attention (single new token vs KV cache)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    kv_bound: Optional[int] = None,
) -> jnp.ndarray:
    """``kv_bound`` is a static host-known upper bound on ``lengths``: decode
    only reads the first ``kv_bound`` cache slots instead of streaming all
    ``S`` padded blocks (serving buckets it to a power of two so short
    contexts stop paying the full-cache bandwidth tax).  Invalid for ring
    caches, whose live tokens wrap the whole buffer."""
    backend = backend or default_backend()
    if backend == "pallas":
        from . import decode_attention as da

        # the kernel bounds its own kv grid: the cache operand stays whole
        # (no slice copy), blocks past the bound are simply never streamed
        return da.decode_attention(
            q, k_cache, v_cache, lengths, softcap=softcap, window=window,
            scale=scale, kv_bound=kv_bound,
        )
    if kv_bound is not None and kv_bound < k_cache.shape[1]:
        k_cache = k_cache[:, :kv_bound]
        v_cache = v_cache[:, :kv_bound]
    # ref and flash share the same (already memory-light) computation
    return ref.decode_attention(
        q, k_cache, v_cache, lengths, softcap=softcap, window=window, scale=scale
    )


# ---------------------------------------------------------------------------
# Packed varlen prefill (many prompt chunks, one launch, paged context)
# ---------------------------------------------------------------------------
def varlen_prefill_jnp(
    q: jnp.ndarray,            # (T, h, d)   packed queries
    k: jnp.ndarray,            # (T, kvh, d) packed chunk K
    v: jnp.ndarray,            # (T, kvh, d)
    k_pages: jnp.ndarray,      # (num_pages, page_size, kvh, d)
    v_pages: jnp.ndarray,
    cu_seqlens: jnp.ndarray,   # (C+1,) int32
    chunk_lens: jnp.ndarray,   # (C,) int32
    chunk_pos0: jnp.ndarray,   # (C,) int32 (page-aligned)
    page_tables: jnp.ndarray,  # (C, max_pages) int32
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    pages_bound: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, page_size, kvh) f32
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Masked one-shot packed prefill (jit-friendly, any backend).

    Scores are the concatenation of a per-token gathered context block
    (``ctx_bound`` pages of the owning chunk's request) and the packed
    buffer itself, masked so a token sees exactly its request's committed
    positions plus the causal prefix of its own chunk.  Rows outside any
    chunk's real tokens come back zero (a manual safe softmax — not
    ``jax.nn.softmax``, which would go uniform on fully-masked rows).  With
    a quantized pool (``k_scales``/``v_scales`` given) only the gathered
    context dequantizes — the packed chunk K/V stay full precision.
    """
    T, h, d = q.shape
    page_size, kvh = k_pages.shape[1], k_pages.shape[2]
    C, max_pages = page_tables.shape
    rep = h // kvh
    scale = scale if scale is not None else d ** -0.5
    ctx_pages = max_pages if pages_bound is None else min(pages_bound, max_pages)
    ctx_pages = max(ctx_pages, 1)
    Lc = ctx_pages * page_size

    cu = jnp.asarray(cu_seqlens, jnp.int32)
    lens = jnp.asarray(chunk_lens, jnp.int32)
    pos0 = jnp.asarray(chunk_pos0, jnp.int32)
    tok = jnp.arange(T, dtype=jnp.int32)
    # token -> owning chunk (trailing buffer pad maps to the last chunk and
    # is masked out by its real length)
    tc = jnp.clip(
        jnp.searchsorted(cu[:-1], tok, side="right").astype(jnp.int32) - 1,
        0, C - 1,
    )
    off = tok - cu[tc]                       # chunk-local offset
    q_valid = off < lens[tc]
    q_pos = pos0[tc] + off                   # absolute positions

    qg = q.reshape(T, kvh, rep, d)
    # context score/value gathers: when chunk spans are page-aligned (the
    # packed layout contract, enforced by the Pallas kernel) the gather runs
    # per page-sized BLOCK — a ``page_size``× smaller index set than per
    # token.  A block straddling two chunks would gather the wrong request's
    # pages, so the fast path additionally requires page-aligned
    # ``cu_seqlens``: checked when the boundaries are concrete (free-form
    # test inputs fall back to the exact per-token gather); under jit the
    # boundaries are traced and the engine's packing contract guarantees
    # alignment.
    blocked = T % page_size == 0
    if blocked:
        try:
            import numpy as _np

            blocked = bool((_np.asarray(cu_seqlens) % page_size == 0).all())
        except Exception:  # traced under jit: trust the packing contract
            pass
    if blocked:
        nqb = T // page_size
        blk_chunk = jnp.clip(
            jnp.searchsorted(
                cu[:-1] // page_size, jnp.arange(nqb, dtype=jnp.int32),
                side="right",
            ).astype(jnp.int32) - 1,
            0, C - 1,
        )
        blk_tables = page_tables[blk_chunk][:, :ctx_pages]
        kctx = k_pages[blk_tables].reshape(nqb, Lc, kvh, d)
        vctx = v_pages[blk_tables].reshape(nqb, Lc, kvh, d)
        if k_scales is not None:
            ksc = k_scales[blk_tables].reshape(nqb, Lc, kvh)
            vsc = v_scales[blk_tables].reshape(nqb, Lc, kvh)
            kctx = kctx.astype(jnp.float32) * ksc[..., None]
            vctx = vctx.astype(jnp.float32) * vsc[..., None]
        qb = qg.reshape(nqb, page_size, kvh, rep, d)
        s_ctx = (
            jnp.einsum(
                "nbgrd,nlgd->nbgrl", qb, kctx,
                preferred_element_type=jnp.float32,
            ) * scale
        ).reshape(T, kvh, rep, Lc)
    else:
        kctx_c = k_pages[page_tables[:, :ctx_pages]].reshape(C, Lc, kvh, d)
        kctx = kctx_c[tc]
        vctx = v_pages[page_tables[:, :ctx_pages]].reshape(C, Lc, kvh, d)[tc]
        if k_scales is not None:
            ksc = k_scales[page_tables[:, :ctx_pages]].reshape(C, Lc, kvh)[tc]
            vsc = v_scales[page_tables[:, :ctx_pages]].reshape(C, Lc, kvh)[tc]
            kctx = kctx.astype(jnp.float32) * ksc[..., None]
            vctx = vctx.astype(jnp.float32) * vsc[..., None]
        s_ctx = jnp.einsum(
            "tgrd,tlgd->tgrl", qg, kctx, preferred_element_type=jnp.float32
        ) * scale                            # (T, kvh, rep, Lc)
    s_in = jnp.einsum(
        "tgrd,ugd->tgru", qg, k, preferred_element_type=jnp.float32
    ) * scale                                # (T, kvh, rep, T)
    s_all = _soft_cap(jnp.concatenate([s_ctx, s_in], axis=-1), softcap)

    ctx_pos = jnp.arange(Lc, dtype=jnp.int32)
    m_ctx = q_valid[:, None] & (ctx_pos[None, :] < pos0[tc][:, None])
    if window is not None:
        m_ctx &= (q_pos[:, None] - ctx_pos[None, :]) < window
    m_in = (
        q_valid[:, None]
        & q_valid[None, :]                   # keys must be real tokens too
        & (tc[:, None] == tc[None, :])       # no cross-request leakage
        & (q_pos[:, None] >= q_pos[None, :])
    )
    if window is not None:
        m_in &= (q_pos[:, None] - q_pos[None, :]) < window
    mask = jnp.concatenate(
        [m_ctx[:, None, None, :], m_in[:, None, None, :]], axis=-1
    )                                         # (T, 1, 1, Lc+T)
    s_all = jnp.where(mask, s_all, NEG_INF)
    m = jnp.max(s_all, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s_all - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-37)
    p = p / l
    p_ctx = p[..., :Lc].astype(vctx.dtype)
    if blocked:
        out_ctx = jnp.einsum(
            "nbgrl,nlgd->nbgrd",
            p_ctx.reshape(nqb, page_size, kvh, rep, Lc), vctx,
            preferred_element_type=jnp.float32,
        ).reshape(T, kvh, rep, d)
    else:
        out_ctx = jnp.einsum(
            "tgrl,tlgd->tgrd", p_ctx, vctx,
            preferred_element_type=jnp.float32,
        )
    out = out_ctx + jnp.einsum(
        "tgru,ugd->tgrd", p[..., Lc:].astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(T, h, d).astype(q.dtype)


def varlen_prefill(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    cu_seqlens: jnp.ndarray,
    chunk_lens: jnp.ndarray,
    chunk_pos0: jnp.ndarray,
    page_tables: jnp.ndarray,
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    pages_bound: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Packed ragged-prefill attention: chunks from many requests share one
    token-packed buffer; each chunk attends its request's committed pages
    plus the causal prefix of its own tokens.  ``pages_bound`` statically
    bounds context pages per chunk (host-known, bucketed)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    quantized = k_scales is not None
    backend = backend or default_backend()

    def body(q, k, v, k_pages, v_pages, cu_seqlens, chunk_lens, chunk_pos0,
             page_tables, *scales):
        sc = dict(zip(("k_scales", "v_scales"), scales))
        if backend == "pallas":
            from . import varlen_prefill as vp  # lazy: pallas import cost

            return vp.varlen_prefill(
                q, k, v, k_pages, v_pages, cu_seqlens, chunk_lens,
                chunk_pos0, page_tables, softcap=softcap, window=window,
                scale=scale, pages_bound=pages_bound, **sc,
            )
        # ref and flash share the masked one-shot computation (jit-friendly;
        # ref.varlen_prefill is the host-loop oracle used by tests)
        return varlen_prefill_jnp(
            q, k, v, k_pages, v_pages, cu_seqlens, chunk_lens, chunk_pos0,
            page_tables, softcap=softcap, window=window, scale=scale,
            pages_bound=pages_bound, **sc,
        )

    extra = (k_scales, v_scales) if quantized else ()
    tp = _heads_shard_info(q.shape[1], k_pages.shape[2])
    if tp is None:
        return body(
            q, k, v, k_pages, v_pages, cu_seqlens, chunk_lens, chunk_pos0,
            page_tables, *extra,
        )
    mesh, ax = tp
    P = jax.sharding.PartitionSpec
    tok = P(None, ax, None)                                 # (T, heads, d)
    pool = P(None, None, ax, None)
    in_specs = (tok, tok, tok, pool, pool, P(None), P(None), P(None),
                P(None, None))
    if quantized:
        # scale pools shard on the kv-head axis with their pages
        in_specs += (P(None, None, ax), P(None, None, ax))
    return _shard_heads(
        body, mesh, ax,
        in_specs=in_specs,
        out_specs=tok,
    )(q, k, v, k_pages, v_pages, cu_seqlens, chunk_lens, chunk_pos0,
      page_tables, *extra)


# ---------------------------------------------------------------------------
# Paged decode attention (single new token vs a paged KV pool)
# ---------------------------------------------------------------------------
def paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    pages_bound: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Decode attention over a paged KV cache (global page pool + per-request
    page table).  ``pages_bound`` statically bounds the live pages per
    request (host-known, bucketed), so neither path iterates the padded
    page-table width."""
    if pages_bound is not None and pages_bound < page_table.shape[1]:
        page_table = page_table[:, :pages_bound]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    quantized = k_scales is not None
    backend = backend or default_backend()

    def body(q, k_pages, v_pages, page_table, lengths, *scales):
        sc = dict(zip(("k_scales", "v_scales"), scales))
        if backend == "pallas":
            from . import paged_attention as pa

            return pa.paged_attention(
                q, k_pages, v_pages, page_table, lengths,
                softcap=softcap, window=window, scale=scale, **sc,
            )
        # ref and flash share the gather-based computation
        return ref.paged_attention(
            q, k_pages, v_pages, page_table, lengths,
            softcap=softcap, window=window, scale=scale, **sc,
        )

    extra = (k_scales, v_scales) if quantized else ()
    tp = _heads_shard_info(q.shape[2], k_pages.shape[2])
    if tp is None:
        return body(q, k_pages, v_pages, page_table, lengths, *extra)
    mesh, ax = tp
    P = jax.sharding.PartitionSpec
    hsplit = P(None, None, ax, None)
    in_specs = (hsplit, hsplit, hsplit, P(None, None), P(None))
    if quantized:
        # scale pools shard on the kv-head axis with their pages
        in_specs += (P(None, None, ax), P(None, None, ax))
    return _shard_heads(
        body, mesh, ax,
        in_specs=in_specs,
        out_specs=hsplit,
    )(q, k_pages, v_pages, page_table, lengths, *extra)


# ---------------------------------------------------------------------------
# Page copy (copy-on-write sharing in the paged KV pool)
# ---------------------------------------------------------------------------
def copy_pages(
    k_pages: jnp.ndarray,      # (L, num_pages, page_size, kvh, d)
    v_pages: jnp.ndarray,
    src: jnp.ndarray,          # (n,) int32 physical source pages
    dst: jnp.ndarray,          # (n,) int32 physical destination pages
    k_scales: Optional[jnp.ndarray] = None,  # (L, num_pages, page_size, kvh)
    v_scales: Optional[jnp.ndarray] = None,
):
    """Device-side physical page copy across every layer of the paged KV
    pool: the copy-on-write primitive behind automatic prefix caching.

    When a request is about to append a token into a page that other
    holders (the prefix cache / other requests) still reference, the engine
    first duplicates that page into a private one and remaps the request's
    page table — committed cache content is never mutated, so greedy tokens
    stay bit-identical to a cache-off run.  A gather + scatter on the page
    axis (jit-friendly, donation-safe: callers donate the pools so XLA
    copies in place).  With a quantized pool the scale rows move with their
    pages (4-tuple return); otherwise the 2-tuple return is unchanged."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = (
        k_pages.at[:, dst].set(k_pages[:, src]),
        v_pages.at[:, dst].set(v_pages[:, src]),
    )
    if k_scales is None:
        return out
    return out + (
        k_scales.at[:, dst].set(k_scales[:, src]),
        v_scales.at[:, dst].set(v_scales[:, src]),
    )


# ---------------------------------------------------------------------------
# Page export / import (live KV migration between page pools)
# ---------------------------------------------------------------------------
def export_pages(
    k_pages: jnp.ndarray,      # (L, num_pages, page_size, kvh, d)
    v_pages: jnp.ndarray,
    idx: jnp.ndarray,          # (n,) int32 physical pages to export
    k_scales: Optional[jnp.ndarray] = None,  # (L, num_pages, page_size, kvh)
    v_scales: Optional[jnp.ndarray] = None,
):
    """Gather a request's live pages out of the pool into a CONTIGUOUS
    snapshot ``(L, n, page_size, kvh, d)`` — the transferable half of live
    KV migration.  Duplicate indices are legal (callers pow2-pad ``idx``
    with repeats to bound jit variants; the padded rows are sliced off on
    the host).  With a quantized pool the per-page scale rows travel with
    their pages (4-tuple return), so the snapshot is exact stored bytes —
    no dequantize/requantize round trip on the migration path."""
    idx = jnp.asarray(idx, jnp.int32)
    out = (k_pages[:, idx], v_pages[:, idx])
    if k_scales is None:
        return out
    return out + (k_scales[:, idx], v_scales[:, idx])


def import_pages(
    k_pages: jnp.ndarray,      # (L, num_pages, page_size, kvh, d)
    v_pages: jnp.ndarray,
    dst: jnp.ndarray,          # (n,) int32 freshly allocated destination pages
    k_snap: jnp.ndarray,       # (L, n, page_size, kvh, d) exported snapshot
    v_snap: jnp.ndarray,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    k_scale_snap: Optional[jnp.ndarray] = None,  # (L, n, page_size, kvh)
    v_scale_snap: Optional[jnp.ndarray] = None,
):
    """Scatter an :func:`export_pages` snapshot into a destination pool's
    freshly allocated pages (donation-safe on the pools, like
    :func:`copy_pages`).  Duplicate ``dst`` indices are legal when the
    matching snapshot rows are identical (the pow2-padding contract:
    callers repeat the LAST real page in both ``dst`` and the snapshot, so
    the duplicate write is idempotent)."""
    dst = jnp.asarray(dst, jnp.int32)
    out = (
        k_pages.at[:, dst].set(k_snap),
        v_pages.at[:, dst].set(v_snap),
    )
    if k_scales is None:
        return out
    return out + (
        k_scales.at[:, dst].set(k_scale_snap),
        v_scales.at[:, dst].set(v_scale_snap),
    )


# ---------------------------------------------------------------------------
# Speculative-decoding verification (k+1-token windows vs a paged KV pool)
# ---------------------------------------------------------------------------
def spec_verify_jnp(
    q: jnp.ndarray,            # (b, W, h, d) in-flight windows
    k_pages: jnp.ndarray,      # (num_pages, page_size, kvh, d)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # (b, max_pages) int32
    lengths: jnp.ndarray,      # (b,) committed tokens BEFORE the window
    window_lens: jnp.ndarray,  # (b,) real window tokens per row (0..W)
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    k_scales: Optional[jnp.ndarray] = None,  # (num_pages, page_size, kvh) f32
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Masked one-shot verification (jit-friendly, any backend).

    Gathers each row's pages back into a contiguous cache (the caller slices
    ``page_table`` to ``pages_bound`` first) and scores all ``W`` window
    positions at once: query ``w`` at absolute position ``lengths[b] + w``
    attends every position ``<= lengths[b] + w`` — the window's own K/V are
    already in the pages, so per-query causal masking on absolute positions
    is the whole story.  Rows past ``window_lens[b]`` come back exactly zero
    (manual safe softmax, not ``jax.nn.softmax``, which would go uniform on
    fully-masked rows).
    """
    b, W, h, d = q.shape
    page_size, kvh = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    rep = h // kvh
    scale = scale if scale is not None else d ** -0.5
    Lk = max_pages * page_size
    k = k_pages[page_table].reshape(b, Lk, kvh, d)
    v = v_pages[page_table].reshape(b, Lk, kvh, d)
    if k_scales is not None:
        ks = k_scales[page_table].reshape(b, Lk, kvh)
        vs = v_scales[page_table].reshape(b, Lk, kvh)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    qg = q.reshape(b, W, kvh, rep, d)
    s = jnp.einsum(
        "bwgrd,bkgd->bgrwk", qg, k, preferred_element_type=jnp.float32
    ) * scale                                  # (b, kvh, rep, W, Lk)
    s = _soft_cap(s, softcap)
    lens = jnp.asarray(lengths, jnp.int32)
    wlens = jnp.asarray(window_lens, jnp.int32)
    k_pos = jnp.arange(Lk, dtype=jnp.int32)[None, None, :]
    q_pos = lens[:, None, None] + jnp.arange(W, dtype=jnp.int32)[None, :, None]
    valid = (k_pos <= q_pos) & (
        jnp.arange(W, dtype=jnp.int32)[None, :, None] < wlens[:, None, None]
    )
    if window is not None:
        valid &= (q_pos - k_pos) < window
    mask = valid[:, None, None]                # (b, 1, 1, W, Lk)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-37)
    p = p / l
    out = jnp.einsum(
        "bgrwk,bkgd->bwgrd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, W, h, d).astype(q.dtype)


def spec_verify(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    window_lens: jnp.ndarray,
    *,
    softcap: float = 0.0,
    window=None,
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    pages_bound: Optional[int] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Speculative multi-token verification over a paged KV cache: one
    ``(b, W)`` launch scores each slot's ``[next_token, draft_1..draft_k]``
    window against its committed pages plus the window's own causal prefix
    (the window K/V are scattered into the pages first).  ``pages_bound``
    statically bounds live+in-flight pages per request (host-known,
    bucketed) so neither path iterates the padded page-table width."""
    if pages_bound is not None and pages_bound < page_table.shape[1]:
        page_table = page_table[:, :pages_bound]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    quantized = k_scales is not None
    backend = backend or default_backend()

    def body(q, k_pages, v_pages, page_table, lengths, window_lens, *scales):
        sc = dict(zip(("k_scales", "v_scales"), scales))
        if backend == "pallas":
            from . import spec_verify as sv  # lazy: pallas import cost

            return sv.spec_verify(
                q, k_pages, v_pages, page_table, lengths, window_lens,
                softcap=softcap, window=window, scale=scale, **sc,
            )
        # ref and flash share the gather-based one-shot computation (jit-
        # friendly; ref.spec_verify is the host-loop oracle used by tests)
        return spec_verify_jnp(
            q, k_pages, v_pages, page_table, lengths, window_lens,
            softcap=softcap, window=window, scale=scale, **sc,
        )

    extra = (k_scales, v_scales) if quantized else ()
    tp = _heads_shard_info(q.shape[2], k_pages.shape[2])
    if tp is None:
        return body(q, k_pages, v_pages, page_table, lengths, window_lens,
                    *extra)
    mesh, ax = tp
    P = jax.sharding.PartitionSpec
    hsplit = P(None, None, ax, None)
    in_specs = (hsplit, hsplit, hsplit, P(None, None), P(None), P(None))
    if quantized:
        # scale pools shard on the kv-head axis with their pages
        in_specs += (P(None, None, ax), P(None, None, ax))
    return _shard_heads(
        body, mesh, ax,
        in_specs=in_specs,
        out_specs=hsplit,
    )(q, k_pages, v_pages, page_table, lengths, window_lens, *extra)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    eps: float = 1e-6,
    *,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    backend = backend or default_backend()
    if backend != "pallas":
        return ref.rmsnorm(x, weight, eps=eps)
    from . import rmsnorm as rn
    from ..sharding.specs import activation_rules

    body = functools.partial(rn.rmsnorm, eps=eps)
    rules = activation_rules()
    if rules is None:
        return body(x, weight)
    # a Mosaic kernel cannot be partitioned by GSPMD: under sharding rules
    # every device normalizes the whole (replicated) activation itself
    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        body, mesh=rules.mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False,
    )(x, weight)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------
def ssd_chunked_jnp(
    x: jnp.ndarray,       # (b, s, h, p)
    dt: jnp.ndarray,      # (b, s, h)
    A: jnp.ndarray,       # (h,)
    B: jnp.ndarray,       # (b, s, n)
    C: jnp.ndarray,       # (b, s, n)
    *,
    chunk: int = 64,
    initial_state: Optional[jnp.ndarray] = None,
    return_state: bool = False,
):
    """Chunked state-space duality: quadratic intra-chunk attention-like
    computation + linear inter-chunk recurrence (the Mamba-2 algorithm),
    scanned over chunks so peak memory is O(chunk^2) not O(s^2)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    nchunk = (s + chunk - 1) // chunk
    pad = nchunk * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))

    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def to_chunks(t):  # (b, s, ...) -> (nchunk, b, chunk, ...)
        return jnp.moveaxis(t.reshape((b, nchunk, chunk) + t.shape[2:]), 1, 0)

    xs = (to_chunks(xf), to_chunks(dtf), to_chunks(Bf), to_chunks(Cf))
    state0 = (
        initial_state.astype(jnp.float32)
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )

    def step(S, inputs):
        x_c, dt_c, B_c, C_c = inputs                 # (b, chunk, ...)
        a = dt_c * Af[None, None, :]                 # (b, chunk, h)  log decays
        cum = jnp.cumsum(a, axis=1)                  # inclusive
        # intra-chunk: y[q] += C_q · sum_{k<=q} exp(cum_q - cum_k) dt_k x_k B_k
        decay_qk = jnp.exp(cum[:, :, None, :] - cum[:, None, :, :])   # (b, q, k, h)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay_qk = jnp.where(causal[None, :, :, None], decay_qk, 0.0)
        cb = jnp.einsum("bqn,bkn->bqk", C_c, B_c)                      # (b, q, k)
        y_intra = jnp.einsum("bqk,bqkh,bkh,bkhp->bqhp", cb, decay_qk, dt_c, x_c)
        # inter-chunk: contribution of carried state
        decay_q = jnp.exp(cum)                                         # (b, q, h)
        y_inter = jnp.einsum("bqn,bhpn,bqh->bqhp", C_c, S, decay_q)
        # state update: S' = exp(sum a) S + sum_k exp(cum_last - cum_k) dt_k x_k B_k
        chunk_decay = jnp.exp(cum[:, -1, :])                           # (b, h)
        decay_k = jnp.exp(cum[:, -1, None, :] - cum)                   # (b, k, h)
        dS = jnp.einsum("bkh,bkh,bkhp,bkn->bhpn", decay_k, dt_c, x_c, B_c)
        S_new = chunk_decay[:, :, None, None] * S + dS
        return S_new, y_intra + y_inter

    final_state, ys = jax.lax.scan(step, state0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, nchunk * chunk, h, p)[:, :s]
    y = y.astype(x.dtype)
    if return_state:
        return y, final_state.astype(x.dtype)
    return y


def ssd(
    x, dt, A, B, C, *,
    chunk: int = 64,
    initial_state=None,
    return_state: bool = False,
    backend: Optional[str] = None,
):
    backend = backend or default_backend()
    if backend == "ref":
        return ref.ssd(x, dt, A, B, C, initial_state=initial_state, return_state=return_state)
    if backend == "flash":
        return ssd_chunked_jnp(
            x, dt, A, B, C, chunk=chunk, initial_state=initial_state,
            return_state=return_state,
        )
    if backend == "pallas":
        from . import ssd_scan

        return ssd_scan.ssd(
            x, dt, A, B, C, chunk=chunk, initial_state=initial_state,
            return_state=return_state,
        )
    raise ValueError(f"unknown ssd backend {backend!r}")


def ssd_step(x, dt, A, B, C, state, *, backend: Optional[str] = None):
    """Decode step — shared implementation (already O(1) in seq)."""
    return ref.ssd_step(x, dt, A, B, C, state)

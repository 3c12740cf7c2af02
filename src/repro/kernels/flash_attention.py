"""Pallas TPU flash attention (forward).

TPU-native blocking: grid = (batch, q_heads, q_blocks, kv_blocks) with the
kv dimension innermost and sequential, so the online-softmax state
(m, l, acc) lives in VMEM scratch across kv steps and the output block is
written once on the last kv step.  The wrapper lays q/k/v out head-major,
``(b, heads, seq, d)``, so every block is a ``(seq_block, d)`` tile — the
TPU compiler wants the last two block dims to be multiples of (8, 128) or
whole array dims, which a one-head slice of ``(b, seq, heads, d)`` is not.
Block shapes keep the MXU busy (q/kv blocks are multiples of 128 on the
lane dim; head_dim is the contraction) and the working set well under VMEM
(~16 MB on v5e):

    q (bq, d) + k,v (bk, d) + acc (bq, d) fp32
    ≈ 128·128·(2+2·2+4) B ≈ 0.16 MB per step

GQA is expressed in the k/v index_map (query head h reads kv head h//rep).
The sliding window arrives as a scalar-prefetch operand so one compiled
kernel serves alternating local/global layers (gemma2). Validated against
:mod:`.ref` in interpret mode on CPU (tests sweep shapes/dtypes/options).
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
    w_ref,                     # scalar prefetch: (1,) int32 window (0 = none)
    q_ref, k_ref, v_ref,       # (1, 1, block_q, d), (1, 1, block_k, d)
    o_ref,                     # (1, 1, block_q, d)
    m_ref, l_ref, acc_ref,     # VMEM scratch
    *,
    causal: bool,
    softcap: float,
    q_offset: int,
    block_q: int,
    block_k: int,
    sk: int,
    scale: float,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                         # (bq, d)
    k = k_ref[0, 0]                                         # (bk, d)
    v = v_ref[0, 0]
    # zero padded kv rows: partial trailing blocks are filled with undefined
    # values (NaN in interpret mode; garbage on TPU) and 0 * NaN = NaN
    kv_valid = (
        kj * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    ) < sk
    v = jnp.where(kv_valid, v, jnp.zeros_like(v))
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                               # (bq, bk)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < sk
    if causal:
        mask &= q_pos >= k_pos
    w = w_ref[0]
    # (w <= 0) | ...: Mosaic cannot select between boolean vectors
    mask &= (w <= 0) | ((q_pos - k_pos) < w)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                     # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,            # (b, sq, h, d)
    k: jnp.ndarray,            # (b, sk, kvh, d)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window=None,
    softcap: float = 0.0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wval = jnp.asarray([0], jnp.int32) if window is None else jnp.asarray(
        [window], jnp.int32
    ).reshape((1,))

    kernel = functools.partial(
        _kernel,
        causal=causal, softcap=float(softcap), q_offset=int(q_offset),
        block_q=block_q, block_k=block_k, sk=sk, scale=float(scale),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, kj, w: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, kj, w: (bi, hi // rep, kj, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, qi, kj, w: (bi, hi // rep, kj, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, kj, w: (bi, hi, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    heads_major = lambda t: jnp.swapaxes(t, 1, 2)           # (b, s, h, d) <-> (b, h, s, d)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(wval, heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)

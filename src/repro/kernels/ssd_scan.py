"""Pallas TPU Mamba-2 SSD (state-space duality) chunked scan.

Grid = (batch, chunks); the chunk dimension is innermost and sequential so
the recurrent state S (h, p, n) lives in VMEM scratch across chunks — the
inter-chunk linear recurrence — while each chunk's intra-chunk quadratic
term runs on the MXU. This mirrors the Mamba-2 SSD algorithm's chunked
decomposition, retiled for the TPU memory hierarchy: per-chunk working set

    x (Q, h·p) + B,C (Q, n) + decay (Q, Q, h) + state (h, p, n) fp32
    ≈ 64·64·(h + …)·4 B  ≈ 1–2 MB  « 16 MB VMEM

All accumulation in fp32. The (optional) initial state streams in as a
normal operand; the final state streams out (serving prefill→decode
handoff). Validated against the sequential :func:`repro.kernels.ref.ssd`
oracle in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref,      # (1, Q, h, p)
    dt_ref,     # (1, Q, h)
    A_ref,      # (h,)
    B_ref,      # (1, Q, n)
    C_ref,      # (1, Q, n)
    s0_ref,     # (1, h, p, n) initial state
    y_ref,      # (1, Q, h, p)
    sf_ref,     # (1, h, p, n) final state
    state_ref,  # VMEM scratch (h, p, n) fp32
    *,
    chunk: int,
    seq_len: int,
):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)           # (Q, h, p)
    dt = dt_ref[0].astype(jnp.float32)         # (Q, h)
    A = A_ref[...].astype(jnp.float32)         # (h,)
    B = B_ref[0].astype(jnp.float32)           # (Q, n)
    C = C_ref[0].astype(jnp.float32)           # (Q, n)

    # zero padded timesteps in the trailing partial chunk
    t_pos = ci * chunk + jax.lax.iota(jnp.int32, chunk)
    t_valid = t_pos < seq_len
    dt = jnp.where(t_valid[:, None], dt, 0.0)  # decay exp(0)=1, no input

    a = dt * A[None, :]                        # (Q, h) log-decays
    cum = jnp.cumsum(a, axis=0)                # inclusive
    # intra-chunk quadratic term
    decay_qk = jnp.exp(cum[:, None, :] - cum[None, :, :])       # (Q, K, h)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    decay_qk = jnp.where(causal[:, :, None], decay_qk, 0.0)
    cb = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                            # (Q, K)
    # y_intra[q,h,p] = sum_k cb[q,k] * decay_qk[q,k,h] * dt[k,h] * x[k,h,p]
    w = cb[:, :, None] * decay_qk * dt[None, :, :]               # (Q, K, h)
    y_intra = jnp.einsum("qkh,khp->qhp", w, x)
    # inter-chunk contribution from the carried state
    S = state_ref[...]                                           # (h, p, n)
    decay_q = jnp.exp(cum)                                       # (Q, h)
    y_inter = jnp.einsum("qn,hpn,qh->qhp", C, S, decay_q)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update
    chunk_decay = jnp.exp(cum[-1])                               # (h,)
    decay_k = jnp.exp(cum[-1][None, :] - cum)                    # (K, h)
    dS = jnp.einsum("kh,khp,kn->hpn", decay_k * dt, x, B)
    state_ref[...] = chunk_decay[:, None, None] * S + dS

    @pl.when(ci == nc - 1)
    def _finish():
        sf_ref[0] = state_ref[...].astype(sf_ref.dtype)


def ssd(
    x: jnp.ndarray,       # (b, s, h, p)
    dt: jnp.ndarray,      # (b, s, h)
    A: jnp.ndarray,       # (h,)
    B: jnp.ndarray,       # (b, s, n)
    C: jnp.ndarray,       # (b, s, n)
    *,
    chunk: int = 64,
    initial_state: Optional[jnp.ndarray] = None,
    return_state: bool = False,
    interpret: Optional[bool] = None,
):
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    nc = pl.cdiv(s, chunk)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    s0 = (
        initial_state
        if initial_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )

    kernel = functools.partial(_kernel, chunk=chunk, seq_len=s)
    y, sf = pl.pallas_call(
        kernel,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, h, p), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, chunk, h), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((h,), lambda bi, ci: (0,)),
            pl.BlockSpec((1, chunk, n), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, h, p, n), lambda bi, ci: (bi, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, h, p), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda bi, ci: (bi, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s if s % chunk == 0 else nc * chunk, h, p), x.dtype)
            if False
            else jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), s0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, A, B, C, s0)
    if return_state:
        return y, sf.astype(x.dtype)
    return y

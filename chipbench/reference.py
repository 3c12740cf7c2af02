"""Plain float32 reference of a dense decoder (Llama-style equations).

Per layer: ``h = rms(x) * (1 + g1)``; ``q, k, v = h Wq, h Wk, h Wv``;
rotary embedding on ``q`` and ``k`` (half-split pairs, frequencies
``theta^(-i / (dh/2))``); causal softmax attention with ``H / KV`` query
heads per kv head, scaled by ``1 / sqrt(dh)``; ``x += o Wo``;
``h2 = rms(x) * (1 + g2)``; ``x += (silu(h2 Wg) * h2 Wu) Wd``.  Then
``logits = (rms(x) * (1 + g)) W_head``.

It imports nothing of the program.  Weights come from :mod:`weights`,
one layer at a time, and every matmul runs at float32 ``highest``
precision.  ``low=True`` is the control: every matmul operand, and the
cached K and V, rounded to float8 (e4m3) with a scale per slice (weights
per output column, activations per row, K/V per token and head) -- the
step below the configuration's bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

PAD = 256   # sequences are padded to a multiple of this, to share compiles


def _fp8(x, axis):
    """``x`` rounded to float8 e4m3, scaled so each slice along ``axis``
    spans the format's range (448)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, low: bool):
    """``x (n, k) @ w (k, m)`` in float32; with ``low`` both operands are
    rounded to float8 first (``x`` per row, ``w`` per output column)."""
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    n, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("d", "low"))
def _layer(x, w, d: W.Dims, low: bool):
    """One decoder layer over one (padded) sequence ``x (n, D)``."""
    n = x.shape[0]
    D, H, KV, dh = d.d_model, d.heads, d.kv_heads, d.head_dim
    h = _norm(x, w["ln1"], d.norm_eps)
    q = _mm(h, w["attn"]["wq"].reshape(D, H * dh), low).reshape(n, H, dh)
    k = _mm(h, w["attn"]["wk"].reshape(D, KV * dh), low).reshape(n, KV, dh)
    v = _mm(h, w["attn"]["wv"].reshape(D, KV * dh), low).reshape(n, KV, dh)
    q, k = _rope(q, d.rope_theta), _rope(k, d.rope_theta)
    if low:
        k, v = _fp8(k, -1), _fp8(v, -1)
    rep = H // KV
    qg = q.reshape(n, KV, rep, dh)
    s = jnp.einsum("qgrd,kgd->grqk", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(float(dh))
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("grqk,kgd->qgrd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(n, H * dh)
    x = x + _mm(o, w["attn"]["wo"].reshape(H * dh, D), low)
    h2 = _norm(x, w["ln2"], d.norm_eps)
    m = jax.nn.silu(_mm(h2, w["mlp"]["w_gate"], low)) * _mm(
        h2, w["mlp"]["w_up"], low)
    return x + _mm(m, w["mlp"]["w_down"], low)


@partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, g, head, eps: float, low: bool):
    return _mm(_norm(x, g, eps), head.T, low)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def logits_at(d: W.Dims, seed: int, seqs: Sequence[np.ndarray],
              rows: Sequence[np.ndarray], low: bool = False) -> List[jax.Array]:
    """Logits ``(len(rows[i]), vocab)`` of sequence ``i`` at positions
    ``rows[i]``, each position seeing the tokens up to and including it,
    left on the device.  The model's layers run one at a time over every
    sequence; all sequences are padded to one length, so each layer
    compiles once."""
    key = W.root_key(seed)
    table = jax.jit(W.table, static_argnums=(1, 2, 3))
    embed = table(key, "embed", d.vocab, d.d_model)
    n = -(-max(len(s) for s in seqs) // PAD) * PAD
    xs = []
    for s in seqs:
        ids = np.zeros((n,), np.int32)
        ids[: len(s)] = s
        xs.append(jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32))
    del embed
    make_layer = jax.jit(W.layer, static_argnums=(2,))
    for l in range(d.layers):
        w = _f32(make_layer(key, l, d))
        xs = [_layer(x, w, d, low) for x in xs]
        del w
    g = jax.jit(W.final_norm, static_argnums=(1,))(key, d).astype(jnp.float32)
    head = table(key, "lm_head", d.vocab, d.d_model).astype(jnp.float32)
    m = -(-max(len(r) for r in rows) // PAD) * PAD
    out = []
    for x, r in zip(xs, rows):
        sel = np.zeros((m,), np.int32)
        sel[: len(r)] = r
        out.append(_head(x[jnp.asarray(sel)], g, head, d.norm_eps, low)[: len(r)])
    return out

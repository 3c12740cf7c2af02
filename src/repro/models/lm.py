"""Language models: decoder-only (dense / MoE / SSM / hybrid) and enc-dec.

All models share one API so the platform's predictor, the launcher, and the
dry-run treat every architecture uniformly:

* ``param_defs()`` / ``init(rng, dtype)`` / ``param_specs(dtype)``
* ``forward(params, batch) -> (logits, aux)`` — full-sequence (training)
* ``init_cache(batch, max_seq, dtype)`` / ``cache_specs(...)``
* ``prefill(params, batch, cache) -> (last_logits, cache)``
* ``decode(params, tokens, cache) -> (logits, cache)`` — one token step

Layers are stacked and scanned (``lax.scan``) so compile time and HLO size
are depth-independent — required for 95-layer × 512-device dry-runs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import kvquant, ops
from ..sharding.specs import opt_enabled, param_pspecs, shard_act
from .config import ArchConfig
from .modules import (
    attn_decode,
    attn_decode_paged,
    attn_decode_spec,
    attn_defs,
    attn_full,
    attn_prefill_packed,
    attn_prefill_paged,
    causal_conv1d,
    cross_attn_decode,
    mamba_defs,
    mamba_forward,
    mamba_step,
    mlp_apply,
    mlp_defs,
    moe_apply,
    moe_defs,
    norm_defs,
    sinusoidal,
)
from .params import P, init_params, param_specs

_BIG_WINDOW = jnp.int32(1 << 30)
# serve caches longer than this switch to a ring buffer of
# ``cfg.long_context_window`` slots (hybrid archs only; attn-free SSM has no cache)
_RING_THRESHOLD = 65_536


class BaseModel:
    def __init__(
        self,
        cfg: ArchConfig,
        backend: Optional[str] = None,
        compute_dtype=None,
    ) -> None:
        cfg.validate()
        self.cfg = cfg
        # None: the platform's own kernels (ops.default_backend)
        self.backend = backend or ops.default_backend()
        # mixed precision: weights cast per-layer inside the scan body so only
        # one layer's low-precision copy is live at a time
        self.compute_dtype = jnp.dtype(compute_dtype) if compute_dtype else None

    def _cast(self, tree):
        if self.compute_dtype is None:
            return tree
        cd = self.compute_dtype

        def cast(t):
            return t.astype(cd) if t.dtype in (jnp.float32, jnp.float64) else t

        return jax.tree.map(cast, tree)

    def _cast_mamba(self, blk):
        """Cast a mamba block, keeping the fp32 SSD scalars (A/D/dt) exact."""
        if self.compute_dtype is None:
            return blk
        keep = {"A_log", "D", "dt_bias"}
        out = dict(blk)
        out["mamba"] = {
            k: (v if k in keep else self._cast(v)) for k, v in blk["mamba"].items()
        }
        out["ln"] = self._cast(blk["ln"])
        return out

    # -- params ---------------------------------------------------------------
    def param_defs(self):
        raise NotImplementedError

    def init(self, rng: jax.Array, dtype=jnp.float32):
        return init_params(rng, self.param_defs(), dtype)

    def param_specs(self, dtype=jnp.float32):
        return param_specs(self.param_defs(), dtype)

    # -- helpers ----------------------------------------------------------------
    def _norm(self, x, w):
        return ops.rmsnorm(x, w, self.cfg.norm_eps, backend=self.backend)

    def _embed_tokens(self, params, tokens):
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.compute_dtype is not None:
            x = x.astype(self.compute_dtype)
        if self.cfg.scale_embed:
            x = x * math.sqrt(self.cfg.d_model)
        return x

    def _logits(self, params, x):
        cfg = self.cfg
        x = self._norm(x, self._cast(params["final_norm"]))
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        head = self._cast(head)
        # MXU matmul in compute dtype, fp32 accumulation/output
        logits = jnp.einsum(
            "bsd,dv->bsv", x.astype(head.dtype), head,
            preferred_element_type=jnp.float32,
        )
        if cfg.logit_softcap > 0:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return shard_act(logits, ("batch", "seq", "act_vocab"))

    def _prefill_logits(self, params, batch, x, new_cache, b, s):
        """Last-token logits + per-row positions.  With ``batch["lengths"]``
        prompts are RIGHT-padded to a common (bucketed) length: causal
        attention never reads the trailing pads, so logits gathered at
        ``lengths - 1`` are exactly the unpadded values — prefill shapes can
        be bucketed without changing numerics.  SSM/hybrid state scans the
        whole row (pads included), so only attention families may be ragged.
        """
        lengths = batch.get("lengths")
        if lengths is None:
            new_cache["pos"] = jnp.full((b,), s, jnp.int32)
            x_last = x[:, -1:, :]
        else:
            if self.cfg.family not in ("dense", "moe", "encdec"):
                raise NotImplementedError(
                    "ragged (right-padded) prefill requires a pure-attention "
                    "cache; ssm/hybrid state would absorb the pad tokens"
                )
            lengths = jnp.asarray(lengths, jnp.int32)
            new_cache["pos"] = lengths
            x_last = x[jnp.arange(b), lengths - 1][:, None, :]
        logits = self._logits(params, x_last)[:, 0]
        return logits, new_cache



def _scan_cached(body, x0, per_layer_xs, stacks, length):
    """Scan over layers with cache STACKS carried (not xs/ys).

    ``body(x, xs_l, caches_l, li) -> (x, new_caches_l)``. Each step
    dynamic-slices layer ``li`` from every stack and writes the update back
    with a dynamic-update-slice on the carry — the in-place while-loop
    pattern XLA aliases to a single buffer (a cache passed as scan xs/ys
    would be double-buffered, and hoisted dtype-converts could materialize
    whole-stack copies)."""

    def wrapped(carry, xs):
        x, stacks_c = carry
        xs_l, li = xs
        caches_l = {
            k: jax.lax.dynamic_index_in_dim(v, li, 0, keepdims=False)
            for k, v in stacks_c.items()
        }
        x, new_l = body(x, xs_l, caches_l, li)
        stacks_n = {
            k: jax.lax.dynamic_update_index_in_dim(
                stacks_c[k], new_l[k].astype(stacks_c[k].dtype), li, 0
            )
            if k in new_l
            else stacks_c[k]
            for k in stacks_c
        }
        return (x, stacks_n), None

    (x, stacks), _ = jax.lax.scan(
        wrapped, (x0, dict(stacks)), (per_layer_xs, jnp.arange(length))
    )
    return x, stacks


# =============================================================================
# Decoder-only LM (dense / moe / ssm / hybrid)
# =============================================================================
class DecoderLM(BaseModel):
    # -- parameter definitions -------------------------------------------------
    def param_defs(self):
        cfg = self.cfg
        V, D, L = cfg.vocab_size, cfg.d_model, cfg.num_layers
        defs: Dict[str, Any] = {
            "embed": P((V, D), std=0.02, axes=("vocab", "embed")),
            "blocks": self._block_defs((L,)),
            "final_norm": norm_defs(cfg, ()),
        }
        if cfg.family == "hybrid":
            defs["shared"] = {
                "ln1": norm_defs(cfg, ()),
                "attn": attn_defs(cfg, ()),
                "ln2": norm_defs(cfg, ()),
                "mlp": mlp_defs(cfg, ()),
            }
        if not cfg.tie_embeddings:
            defs["lm_head"] = P((D, V), std=0.02, axes=("embed", "vocab"))
        return defs

    def _block_defs(self, Lp: Tuple[int, ...]):
        cfg = self.cfg
        if cfg.family in ("ssm", "hybrid"):
            return {"ln": norm_defs(cfg, Lp), "mamba": mamba_defs(cfg, Lp)}
        if cfg.family == "moe" and cfg.moe_every == 2:
            # llama4-style interleave: scan over (dense, moe) super-layers
            L2 = (Lp[0] // 2,)
            return {
                "a": self._attn_block_defs(L2, kind="dense"),
                "b": self._attn_block_defs(L2, kind="moe"),
            }
        kind = "moe" if cfg.family == "moe" else "dense"
        return self._attn_block_defs(Lp, kind=kind)

    def _attn_block_defs(self, Lp: Tuple[int, ...], kind: str):
        cfg = self.cfg
        blk: Dict[str, Any] = {
            "ln1": norm_defs(cfg, Lp),
            "attn": attn_defs(cfg, Lp),
            "ln2": norm_defs(cfg, Lp),
        }
        if kind == "moe":
            blk["mlp"] = moe_defs(cfg, Lp)
        else:
            d_ff = cfg.dense_d_ff if (cfg.family == "moe" and cfg.moe_every == 2) else cfg.d_ff
            blk["mlp"] = mlp_defs(cfg, Lp, d_ff=d_ff)
        if cfg.post_norms:
            blk["post_attn_norm"] = norm_defs(cfg, Lp)
            blk["post_mlp_norm"] = norm_defs(cfg, Lp)
        return blk

    @property
    def _interleaved(self) -> bool:
        return self.cfg.family == "moe" and self.cfg.moe_every == 2

    # -- per-layer static metadata ----------------------------------------------
    def _layer_windows(self, sk_hint: int) -> Optional[jnp.ndarray]:
        """Per-layer window values for alternating local/global attention."""
        cfg = self.cfg
        if cfg.global_every <= 0 or cfg.sliding_window <= 0:
            return None
        L = cfg.num_layers
        is_global = (jnp.arange(L) % cfg.global_every) == (cfg.global_every - 1)
        return jnp.where(is_global, _BIG_WINDOW, jnp.int32(cfg.sliding_window))

    # -- attention/mlp block bodies ----------------------------------------------
    def _attn_block_full(self, blk, x, window, q_offset=0, return_kv=False):
        cfg = self.cfg
        blk = self._cast(blk)
        h = self._norm(x, blk["ln1"])
        res = attn_full(
            blk["attn"], h, cfg, backend=self.backend,
            window=window, q_offset=q_offset, return_kv=return_kv,
        )
        a, kv = res if return_kv else (res, None)
        if opt_enabled("rs_block_outputs"):
            # constrain the TP partial-sum output to the seq-sharded layout
            # BEFORE the residual add: GSPMD emits reduce-scatter (half the
            # bytes of the all-reduce it would otherwise place after the add)
            a = shard_act(a, ("batch", "seq", "act_embed"))
        if cfg.post_norms:
            a = self._norm(a, blk["post_attn_norm"])
        x = x + a
        h2 = self._norm(x, blk["ln2"])
        if "router" in blk["mlp"]:
            m, aux = moe_apply(blk["mlp"], h2, cfg)
        else:
            m, aux = mlp_apply(blk["mlp"], h2), jnp.float32(0.0)
        if opt_enabled("rs_block_outputs"):
            m = shard_act(m, ("batch", "seq", "act_embed"))
        if cfg.post_norms:
            m = self._norm(m, blk["post_mlp_norm"])
        x = shard_act(x + m, ("batch", "seq", "act_embed"))
        return (x, aux, kv) if return_kv else (x, aux)

    def _block_ffn(self, blk, x):
        """ln2 + (MoE|MLP) + optional post-norm, residual-added.  ``blk`` is
        already cast to the compute dtype."""
        cfg = self.cfg
        h2 = self._norm(x, blk["ln2"])
        if "router" in blk["mlp"]:
            m, _ = moe_apply(blk["mlp"], h2, cfg)
        else:
            m = mlp_apply(blk["mlp"], h2)
        if cfg.post_norms:
            m = self._norm(m, blk["post_mlp_norm"])
        return x + m

    def _attn_block_decode(self, blk, x1, kc, vc, pos, window, ring=False,
                           uniform_pos=True, kv_bound=None):
        cfg = self.cfg
        blk = self._cast(blk)
        h = self._norm(x1, blk["ln1"])
        a, kc, vc = attn_decode(
            blk["attn"], h, kc, vc, pos, cfg, backend=self.backend,
            window=window, ring=ring, uniform_pos=uniform_pos, kv_bound=kv_bound,
        )
        if cfg.post_norms:
            a = self._norm(a, blk["post_attn_norm"])
        x1 = x1 + a
        return self._block_ffn(blk, x1), kc, vc

    def _mamba_block_full(self, blk, x, state=None, conv=None, return_state=False):
        blk = self._cast_mamba(blk)
        h = self._norm(x, blk["ln"])
        out = mamba_forward(
            blk["mamba"], h, self.cfg, backend=self.backend,
            ssm_state=state, conv_state=conv, return_state=return_state,
        )
        if return_state:
            y, new_state, new_conv = out
            return shard_act(x + y, ("batch", "seq", "act_embed")), new_state, new_conv
        return shard_act(x + out, ("batch", "seq", "act_embed"))

    def _mamba_block_step(self, blk, x1, state, conv):
        blk = self._cast_mamba(blk)
        h = self._norm(x1, blk["ln"])
        y, state, conv = mamba_step(
            blk["mamba"], h, state, conv, self.cfg, backend=self.backend
        )
        return x1 + y, state, conv

    def _shared_block_full(self, shared, x, window=None, kv_cache=None):
        """Zamba2 shared attention+MLP block (full sequence)."""
        shared = self._cast(shared)
        h = self._norm(x, shared["ln1"])
        if kv_cache is not None:
            a, (k, v) = attn_full(
                shared["attn"], h, self.cfg, backend=self.backend,
                window=window, return_kv=True,
            )
        else:
            a = attn_full(shared["attn"], h, self.cfg, backend=self.backend, window=window)
            k = v = None
        x = x + a
        x = x + mlp_apply(shared["mlp"], self._norm(x, shared["ln2"]))
        x = shard_act(x, ("batch", "seq", "act_embed"))
        return (x, (k, v)) if kv_cache is not None else x

    # -- forward (training) -------------------------------------------------------
    def forward(self, params, batch, remat: bool = False):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        x = shard_act(x, ("batch", "seq", "act_embed"))
        if cfg.family in ("dense", "moe"):
            if self._interleaved:

                def body(carry, blk):
                    x, aux = carry
                    x, a1 = self._attn_block_full(blk["a"], x, None)
                    x, a2 = self._attn_block_full(blk["b"], x, None)
                    return (x, aux + a1 + a2), None

                if remat:
                    body = jax.checkpoint(body)
                (x, aux), _ = jax.lax.scan(
                    body, (x, jnp.float32(0.0)), params["blocks"]
                )
                return self._logits(params, x), aux
            windows = self._layer_windows(tokens.shape[1])

            def body(carry, xs):
                x, aux = carry
                blk = xs[0]
                window = xs[1] if windows is not None else None
                x, a = self._attn_block_full(blk, x, window)
                return (x, aux + a), None

            if remat:
                body = jax.checkpoint(body)
            xs = (params["blocks"],) + ((windows,) if windows is not None else ())
            (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
        elif cfg.family == "ssm":

            def body(x, blk):
                return self._mamba_block_full(blk, x), None

            if remat:
                body = jax.checkpoint(body)
            x, _ = jax.lax.scan(body, x, params["blocks"])
            aux = jnp.float32(0.0)
        elif cfg.family == "hybrid":
            x = self._hybrid_forward(params, x, remat)
            aux = jnp.float32(0.0)
        else:
            raise ValueError(cfg.family)
        return self._logits(params, x), aux

    def _hybrid_forward(self, params, x, remat: bool = False):
        cfg = self.cfg
        G = cfg.num_layers // cfg.hybrid_attn_every
        grouped = jax.tree.map(
            lambda t: t.reshape((G, cfg.hybrid_attn_every) + t.shape[1:]),
            params["blocks"],
        )
        shared = params["shared"]

        def group_body(x, mamba_g):
            def inner(x, blk):
                return self._mamba_block_full(blk, x), None

            x, _ = jax.lax.scan(inner, x, mamba_g)
            x = self._shared_block_full(shared, x)
            return x, None

        if remat:
            group_body = jax.checkpoint(group_body)
        x, _ = jax.lax.scan(group_body, x, grouped)
        return x

    # -- serving caches --------------------------------------------------------------
    def _cache_len(self, max_seq: int) -> Tuple[int, bool]:
        cfg = self.cfg
        if cfg.family == "hybrid" and max_seq > _RING_THRESHOLD:
            return cfg.long_context_window, True
        return max_seq, False

    def cache_defs(self, batch: int, max_seq: int, dtype="bfloat16") -> Dict[str, P]:
        """Cache described as a P-tree (reuses init/specs/pspec machinery)."""
        cfg = self.cfg
        kv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
        defs: Dict[str, Any] = {"pos": P((batch,), "zeros", dtype="int32", axes=("batch",))}
        # shard the kv-cache sequence dim over "model" when heads can't split
        kv_axes = ("layer", "batch", "kv_seq", "act_kv", "head_dim")
        if cfg.family in ("dense", "moe"):
            S, _ = self._cache_len(max_seq)
            L = cfg.num_layers
            if self._interleaved:
                pair_axes = ("layer", None) + kv_axes[1:]
                defs["k"] = P((L // 2, 2, batch, S, kv, dh), "zeros", dtype=dtype, axes=pair_axes)
                defs["v"] = P((L // 2, 2, batch, S, kv, dh), "zeros", dtype=dtype, axes=pair_axes)
            else:
                defs["k"] = P((L, batch, S, kv, dh), "zeros", dtype=dtype, axes=kv_axes)
                defs["v"] = P((L, batch, S, kv, dh), "zeros", dtype=dtype, axes=kv_axes)
        elif cfg.family == "ssm":
            L = cfg.num_layers
            defs.update(self._ssm_cache_defs((L,), batch, dtype))
        elif cfg.family == "hybrid":
            L, E = cfg.num_layers, cfg.hybrid_attn_every
            G = L // E
            S, _ = self._cache_len(max_seq)
            defs.update(self._ssm_cache_defs((G, E), batch, dtype))
            ga = ("group", "batch", "kv_seq", "act_kv", "head_dim")
            defs["k"] = P((G, batch, S, kv, dh), "zeros", dtype=dtype, axes=ga)
            defs["v"] = P((G, batch, S, kv, dh), "zeros", dtype=dtype, axes=ga)
        return defs

    def _ssm_cache_defs(self, Lp: Tuple[int, ...], batch: int, dtype) -> Dict[str, P]:
        cfg = self.cfg
        h, ph, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        conv_dim = cfg.ssm_inner + 2 * n
        la = ("layer",) * len(Lp)
        return {
            "ssm": P(
                Lp + (batch, h, ph, n), "zeros", dtype="float32",
                axes=la + ("batch", "ssm_heads", None, None),
            ),
            "conv": P(
                Lp + (batch, cfg.conv_kernel - 1, conv_dim), "zeros", dtype=dtype,
                axes=la + ("batch", None, "conv_dim"),
            ),
        }

    def init_cache(self, batch: int, max_seq: int, dtype="bfloat16"):
        return init_params(jax.random.PRNGKey(0), self.cache_defs(batch, max_seq, dtype))

    def cache_specs(self, batch: int, max_seq: int, dtype="bfloat16"):
        return param_specs(self.cache_defs(batch, max_seq, dtype))

    def paged_cache_defs(self, num_pages: int, page_size: int,
                         dtype="bfloat16") -> Dict[str, P]:
        """Paged KV layout: one global pool of ``page_size``-token pages per
        layer, indexed through per-request page tables — HBM scales with the
        page pool (live tokens), not ``num_slots * max_seq``."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe") or self._interleaved:
            raise NotImplementedError(
                "paged KV cache supports dense/moe (non-interleaved) decoder "
                "caches only; ssm/hybrid state is not paged"
            )
        kv, dh, L = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
        axes = ("layer", None, "kv_seq", "act_kv", "head_dim")
        if kvquant.is_quantized(dtype):
            # quantized pool: int8/fp8 pages + a parallel float32 scale pool
            # (one scale per page row per kv head); scales shard with heads
            store = kvquant.pool_dtype(dtype)
            sc_axes = ("layer", None, "kv_seq", "act_kv")
            return {
                "k_pages": P((L, num_pages, page_size, kv, dh), "zeros",
                             dtype=store, axes=axes),
                "v_pages": P((L, num_pages, page_size, kv, dh), "zeros",
                             dtype=store, axes=axes),
                "k_scales": P((L, num_pages, page_size, kv), "zeros",
                              dtype="float32", axes=sc_axes),
                "v_scales": P((L, num_pages, page_size, kv), "zeros",
                              dtype="float32", axes=sc_axes),
            }
        return {
            "k_pages": P((L, num_pages, page_size, kv, dh), "zeros",
                         dtype=dtype, axes=axes),
            "v_pages": P((L, num_pages, page_size, kv, dh), "zeros",
                         dtype=dtype, axes=axes),
        }

    def init_paged_cache(self, num_pages: int, page_size: int, dtype="bfloat16"):
        return init_params(
            jax.random.PRNGKey(0), self.paged_cache_defs(num_pages, page_size, dtype)
        )

    def paged_cache_pspecs(self, rules, num_pages: int, page_size: int,
                           dtype="bfloat16"):
        """PartitionSpec tree for the paged pool under ``rules``: the
        ``act_kv`` head dim shards over "model" (each shard holds kv/tp
        heads of EVERY page), everything else replicates — page accounting
        stays host-global.  Non-divisible kv head counts fall back to full
        replication via the rules themselves."""
        return param_pspecs(
            self.paged_cache_defs(num_pages, page_size, dtype), rules
        )

    # -- prefill -----------------------------------------------------------------------
    def prefill(self, params, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = self._embed_tokens(params, tokens)
        x = shard_act(x, ("batch", "seq", "act_embed"))
        new_cache = dict(cache)
        if cfg.family in ("dense", "moe"):
            if self._interleaved:
                L2 = cfg.num_layers // 2

                def body(x, blk, caches, li):
                    kc, vc = caches["k"], caches["v"]     # (2, b, S, kv, dh)
                    x, _, (k1, v1) = self._attn_block_full(blk["a"], x, None, return_kv=True)
                    x, _, (k2, v2) = self._attn_block_full(blk["b"], x, None, return_kv=True)
                    write = lambda c, t: jax.lax.dynamic_update_slice(
                        c, t.astype(c.dtype), (0, 0, 0, 0)
                    )
                    return x, {
                        "k": jnp.stack([write(kc[0], k1), write(kc[1], k2)]),
                        "v": jnp.stack([write(vc[0], v1), write(vc[1], v2)]),
                    }

                x, stacks = _scan_cached(
                    body, x, params["blocks"],
                    {"k": cache["k"], "v": cache["v"]}, L2,
                )
            else:
                windows = self._layer_windows(s)
                xs = (
                    (params["blocks"], windows)
                    if windows is not None
                    else (params["blocks"],)
                )

                def body(x, xs_l, caches, li):
                    blk = xs_l[0]
                    window = xs_l[1] if len(xs_l) > 1 else None
                    x, _, (k, v) = self._attn_block_full(blk, x, window, return_kv=True)
                    kc = jax.lax.dynamic_update_slice(
                        caches["k"], k.astype(caches["k"].dtype), (0, 0, 0, 0)
                    )
                    vc = jax.lax.dynamic_update_slice(
                        caches["v"], v.astype(caches["v"].dtype), (0, 0, 0, 0)
                    )
                    return x, {"k": kc, "v": vc}

                x, stacks = _scan_cached(
                    body, x, xs, {"k": cache["k"], "v": cache["v"]}, cfg.num_layers
                )
            new_cache.update(stacks)
        elif cfg.family == "ssm":

            def body(x, blk, caches, li):
                x, st, cv = self._mamba_block_full(
                    blk, x, state=None, conv=None, return_state=True
                )
                return x, {"ssm": st, "conv": cv}

            x, stacks = _scan_cached(
                body, x, params["blocks"],
                {"ssm": cache["ssm"], "conv": cache["conv"]}, cfg.num_layers,
            )
            new_cache.update(stacks)
        elif cfg.family == "hybrid":
            x, new_cache = self._hybrid_prefill(params, x, cache)
        return self._prefill_logits(params, batch, x, new_cache, b, s)

    def _hybrid_prefill(self, params, x, cache):
        cfg = self.cfg
        G, E = cfg.num_layers // cfg.hybrid_attn_every, cfg.hybrid_attn_every
        grouped = jax.tree.map(
            lambda t: t.reshape((G, E) + t.shape[1:]), params["blocks"]
        )
        shared = params["shared"]
        S = cache["k"].shape[2]
        s = x.shape[1]

        def body(x, mamba_g, caches, gi):
            ssm_g, conv_g, kc, vc = (
                caches["ssm"], caches["conv"], caches["k"], caches["v"]
            )

            def inner(x, xs2):
                blk, st, cv = xs2
                x, st, cv = self._mamba_block_full(blk, x, return_state=True)
                return x, (st, cv)

            x, (ssm_g, conv_g) = jax.lax.scan(inner, x, (mamba_g, ssm_g, conv_g))
            x, (k, v) = self._shared_block_full(shared, x, kv_cache=True)
            if s <= S:
                kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, 0, 0, 0))
            else:
                # ring cache shorter than the prompt: keep the last S tokens,
                # placed at their pos-mod-S slots (ring invariant for decode)
                shift = (s - S) % S
                kc = jnp.roll(k[:, -S:], shift, axis=1).astype(kc.dtype)
                vc = jnp.roll(v[:, -S:], shift, axis=1).astype(vc.dtype)
            return x, {"ssm": ssm_g, "conv": conv_g, "k": kc, "v": vc}

        x, stacks = _scan_cached(
            body, x, grouped,
            {"ssm": cache["ssm"], "conv": cache["conv"], "k": cache["k"], "v": cache["v"]},
            G,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        return x, new_cache

    # -- decode ------------------------------------------------------------------------
    def decode(self, params, tokens, cache, uniform_pos=True, kv_bound=None):
        """One token step. tokens: (b,) int32. Returns (logits, new cache).

        ``uniform_pos=False`` selects the masked per-row cache-update path so
        slots may sit at different sequence positions (continuous batching).
        ``kv_bound`` is a static host-known bound on the live cache lengths:
        attention streams only that prefix of the cache instead of all of
        padded ``max_seq`` (the serving engine buckets it to a power of two).
        """
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed_tokens(params, tokens)[:, None, :]       # (b, 1, D)
        new_cache = dict(cache)
        if cfg.family in ("dense", "moe"):
            if self._interleaved:
                L2 = cfg.num_layers // 2

                def body(x1, blk, caches, li):
                    kc, vc = caches["k"], caches["v"]     # (2, b, S, kv, dh)
                    x1, k0, v0 = self._attn_block_decode(
                        blk["a"], x1, kc[0], vc[0], pos, None,
                        uniform_pos=uniform_pos, kv_bound=kv_bound,
                    )
                    x1, k1, v1 = self._attn_block_decode(
                        blk["b"], x1, kc[1], vc[1], pos, None,
                        uniform_pos=uniform_pos, kv_bound=kv_bound,
                    )
                    return x1, {"k": jnp.stack([k0, k1]), "v": jnp.stack([v0, v1])}

                x, stacks = _scan_cached(
                    body, x, params["blocks"], {"k": cache["k"], "v": cache["v"]}, L2
                )
            else:
                windows = self._layer_windows(0)
                xs = (
                    (params["blocks"], windows)
                    if windows is not None
                    else (params["blocks"],)
                )

                def body(x1, xs_l, caches, li):
                    blk = xs_l[0]
                    window = xs_l[1] if len(xs_l) > 1 else None
                    x1, kc, vc = self._attn_block_decode(
                        blk, x1, caches["k"], caches["v"], pos, window,
                        uniform_pos=uniform_pos, kv_bound=kv_bound,
                    )
                    return x1, {"k": kc, "v": vc}

                x, stacks = _scan_cached(
                    body, x, xs, {"k": cache["k"], "v": cache["v"]}, cfg.num_layers
                )
            new_cache.update(stacks)
        elif cfg.family == "ssm":

            def body(x1, blk, caches, li):
                y, st, cv = self._mamba_block_step(
                    blk, x1[:, 0], caches["ssm"], caches["conv"]
                )
                return y[:, None], {"ssm": st, "conv": cv}

            x, stacks = _scan_cached(
                body, x, params["blocks"],
                {"ssm": cache["ssm"], "conv": cache["conv"]}, cfg.num_layers,
            )
            new_cache.update(stacks)
        elif cfg.family == "hybrid":
            x, new_cache = self._hybrid_decode(params, x, cache, uniform_pos=uniform_pos)
        new_cache["pos"] = pos + 1
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    # -- paged serving (global page pool + per-request page tables) --------------------
    @staticmethod
    def _paged_stacks(cache):
        """Cache stacks the paged serving bodies carry through the layer
        scan — the float32 scale pools ride along when the pool is
        quantized."""
        return {
            k: cache[k]
            for k in ("k_pages", "v_pages", "k_scales", "v_scales")
            if k in cache
        }

    def decode_paged(self, params, tokens, cache, page_table, lengths,
                     pages_bound=None):
        """One paged decode step for a pool of slots.

        ``tokens``: (b,) next-token ids; ``page_table``: (b, max_pages)
        int32 physical page ids; ``lengths``: (b,) int32 tokens already held
        per slot — the new token is appended at logical position ``lengths``
        and attention covers ``lengths + 1`` tokens.  ``pages_bound``
        statically bounds live pages per request (host-known, bucketed) so
        the paged kernel's grid tracks actual context lengths.
        Returns (logits, new cache)."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe") or self._interleaved:
            raise NotImplementedError(
                "paged decode supports dense/moe (non-interleaved) only"
            )
        pos = jnp.asarray(lengths, jnp.int32)
        x = self._embed_tokens(params, tokens)[:, None, :]       # (b, 1, D)
        x = shard_act(x, ("batch", None, "act_embed"))
        windows = self._layer_windows(0)
        xs = (
            (params["blocks"], windows)
            if windows is not None
            else (params["blocks"],)
        )

        def body(x1, xs_l, caches, li):
            blk = self._cast(xs_l[0])
            window = xs_l[1] if len(xs_l) > 1 else None
            h = self._norm(x1, blk["ln1"])
            if "k_scales" in caches:
                a, kp, vp, ksc, vsc = attn_decode_paged(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    page_table, pos, cfg, backend=self.backend,
                    window=window, pages_bound=pages_bound,
                    k_scales=caches["k_scales"], v_scales=caches["v_scales"],
                )
                new_l = {"k_pages": kp, "v_pages": vp,
                         "k_scales": ksc, "v_scales": vsc}
            else:
                a, kp, vp = attn_decode_paged(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    page_table, pos, cfg, backend=self.backend,
                    window=window, pages_bound=pages_bound,
                )
                new_l = {"k_pages": kp, "v_pages": vp}
            if cfg.post_norms:
                a = self._norm(a, blk["post_attn_norm"])
            x1 = x1 + a
            return self._block_ffn(blk, x1), new_l

        x, stacks = _scan_cached(
            body, x, xs, self._paged_stacks(cache), cfg.num_layers,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    def decode_spec(self, params, tokens, cache, page_table, lengths,
                    window_lens, pages_bound=None):
        """Speculative-decoding verification step for a pool of slots.

        ``tokens``: (b, W) int32 in-flight windows — per slot the pending
        ``next_token`` followed by up to ``W - 1`` prompt-lookup draft
        tokens, right-padded; ``window_lens``: (b,) real tokens per window
        (0 for idle slots).  ``lengths``: (b,) tokens already committed —
        the window occupies logical positions ``[lengths, lengths +
        window_lens)``.  Every layer scatters the window's K/V into the
        request's pages, then attends the committed context plus the
        window's own causal prefix (one varlen-style launch per layer
        instead of ``W`` sequential decode steps — the KV pool streams
        once).  ``pages_bound`` statically bounds live+in-flight pages.

        Returns (logits (b, W, V), new cache): row ``w`` holds the
        next-token distribution after consuming ``tokens[:, :w + 1]``, so
        greedy acceptance compares ``argmax(logits[:, w - 1])`` against
        ``tokens[:, w]`` — accepted tokens are bit-identical to running the
        one-token decode path sequentially."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe") or self._interleaved:
            raise NotImplementedError(
                "speculative paged decode supports dense/moe "
                "(non-interleaved) only"
            )
        pos = jnp.asarray(lengths, jnp.int32)
        wlens = jnp.asarray(window_lens, jnp.int32)
        x = self._embed_tokens(params, tokens)                   # (b, W, D)
        x = shard_act(x, ("batch", None, "act_embed"))
        windows = self._layer_windows(0)
        xs = (
            (params["blocks"], windows)
            if windows is not None
            else (params["blocks"],)
        )

        def body(x1, xs_l, caches, li):
            blk = self._cast(xs_l[0])
            window = xs_l[1] if len(xs_l) > 1 else None
            h = self._norm(x1, blk["ln1"])
            if "k_scales" in caches:
                a, kp, vp, ksc, vsc = attn_decode_spec(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    page_table, pos, wlens, cfg, backend=self.backend,
                    window=window, pages_bound=pages_bound,
                    k_scales=caches["k_scales"], v_scales=caches["v_scales"],
                )
                new_l = {"k_pages": kp, "v_pages": vp,
                         "k_scales": ksc, "v_scales": vsc}
            else:
                a, kp, vp = attn_decode_spec(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    page_table, pos, wlens, cfg, backend=self.backend,
                    window=window, pages_bound=pages_bound,
                )
                new_l = {"k_pages": kp, "v_pages": vp}
            if cfg.post_norms:
                a = self._norm(a, blk["post_attn_norm"])
            x1 = x1 + a
            return self._block_ffn(blk, x1), new_l

        x, stacks = _scan_cached(
            body, x, xs, self._paged_stacks(cache), cfg.num_layers,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        logits = self._logits(params, x)                         # (b, W, V)
        return logits, new_cache

    def prefill_paged_chunk(self, params, tokens, cache, page_row,
                            last_index, pos0: int):
        """One chunked-prefill step: process a (1, c) prompt chunk starting
        at static page-aligned absolute position ``pos0``, attending to the
        request's already-paged context and appending the chunk's K/V to its
        pages (``page_row``: (max_pages,) int32).  The chunk may be right-
        padded to a page multiple so chunk shapes stay bucketed;
        ``last_index`` (dynamic scalar) is the final *real* token's offset
        within the chunk.  Returns (logits (1, V) at ``last_index``, new
        cache) — the logits only matter for the final chunk, whose argmax is
        the request's first generated token."""
        cfg = self.cfg
        if cfg.family not in ("dense", "moe") or self._interleaved:
            raise NotImplementedError(
                "chunked paged prefill supports dense/moe (non-interleaved) only"
            )
        b, c = tokens.shape
        x = self._embed_tokens(params, tokens)
        x = shard_act(x, ("batch", "seq", "act_embed"))
        windows = self._layer_windows(c)
        xs = (
            (params["blocks"], windows)
            if windows is not None
            else (params["blocks"],)
        )

        def body(x, xs_l, caches, li):
            blk = self._cast(xs_l[0])
            window = xs_l[1] if len(xs_l) > 1 else None
            h = self._norm(x, blk["ln1"])
            if "k_scales" in caches:
                a, kp, vp, ksc, vsc = attn_prefill_paged(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    page_row, pos0, cfg, backend=self.backend, window=window,
                    k_scales=caches["k_scales"], v_scales=caches["v_scales"],
                )
                new_l = {"k_pages": kp, "v_pages": vp,
                         "k_scales": ksc, "v_scales": vsc}
            else:
                a, kp, vp = attn_prefill_paged(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    page_row, pos0, cfg, backend=self.backend, window=window,
                )
                new_l = {"k_pages": kp, "v_pages": vp}
            if cfg.post_norms:
                a = self._norm(a, blk["post_attn_norm"])
            x = x + a
            return self._block_ffn(blk, x), new_l

        x, stacks = _scan_cached(
            body, x, xs, self._paged_stacks(cache), cfg.num_layers,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        last = jnp.asarray(last_index, jnp.int32)
        logits = self._logits(params, x[:, last][:, None, :])[:, 0]
        return logits, new_cache

    def prefill_packed(self, params, batch, cache, pages_bound=None):
        """One packed varlen-prefill launch: process prompt chunks from MANY
        requests in a single token-packed ``(1, T)`` buffer, each chunk
        attending its request's already-committed pages (via the per-chunk
        page-table rows) plus the causal prefix of its own tokens, with the
        packed K/V scattered straight into the paged pool.

        ``batch`` holds the packed tokens plus the packing metadata of
        :func:`repro.models.modules.attn_prefill_packed`, and ``last_idx``
        (C,) — the packed row of each chunk's last real token.  Returns
        (logits (C, V) gathered at ``last_idx``, new cache); only rows of
        chunks that complete their prompt this launch are meaningful (their
        argmax is the request's first generated token).
        """
        cfg = self.cfg
        if cfg.family not in ("dense", "moe") or self._interleaved:
            raise NotImplementedError(
                "packed paged prefill supports dense/moe (non-interleaved) only"
            )
        tokens = batch["tokens"]
        b, T = tokens.shape
        meta = {
            k: batch[k]
            for k in ("tok_pos", "dst_page", "dst_off", "cu_seqlens",
                      "chunk_lens", "chunk_pos0", "page_tables")
        }
        x = self._embed_tokens(params, tokens)
        x = shard_act(x, ("batch", "seq", "act_embed"))
        windows = self._layer_windows(T)
        xs = (
            (params["blocks"], windows)
            if windows is not None
            else (params["blocks"],)
        )

        def body(x, xs_l, caches, li):
            blk = self._cast(xs_l[0])
            window = xs_l[1] if len(xs_l) > 1 else None
            h = self._norm(x, blk["ln1"])
            if "k_scales" in caches:
                a, kp, vp, ksc, vsc = attn_prefill_packed(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    meta, cfg, backend=self.backend, window=window,
                    pages_bound=pages_bound,
                    k_scales=caches["k_scales"], v_scales=caches["v_scales"],
                )
                new_l = {"k_pages": kp, "v_pages": vp,
                         "k_scales": ksc, "v_scales": vsc}
            else:
                a, kp, vp = attn_prefill_packed(
                    blk["attn"], h, caches["k_pages"], caches["v_pages"],
                    meta, cfg, backend=self.backend, window=window,
                    pages_bound=pages_bound,
                )
                new_l = {"k_pages": kp, "v_pages": vp}
            if cfg.post_norms:
                a = self._norm(a, blk["post_attn_norm"])
            x = x + a
            return self._block_ffn(blk, x), new_l

        x, stacks = _scan_cached(
            body, x, xs, self._paged_stacks(cache), cfg.num_layers,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        last = jnp.asarray(batch["last_idx"], jnp.int32)
        logits = self._logits(params, x[0, last][:, None, :])[:, 0]
        return logits, new_cache

    def _hybrid_decode(self, params, x, cache, uniform_pos=True):
        cfg = self.cfg
        G, E = cfg.num_layers // cfg.hybrid_attn_every, cfg.hybrid_attn_every
        grouped = jax.tree.map(
            lambda t: t.reshape((G, E) + t.shape[1:]), params["blocks"]
        )
        shared = self._cast(params["shared"])
        pos = cache["pos"]
        # ring semantics are a no-op while pos < cache length, so always on
        ring = True

        def body(x1, mamba_g, caches, gi):
            ssm_g, conv_g, kc, vc = (
                caches["ssm"], caches["conv"], caches["k"], caches["v"]
            )

            def inner(x1s, xs2):
                blk, st, cv = xs2
                y, st, cv = self._mamba_block_step(blk, x1s, st, cv)
                return y, (st, cv)

            y, (ssm_g, conv_g) = jax.lax.scan(inner, x1[:, 0], (mamba_g, ssm_g, conv_g))
            x1 = y[:, None]
            h = self._norm(x1, shared["ln1"])
            a, kc, vc = attn_decode(
                shared["attn"], h, kc, vc, pos, cfg, backend=self.backend,
                ring=ring, uniform_pos=uniform_pos,
            )
            x1 = x1 + a
            x1 = x1 + mlp_apply(shared["mlp"], self._norm(x1, shared["ln2"]))
            return x1, {"ssm": ssm_g, "conv": conv_g, "k": kc, "v": vc}

        x, stacks = _scan_cached(
            body, x, grouped,
            {"ssm": cache["ssm"], "conv": cache["conv"], "k": cache["k"], "v": cache["v"]},
            G,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        return x, new_cache


# =============================================================================
# Encoder–decoder (whisper-style; conv/audio frontend is a stub)
# =============================================================================
class EncDecLM(BaseModel):
    def param_defs(self):
        cfg = self.cfg
        V, D = cfg.vocab_size, cfg.d_model
        Le, Ld = (cfg.encoder_layers,), (cfg.num_layers,)
        enc_blk = {
            "ln1": norm_defs(cfg, Le),
            "attn": attn_defs(cfg, Le),
            "ln2": norm_defs(cfg, Le),
            "mlp": mlp_defs(cfg, Le, gated=False),
        }
        dec_blk = {
            "ln1": norm_defs(cfg, Ld),
            "self_attn": attn_defs(cfg, Ld),
            "ln2": norm_defs(cfg, Ld),
            "cross_attn": attn_defs(cfg, Ld, cross=True),
            "ln3": norm_defs(cfg, Ld),
            "mlp": mlp_defs(cfg, Ld, gated=False),
        }
        return {
            "embed": P((V, D), std=0.02, axes=("vocab", "embed")),
            "enc_blocks": enc_blk,
            "enc_norm": norm_defs(cfg, ()),
            "dec_blocks": dec_blk,
            "final_norm": norm_defs(cfg, ()),
            "lm_head": P((D, V), std=0.02, axes=("embed", "vocab")),
        }

    # -- encoder -------------------------------------------------------------
    def encode(self, params, frames, remat: bool = False):
        """frames: (b, Se, D) — precomputed frame embeddings (frontend stub)."""
        cfg = self.cfg
        Se = frames.shape[1]
        x = frames + sinusoidal(jnp.arange(Se), cfg.d_model).astype(frames.dtype)
        x = shard_act(x, ("batch", "seq", "act_embed"))

        def body(x, blk):
            blk = self._cast(blk)
            h = self._norm(x, blk["ln1"])
            x = x + attn_full(
                blk["attn"], h, cfg, backend=self.backend, causal=False, use_rope=False
            )
            x = x + mlp_apply(blk["mlp"], self._norm(x, blk["ln2"]))
            return shard_act(x, ("batch", "seq", "act_embed")), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
        return self._norm(x, params["enc_norm"])

    def _embed_dec(self, params, tokens, pos0=0):
        b, s = tokens.shape
        x = self._embed_tokens(params, tokens)
        x = x + sinusoidal(pos0 + jnp.arange(s), self.cfg.d_model).astype(x.dtype)
        return shard_act(x, ("batch", "seq", "act_embed"))

    # -- training forward -------------------------------------------------------
    def forward(self, params, batch, remat: bool = False):
        cfg = self.cfg
        enc = self.encode(params, batch["frames"], remat=remat)
        x = self._embed_dec(params, batch["tokens"])

        def body(x, blk):
            blk = self._cast(blk)
            h = self._norm(x, blk["ln1"])
            x = x + attn_full(
                blk["self_attn"], h, cfg, backend=self.backend, use_rope=False
            )
            h2 = self._norm(x, blk["ln2"])
            x = x + attn_full(
                blk["cross_attn"], h2, cfg, backend=self.backend,
                use_rope=False, kv_from=enc,
            )
            x = x + mlp_apply(blk["mlp"], self._norm(x, blk["ln3"]))
            return shard_act(x, ("batch", "seq", "act_embed")), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["dec_blocks"])
        return self._logits(params, x), jnp.float32(0.0)

    # -- serving -------------------------------------------------------------------
    def cache_defs(self, batch: int, max_seq: int, dtype="bfloat16") -> Dict[str, P]:
        cfg = self.cfg
        kv, dh, L = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_layers
        Se = cfg.encoder_seq
        kv_axes = ("layer", "batch", "kv_seq", "act_kv", "head_dim")
        return {
            "pos": P((batch,), "zeros", dtype="int32", axes=("batch",)),
            "k": P((L, batch, max_seq, kv, dh), "zeros", dtype=dtype, axes=kv_axes),
            "v": P((L, batch, max_seq, kv, dh), "zeros", dtype=dtype, axes=kv_axes),
            "k_cross": P((L, batch, Se, kv, dh), "zeros", dtype=dtype, axes=kv_axes),
            "v_cross": P((L, batch, Se, kv, dh), "zeros", dtype=dtype, axes=kv_axes),
        }

    def init_cache(self, batch: int, max_seq: int, dtype="bfloat16"):
        return init_params(jax.random.PRNGKey(0), self.cache_defs(batch, max_seq, dtype))

    def cache_specs(self, batch: int, max_seq: int, dtype="bfloat16"):
        return param_specs(self.cache_defs(batch, max_seq, dtype))

    def prefill(self, params, batch, cache):
        """batch: {frames, tokens}; encodes, caches cross-KV, fills self-KV."""
        cfg = self.cfg
        enc = self.encode(params, batch["frames"])
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = self._embed_dec(params, tokens)

        def body(x, blk, caches, li):
            blk = self._cast(blk)
            h = self._norm(x, blk["ln1"])
            a, (k, v) = attn_full(
                blk["self_attn"], h, cfg, backend=self.backend,
                use_rope=False, return_kv=True,
            )
            x = x + a
            kc = jax.lax.dynamic_update_slice(
                caches["k"], k.astype(caches["k"].dtype), (0, 0, 0, 0)
            )
            vc = jax.lax.dynamic_update_slice(
                caches["v"], v.astype(caches["v"].dtype), (0, 0, 0, 0)
            )
            h2 = self._norm(x, blk["ln2"])
            # cross attention; cache enc K/V for decode
            kx_new = jnp.einsum("bsd,dhk->bshk", enc, blk["cross_attn"]["wk"])
            vx_new = jnp.einsum("bsd,dhk->bshk", enc, blk["cross_attn"]["wv"])
            q = jnp.einsum("bsd,dhk->bshk", h2, blk["cross_attn"]["wq"])
            o = ops.attention(q, kx_new, vx_new, causal=False, backend=self.backend)
            x = x + jnp.einsum("bshk,hkd->bsd", o, blk["cross_attn"]["wo"])
            x = x + mlp_apply(blk["mlp"], self._norm(x, blk["ln3"]))
            return x, {"k": kc, "v": vc, "k_cross": kx_new, "v_cross": vx_new}

        x, stacks = _scan_cached(
            body, x, params["dec_blocks"],
            {"k": cache["k"], "v": cache["v"],
             "k_cross": cache["k_cross"], "v_cross": cache["v_cross"]},
            cfg.num_layers,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        return self._prefill_logits(params, batch, x, new_cache, b, s)

    def decode(self, params, tokens, cache, uniform_pos=True, kv_bound=None):
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed_tokens(params, tokens)[:, None, :]
        x = x + sinusoidal(pos[:, None], cfg.d_model).astype(x.dtype)[:, :, :]

        def body(x1, blk, caches, li):
            blk = self._cast(blk)
            h = self._norm(x1, blk["ln1"])
            a, kc, vc = attn_decode(
                blk["self_attn"], h, caches["k"], caches["v"], pos, cfg,
                backend=self.backend, use_rope=False, uniform_pos=uniform_pos,
                kv_bound=kv_bound,
            )
            x1 = x1 + a
            h2 = self._norm(x1, blk["ln2"])
            x1 = x1 + cross_attn_decode(
                blk["cross_attn"], h2, caches["k_cross"], caches["v_cross"],
                cfg, backend=self.backend,
            )
            x1 = x1 + mlp_apply(blk["mlp"], self._norm(x1, blk["ln3"]))
            return x1, {"k": kc, "v": vc}

        x, stacks = _scan_cached(
            body, x, params["dec_blocks"],
            {"k": cache["k"], "v": cache["v"],
             "k_cross": cache["k_cross"], "v_cross": cache["v_cross"]},
            cfg.num_layers,
        )
        new_cache = dict(cache)
        new_cache.update(stacks)
        new_cache["pos"] = pos + 1
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache


def _layer_slice(tree, l: int):
    return jax.tree.map(lambda t: t[l], tree)


def _forward_instrumented_decoder(self, params, batch, hook):
    """Layer-by-layer forward with a ``hook(name, thunk)`` around each layer.

    This is the FRAMEWORK-level tracing path (paper §4.4.4): like TF's
    RunOptions tracer, it trades throughput for per-layer visibility —
    each layer runs (and synchronizes) separately.
    """
    cfg = self.cfg
    x = hook("embed", lambda: self._embed_tokens(params, batch["tokens"]))
    if cfg.family in ("dense", "moe"):
        if self._interleaved:
            L2 = cfg.num_layers // 2
            for l in range(L2):
                blk = _layer_slice(params["blocks"], l)
                x = hook(
                    f"layer_{2*l:03d}_dense",
                    lambda blk=blk, x=x: self._attn_block_full(blk["a"], x, None)[0],
                )
                x = hook(
                    f"layer_{2*l+1:03d}_moe",
                    lambda blk=blk, x=x: self._attn_block_full(blk["b"], x, None)[0],
                )
        else:
            windows = self._layer_windows(batch["tokens"].shape[1])
            import numpy as _np

            wvals = None if windows is None else _np.asarray(windows)
            for l in range(cfg.num_layers):
                blk = _layer_slice(params["blocks"], l)
                w = None if wvals is None else int(wvals[l])
                name = f"layer_{l:03d}_attn" + ("" if w is None else f"_w{w}")
                x = hook(
                    name, lambda blk=blk, x=x, w=w: self._attn_block_full(blk, x, w)[0]
                )
    elif cfg.family == "ssm":
        for l in range(cfg.num_layers):
            blk = _layer_slice(params["blocks"], l)
            x = hook(
                f"layer_{l:03d}_mamba",
                lambda blk=blk, x=x: self._mamba_block_full(blk, x),
            )
    elif cfg.family == "hybrid":
        G, E = cfg.num_layers // cfg.hybrid_attn_every, cfg.hybrid_attn_every
        for g in range(G):
            for e in range(E):
                l = g * E + e
                blk = _layer_slice(params["blocks"], l)
                x = hook(
                    f"layer_{l:03d}_mamba",
                    lambda blk=blk, x=x: self._mamba_block_full(blk, x),
                )
            x = hook(
                f"layer_{g:03d}_shared_attn",
                lambda x=x: self._shared_block_full(params["shared"], x),
            )
    return hook("logits", lambda: self._logits(params, x))


def _forward_instrumented_encdec(self, params, batch, hook):
    cfg = self.cfg
    frames = batch["frames"]
    Se = frames.shape[1]
    x = hook(
        "enc_embed",
        lambda: frames
        + sinusoidal(jnp.arange(Se), cfg.d_model).astype(frames.dtype),
    )
    for l in range(cfg.encoder_layers):
        blk = self._cast(_layer_slice(params["enc_blocks"], l))

        def enc_layer(blk=blk, x=x):
            h = self._norm(x, blk["ln1"])
            y = x + attn_full(
                blk["attn"], h, cfg, backend=self.backend, causal=False, use_rope=False
            )
            return y + mlp_apply(blk["mlp"], self._norm(y, blk["ln2"]))

        x = hook(f"enc_layer_{l:03d}", enc_layer)
    enc = hook("enc_norm", lambda x=x: self._norm(x, params["enc_norm"]))
    x = hook("dec_embed", lambda: self._embed_dec(params, batch["tokens"]))
    for l in range(cfg.num_layers):
        blk = self._cast(_layer_slice(params["dec_blocks"], l))

        def dec_layer(blk=blk, x=x):
            h = self._norm(x, blk["ln1"])
            y = x + attn_full(
                blk["self_attn"], h, cfg, backend=self.backend, use_rope=False
            )
            h2 = self._norm(y, blk["ln2"])
            y = y + attn_full(
                blk["cross_attn"], h2, cfg, backend=self.backend,
                use_rope=False, kv_from=enc,
            )
            return y + mlp_apply(blk["mlp"], self._norm(y, blk["ln3"]))

        x = hook(f"dec_layer_{l:03d}", dec_layer)
    return hook("logits", lambda: self._logits(params, x))


DecoderLM.forward_instrumented = _forward_instrumented_decoder
EncDecLM.forward_instrumented = _forward_instrumented_encdec


def build_model(
    cfg: ArchConfig, backend: Optional[str] = None, compute_dtype=None
) -> BaseModel:
    if cfg.family == "encdec":
        return EncDecLM(cfg, backend, compute_dtype)
    return DecoderLM(cfg, backend, compute_dtype)

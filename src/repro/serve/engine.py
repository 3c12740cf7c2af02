"""Batched + continuous-batching + paged serving engine.

This is the platform's "cloud scenario" executor (the paper deploys models
either for cloud serving or edge inference). Three generate paths share the
prefill/decode jits:

* ``generate``          — static fixed-batch: requests grouped into padded
  batches, prefilled once, decoded token-by-token with cache donation so
  decode is allocation-free at steady state.
* ``serve_continuous``  — slot-based continuous batching: a fixed pool of
  dense KV-cache slots; finished sequences free their slot and queued
  prompts are admitted at decode-step boundaries (batch-1 prefill scattered
  into the pooled cache).  Uses the model's masked per-row cache-update path
  (``uniform_pos=False``) because slots sit at different sequence positions.
* ``serve_paged``       — paged KV cache: a global pool of ``page_size``-
  token pages plus per-request page tables; admission is keyed on free
  pages, prompts prefill interleaved at decode-step boundaries, and the
  pool preempts the youngest request when pages run out.  HBM scales with
  live tokens instead of ``num_slots * max_seq``.  Two prefill pipelines
  (``prefill_mode``): ``packed`` (default) coalesces every admissible
  prompt chunk into ONE token-packed varlen launch per boundary — a fixed
  packed-buffer size (``prefill_budget`` tokens, the knob that bounds
  decode latency) writing straight into the page pool, one compile
  regardless of how prompt lengths mix; ``chunked`` is the legacy
  one-chunk-per-slot-per-boundary path (one jit variant per chunk
  length × offset).  ``spec_k > 0`` adds self-speculative decoding: a
  host-side prompt-lookup drafter (n-gram match against the request's
  prompt + output) proposes up to ``spec_k`` tokens per slot and one
  paged multi-token verification launch scores every slot's window —
  greedy exact-match acceptance keeps tokens bit-identical, rejected
  suffixes roll back by rewinding lengths (append-only pages).  The
  decode loop keeps page tables / positions device-resident (patched only
  for slots that changed) and fuses argmax + acceptance into the launch,
  so a steady-state boundary costs one small int32 fetch.

Two shape disciplines keep XLA compile counts bounded (tracked per engine
instance in ``compile_stats``; each ``serve_paged`` run reports only its
own delta): prompts are RIGHT-padded to power-of-two length buckets
(floored at ``page_size``) — causal attention never reads trailing pads, so
bucketing is numerically exact for attention families — and decode passes a
bucketed static ``kv_bound`` so attention streams only the live prefix of
the cache rather than all of padded ``max_seq``.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial, wraps
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec

from ..core.analysis import percentile
from ..kernels import kvquant, ops
from ..models.lm import BaseModel
from ..models.params import tree_map_defs
from ..sharding.specs import (
    ShardingRules, param_pspecs, set_activation_rules, tp_degree,
)
from .faults import FaultContext, WorkerCrash, WorkerDrain
from .page_table import (
    PagePool, PageSnapshot, PageTable, PrefixCache, page_checksums,
    pages_needed,
)
from .scheduler import (
    PagedSlotPool, PrefillBudget, SlotPool, SpecLedger, TenantLedger,
    TenantSpec,
)


def _named_shardings(mesh, pspecs):
    """PartitionSpec tree -> NamedSharding tree (PartitionSpec subclasses
    tuple, so plain tree_map would descend into it)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def bucket_pow2(n: int, floor: int = 1, cap: Optional[int] = None) -> int:
    """Smallest power-of-two multiple of ``floor`` that is >= ``n``, clipped
    to ``cap``.  Callers guarantee ``n <= cap``; the clip keeps the top
    bucket from overshooting the cache."""
    b = max(floor, 1)
    while b < n:
        b *= 2
    return min(b, cap) if cap is not None else b


def ngram_propose(context: np.ndarray, ngram: int, max_tokens: int) -> List[int]:
    """Prompt-lookup drafting: match the last ``ngram`` tokens of ``context``
    (prompt + everything committed so far, ending at the pending next token)
    against earlier context; the tokens that FOLLOWED the match become the
    draft.  No second model — summarization/extraction-style continuations
    repeat their source, so the continuation of an earlier occurrence is a
    cheap, often-right guess.  Scanning from the most recent match backwards,
    the first one with a FULL ``max_tokens`` continuation wins (a short
    repetition period would otherwise cap every draft at the period length:
    the most recent occurrence sits so close to the end that only a couple
    of continuation tokens exist); if none has a full continuation the most
    recent match is used.  Returns up to ``max_tokens`` draft ids (empty
    when nothing matches — the engine then falls back to a plain decode
    step, so adversarial text pays only this O(len * ngram) host scan)."""
    n = len(context)
    if max_tokens <= 0 or ngram < 1 or n < ngram + 1:
        return []
    pat = context[-ngram:]
    # vectorized sliding-window match (the scan runs per slot per decode
    # boundary, so the no-match case must stay cheap)
    windows = np.lib.stride_tricks.sliding_window_view(context, ngram)
    hits = np.nonzero((windows == pat).all(axis=1))[0]
    hits = hits[hits < n - ngram]          # drop the suffix occurrence itself
    if hits.size == 0:
        return []
    full = hits[hits + ngram + max_tokens <= n]
    best = int(full[-1]) if full.size else int(hits[-1])
    cont = context[best + ngram : best + ngram + max_tokens]
    return [int(t) for t in cont]


class _LoopSpan:
    """A timed span of the ``serve_paged`` loop, on two clocks.

    A ``jax.profiler.TraceAnnotation`` of the span's name puts it on the
    profiler's clock, on the host line beside the device operations of a
    trace; ``t0``/``t1`` are the loop clock's readings at its ends.  With
    a tracer, ``tags`` is a dict for the caller to fill (it starts with the
    loop's ``step``), and ``tracer.event(name, t0, t1, **tags)`` publishes
    the span on the loop's clock as well."""

    __slots__ = ("name", "tags", "t0", "t1", "_tracer", "_clock", "_ann")

    def __init__(self, name: str, tracer, clock: Callable[[], float],
                 step: int) -> None:
        self.name = name
        self.tags = {"step": step} if tracer is not None else None
        self._tracer = tracer
        self._clock = clock
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> _LoopSpan:
        self._ann.__enter__()
        self.t0 = self._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = self._clock()
        self._ann.__exit__(exc_type, exc, tb)
        if self._tracer is not None and exc_type is None:
            self._tracer.event(self.name, self.t0, self.t1, **self.tags)


def _fill_itl(results: List[RequestResult]) -> List[float]:
    """Set each result's inter-token percentiles from its token times;
    returns every gap between consecutive tokens of ``results``."""
    gaps_all: List[float] = []
    for r in results:
        t = r.token_times_s
        gaps = [b - a for a, b in zip(t, t[1:])]
        if gaps:
            r.itl_p50_s = percentile(gaps, 50.0)
            r.itl_p99_s = percentile(gaps, 99.0)
            gaps_all.extend(gaps)
    return gaps_all


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (b, new_tokens)
    prefill_s: float
    decode_s: float
    tokens_per_s: float


@dataclass
class ServeRequest:
    """One prompt for the continuous-batching / paged loops."""

    request_id: int
    prompt: np.ndarray
    max_new_tokens: int
    # multi-tenant serving: tenant identity, priority tier and latency SLO
    # (defaults keep single-tenant callers unchanged)
    tenant: str = "default"
    priority: int = 1
    slo_ms: float = 0.0


@dataclass
class RequestResult:
    """Per-request serving metrics (continuous batching)."""

    request_id: int
    tokens: np.ndarray          # (max_new_tokens,)
    slot: int
    admit_step: int             # decode-step boundary at which it was admitted
    finish_step: int
    ttft_s: float               # submit -> first token (prefill argmax)
    latency_s: float            # submit -> last token
    tokens_per_s: float
    # -- inter-token latency (paged engine): gaps between consecutive token
    # emissions; a speculative boundary emits several tokens at one instant,
    # so accepted drafts show up as (near-)zero gaps pulling p50 down -------
    itl_p50_s: float = 0.0
    itl_p99_s: float = 0.0
    # -- per-token timeline (paged engine): submission -> first admission,
    # and every token emitted on this worker as an offset from submission
    # (a speculative boundary repeats its time once per token it emits; a
    # request restored from a snapshot holds only the tokens emitted here)
    queue_s: float = 0.0
    token_times_s: List[float] = field(default_factory=list)
    # -- speculative-decoding ledger (0s when spec_k == 0) ------------------
    draft_proposed: int = 0
    draft_accepted: int = 0
    # -- terminal status (fleet-parity semantics): every request ends
    # "completed" or "rejected"; a completed request past the run deadline
    # stays completed but falls out of goodput (within_deadline=False) ------
    status: str = "completed"
    reason: str = ""
    tenant: str = "default"
    priority: int = 1
    within_deadline: bool = True


@dataclass
class ContinuousStats:
    """Aggregate output of one ``serve_continuous`` run."""

    results: List[RequestResult]
    steps: int                  # decode steps executed
    wall_s: float
    total_tokens: int
    throughput_tps: float
    mean_slot_occupancy: float  # active slots per decode step


@dataclass
class PagedStats:
    """Aggregate output of one ``serve_paged`` run."""

    results: List[RequestResult]
    steps: int                  # decode steps executed
    wall_s: float
    total_tokens: int
    throughput_tps: float
    mean_slot_occupancy: float  # active slots per decode step
    peak_slot_occupancy: int    # max concurrent requests observed
    page_size: int
    num_pages: int              # allocatable pages in the pool
    mean_pages_in_use: float
    peak_pages_in_use: int
    preemptions: int
    prefill_chunks: int         # prompt chunks prefilled (spans in packed mode)
    compile_stats: Dict[str, int] = field(default_factory=dict)
    # -- prefill pipeline (packed varlen launches) --------------------------
    prefill_mode: str = "packed"
    prefill_launches: int = 0   # packed launches (== prefill_chunks if chunked)
    prefill_s: float = 0.0      # wall time spent inside prefill calls
    prefill_tokens: int = 0     # real prompt tokens COMPUTED by prefill
    prefill_padded_tokens: int = 0  # packed-buffer slots spent on padding
    prefill_budget: int = 0     # packed-buffer tokens per boundary (0 = chunked)
    prefill_budget_stats: Dict[str, float] = field(default_factory=dict)
    # (q block, key stage) pairs of the packed launches, summed: the live
    # ones the varlen kernel iterates, and its whole nqb x (bound + nqb)
    # grid (``kernels.varlen_prefill.work_items``); 0 on a backend that
    # does not run that kernel
    prefill_kv_live: int = 0
    prefill_kv_rect: int = 0
    # -- prompt-token ledger: admitted tokens split exactly into computed
    # (prefill_tokens above), served from the prefix cache, and abandoned by
    # preemption before they were ever prefilled.  Invariant (asserted in
    # tests) over any completed run:
    #   prompt_tokens_admitted ==
    #       prefill_tokens + saved_prefill_tokens + prefill_tokens_dropped
    prompt_tokens_admitted: int = 0   # per admission (re-admissions count again)
    saved_prefill_tokens: int = 0     # prompt tokens served from cached pages
    prefill_tokens_dropped: int = 0   # admitted but preempted before prefill
    # -- automatic prefix caching -------------------------------------------
    prefix_cache: bool = False
    cow_copies: int = 0         # shared pages split by copy-on-write
    cache_evictions: int = 0    # cached-unreferenced pages reclaimed
    prefix_stats: Dict[str, float] = field(default_factory=dict)
    # -- decode loop / speculative decoding ---------------------------------
    decode_s: float = 0.0       # wall time spent inside decode/verify launches
    spec_k: int = 0             # draft depth (0 = speculation disabled)
    spec_stats: Dict[str, float] = field(default_factory=dict)  # SpecLedger
    itl_p50_ms: float = 0.0     # inter-token latency over every gap in the run
    itl_p99_ms: float = 0.0
    # -- host loop: iterations of the serving loop, and their wall time
    # less the waits for the device (packed prefill's ``prefill:wait``,
    # each decode step's ``decode:fetch``), summed over iterations
    boundaries: int = 0
    host_s: float = 0.0
    # -- tensor parallelism -------------------------------------------------
    tp: int = 1                 # effective model-axis degree (1 = unsharded)
    # -- quantized KV pages -------------------------------------------------
    kv_dtype: str = "float32"   # pool storage mode (int8/fp8 = quantized)
    kv_bytes_per_token: float = 0.0  # pool bytes per token incl. scales
    # -- SLO / multi-tenant admission ---------------------------------------
    completed: int = 0          # terminal completed (== len(results) w/o TTL)
    rejected: int = 0           # terminal rejected (deadline / SLO shed)
    deferred: int = 0           # tenant-boundary deferrals (bucket ran dry)
    goodput: float = 1.0        # completed within deadline / submitted
    deadline_ms: float = 0.0    # run TTL handed to serve_paged (0 = none)
    # -- live KV migration (checkpoint / restore) ---------------------------
    checkpoints_saved: int = 0  # slot snapshots taken this run
    checkpoint_bytes: int = 0   # bytes gathered into snapshots
    restored_requests: int = 0  # requests resumed from a snapshot
    restored_tokens: int = 0    # KV positions restored without recompute
    restore_bytes: int = 0      # bytes scattered back into the pool
    checksum_failures: int = 0  # snapshots rejected by verify -> replayed


class ServingEngine:
    def __init__(
        self,
        model: BaseModel,
        params,
        max_batch: int,
        max_seq: int,
        cache_dtype: str = "float32",
        page_size: int = 16,
        rules: Optional[ShardingRules] = None,
        kv_dtype: Optional[str] = None,
    ) -> None:
        self.model = model
        # tensor parallelism: ``rules`` maps the existing logical axes
        # (heads/kv/ffn/vocab + activations) onto a device mesh.  Weights are
        # placed once here; every jit body runs under the rules (see
        # ``_ruled``) so shard_act constraints and the kernels' shard_map
        # head splits activate at trace time.  ``tp`` is the EFFECTIVE
        # degree: 1 when the head counts don't divide the model axis (the
        # specs.py replication fallback).
        self.rules = rules
        cfg = getattr(model, "cfg", None)
        self.tp = tp_degree(
            rules,
            int(getattr(cfg, "num_heads", 1) or 1),
            int(getattr(cfg, "num_kv_heads", 1) or 1),
        )
        if rules is not None:
            params = jax.device_put(
                params,
                _named_shardings(
                    rules.mesh, param_pspecs(model.param_defs(), rules)
                ),
            )
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.cache_dtype = cache_dtype
        # quantized KV pages: ``kv_dtype`` in {"int8", "fp8"} stores the
        # paged pool quantized (parallel f32 scale pool, dequant fused into
        # the serving kernels); None keeps the full-precision pool and every
        # code path bit-identical to an engine without the argument
        self._kv_quantized = kvquant.is_quantized(kv_dtype)  # validates too
        self.kv_dtype = kv_dtype
        # tokens per KV page (paged engine) — doubles as the prefill length-
        # bucket floor so admission shapes snap to page boundaries
        self.page_size = page_size
        self._prefill = jax.jit(self._ruled(model.prefill))
        # decode jits keyed by (uniform_pos, kv_bound): the kv bound is a
        # static power-of-two bucket, so short contexts stop streaming the
        # whole padded cache and compile count stays logarithmic
        self._decode_fns: Dict[Tuple[bool, Optional[int]], Callable] = {}
        self._paged_decode_fns: Dict[int, Callable] = {}
        self._spec_decode_fns: Dict[Tuple[int, int], Callable] = {}
        # jitted slot-level patch of the device-resident decode mirrors
        # (page-table rows / positions / next tokens / active mask): one
        # donated scatter call per dirty boundary instead of eager .at[]
        # updates, whose per-call dispatch cost dwarfs the transfer itself.
        # Dirty counts are pow2-bucketed (padded with repeats of the last
        # dirty slot) so the scatter compiles log2(num_slots) variants, not
        # one per distinct count; the bucket set is compile-accounted
        def mirror_patch(table, pos, nxt, mask, idx, rows, p, n, m):
            return (
                table.at[idx].set(rows),
                pos.at[idx].set(p),
                nxt.at[idx].set(n),
                mask.at[idx].set(m),
            )

        self._mirror_patch = jax.jit(mirror_patch, donate_argnums=(0, 1, 2, 3))
        self._mirror_patch_shapes: set = set()
        # copy-on-write page duplication (prefix caching): one donated
        # gather/scatter over the pools per shared page about to be written
        # (the quantized variant donates and copies the scale pools too)
        self._cow_copy = jax.jit(ops.copy_pages, donate_argnums=(0, 1))
        self._cow_copy_q = jax.jit(ops.copy_pages, donate_argnums=(0, 1, 4, 5))
        self._cow_shapes: set = set()
        # live KV migration: checkpoint gathers a request's pages into a
        # contiguous snapshot (no donation — the pool stays live), restore
        # scatters a snapshot into freshly allocated pages (donated pools,
        # like COW).  The quantized variants move the scale pools too.
        self._export = jax.jit(ops.export_pages)
        self._import = jax.jit(ops.import_pages, donate_argnums=(0, 1))
        self._import_q = jax.jit(ops.import_pages, donate_argnums=(0, 1, 5, 6))
        self._xfer_shapes: set = set()
        self._paged_prefill_fns: Dict[Tuple[int, int], Callable] = {}
        self._packed_prefill_fns: Dict[Tuple[int, int, int, int], Callable] = {}
        self._slot_writers: Dict[int, Callable] = {}
        self._prefill_shapes: set = set()
        fam = getattr(model.cfg, "family", "")
        # right-padded ragged prefill (and kv-bounded decode) is exact only
        # for pure-attention caches; ssm/hybrid state scans absorb pads and
        # the hybrid ring cache wraps, so those keep exact-length shapes
        self._ragged_ok = fam in ("dense", "moe", "encdec")

    def _ruled(self, fn: Callable) -> Callable:
        """Run ``fn`` under this engine's activation sharding rules.

        jit traces the wrapped body on first call, so entering the context
        inside the wrapper is what makes ``shard_act`` constraints and the
        serving kernels' shard_map head splits visible to GSPMD.  Identity
        when the engine has no rules (single-device); the wrapper keeps
        ``fn``'s name, which names the compiled program."""
        if self.rules is None:
            return fn
        rules = self.rules

        @wraps(fn)
        def wrapped(*args, **kwargs):
            with set_activation_rules(rules):
                return fn(*args, **kwargs)

        return wrapped

    # -- compile accounting --------------------------------------------------
    def compile_stats(self) -> Dict[str, int]:
        """Distinct jitted variants per path (the engine's compile budget).

        Counts are per-ENGINE-INSTANCE (every variant cache lives on
        ``self``), cumulative over the instance's lifetime; engines built in
        the same process never see each other's counts.  Per-run reporting
        (``PagedStats.compile_stats``) uses :meth:`_compile_delta` so a run's
        numbers aren't inflated by warmups or other serve modes that shared
        the instance.
        """
        return {
            "prefill": len(self._prefill_shapes),
            "decode": len(self._decode_fns),
            "paged_prefill": len(self._paged_prefill_fns),
            "packed_prefill": len(self._packed_prefill_fns),
            "paged_decode": len(self._paged_decode_fns),
            "spec_decode": len(self._spec_decode_fns),
            "mirror_patch": len(self._mirror_patch_shapes),
            "cow_copy": len(self._cow_shapes),
            "page_xfer": len(self._xfer_shapes),
        }

    def _compile_delta(self, before: Dict[str, int]) -> Dict[str, int]:
        """Jit variants added since a ``compile_stats()`` snapshot."""
        return {k: v - before.get(k, 0) for k, v in self.compile_stats().items()}

    def _decode_step_fn(self, uniform: bool, kv_bound: Optional[int]) -> Callable:
        key = (uniform, kv_bound)
        fn = self._decode_fns.get(key)
        if fn is None:
            fn = jax.jit(
                self._ruled(
                    partial(self.model.decode, uniform_pos=uniform,
                            kv_bound=kv_bound)
                ),
                donate_argnums=(2,),
            )
            self._decode_fns[key] = fn
        return fn

    def _kv_bucket(self, live_len: int) -> Optional[int]:
        if not self._ragged_ok:
            return None
        return bucket_pow2(live_len, floor=min(self.page_size, self.max_seq),
                           cap=self.max_seq)

    # -- prompt padding ------------------------------------------------------
    def _pad_prompts(
        self, prompts: List[np.ndarray], max_new_tokens: int = 0
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Pad a prompt batch to one prefill shape.

        Attention families RIGHT-pad to a power-of-two bucket (floored at
        ``page_size``): causal attention never reads trailing pads and the
        model gathers logits at ``lengths - 1``, so every distinct prompt
        length no longer costs a fresh XLA compile.  SSM/hybrid keep the
        exact batch max (left-padded) since their state scans the full row.
        """
        b = len(prompts)
        if b > self.max_batch:
            raise ValueError(f"batch {b} > max_batch {self.max_batch}")
        lens = np.asarray([len(p) for p in prompts], np.int32)
        max_len = int(lens.max())
        if max_len + max_new_tokens > self.max_seq:
            raise ValueError("prompt + generation exceeds max_seq")
        if self._ragged_ok:
            padded = bucket_pow2(
                max_len,
                floor=min(self.page_size, self.max_seq),
                cap=max(self.max_seq - max_new_tokens, max_len),
            )
            out = np.zeros((b, padded), np.int32)
            for i, p in enumerate(prompts):
                out[i, : len(p)] = p
            return out, lens
        out = np.zeros((b, max_len), np.int32)
        for i, p in enumerate(prompts):
            # left-pad so every prompt's last token sits at max_len-1; the
            # causal mask plus identical suffix alignment keeps decode simple
            out[i, max_len - len(p):] = p
        return out, lens

    def generate(
        self,
        prompts: List[np.ndarray],
        max_new_tokens: int,
        extra_inputs: Optional[Dict[str, Any]] = None,
        greedy: bool = True,
    ) -> GenerationResult:
        tokens, lens = self._pad_prompts(prompts, max_new_tokens)
        b, s = tokens.shape
        max_len = int(lens.max())
        cache = self.model.init_cache(b, self.max_seq, dtype=self.cache_dtype)
        batch = {"tokens": jnp.asarray(tokens)}
        if self._ragged_ok:
            batch["lengths"] = jnp.asarray(lens)
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
        t0 = time.perf_counter()
        logits, cache = jax.block_until_ready(self._prefill(self.params, batch, cache))
        self._prefill_shapes.add((b, s))
        t1 = time.perf_counter()
        out = np.zeros((b, max_new_tokens), np.int32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # left-padded families sit at one common position; right-padded ragged
        # batches decode at per-row positions via the masked-update path
        uniform = (not self._ragged_ok) or bool((lens == lens[0]).all())
        for i in range(max_new_tokens):
            out[:, i] = np.asarray(nxt)
            decode = self._decode_step_fn(uniform, self._kv_bucket(max_len + i + 1))
            logits, cache = decode(self.params, nxt, cache)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.block_until_ready(logits)
        t2 = time.perf_counter()
        decode_s = t2 - t1
        return GenerationResult(
            tokens=out,
            prefill_s=t1 - t0,
            decode_s=decode_s,
            tokens_per_s=b * max_new_tokens / decode_s if decode_s > 0 else float("inf"),
        )

    # -- continuous batching -------------------------------------------------
    def _slot_writer(self, num_slots: int) -> Callable:
        """Jitted scatter of a batch-1 cache into slot ``i`` of the pool.

        The batch axis of each cache leaf comes from the model's own P-tree
        axis names, so this works for every cache layout (dense/MoE KV,
        interleaved pairs, SSM state, hybrid, enc-dec cross caches).
        """
        writer = self._slot_writers.get(num_slots)
        if writer is not None:
            return writer
        defs = self.model.cache_defs(num_slots, self.max_seq, dtype=self.cache_dtype)
        axis_tree = tree_map_defs(lambda path, p: p.axes.index("batch"), defs)

        def write(pool, one, slot):
            def w(dst, src, ax):
                starts = tuple(slot if i == ax else 0 for i in range(dst.ndim))
                return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), starts)

            return jax.tree.map(w, pool, one, axis_tree)

        writer = jax.jit(write, donate_argnums=(0,))
        self._slot_writers[num_slots] = writer
        return writer

    def serve_continuous(
        self,
        requests: List[ServeRequest],
        num_slots: Optional[int] = None,
        clock: Callable[[], float] = time.perf_counter,
    ) -> ContinuousStats:
        """Slot-based continuous-batching generate loop.

        All prompts are padded to a common (bucketed) prefill length — one
        compile; admission runs a batch-1 prefill and scatters its cache into
        the free slot, then every decode step advances all active slots
        together.  ``clock`` is injectable so tests measure deterministic
        timings.
        """
        if not requests:
            return ContinuousStats([], 0, 0.0, 0, 0.0, 0.0)
        if getattr(self.model.cfg, "family", "") == "encdec":
            raise NotImplementedError(
                "continuous batching does not support encoder-decoder models: "
                "admission prefill would need per-request encoder frames"
            )
        num_slots = num_slots or self.max_batch
        max_prompt = max(len(r.prompt) for r in requests)
        if self._ragged_ok:
            prefill_len = bucket_pow2(
                max_prompt, floor=min(self.page_size, self.max_seq), cap=self.max_seq
            )
        else:
            prefill_len = max_prompt
        for r in requests:
            # left-padded families start every slot at prefill_len, so their
            # decode budget is measured from the padded length, not the
            # prompt's own; right-padded ragged slots start at len(prompt)
            start = len(r.prompt) if self._ragged_ok else prefill_len
            if start + r.max_new_tokens > self.max_seq:
                raise ValueError(
                    f"request {r.request_id}: prompt + generation exceeds max_seq"
                )
        pool = SlotPool(num_slots)
        cache = self.model.init_cache(num_slots, self.max_seq, dtype=self.cache_dtype)
        write = self._slot_writer(num_slots)
        # one reusable batch-1 cache for admission prefills (prefill is
        # functional: it returns a fresh tree, the zeros base is never mutated)
        cache1 = self.model.init_cache(1, self.max_seq, dtype=self.cache_dtype)
        queue = deque(requests)
        nxt = np.zeros((num_slots,), np.int32)
        # slot -> [generated tokens]; slot -> live length (prompt + generated)
        slot_tokens: Dict[int, List[int]] = {}
        slot_len: Dict[int, int] = {}
        finished: Dict[int, RequestResult] = {}
        t_start = clock()
        submit_s = {r.request_id: t_start for r in requests}
        step = 0
        occupancy_sum = 0
        while queue or pool.num_active:
            # retire sequences that already hold all their tokens, so their
            # slots are free for admission at this same step boundary
            for slot in list(pool.active):
                req = pool.active[slot]
                if len(slot_tokens[slot]) >= req.max_new_tokens:
                    now = clock()
                    finished[req.request_id] = RequestResult(
                        request_id=req.request_id,
                        tokens=np.asarray(slot_tokens.pop(slot), np.int32),
                        slot=slot,
                        admit_step=req._admit_step,  # type: ignore[attr-defined]
                        finish_step=step,
                        ttft_s=req._ttft_s,          # type: ignore[attr-defined]
                        latency_s=now - submit_s[req.request_id],
                        tokens_per_s=(
                            req.max_new_tokens / (now - submit_s[req.request_id])
                            if now > submit_s[req.request_id] else float("inf")
                        ),
                    )
                    pool.release(slot)
                    slot_len.pop(slot, None)
            # admission at the decode-step boundary: fill every free slot
            while queue and pool.num_free:
                req = queue.popleft()
                slot = pool.admit(req, step=step)
                padded = np.zeros((prefill_len,), np.int32)
                batch1 = {}
                if self._ragged_ok:
                    padded[: len(req.prompt)] = req.prompt
                    batch1["lengths"] = jnp.asarray([len(req.prompt)], jnp.int32)
                else:
                    padded[prefill_len - len(req.prompt):] = req.prompt
                batch1["tokens"] = jnp.asarray(padded[None])
                logits1, filled = self._prefill(self.params, batch1, cache1)
                self._prefill_shapes.add((1, prefill_len))
                tok0 = int(jnp.argmax(logits1[0]))
                cache = write(cache, filled, jnp.int32(slot))
                nxt[slot] = tok0
                slot_tokens[slot] = [tok0]
                slot_len[slot] = (
                    len(req.prompt) if self._ragged_ok else prefill_len
                )
                req._admit_step = step          # type: ignore[attr-defined]
                req._ttft_s = clock() - submit_s[req.request_id]  # type: ignore
            if not pool.num_active:
                if queue:
                    continue            # freshly-retired slots admit the queue
                break
            if all(
                len(slot_tokens[s]) >= pool.active[s].max_new_tokens
                for s in pool.active
            ):
                continue  # every active slot is at budget: retire, don't decode
            # one decode step for the whole pool (inactive slots are ignored);
            # the kv bound tracks the longest live slot, not padded max_seq
            decode = self._decode_step_fn(
                False, self._kv_bucket(max(slot_len.values()) + 1)
            )
            logits, cache = decode(self.params, jnp.asarray(nxt), cache)
            tokens_all = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            step += 1
            occupancy_sum += pool.num_active
            for slot in pool.active:
                if len(slot_tokens[slot]) < pool.active[slot].max_new_tokens:
                    slot_tokens[slot].append(int(tokens_all[slot]))
                    nxt[slot] = tokens_all[slot]
                    slot_len[slot] += 1
        jax.block_until_ready(cache["pos"])
        wall = clock() - t_start
        results = [finished[r.request_id] for r in requests]
        total_tokens = sum(len(r.tokens) for r in results)
        return ContinuousStats(
            results=results,
            steps=step,
            wall_s=wall,
            total_tokens=total_tokens,
            throughput_tps=total_tokens / wall if wall > 0 else float("inf"),
            mean_slot_occupancy=occupancy_sum / step if step else float(num_slots),
        )

    # -- paged serving -------------------------------------------------------
    def _paged_decode_fn(self, pages_bound: int) -> Callable:
        """One fused paged decode step: attention + on-device argmax + the
        device-resident next-token/position bump for masked rows.  Fetching
        the returned ``tok`` array is the boundary's only host sync — no
        separate argmax dispatch, no per-step table/position re-upload."""
        fn = self._paged_decode_fns.get(pages_bound)
        if fn is None:

            def paged_decode_step(params, nxt, cache, table, pos, mask):
                logits, cache = self.model.decode_paged(
                    params, nxt, cache, table, pos, pages_bound=pages_bound
                )
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                new_nxt = jnp.where(mask, tok, nxt)
                new_pos = jnp.where(mask, pos + 1, pos)
                return tok, new_nxt, new_pos, cache

            fn = jax.jit(self._ruled(paged_decode_step),
                         donate_argnums=(1, 2, 4))
            self._paged_decode_fns[pages_bound] = fn
        return fn

    def _spec_decode_fn(self, pages_bound: int, W: int) -> Callable:
        """One fused verify step: multi-token paged attention over each
        slot's ``[next_token, draft_1..draft_k]`` window + on-device greedy
        argmax + exact-match draft acceptance + the position bump by
        ``accepted + 1``.  One jit variant per (pages bucket, window size)
        — draft depth is a config knob, not a per-step shape.  Returns
        ``(greedy (b, W), n_accept (b,), new_pos, new_nxt, cache)``; greedy
        row ``w`` is the model's next token after consuming the window's
        first ``w + 1`` tokens, so the emitted tokens
        ``greedy[:, :n_accept + 1]`` are bit-identical to the
        non-speculative decode sequence.  Positions and the next-token
        mirror advance on device, so a verify boundary leaves nothing to
        re-upload before the next launch."""
        key = (pages_bound, W)
        fn = self._spec_decode_fns.get(key)
        if fn is None:

            def spec_verify_step(params, win, cache, table, pos, wlens, nxt):
                logits, cache = self.model.decode_spec(
                    params, win, cache, table, pos, wlens,
                    pages_bound=pages_bound,
                )
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if W > 1:
                    # draft j survives iff it equals the model's own greedy
                    # choice at the previous position AND every earlier
                    # draft survived (cumprod); pad columns never match
                    m = (win[:, 1:] == greedy[:, :-1]) & (
                        jnp.arange(1, W, dtype=jnp.int32)[None, :]
                        < wlens[:, None]
                    )
                    n_accept = (
                        jnp.cumprod(m.astype(jnp.int32), axis=1)
                        .sum(axis=1)
                        .astype(jnp.int32)
                    )
                else:
                    n_accept = jnp.zeros(win.shape[:1], jnp.int32)
                active = wlens > 0
                new_pos = jnp.where(active, pos + n_accept + 1, pos)
                # last emitted token = greedy at the last accepted position:
                # advancing the next-token mirror on device leaves a verify
                # boundary with nothing to re-upload before the next launch
                last = jnp.take_along_axis(greedy, n_accept[:, None], axis=1)
                new_nxt = jnp.where(active, last[:, 0], nxt)
                return greedy, n_accept, new_pos, new_nxt, cache

            fn = jax.jit(self._ruled(spec_verify_step),
                         donate_argnums=(2, 4, 6))
            self._spec_decode_fns[key] = fn
        return fn

    def _paged_prefill_fn(self, chunk_len: int, pos0: int) -> Callable:
        """Chunk shapes are page-bucketed, so variants are keyed by
        (chunk_len, pos0) with at most ``prefill_chunk / page_size`` chunk
        lengths and ``max_seq / prefill_chunk`` offsets (the context-gather
        shape is exactly ``pos0`` tokens — garbage-free, at the price of one
        variant per chunk offset, shared across all requests)."""
        key = (chunk_len, pos0)
        fn = self._paged_prefill_fns.get(key)
        if fn is None:

            def paged_prefill_chunk(params, chunk, cache, table_row, last):
                return self.model.prefill_paged_chunk(
                    params, chunk, cache, table_row, last, pos0=pos0
                )

            fn = jax.jit(self._ruled(paged_prefill_chunk), donate_argnums=(2,))
            self._paged_prefill_fns[key] = fn
        return fn

    def _packed_prefill_fn(self, t_pack: int, num_chunks: int,
                           max_pages: int, pages_bound: int) -> Callable:
        """One jit variant per (packed length, chunk rows, table width,
        context-pages bound) — i.e. ONE compile per serve configuration for
        every way prompt lengths mix inside the buffer, times a logarithmic
        handful of pow2 ``pages_bound`` buckets (the bound keeps a launch
        whose chunks have little committed context from paying the
        full-table context gather)."""
        key = (t_pack, num_chunks, max_pages, pages_bound)
        fn = self._packed_prefill_fns.get(key)
        if fn is None:

            def packed_prefill_step(params, batch, cache):
                return self.model.prefill_packed(
                    params, batch, cache, pages_bound=pages_bound
                )

            fn = jax.jit(self._ruled(packed_prefill_step), donate_argnums=(2,))
            self._packed_prefill_fns[key] = fn
        return fn

    def serve_paged(
        self,
        requests: List[ServeRequest],
        num_slots: Optional[int] = None,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        overcommit: float = 1.0,
        prefill_mode: str = "packed",
        prefill_budget: Optional[int] = None,
        spec_k: int = 0,
        spec_ngram: int = 3,
        prefix_cache: bool = False,
        clock: Callable[[], float] = time.perf_counter,
        tracer=None,
        fault_hook: Optional[Callable] = None,
        deadline_ms: float = 0.0,
        tenants: Optional[List[TenantSpec]] = None,
        fairness: bool = True,
        checkpoint_every: int = 0,
        checkpoints: Optional[Dict[int, PageSnapshot]] = None,
        restores: Optional[Dict[int, PageSnapshot]] = None,
    ) -> PagedStats:
        """Paged-KV continuous batching.

        The KV cache is a global pool of ``num_pages`` pages of ``page_size``
        tokens; each slot owns only the pages its live tokens need, recorded
        in a per-slot page table.  Admission is keyed on *free pages*: a
        request enters when a slot and its prompt's pages are available AND
        the pool's committed worst-case pages (every active request's
        ``prompt + max_new_tokens``) stay within ``capacity * overcommit`` —
        at the default 1.0 growth can never fail, so preemption never fires.
        ``overcommit > 1`` admits more aggressively (live usage is usually
        far below worst case); if the gamble loses and a decode step finds
        the pool dry, the youngest request is preempted (pages freed,
        request requeued for recompute-style restart).

        Prefill interleaves with decode at step boundaries in one of two
        pipelines.  ``prefill_mode="packed"`` (default) coalesces every
        prefilling slot's next span into ONE token-packed varlen launch of
        ``prefill_budget`` tokens per boundary (oldest request first): no
        pow2 padding, the kernel writes K/V straight into the page pool,
        and one jit variant serves every length mix — ``prefill_budget`` is
        the knob bounding how much prefill work may delay the decode step.
        ``prefill_mode="chunked"`` is the legacy path: one
        ``prefill_chunk``-token batch-1 chunk per slot per boundary, one
        jit variant per chunk length × offset.  Greedy tokens are identical
        to ``serve_continuous`` in both modes.

        ``spec_k > 0`` turns on self-speculative decoding: at each boundary
        a host-side prompt-lookup drafter (n-gram match of the last
        ``spec_ngram`` committed tokens against the request's prompt +
        output) proposes up to ``spec_k`` draft tokens per slot, and ONE
        multi-token verification launch scores every slot's ``[next_token,
        draft_1..draft_k]`` window against the paged pool — the KV working
        set streams once for up to ``spec_k + 1`` tokens.  Acceptance is
        greedy exact-match, so emitted tokens stay bit-identical to the
        non-speculative path; rejected suffixes roll back by rewinding
        ``lengths`` (pages are append-only) plus a page-table truncation
        when a rejected draft had opened a fresh page.  Boundaries where no
        slot has a draft fall back to a plain fused decode step, so
        lookup-hostile text pays only the host-side scan.

        ``prefix_cache=True`` turns on automatic prefix caching: every full
        prompt page a request prefills is registered in a
        :class:`~repro.serve.page_table.PrefixCache` (hash-chained token
        blocks -> physical pages), and admission maps the longest cached
        page-aligned prefix of each new prompt read-only into the slot's
        table — only the uncached suffix is prefilled (page-aligned, so the
        packed/chunked pipelines need no new shapes), cached tokens cost
        the :class:`PrefillBudget` nothing, and the worst-case page
        commitment counts shared pages ONCE globally, multiplying peak
        concurrency on shared-prefix workloads.  A full hit (page-aligned
        prompt entirely cached) skips prefill outright and replays the last
        prompt token through the decode path — the append into the shared
        last page copy-on-writes it to a private page first (a device-side
        page copy), so cached content is never mutated and greedy tokens
        stay bit-identical to a cache-off run.  Pages released by finished
        requests stay cached (refcount 1: the cache's own reference) in an
        LRU tier reclaimed only when admission/growth/COW actually need
        pages; eviction never touches a referenced page, and preemption
        still works unchanged (shared pages just drop a reference).

        ``fault_hook`` (None by default — the zero-cost path) is called once
        per loop boundary with a :class:`~repro.serve.faults.FaultContext`
        (step counter, page pool, clock, tracer): the fleet's fault
        injection and heartbeat-lease hooks both ride it.  A hook that
        raises :class:`~repro.serve.faults.WorkerCrash` kills the run, but
        resumably: the exception is re-raised carrying ``results`` (every
        request already finished — commit-worthy) and ``pending`` (every
        request not yet finished — replayable from its prompt, exactly the
        preemption-recompute contract), so a router can requeue the
        worker's in-flight work onto survivors with zero silent losses.

        ``deadline_ms > 0`` sets a run TTL (fleet-parity semantics): a
        request still queued past the deadline is terminally ``rejected``
        (never silently dropped), and a request that finishes late stays
        ``completed`` but falls out of ``goodput``.  With a warm decode-rate
        estimate, admission also sheds queued work whose deadline is
        already unmeetable given the queue's prompt tokens ahead, the
        per-boundary prefill budget, and the measured decode tok/s.
        ``tenants`` registers :class:`~repro.serve.scheduler.TenantSpec`
        contracts (priority tier, fair-share weight, token bucket charged
        in prompt+decode tokens); admission then dequeues by priority tier
        and weighted fair share instead of FIFO (work-conserving: dry
        tenants are deprioritized, never starved), and preemption evicts
        the lowest-priority youngest slot first.  ``fairness=False`` keeps
        strict FIFO admission (the baseline the SLO benchmark compares
        against).

        ``checkpoint_every=K > 0`` (with a ``checkpoints`` dict) makes
        in-flight KV state a transferable artifact: every K decode steps,
        each decoding slot's live pages are gathered into a contiguous
        :class:`~repro.serve.page_table.PageSnapshot` (exact stored bytes —
        quantized pools snapshot codes + scales — plus per-page checksums,
        lengths and emitted tokens) and written to ``checkpoints`` keyed by
        request id.  The checkpoint runs at the boundary top, BEFORE the
        fault hook, so a crash at boundary S leaves checkpoints as-of S
        (staleness is bounded by the cadence K).  A
        :class:`~repro.serve.faults.WorkerDrain` raised by the hook
        additionally snapshots every live decoding slot fresh before the
        crash re-raises — planned handoff loses zero tokens.  ``restores``
        maps request ids to snapshots a previous worker checkpointed: at
        admission such a request skips prefill entirely — checksums are
        verified, pages scatter into freshly allocated pages, lengths and
        emitted tokens rebuild the slot, and decoding continues
        bit-identically to an undisturbed run.  A failed verify counts a
        ``checksum_failure``, drops the snapshot, and the request falls
        back to ordinary prefill (replay-from-prompt) — corrupted state is
        never served.
        """
        if prefill_mode not in ("packed", "chunked"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        if checkpoint_every < 0:
            raise ValueError("checkpoint_every must be >= 0")
        if checkpoint_every > 0 and checkpoints is None:
            raise ValueError("checkpoint_every > 0 needs a checkpoints dict")
        if not requests:
            return PagedStats([], 0, 0.0, 0, 0.0, 0.0, 0, self.page_size, 0,
                              0.0, 0, 0, 0, {}, prefill_mode=prefill_mode,
                              tp=self.tp,
                              kv_dtype=self.kv_dtype or self.cache_dtype)
        if overcommit <= 0:
            raise ValueError("overcommit must be > 0")
        compiles_before = self.compile_stats()
        page_size = page_size or self.page_size
        num_slots = num_slots or self.max_batch
        prefill_chunk = prefill_chunk or 4 * page_size
        prefill_chunk = max(
            page_size, (prefill_chunk // page_size) * page_size
        )  # chunk starts must stay page-aligned
        packed = prefill_mode == "packed"
        # packed-buffer size: the per-boundary prefill token budget, snapped
        # to a page multiple (chunk spans inside the buffer are page-aligned)
        t_pack = max(
            page_size,
            ((prefill_budget or 4 * prefill_chunk) // page_size) * page_size,
        )
        budget = PrefillBudget(t_pack) if packed else None
        max_pages_per_seq = pages_needed(self.max_seq, page_size)
        if num_pages is None:
            num_pages = num_slots * max_pages_per_seq + 1
        pool = PagePool(num_pages, page_size, reserved=1)
        # admission budget: worst-case commitment per the overcommit factor,
        # but never above physical capacity (growth still needs real pages)
        commit_budget = min(pool.capacity, pool.capacity * overcommit)
        for r in requests:
            if len(r.prompt) + r.max_new_tokens > self.max_seq:
                raise ValueError(
                    f"request {r.request_id}: prompt + generation exceeds max_seq"
                )
            if pool.pages_needed(len(r.prompt) + r.max_new_tokens) > commit_budget:
                raise ValueError(
                    f"request {r.request_id}: needs more pages than the pool "
                    f"(or overcommit budget) admits"
                )
        slots = PagedSlotPool(num_slots, pool, tracer=tracer, clock=clock)
        table = PageTable(num_slots, max_pages_per_seq, scratch_page=0)
        pcache = PrefixCache(pool) if prefix_cache else None
        # quantized mode swaps the pool dtype and adds the f32 scale pools
        # (paged_cache_defs branches on the dtype string)
        pool_dtype = self.kv_dtype or self.cache_dtype
        cache = self.model.init_paged_cache(
            num_pages, page_size, dtype=pool_dtype
        )
        if self.rules is not None:
            # heads-split pool: each shard holds kv/tp heads of EVERY page,
            # so a fixed per-shard page budget carries tp× the tokens while
            # the PagePool/PageTable accounting above stays host-global
            # (the scale pools shard on the same kv-head axis)
            cache = jax.device_put(
                cache,
                _named_shardings(
                    self.rules.mesh,
                    self.model.paged_cache_pspecs(
                        self.rules, num_pages, page_size,
                        dtype=pool_dtype,
                    ),
                ),
            )
        queue = deque(requests)
        # -- SLO / multi-tenant admission state ---------------------------
        tenant_ledger = TenantLedger(tenants or ())
        fair = fairness and (
            bool(tenants)
            or any(getattr(r, "tenant", "default") != "default"
                   or getattr(r, "priority", 1) != 1 for r in requests)
        )

        def req_cost(r) -> float:
            # bucket charge: prompt + worst-case decode tokens
            return float(len(r.prompt) + r.max_new_tokens)

        def req_prio(r) -> int:
            p = getattr(r, "priority", None)
            return 1 if p is None else int(p)

        rejected_n = 0
        deferred_n = 0
        decode_tokens_emitted = 0
        nxt = np.zeros((num_slots,), np.int32)
        lengths = np.zeros((num_slots,), np.int32)   # live tokens per slot
        slot_tokens: Dict[int, List[int]] = {}
        slot_times: Dict[int, List[float]] = {}      # token-emission clocks
        prefilling: Dict[int, int] = {}              # slot -> next chunk start
        decoding: set = set()
        admit_order: Dict[int, int] = {}             # slot -> admission sequence
        admit_seq = 0
        # prefix-cache bookkeeping: per-slot worst-case PRIVATE page
        # commitment, cached tokens granted at admission, prompt tokens
        # prefilled this admission, and full-hit slots awaiting their first
        # decode emission (their TTFT is that boundary, not a prefill)
        slot_commit: Dict[int, int] = {}
        slot_cached: Dict[int, int] = {}
        slot_prefilled: Dict[int, int] = {}
        replay_first: set = set()
        # slots rebuilt from a migrated snapshot: their prompt was never
        # admitted to THIS worker's prefill ledger, so a later preemption
        # must not charge it as dropped prefill debt
        restored_slots: set = set()
        # pages slots mapped FROM the cache (not allocated themselves): the
        # commitment ledger counts each of these once globally, no matter
        # how many requests share it — the concurrency multiplier
        pinned_refs: Dict[int, int] = {}             # page -> mapping slots
        slot_shared: Dict[int, List[int]] = {}       # slot -> acquired pages
        finished: Dict[int, RequestResult] = {}
        t_start = clock()
        submit_s = {r.request_id: t_start for r in requests}
        deadline = t_start + deadline_ms / 1e3 if deadline_ms > 0 else None
        step = 0
        occupancy_sum = 0
        peak_occupancy = 0
        pages_sum = 0.0
        samples = 0
        chunks_done = 0
        prefill_launches = 0
        prefill_s = 0.0
        prefill_tokens = 0
        prefill_padded = 0
        kv_live = kv_rect = 0
        prompt_admitted = 0
        saved_tokens = 0
        dropped_tokens = 0
        cow_copies = 0
        ckpt_saved = 0
        ckpt_bytes = 0
        restored_n = 0
        restored_tok = 0
        restore_bytes = 0
        checksum_failures = 0
        last_ckpt_step = -1
        decode_s = 0.0
        spec = spec_k > 0
        ledger = SpecLedger() if spec else None
        admitted_at: Dict[int, float] = {}           # request -> first admission
        boundaries = 0
        host_s = 0.0
        # -- device-resident decode state: the page table, per-slot positions
        # and (non-spec) next tokens / active mask live on device and are
        # patched only for slots that changed (admission, page growth,
        # release, rollback) — steady-state boundaries upload nothing and
        # fetch one small int32 array (the fused argmax / acceptance result)
        dev_table = jnp.zeros((num_slots, max_pages_per_seq), jnp.int32)
        dev_pos = jnp.zeros((num_slots,), jnp.int32)
        dev_nxt = jnp.zeros((num_slots,), jnp.int32)
        dev_mask = jnp.zeros((num_slots,), bool)
        if self.rules is not None:
            # explicitly replicated so the donated mirror-patch scatter and
            # the decode launches agree on placement from the first step
            # (no GSPMD resharding inserted at a steady-state boundary)
            rep = NamedSharding(self.rules.mesh, PartitionSpec())
            dev_table, dev_pos, dev_nxt, dev_mask = (
                jax.device_put(a, rep)
                for a in (dev_table, dev_pos, dev_nxt, dev_mask)
            )
        cur_mask = np.zeros((num_slots,), bool)
        dirty: set = set()                           # slots needing a patch
        # -- analytic TP-collective ledger: every transformer layer closes
        # two tensor-parallel boundaries (attention o-proj, MLP down-proj),
        # each summing a (tokens, d_model) partial block output across the
        # model axis.  Ring all-reduce moves 2(tp-1)/tp of the payload per
        # shard; reduce-scatter (rs_block_outputs, seq-shardable launches
        # only) halves that.  Emitted per launch for analysis.tp_summary.
        tp = self.tp
        rs_opt = bool(
            self.rules is not None
            and self.rules.opts.get("rs_block_outputs")
        )
        d_model = int(getattr(self.model.cfg, "d_model", 0) or 0)
        n_layers = int(getattr(self.model.cfg, "num_layers", 0) or 0)

        def tp_event(phase: str, t0: float, t1: float, tokens: int,
                     seq_shardable: bool = False) -> None:
            if tp <= 1 or tracer is None or not tokens:
                return
            kind = (
                "reduce_scatter"
                if rs_opt and seq_shardable and tokens % tp == 0
                else "psum"
            )
            count = 2 * n_layers
            payload = tokens * d_model * 4           # f32 block outputs
            factor = (tp - 1) / tp * (2.0 if kind == "psum" else 1.0)
            tracer.event(
                "tp:collective", t0, t1, phase=phase, kind=kind, tp=tp,
                count=count, payload_bytes=payload * count,
                moved_bytes=int(payload * count * factor),
            )

        def _span(name: str, timed: bool = False):
            """A span of this loop (``_LoopSpan``); without a tracer, and
            unless the loop needs its times, only the profiler's
            annotation."""
            if tracer is None and not timed:
                return jax.profiler.TraceAnnotation(name)
            return _LoopSpan(name, tracer, clock, step)

        def sync_device(active: List[int]) -> int:
            """Patch the device mirrors for slots whose table row, position,
            next token or active-mask bit changed since the last launch —
            one jitted donated scatter over exactly the dirty slots.
            Returns how many slots were patched."""
            nonlocal dev_table, dev_pos, dev_nxt, dev_mask, cur_mask
            new_mask = np.zeros((num_slots,), bool)
            new_mask[active] = True
            stale = dirty | set(np.nonzero(new_mask != cur_mask)[0].tolist())
            if stale:
                # pad the dirty set to a pow2 bucket with repeats of the
                # last dirty slot (duplicate scatter indices write the same
                # values, so the patch is idempotent): log2(num_slots)
                # variants instead of one per distinct dirty count
                cnt = bucket_pow2(len(stale), cap=num_slots)
                # keyed by the full traced shape: a same-engine run with a
                # different slot count / table width re-traces the patch jit
                # and must show up in the compile delta
                self._mirror_patch_shapes.add((num_slots, max_pages_per_seq, cnt))
                idx = np.fromiter(sorted(stale), np.int32, len(stale))
                idx = np.concatenate(
                    [idx, np.full((cnt - len(idx),), idx[-1], np.int32)]
                )
                rows = np.where(
                    new_mask[idx, None], table.table[idx], np.int32(0)
                )
                dev_table, dev_pos, dev_nxt, dev_mask = self._mirror_patch(
                    dev_table, dev_pos, dev_nxt, dev_mask, idx, rows,
                    np.where(new_mask[idx], lengths[idx], 0).astype(np.int32),
                    np.where(new_mask[idx], nxt[idx], 0).astype(np.int32),
                    new_mask[idx],
                )
                cur_mask = new_mask
                dirty.clear()
            return len(stale)

        def unpin(slot: int, page: int) -> None:
            """Drop ``slot``'s record of mapping ``page`` from the cache (the
            commitment ledger's pinned set must mirror the actual mappings)."""
            held = slot_shared.get(slot, [])
            if page in held:
                held.remove(page)
                pinned_refs[page] -= 1
                if not pinned_refs[page]:
                    del pinned_refs[page]

        def release_slot(slot: int, preempted: bool = False):
            nonlocal dropped_tokens
            req = slots.release_paged(slot, table.clear(slot), preempted=preempted)
            if preempted and slot not in restored_slots:
                # prompt tokens this admission promised but never prefilled:
                # the recompute debt the saved-token ledger must stay exact
                # against (cached grants + computed tokens cover the rest)
                dropped_tokens += max(
                    len(req.prompt)
                    - slot_cached.get(slot, 0)
                    - slot_prefilled.get(slot, 0),
                    0,
                )
            lengths[slot] = 0
            slot_tokens.pop(slot, None)
            slot_times.pop(slot, None)
            prefilling.pop(slot, None)
            decoding.discard(slot)
            admit_order.pop(slot, None)
            slot_commit.pop(slot, None)
            slot_cached.pop(slot, None)
            slot_prefilled.pop(slot, None)
            replay_first.discard(slot)
            restored_slots.discard(slot)
            for p in list(slot_shared.get(slot, [])):
                unpin(slot, p)
            slot_shared.pop(slot, None)
            dirty.add(slot)
            return req

        def preempt_one() -> Optional[int]:
            """Evict the lowest-priority youngest request (recompute-style):
            free its pages and push it back to the queue front.  Within one
            priority tier this is the globally youngest slot; best-effort
            work is always evicted before any higher tier.  The victim may
            be the very slot that asked to grow — self-preemption parks it
            back in the queue rather than evicting older work for it."""
            if not admit_order:
                return None
            victim = min(
                admit_order,
                key=lambda s: (req_prio(slots.active[s]), -admit_order[s]),
            )
            queue.appendleft(release_slot(victim, preempted=True))
            return victim

        def ensure_free(n: int) -> bool:
            """Guarantee ``n`` free pages, reclaiming cached-but-unreferenced
            pages (LRU, true free) before the caller has to queue or preempt
            live work — the ONLY path that evicts cache entries (the run's
            eviction count is the cache's own ``evicted_pages``)."""
            if pool.num_free >= n:
                return True
            if pcache is not None:
                evicted = pcache.evict(n - pool.num_free)
                if evicted and tracer is not None:
                    now = clock()
                    tracer.event("prefix:evict", now, now, pages=evicted)
            return pool.num_free >= n

        def cow_if_shared(s: int) -> bool:
            """Copy-on-write guard before any append at position
            ``lengths[s]``: if the destination page is still referenced by
            other holders (the prefix cache / other requests), duplicate it
            on device into a private page and remap the slot's table —
            committed cache content is never mutated.  Returns False when
            no page can be found for the copy (caller preempts)."""
            nonlocal cache, cow_copies
            li = int(lengths[s]) // page_size
            held = table.pages_of(s)
            if li >= len(held):
                return True          # append opens a fresh page (growth path)
            p = held[li]
            if pool.refcount(p) <= 1:
                return True          # exclusively ours already
            if not ensure_free(1):
                return False
            fresh = pool.alloc(1)
            if fresh is None:  # pragma: no cover - guarded by ensure_free
                return False
            t0c = clock()
            src_d = np.asarray([p], np.int32)
            dst_d = np.asarray([fresh[0]], np.int32)
            if "k_scales" in cache:
                # the scale rows move with their pages
                (cache["k_pages"], cache["v_pages"],
                 cache["k_scales"], cache["v_scales"]) = self._cow_copy_q(
                    cache["k_pages"], cache["v_pages"], src_d, dst_d,
                    cache["k_scales"], cache["v_scales"],
                )
            else:
                cache["k_pages"], cache["v_pages"] = self._cow_copy(
                    cache["k_pages"], cache["v_pages"], src_d, dst_d,
                )
            # pool shapes are per-call arguments: one jit variant per
            # (pool size, page size) configuration
            self._cow_shapes.add((num_pages, page_size))
            table.replace(s, li, fresh[0])
            pool.free([p])           # drop our reference to the shared page
            unpin(s, p)              # no longer mapped from the cache
            cow_copies += 1
            dirty.add(s)
            if tracer is not None:
                tracer.event("prefix:cow", t0c, clock(), slot=s, page=fresh[0])
            return True

        def snapshot_slot(s: int) -> Optional[PageSnapshot]:
            """Gather slot ``s``'s live pages into a transferable
            :class:`PageSnapshot`: one jitted gather of exactly the pages
            holding its first ``lengths[s]`` tokens (K/V pools and, when
            quantized, the parallel scale pools — exact stored bytes), plus
            emitted tokens, length and per-page checksums.  The gather index
            is padded to a pow2 bucket with repeats of the last real page
            (sliced off host-side) so variant count stays log2-bounded.
            Returns None for slots still prefilling (nothing to migrate —
            replay-from-prompt is already the cheapest recovery for them)."""
            nonlocal ckpt_saved, ckpt_bytes
            if s not in decoding or not slot_tokens.get(s):
                return None
            req = slots.active[s]
            length = int(lengths[s])
            held = table.pages_of(s)[: pool.pages_needed(max(length, 1))]
            if not held:
                return None
            t0s = clock()
            cnt = bucket_pow2(len(held), cap=max_pages_per_seq)
            self._xfer_shapes.add((num_pages, page_size, cnt))
            idx = np.fromiter(held, np.int32, len(held))
            idx = np.concatenate(
                [idx, np.full((cnt - len(idx),), idx[-1], np.int32)]
            )
            if "k_scales" in cache:
                arrs = self._export(
                    cache["k_pages"], cache["v_pages"], idx,
                    cache["k_scales"], cache["v_scales"],
                )
                k, v, ks, vs = (
                    np.asarray(a)[:, : len(held)] for a in arrs
                )
            else:
                arrs = self._export(cache["k_pages"], cache["v_pages"], idx)
                k, v = (np.asarray(a)[:, : len(held)] for a in arrs)
                ks = vs = None
            snap = PageSnapshot(
                request_id=req.request_id,
                prompt_len=len(req.prompt),
                length=length,
                tokens=np.asarray(slot_tokens[s], np.int32),
                k=k, v=v, k_scales=ks, v_scales=vs,
                checksums=page_checksums(k, v, ks, vs),
                step=step,
                kv_dtype=pool_dtype,
            )
            ckpt_saved += 1
            ckpt_bytes += snap.nbytes
            if tracer is not None:
                tracer.event(
                    "ckpt:save", t0s, clock(), request=req.request_id,
                    step=step, pages=len(held), bytes=snap.nbytes,
                    tokens=len(slot_tokens[s]),
                )
            return snap

        def emit_tenant(req, status: str, now: float, latency: float) -> None:
            if tracer is None:
                return
            slo = getattr(req, "slo_ms", 0.0) or deadline_ms
            slo_ok = (status == "completed"
                      and (slo <= 0 or latency * 1e3 <= slo))
            tracer.event(
                "sched:tenant", now, now,
                tenant=getattr(req, "tenant", "default"),
                priority=req_prio(req),
                status=status,
                latency_s=latency,
                slo_ms=slo,
                slo_ok=slo_ok,
                tokens=req_cost(req),
            )

        def reject(req, reason: str) -> None:
            """Terminal ``rejected`` result — fleet parity, never silent."""
            nonlocal rejected_n
            now_r = clock()
            latency = now_r - submit_s[req.request_id]
            finished[req.request_id] = RequestResult(
                request_id=req.request_id,
                tokens=np.zeros((0,), np.int32),
                slot=-1,
                admit_step=-1,
                finish_step=step,
                ttft_s=0.0,
                latency_s=latency,
                tokens_per_s=0.0,
                status="rejected",
                reason=reason,
                tenant=getattr(req, "tenant", "default"),
                priority=req_prio(req),
                within_deadline=False,
            )
            rejected_n += 1
            emit_tenant(req, "rejected", now_r, latency)

        def unmeetable(req, queued_prompt_ahead: int, now: float) -> bool:
            """SLO-aware admission estimate: the queue's prompt tokens ahead
            flow through the per-boundary prefill budget, then the request
            decodes at the measured per-slot tok/s — shed it when even that
            optimistic finish lands past the deadline."""
            if deadline is None or step == 0 or decode_s <= 0:
                return False
            decode_tps = decode_tokens_emitted / decode_s
            if decode_tps <= 0:
                return False
            boundary_s = (prefill_s + decode_s) / step
            prefill_wait = (
                (queued_prompt_ahead + len(req.prompt)) / t_pack * boundary_s
                if packed else 0.0
            )
            per_slot_tps = decode_tps / max(1, slots.num_active)
            est_finish = now + prefill_wait + req.max_new_tokens / per_slot_tps
            return est_finish > deadline

        def pick_admission(now: float) -> int:
            """Index of the next admission candidate: priority tier first,
            then weighted fair share across tenants (dry buckets sink the
            tenant — work-conserving rate limiting), then FIFO order."""
            if not fair or len(queue) == 1:
                return 0
            best, best_key = 0, None
            for i, r in enumerate(queue):
                tname = getattr(r, "tenant", "default")
                dry = 1 if tenant_ledger.dry(tname, req_cost(r), now) else 0
                key = (dry, -req_prio(r),
                       tenant_ledger.vtime.get(tname, 0.0), i)
                if best_key is None or key < best_key:
                    best, best_key = i, key
            return best

        while queue or slots.num_active:
            t_boundary = clock()
            waited = 0.0        # inside prefill:wait and decode:fetch
            progressed = False
            # 0a) periodic checkpoint: runs BEFORE the fault hook, so a
            #     crash at boundary S observes checkpoints as-of S — the
            #     migration staleness bound is exactly the cadence.  Cadence
            #     is keyed on the decode-step counter, once per value
            #     (prefill-only boundaries don't advance ``step``).
            if (
                checkpoint_every > 0
                and checkpoints is not None
                and step > 0
                and step % checkpoint_every == 0
                and step != last_ckpt_step
            ):
                last_ckpt_step = step
                for s in sorted(decoding):
                    snap = snapshot_slot(s)
                    if snap is not None:
                        checkpoints[snap.request_id] = snap
            # 0b) boundary fault/heartbeat hook.  WorkerCrash can only be
            #    raised here, so the resumable snapshot (finished results +
            #    replayable pending requests) is attached at this one site.
            if fault_hook is not None:
                try:
                    fault_hook(FaultContext(
                        step=step, pool=pool, clock=clock, tracer=tracer,
                        checkpoints=checkpoints,
                    ))
                except WorkerCrash as crash:
                    if isinstance(crash, WorkerDrain) and checkpoints is not None:
                        # planned drain: snapshot EVERY live decoding slot
                        # fresh (not the stale periodic copy) so the router
                        # migrates all of them with zero recompute
                        for s in sorted(decoding):
                            snap = snapshot_slot(s)
                            if snap is not None:
                                checkpoints[snap.request_id] = snap
                    crash.results = [
                        finished[r.request_id] for r in requests
                        if r.request_id in finished
                    ]
                    _fill_itl(crash.results)
                    crash.pending = [
                        r for r in requests if r.request_id not in finished
                    ]
                    if hasattr(fault_hook, "release"):
                        fault_hook.release()   # return seized pressure pages
                    raise
            # 1) retire finished sequences, returning their pages
            with _span("sched:retire") as sp:
                retired = 0
                for slot in list(decoding):
                    req = slots.active[slot]
                    if len(slot_tokens[slot]) < req.max_new_tokens:
                        continue
                    now = clock()
                    rid = req.request_id
                    t_sub, t_adm = submit_s[rid], admitted_at[rid]
                    t_first = req._first_at          # type: ignore[attr-defined]
                    prop, acc = ledger.of(rid) if ledger else (0, 0)
                    latency = now - t_sub
                    finished[rid] = RequestResult(
                        request_id=rid,
                        tokens=np.asarray(slot_tokens[slot], np.int32),
                        slot=slot,
                        admit_step=req._admit_step,  # type: ignore[attr-defined]
                        finish_step=step,
                        ttft_s=t_first - t_sub,
                        latency_s=latency,
                        tokens_per_s=(
                            req.max_new_tokens / latency
                            if now > t_sub else float("inf")
                        ),
                        queue_s=t_adm - t_sub,
                        token_times_s=[t - t_sub for t in slot_times[slot]],
                        draft_proposed=prop,
                        draft_accepted=acc,
                        tenant=getattr(req, "tenant", "default"),
                        priority=req_prio(req),
                        # late completions stay completed but fall out of
                        # goodput — the fleet's within_deadline semantics
                        within_deadline=deadline is None or now <= deadline,
                    )
                    if tracer is not None:
                        # one request's phases, tiling submission to finish
                        tracer.event("request:queued", t_sub, t_adm, request=rid)
                        tracer.event("request:prefill", t_adm, t_first,
                                     request=rid)
                        tracer.event("request:decode", t_first, now, request=rid)
                    emit_tenant(req, "completed", now, latency)
                    release_slot(slot)
                    retired += 1
                    progressed = True
                if tracer is not None:
                    sp.tags["retired"] = retired
            # 2) admission keyed on free pages: a request enters only when a
            #    slot AND its prompt's pages are available AND its worst-case
            #    page commitment fits the (possibly overcommitted) pool.
            #    With the prefix cache on, the longest cached page-aligned
            #    prefix is mapped (shared) instead of allocated: only the
            #    uncached suffix needs fresh pages, the commitment ledger
            #    counts each shared page ONCE globally (plus one COW page
            #    for a full hit), and cached-unreferenced pages are evicted
            #    on demand before admission gives up
            with _span("sched:admit") as sp:
                admitted = 0
                if deadline is not None and queue and clock() > deadline:
                    # TTL passed while still queued: terminal rejected (fleet
                    # parity) — expired work leaves the queue, it never runs
                    while queue:
                        reject(queue.popleft(), "deadline")
                    progressed = True
                while queue:
                    now_adm = clock()
                    idx0 = pick_admission(now_adm)
                    req0 = queue[idx0]
                    if unmeetable(
                        req0,
                        sum(len(r.prompt) for r in queue) - len(req0.prompt),
                        now_adm,
                    ):
                        del queue[idx0]
                        reject(req0, "slo-unmeetable")
                        progressed = True
                        continue
                    # migrate-restore admission: a request arriving with a
                    # checkpointed snapshot skips prefill entirely — verify the
                    # per-page checksums, scatter the snapshot into freshly
                    # allocated pages, rebuild lengths + emitted tokens, and
                    # continue decoding bit-identically.  A failed verify drops
                    # the snapshot and falls through to ordinary prefill
                    # (replay-from-prompt): corrupted state is never served.
                    snap = restores.get(req0.request_id) if restores else None
                    if snap is not None and not snap.verify():
                        checksum_failures += 1
                        del restores[req0.request_id]
                        if tracer is not None:
                            now_cf = clock()
                            tracer.event(
                                "migrate:checksum_fail", now_cf, now_cf,
                                request=req0.request_id, step=step,
                                pages=snap.num_pages,
                            )
                        snap = None
                    if snap is not None:
                        worst = pool.pages_needed(
                            len(req0.prompt) + req0.max_new_tokens
                        )
                        npages = snap.num_pages
                        committed = sum(slot_commit.values()) + len(pinned_refs)
                        if not slots.num_free:
                            break
                        if committed + worst > pool.capacity * overcommit:
                            break
                        if not ensure_free(npages):
                            break
                        req = req0
                        del queue[idx0]
                        del restores[req.request_id]
                        if fair:
                            tenant_ledger.on_admit(
                                getattr(req, "tenant", "default"), req_cost(req),
                                now_adm,
                            )
                        t0m = clock()
                        slot, pages = slots.admit_paged(req, npages, step=step)
                        admitted_at.setdefault(req.request_id, now_adm)
                        table.assign(slot, pages)
                        # scatter the snapshot into the fresh pages: destination
                        # AND source are padded to the pow2 bucket with the last
                        # real page (duplicate scatter indices rewrite the same
                        # bytes, so the import is idempotent)
                        cnt = bucket_pow2(len(pages), cap=max_pages_per_seq)
                        self._xfer_shapes.add((num_pages, page_size, cnt))
                        dst = np.fromiter(pages, np.int32, len(pages))
                        dst = np.concatenate(
                            [dst, np.full((cnt - len(pages),), dst[-1], np.int32)]
                        )
                        sel = np.concatenate([
                            np.arange(len(pages), dtype=np.int32),
                            np.full((cnt - len(pages),), len(pages) - 1, np.int32),
                        ])
                        if "k_scales" in cache:
                            (cache["k_pages"], cache["v_pages"],
                             cache["k_scales"], cache["v_scales"]) = self._import_q(
                                cache["k_pages"], cache["v_pages"], dst,
                                jnp.asarray(snap.k[:, sel]),
                                jnp.asarray(snap.v[:, sel]),
                                cache["k_scales"], cache["v_scales"],
                                jnp.asarray(snap.k_scales[:, sel]),
                                jnp.asarray(snap.v_scales[:, sel]),
                            )
                        else:
                            cache["k_pages"], cache["v_pages"] = self._import(
                                cache["k_pages"], cache["v_pages"], dst,
                                jnp.asarray(snap.k[:, sel]),
                                jnp.asarray(snap.v[:, sel]),
                            )
                        lengths[slot] = snap.length
                        toks = [int(t) for t in snap.tokens]
                        slot_tokens[slot] = toks
                        slot_times[slot] = []
                        nxt[slot] = toks[-1]
                        slot_commit[slot] = worst
                        slot_cached[slot] = 0
                        slot_prefilled[slot] = 0
                        admit_order[slot] = admit_seq
                        admit_seq += 1
                        req._admit_step = step      # type: ignore[attr-defined]
                        # first token was emitted on the source worker; TTFT on
                        # the survivor is the restore latency itself
                        req._first_at = clock()     # type: ignore[attr-defined]
                        decoding.add(slot)
                        restored_slots.add(slot)
                        dirty.add(slot)
                        restored_n += 1
                        restored_tok += snap.length
                        restore_bytes += snap.nbytes
                        if tracer is not None:
                            tracer.event(
                                "migrate:restore", t0m, clock(),
                                request=req.request_id, pages=len(pages),
                                bytes=snap.nbytes, tokens=len(toks),
                                length=snap.length,
                            )
                        admitted += 1
                        progressed = True
                        continue
                    hit_pages: List[int] = []
                    cached = 0
                    if pcache is not None:
                        hit_pages, cached = pcache.match(req0.prompt)
                    full_hit = cached >= len(req0.prompt)
                    npages = pool.pages_needed(len(req0.prompt)) - len(hit_pages)
                    worst = pool.pages_needed(len(req0.prompt) + req0.max_new_tokens)
                    # private worst case: shared pages are not this request's
                    # cost (they're pinned once, below); a full hit will split
                    # its shared last page copy-on-write, so reserve that page
                    commit = worst - len(hit_pages) + (1 if full_hit else 0)
                    # shared pages counted once globally: every page some slot
                    # already mapped from the cache plus the ones THIS admission
                    # would newly pin
                    pinned = len(pinned_refs) + sum(
                        1 for p in hit_pages if p not in pinned_refs
                    )
                    committed = sum(slot_commit.values()) + pinned
                    if not slots.num_free:
                        break
                    if committed + commit > pool.capacity * overcommit:
                        break
                    # pin the hit pages BEFORE eviction runs: they are exactly
                    # the cached-unreferenced pages ensure_free may reclaim
                    if hit_pages:
                        pool.incref(hit_pages)
                    if not ensure_free(npages):
                        if hit_pages:
                            pool.free(hit_pages)
                        break
                    req = req0
                    del queue[idx0]
                    if fair:
                        tenant_ledger.on_admit(
                            getattr(req, "tenant", "default"), req_cost(req),
                            now_adm,
                        )
                    if pcache is not None:
                        pcache.record(len(req.prompt), hit_pages)
                    slot, pages = slots.admit_paged(req, npages, step=step)
                    admitted_at.setdefault(req.request_id, now_adm)
                    table.assign(slot, hit_pages + pages)
                    for p in hit_pages:
                        pinned_refs[p] = pinned_refs.get(p, 0) + 1
                    slot_shared[slot] = list(hit_pages)
                    slot_tokens[slot] = []
                    slot_commit[slot] = commit
                    slot_prefilled[slot] = 0
                    prompt_admitted += len(req.prompt)
                    admit_order[slot] = admit_seq
                    admit_seq += 1
                    req._admit_step = step              # type: ignore[attr-defined]
                    if full_hit:
                        # every prompt page is cached: skip prefill entirely and
                        # replay the last prompt token through the decode path
                        # (its append copy-on-writes the shared last page); TTFT
                        # collapses to one decode boundary
                        slot_cached[slot] = len(req.prompt)
                        saved_tokens += len(req.prompt)
                        if budget is not None:
                            budget.credit(len(req.prompt))
                        lengths[slot] = len(req.prompt) - 1
                        nxt[slot] = int(req.prompt[-1])
                        slot_times[slot] = []
                        decoding.add(slot)
                        replay_first.add(slot)
                        dirty.add(slot)
                    else:
                        slot_cached[slot] = cached
                        saved_tokens += cached
                        if budget is not None and cached:
                            budget.credit(cached)
                        lengths[slot] = cached
                        prefilling[slot] = cached
                    if tracer is not None and pcache is not None:
                        now = clock()
                        tracer.event(
                            "prefix:lookup", now, now,
                            prompt_tokens=len(req.prompt), cached_tokens=cached,
                            hit_pages=len(hit_pages), full_hit=int(full_hit),
                        )
                    admitted += 1
                    progressed = True
                if fair and queue:
                    # tenants whose arrived work was passed over because their
                    # bucket ran dry: one deferral per tenant per boundary
                    now_d = clock()
                    seen_dry: set = set()
                    for r in queue:
                        tname = getattr(r, "tenant", "default")
                        if tname not in seen_dry and tenant_ledger.dry(
                                tname, req_cost(r), now_d):
                            seen_dry.add(tname)
                            tenant_ledger.note_defer(tname)
                            deferred_n += 1
                            if tracer is not None:
                                tracer.event("sched:defer", now_d, now_d,
                                             tenant=tname)
                if tracer is not None:
                    sp.tags.update(admitted=admitted, queued=len(queue))
            # 3) prefill at the boundary, interleaved with decode.
            #    packed: coalesce every prefilling slot's next span into ONE
            #    token-packed varlen launch (oldest first, capped by the
            #    per-boundary token budget); chunked: one batch-1 chunk per
            #    slot (legacy path, one jit variant per length × offset)
            if prefilling and packed:
                with _span("prefill:packed", timed=True) as sp:
                    if tracer is not None:
                        # requests already decoding, held up by this launch
                        sp.tags["decoding"] = sum(
                            1 for s in decoding
                            if 0 < len(slot_tokens[s])
                            < slots.active[s].max_new_tokens
                        )
                    budget.begin_step()
                    spans: List[Tuple[int, int, int, int]] = []
                    used = 0
                    for slot in sorted(prefilling, key=lambda s: admit_order[s]):
                        req = slots.active[slot]
                        rem = len(req.prompt) - prefilling[slot]
                        if used >= t_pack:
                            budget.defer(rem)   # left waiting: starvation signal
                            continue
                        # the buffer cap (padded spans) is never looser than the
                        # ledger (real tokens), so grants keep spans page-aligned
                        take = budget.grant(min(rem, t_pack - used))
                        if take <= 0:
                            budget.defer(rem)
                            continue
                        if take < rem:
                            budget.defer(rem - take)
                        span = pages_needed(take, page_size) * page_size
                        spans.append((slot, prefilling[slot], take, span))
                        used += span
                    if spans:
                        num_chunks = num_slots
                        tokens_p = np.zeros((1, t_pack), np.int32)
                        tok_pos = np.zeros((t_pack,), np.int32)
                        # buffer-tail pads scatter their K/V into the scratch
                        # page; offsets cycle so writes spread over its rows
                        dst_page = np.zeros((t_pack,), np.int32)
                        dst_off = (np.arange(t_pack) % page_size).astype(np.int32)
                        cu = np.zeros((num_chunks + 1,), np.int32)
                        lens_c = np.zeros((num_chunks,), np.int32)
                        pos0_c = np.zeros((num_chunks,), np.int32)
                        last_idx = np.zeros((num_chunks,), np.int32)
                        tables_c = np.zeros((num_chunks, max_pages_per_seq), np.int32)
                        off = 0
                        for ci, (slot, start, take, span) in enumerate(spans):
                            req = slots.active[slot]
                            tokens_p[0, off : off + take] = req.prompt[
                                start : start + take
                            ]
                            pos = start + np.arange(span, dtype=np.int32)
                            tok_pos[off : off + span] = pos
                            row = table.table[slot]
                            # chunk-pad K/V lands inside the prompt's already-
                            # allocated pages (length-masked until overwritten),
                            # exactly like the chunked path's padded tail
                            dst_page[off : off + span] = row[pos // page_size]
                            dst_off[off : off + span] = pos % page_size
                            cu[ci + 1] = off + span
                            lens_c[ci] = take
                            pos0_c[ci] = start
                            last_idx[ci] = off + take - 1
                            tables_c[ci] = row
                            off += span
                        cu[len(spans) + 1 :] = off
                        # static bound on committed-context pages this launch,
                        # pow2-bucketed so early (low-context) launches don't
                        # stream/gather the full page-table width
                        ctx_pages = max(
                            pages_needed(start, page_size)
                            for _, start, _, _ in spans
                        )
                        bound = bucket_pow2(max(ctx_pages, 1),
                                            cap=max_pages_per_seq)
                        # the varlen kernel's work items, where it runs
                        live = rect = 0
                        if self.model.backend == "pallas":
                            # lazy: pallas import cost
                            from ..kernels.varlen_prefill import work_items

                            live, rect = work_items(
                                cu, lens_c, pos0_c, t_pack=t_pack,
                                block=page_size, pages_bound=bound,
                            )
                        kv_live += live
                        kv_rect += rect
                        fn = self._packed_prefill_fn(
                            t_pack, num_chunks, max_pages_per_seq, bound
                        )
                        batch_p = {
                            "tokens": jnp.asarray(tokens_p),
                            "tok_pos": jnp.asarray(tok_pos),
                            "dst_page": jnp.asarray(dst_page),
                            "dst_off": jnp.asarray(dst_off),
                            "cu_seqlens": jnp.asarray(cu),
                            "chunk_lens": jnp.asarray(lens_c),
                            "chunk_pos0": jnp.asarray(pos0_c),
                            "page_tables": jnp.asarray(tables_c),
                            "last_idx": jnp.asarray(last_idx),
                        }
                        logits, cache = fn(self.params, batch_p, cache)
                        with _span("prefill:wait", timed=True) as sw:
                            jax.block_until_ready(logits)
                        waited += sw.t1 - sw.t0
                        with _span("prefill:first_tokens") as sf:
                            n_first = 0
                            for ci, (slot, start, take, span) in enumerate(spans):
                                req = slots.active[slot]
                                new_start = start + take
                                lengths[slot] = new_start
                                slot_prefilled[slot] = (
                                    slot_prefilled.get(slot, 0) + take
                                )
                                chunks_done += 1
                                if new_start >= len(req.prompt):
                                    del prefilling[slot]
                                    if pcache is not None:
                                        pcache.insert(req.prompt, table.pages_of(slot))
                                    tok0 = int(jnp.argmax(logits[ci]))
                                    n_first += 1
                                    nxt[slot] = tok0
                                    slot_tokens[slot] = [tok0]
                                    decoding.add(slot)
                                    dirty.add(slot)
                                    tnow = clock()
                                    slot_times[slot] = [tnow]
                                    req._first_at = tnow  # type: ignore
                                else:
                                    prefilling[slot] = new_start
                            if tracer is not None:
                                sf.tags["n"] = n_first
                        real = sum(s[2] for s in spans)
                        prefill_launches += 1
                        prefill_tokens += real
                        prefill_padded += t_pack - real
                        if tracer is not None:
                            sp.tags.update(
                                tokens=real, padding=t_pack - real,
                                chunks=len(spans), buffer=t_pack,
                                budget=budget.tokens_per_step,
                                live=live, rect=rect,
                            )
                prefill_s += sp.t1 - sp.t0
                if spans:
                    tp_event("prefill", sp.t0, sp.t1, t_pack,
                             seq_shardable=True)
                    progressed = True
            elif prefilling:
                t0p = clock()
                chunk_tok = 0
                for slot in list(prefilling):
                    req = slots.active[slot]
                    start = prefilling[slot]
                    c = min(prefill_chunk, len(req.prompt) - start)
                    # bucket the chunk shape to a page multiple so ragged
                    # prompt tails don't compile one jit variant per distinct
                    # residual; pad K/V lands inside the prompt's already-
                    # allocated pages and stays length-masked until decode
                    # overwrites it
                    c_pad = min(
                        prefill_chunk, pages_needed(c, page_size) * page_size
                    )
                    chunk = np.zeros((1, c_pad), np.int32)
                    chunk[0, :c] = req.prompt[start : start + c]
                    fn = self._paged_prefill_fn(c_pad, start)
                    logits, cache = fn(
                        self.params,
                        jnp.asarray(chunk),
                        cache,
                        jnp.asarray(table.table[slot]),
                        jnp.int32(c - 1),
                    )
                    jax.block_until_ready(logits)
                    chunks_done += 1
                    prefill_launches += 1
                    prefill_tokens += c
                    prefill_padded += c_pad - c
                    chunk_tok += c_pad
                    start += c
                    lengths[slot] = start
                    slot_prefilled[slot] = slot_prefilled.get(slot, 0) + c
                    progressed = True
                    if start >= len(req.prompt):
                        del prefilling[slot]
                        if pcache is not None:
                            pcache.insert(req.prompt, table.pages_of(slot))
                        tok0 = int(jnp.argmax(logits[0]))
                        nxt[slot] = tok0
                        slot_tokens[slot] = [tok0]
                        decoding.add(slot)
                        dirty.add(slot)
                        tnow = clock()
                        slot_times[slot] = [tnow]
                        req._first_at = tnow    # type: ignore[attr-defined]
                    else:
                        prefilling[slot] = start
                now = clock()
                prefill_s += now - t0p
                tp_event("prefill", t0p, now, chunk_tok, seq_shardable=True)
            # 4) one decode step over the whole pool.  With ``spec_k > 0``
            #    the prompt-lookup drafter proposes up to ``spec_k`` tokens
            #    per slot and ONE verify launch scores every slot's window;
            #    boundaries with no drafts anywhere fall back to a W=1
            #    launch (numerically the plain decode step)
            active_dec = [
                s for s in decoding
                if len(slot_tokens[s]) < slots.active[s].max_new_tokens
            ]
            drafts: Dict[int, List[int]] = {}
            if spec and active_dec:
                for s in active_dec:
                    req = slots.active[s]
                    rem = req.max_new_tokens - len(slot_tokens[s])
                    # a boundary emits accepted+1 tokens: never draft past
                    # the request's token budget or the cache's max_seq
                    cap = min(spec_k, rem - 1,
                              self.max_seq - int(lengths[s]) - 1)
                    if cap > 0:
                        ctx = np.concatenate(
                            [req.prompt, np.asarray(slot_tokens[s], np.int32)]
                        )
                        drafts[s] = ngram_propose(ctx, spec_ngram, cap)
                    else:
                        drafts[s] = []
            # copy-on-write, then growth, for every decoding row.  The next
            # token (plus any draft tokens — the verify scatter writes them
            # too) appends at ``lengths[s]``: if that position lands in a
            # page other holders still reference (a full-hit slot's shared
            # last page), split it into a private copy FIRST; then grow the
            # table for rows whose window opens a new page.  Both paths
            # reclaim cached-unreferenced pages before preempting the
            # youngest request.  Speculative demand must never evict live
            # work (or self-preempt into a recompute loop): when growth
            # fails, first trim the slot's draft to the pages it already
            # holds — only the REAL next token's page may preempt, exactly
            # like the non-spec path
            if active_dec:
                with _span("pages:grow") as sp:
                    n_grown = 0
                    for s in sorted(active_dec, key=lambda s: admit_order[s]):
                        while s in decoding and not cow_if_shared(s):
                            if preempt_one() is None:
                                raise RuntimeError(
                                    "page pool exhausted with nothing to preempt"
                                )
                        while (
                            s in decoding   # may have been evicted (even by itself)
                            and table.num_pages_of(s) * page_size
                            <= int(lengths[s]) + len(drafts.get(s, ()))
                        ):
                            grown = slots.grow(1) if ensure_free(1) else None
                            if grown is None:
                                d = drafts.get(s)
                                if d:
                                    fit = (table.num_pages_of(s) * page_size
                                           - int(lengths[s]) - 1)
                                    del d[max(fit, 0):]
                                    continue
                                if preempt_one() is None:
                                    raise RuntimeError(
                                        "page pool exhausted with nothing to preempt"
                                    )
                                continue
                            table.append(s, grown[0])
                            dirty.add(s)
                            n_grown += 1
                    if tracer is not None:
                        sp.tags["grown"] = n_grown
            active_dec = [s for s in active_dec if s in decoding]  # may be preempted
            if active_dec:
                with _span("decode:step", timed=True) as sd:
                    use_spec = spec and any(drafts.get(s) for s in active_dec)
                    W = spec_k + 1 if use_spec else 1
                    with _span("decode:patch") as sp:
                        patched = sync_device(active_dec)
                        if tracer is not None:
                            sp.tags["dirty"] = patched
                    live = max(
                        int(lengths[s]) + 1 + len(drafts.get(s, ()))
                        for s in active_dec
                    )
                    bound = bucket_pow2(
                        pages_needed(live, page_size), cap=max_pages_per_seq
                    )
                    if use_spec:
                        win = np.zeros((num_slots, W), np.int32)
                        wlens_h = np.zeros((num_slots,), np.int32)
                        for s in active_dec:
                            d = drafts.get(s, [])
                            win[s, 0] = nxt[s]
                            win[s, 1 : 1 + len(d)] = d
                            wlens_h[s] = 1 + len(d)
                        fn = self._spec_decode_fn(bound, W)
                        greedy, n_acc, dev_pos, dev_nxt, cache = fn(
                            self.params, win, cache, dev_table,
                            dev_pos, wlens_h, dev_nxt,
                        )
                        with _span("decode:fetch", timed=True) as sf:
                            g, na = jax.device_get((greedy, n_acc))
                    else:
                        fn = self._paged_decode_fn(bound)
                        tok, dev_nxt, dev_pos, cache = fn(
                            self.params, dev_nxt, cache, dev_table, dev_pos,
                            dev_mask,
                        )
                        with _span("decode:fetch", timed=True) as sf:
                            g = np.asarray(tok)[:, None]
                        na = np.zeros((num_slots,), np.int32)
                    waited += sf.t1 - sf.t0
                    if tracer is not None:
                        sd.tags.update(slots=len(active_dec), bound=bound,
                                       window=W)
                t0d, now = sd.t0, sd.t1
                decode_s += now - t0d
                tp_event("verify" if use_spec else "decode", t0d, now,
                         num_slots * W)
                step += 1
                occupancy_sum += slots.num_active
                prop_total = acc_total = 0
                for s in active_dec:
                    a = int(na[s])
                    emitted = g[s, : a + 1]
                    req = slots.active[s]
                    slot_tokens[s].extend(int(t) for t in emitted)
                    nxt[s] = int(emitted[-1])
                    lengths[s] += a + 1
                    decode_tokens_emitted += a + 1
                    slot_times[s].extend([now] * (a + 1))
                    if s in replay_first:
                        # full cache hit: the first token came from this
                        # decode boundary, not from a prefill launch
                        replay_first.discard(s)
                        req._first_at = now     # type: ignore[attr-defined]
                    if spec:
                        prop = len(drafts.get(s, ()))
                        ledger.record(req.request_id, prop, a)
                        prop_total += prop
                        acc_total += a
                        # rollback: lengths already rewound to the committed
                        # prefix (the device bump is accepted+1, not the full
                        # window); a rejected suffix that opened a fresh page
                        # hands it straight back to the pool
                        freed = table.truncate(
                            s, pages_needed(int(lengths[s]), page_size)
                        )
                        if freed:
                            pool.free(freed)
                            ledger.record_rollback(len(freed))
                            dirty.add(s)
                if spec:
                    ledger.record_launch(use_spec)
                    if use_spec and tracer is not None:
                        tracer.event(
                            "spec:verify", t0d, now,
                            window=W, slots=len(active_dec),
                            proposed=prop_total, accepted=acc_total,
                            emitted=len(active_dec) + acc_total,
                        )
                progressed = True
            # peak concurrency is a per-boundary property: prefill-only
            # boundaries (no decode yet) still hold admitted requests
            peak_occupancy = max(peak_occupancy, slots.num_active)
            pages_sum += pool.num_in_use
            samples += 1
            slots.record_occupancy(step)
            boundaries += 1
            host_s += clock() - t_boundary - waited
            if not progressed and not prefilling and not decoding:
                raise RuntimeError("paged serve loop stalled (admission deadlock)")
        if fault_hook is not None and hasattr(fault_hook, "release"):
            fault_hook.release()   # pressure seizures held past the last step
        jax.block_until_ready(cache["k_pages"])
        wall = clock() - t_start
        results = [finished[r.request_id] for r in requests]
        gaps = _fill_itl(results)
        total_tokens = sum(len(r.tokens) for r in results)
        completed_n = sum(1 for r in results if r.status == "completed")
        in_goodput = sum(
            1 for r in results
            if r.status == "completed" and r.within_deadline
        )
        return PagedStats(
            results=results,
            steps=step,
            wall_s=wall,
            total_tokens=total_tokens,
            throughput_tps=total_tokens / wall if wall > 0 else float("inf"),
            mean_slot_occupancy=occupancy_sum / step if step else 0.0,
            peak_slot_occupancy=peak_occupancy,
            page_size=page_size,
            num_pages=pool.capacity,
            mean_pages_in_use=pages_sum / samples if samples else 0.0,
            peak_pages_in_use=pool.peak_in_use,
            preemptions=slots.preemptions,
            prefill_chunks=chunks_done,
            compile_stats=self._compile_delta(compiles_before),
            prefill_mode=prefill_mode,
            prefill_launches=prefill_launches,
            prefill_s=prefill_s,
            prefill_tokens=prefill_tokens,
            prefill_padded_tokens=prefill_padded,
            prefill_kv_live=kv_live,
            prefill_kv_rect=kv_rect,
            prefill_budget=t_pack if packed else 0,
            prefill_budget_stats=budget.stats() if budget else {},
            prompt_tokens_admitted=prompt_admitted,
            saved_prefill_tokens=saved_tokens,
            prefill_tokens_dropped=dropped_tokens,
            prefix_cache=prefix_cache,
            cow_copies=cow_copies,
            cache_evictions=pcache.evicted_pages if pcache else 0,
            prefix_stats=pcache.stats() if pcache else {},
            decode_s=decode_s,
            spec_k=spec_k,
            spec_stats=ledger.stats() if ledger else {},
            itl_p50_ms=percentile(gaps, 50.0) * 1e3 if gaps else 0.0,
            itl_p99_ms=percentile(gaps, 99.0) * 1e3 if gaps else 0.0,
            boundaries=boundaries,
            host_s=host_s,
            tp=self.tp,
            kv_dtype=pool_dtype,
            kv_bytes_per_token=float(
                sum(v.nbytes for v in cache.values())
                / (num_pages * page_size)
            ),
            completed=completed_n,
            rejected=rejected_n,
            deferred=deferred_n,
            goodput=in_goodput / len(results) if results else 1.0,
            deadline_ms=deadline_ms,
            checkpoints_saved=ckpt_saved,
            checkpoint_bytes=ckpt_bytes,
            restored_requests=restored_n,
            restored_tokens=restored_tok,
            restore_bytes=restore_bytes,
            checksum_failures=checksum_failures,
        )

"""End-to-end serving driver (the e2e application for this paper's kind).

Serves a model under a Poisson request load through the platform's request
scheduler.  Three executor modes (``--engine``):

* ``static``      — the threaded RequestScheduler coalesces concurrent
                    requests into micro-batches (up to ``--engine-batch``
                    within ``--batch-timeout-ms``) executed by the static
                    prefill/decode engine.
* ``continuous``  — slot-based continuous batching: prompts are admitted
                    into free dense KV slots at decode-step boundaries;
                    reports per-request TTFT and tokens/sec.
* ``paged``       — paged KV cache: a global ``--page-size``-token page pool
                    (``--num-pages``) with per-request page tables, prefill
                    interleaved at decode-step boundaries (``--prefill-mode
                    packed`` coalesces every admissible chunk into one
                    token-packed varlen launch of ``--prefill-budget``
                    tokens; ``chunked`` is the legacy one-chunk-per-slot
                    path), admission keyed on free pages, and youngest-
                    first preemption when the pool is exhausted.
                    ``--spec-k k`` adds self-speculative decoding
                    (prompt-lookup drafting + one paged multi-token
                    verification launch per boundary; greedy tokens stay
                    bit-identical).  Emits ``pages:occupancy`` +
                    ``prefill:packed`` + ``spec:verify`` events and
                    page-occupancy / prefill-saturation / acceptance-rate
                    report sections plus per-request ITL p50/p99.

Latency/throughput metrics and the scheduler's queue/occupancy series flow
into the evaluation database.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \
        --requests 16 --rate-hz 20 --max-new-tokens 8 --engine paged
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..configs import depth_cut, get_config
from ..core.analysis import (
    fleet_section,
    latency_summary,
    recovery_section,
    page_occupancy_section,
    prefill_saturation_section,
    prefix_cache_section,
    slo_section,
    spec_decode_section,
    tp_section,
)
from ..core.evaldb import EvalDB, EvaluationRecord
from ..core.manifest import EngineKnobs
from ..core.tracing import Tracer, TracingServer
from ..core.workload import (
    MultiTenantLoad,
    PoissonLoad,
    SharedPrefixLoad,
    shared_prefix_prompts,
)
from ..models import build_model
from ..serve.engine import ServeRequest, ServingEngine
from ..serve.scheduler import (
    PRIORITY_TIERS,
    RequestScheduler,
    SchedulerConfig,
    TenantSpec,
)
from ..sharding.specs import param_pspecs
from .compile_cache import enable_compile_cache


def _parse_tenants(s: str):
    """Parse ``--tenants``: semicolon-separated tenants, each
    ``name[,key=value...]`` with keys ``prio`` (tier index or name),
    ``weight``, ``rate`` (bucket refill tokens/s), ``burst`` (bucket
    depth), ``hz`` (arrival rate), ``slo`` (ms), ``prompt``/``gen``
    (token shape).  Example::

        --tenants "prem,prio=2,weight=2,hz=20;best,prio=0,rate=400,burst=120"
    """
    out = []
    for chunk in s.split(";"):
        chunk = chunk.strip()
        if not chunk:
            continue
        parts = [p.strip() for p in chunk.split(",")]
        t = {"name": parts[0]}
        for kv in parts[1:]:
            k, _, v = kv.partition("=")
            k = k.strip()
            v = v.strip()
            field, conv = _TENANT_KEYS.get(k, (None, None))
            if field is None:
                raise ValueError(f"unknown tenant key {k!r} in {chunk!r}")
            try:
                t[field] = conv(v)
            except ValueError:
                raise ValueError(
                    f"bad tenant value {k}={v!r} in {chunk!r}") from None
        out.append(t)
    return out


def _parse_priority(v: str) -> int:
    return PRIORITY_TIERS.index(v) if v in PRIORITY_TIERS else int(v)


_TENANT_KEYS = {
    "prio": ("priority", _parse_priority),
    "priority": ("priority", _parse_priority),
    "weight": ("weight", float),
    "rate": ("rate_tokens_per_s", float),
    "burst": ("burst_tokens", float),
    "hz": ("rate_hz", float),
    "slo": ("slo_ms", float),
    "prompt": ("prompt_len", int),
    "gen": ("gen_tokens", int),
}


def _parse_priority_mix(s: str):
    """Parse ``--priority-mix``: ``tier=frac`` pairs, e.g.
    ``best_effort=0.25,standard=0.5,premium=0.25``."""
    out = {}
    for kv in s.split(","):
        k, _, v = kv.partition("=")
        out[k.strip()] = float(v)
    return out


def _serve_static(engine, cfg, args, load, prompts):
    """Poisson arrivals -> threaded micro-batching scheduler -> engine."""
    extra = None
    if cfg.family == "encdec":
        extra = {
            "frames": np.zeros((args.engine_batch, cfg.encoder_seq, cfg.d_model), np.float32)
        }

    def execute(batch):
        ps = [r.payload for r in batch]
        ex = None
        if extra is not None:
            ex = {"frames": extra["frames"][: len(ps)]}
        res = engine.generate(ps, args.max_new_tokens, extra_inputs=ex)
        print(
            f"[serve] batch of {len(ps)}: prefill {res.prefill_s*1e3:.1f} ms, "
            f"decode {res.decode_s*1e3:.1f} ms ({res.tokens_per_s:,.1f} tok/s)"
        )

    sched = RequestScheduler(
        execute,
        SchedulerConfig(
            max_batch=args.engine_batch, batch_timeout_ms=args.batch_timeout_ms
        ),
    ).start()
    t_start = time.perf_counter()
    futs = []
    for req, prompt in zip(load, prompts):
        now = time.perf_counter() - t_start
        if req.arrival_s > now:
            time.sleep(req.arrival_s - now)
        futs.append(sched.submit(payload=prompt))
    for f in futs:
        f.result()
    sched.stop()
    wall = time.perf_counter() - t_start
    latencies = [f.request.latency_s for f in futs]
    generated = len(futs) * args.max_new_tokens
    summary = latency_summary(latencies) if latencies else {}
    summary.update(
        {
            "tokens_per_s": generated / wall,
            **{f"sched_{k}": v for k, v in sched.stats().items()},
        }
    )
    return summary, generated, wall


def _serve_continuous(engine, cfg, args, load, prompts):
    """Offline continuous batching over the same request set."""
    reqs = [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=args.max_new_tokens)
        for i, p in enumerate(prompts)
    ]
    stats = engine.serve_continuous(reqs, num_slots=args.engine_batch)
    for r in stats.results:
        print(
            f"[serve] req {r.request_id}: slot {r.slot} "
            f"(admitted step {r.admit_step}), ttft {r.ttft_s*1e3:.1f} ms, "
            f"{r.tokens_per_s:,.1f} tok/s"
        )
    latencies = [r.latency_s for r in stats.results]
    summary = latency_summary(latencies) if latencies else {}
    summary.update(
        {
            "tokens_per_s": stats.throughput_tps,
            "ttft_mean_ms": float(
                np.mean([r.ttft_s for r in stats.results]) * 1e3
            ),
            "mean_slot_occupancy": stats.mean_slot_occupancy,
            "decode_steps": stats.steps,
        }
    )
    return summary, stats.total_tokens, stats.wall_s


def _tagged_requests(args, load, prompts):
    """Build engine requests carrying each workload request's tenant tags."""
    reqs = []
    for i, (req, p) in enumerate(zip(load, prompts)):
        tags = getattr(req, "tags", None) or {}
        reqs.append(ServeRequest(
            request_id=i, prompt=p, max_new_tokens=args.max_new_tokens,
            tenant=str(tags.get("tenant", "default")),
            priority=int(tags.get("priority", 1)),
            slo_ms=float(tags.get("slo_ms", 0.0) or args.slo_ms),
        ))
    return reqs


def _serve_paged(engine, cfg, args, load, prompts):
    """Offline paged-KV continuous batching with chunked prefill."""
    reqs = _tagged_requests(args, load, prompts)
    tenant_dicts = _parse_tenants(args.tenants) if args.tenants else []
    server = TracingServer()
    tracer = Tracer("serve-paged", server)
    stats = engine.serve_paged(
        reqs,
        num_slots=args.engine_batch,
        page_size=args.page_size,
        num_pages=args.num_pages or None,
        prefill_chunk=args.prefill_chunk or None,
        overcommit=args.overcommit,
        prefill_mode=args.prefill_mode,
        prefill_budget=args.prefill_budget or None,
        spec_k=args.spec_k,
        spec_ngram=args.spec_ngram,
        prefix_cache=args.prefix_cache == "on",
        deadline_ms=args.deadline_ms,
        tenants=[TenantSpec.from_dict(t) for t in tenant_dicts] or None,
        fairness=args.fairness == "on",
        tracer=tracer,
    )
    for r in stats.results:
        if r.status != "completed":
            print(f"[serve] req {r.request_id}: {r.status} ({r.reason})")
            continue
        print(
            f"[serve] req {r.request_id}: slot {r.slot} "
            f"(admitted step {r.admit_step}), ttft {r.ttft_s*1e3:.1f} ms, "
            f"{r.tokens_per_s:,.1f} tok/s"
        )
    section = page_occupancy_section(server.timeline("serve-paged"))
    if section:
        print("[serve] page occupancy:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    section = prefill_saturation_section(server.timeline("serve-paged"))
    if section:
        print("[serve] prefill saturation:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    section = spec_decode_section(server.timeline("serve-paged"))
    if section:
        print("[serve] speculative decoding:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    section = prefix_cache_section(server.timeline("serve-paged"))
    if section:
        print("[serve] prefix cache:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    section = tp_section(server.timeline("serve-paged"))
    if section:
        print("[serve] tensor-parallel collectives:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    section = slo_section(server.timeline("serve-paged"))
    if section:
        print("[serve] multi-tenant SLO:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    done = [r for r in stats.results if r.status == "completed"]
    latencies = [r.latency_s for r in done]
    summary = latency_summary(latencies) if latencies else {}
    summary.update(
        {
            "tokens_per_s": stats.throughput_tps,
            "ttft_mean_ms": float(
                np.mean([r.ttft_s for r in done]) * 1e3
            ) if done else 0.0,
            "completed": float(stats.completed),
            "rejected": float(stats.rejected),
            "deferred": float(stats.deferred),
            "goodput": stats.goodput,
            "mean_slot_occupancy": stats.mean_slot_occupancy,
            "peak_slot_occupancy": float(stats.peak_slot_occupancy),
            "decode_steps": stats.steps,
            "page_size": float(stats.page_size),
            "num_pages": float(stats.num_pages),
            "mean_pages_in_use": stats.mean_pages_in_use,
            "peak_pages_in_use": float(stats.peak_pages_in_use),
            "preemptions": float(stats.preemptions),
            "prefill_chunks": float(stats.prefill_chunks),
            "prefill_launches": float(stats.prefill_launches),
            "prefill_s": stats.prefill_s,
            "prefill_tokens": float(stats.prefill_tokens),
            "prefill_padded_tokens": float(stats.prefill_padded_tokens),
            "decode_s": stats.decode_s,
            "itl_p50_ms": stats.itl_p50_ms,
            "itl_p99_ms": stats.itl_p99_ms,
            "tp": float(stats.tp),
            "spec_k": float(stats.spec_k),
            "prefix_cache": float(stats.prefix_cache),
            "kv_bytes_per_token": stats.kv_bytes_per_token,
            "prompt_tokens_admitted": float(stats.prompt_tokens_admitted),
            "saved_prefill_tokens": float(stats.saved_prefill_tokens),
            "prefill_tokens_dropped": float(stats.prefill_tokens_dropped),
            "cow_copies": float(stats.cow_copies),
            "cache_evictions": float(stats.cache_evictions),
            **{f"compiles_{k}": float(v) for k, v in stats.compile_stats.items()},
            **{f"budget_{k}": v for k, v in stats.prefill_budget_stats.items()},
            **{f"prefix_{k}": v for k, v in stats.prefix_stats.items()},
            **{k: v for k, v in stats.spec_stats.items()},
        }
    )
    return summary, stats.total_tokens, stats.wall_s


def _serve_fleet(engines, cfg, args, load, prompts):
    """Fault-tolerant fleet: N paged workers behind the FleetRouter."""
    from ..serve.faults import FaultPlan
    from ..serve.fleet import FleetConfig, FleetRouter

    reqs = _tagged_requests(args, load, prompts)
    tenant_dicts = _parse_tenants(args.tenants) if args.tenants else []
    server = TracingServer()
    tracer = Tracer("serve-fleet", server)
    plan = FaultPlan.parse(args.fault_plan) if args.fault_plan else FaultPlan()
    if plan:
        print(f"[serve] fault plan: {plan.describe()}")
    router = FleetRouter(
        engines,
        FleetConfig(
            deadline_s=args.deadline_ms / 1e3,
            max_retries=args.retries,
            lease_ttl_s=args.lease_ttl_s,
            fairness=args.fairness == "on",
            recovery=args.recovery,
            checkpoint_every=args.checkpoint_every,
        ),
        tenants=[TenantSpec.from_dict(t) for t in tenant_dicts],
        engine_kwargs=dict(
            num_slots=args.engine_batch,
            page_size=args.page_size,
            num_pages=args.num_pages or None,
            prefill_chunk=args.prefill_chunk or None,
            overcommit=args.overcommit,
            prefill_mode=args.prefill_mode,
            prefill_budget=args.prefill_budget or None,
            spec_k=args.spec_k,
            spec_ngram=args.spec_ngram,
            prefix_cache=args.prefix_cache == "on",
        ),
        fault_plan=plan,
        tracer=tracer,
    )
    if args.drain_at:
        for item in args.drain_at.split(","):
            try:
                wtok, stok = item.strip().split(":")
                router.drain(int(wtok), int(stok))
            except ValueError:
                raise SystemExit(
                    f"[serve] bad --drain-at item {item!r} "
                    f"(expected worker:step)"
                )
    stats = router.serve(reqs)
    for r in stats.results:
        tail = (
            f"{len(r.tokens)} tokens" if r.status == "completed"
            else f"reason={r.reason}"
        )
        print(
            f"[serve] req {r.request_id}: {r.status} on worker {r.worker} "
            f"after {r.attempts} attempt(s), {tail}"
        )
    section = fleet_section(server.timeline("serve-fleet"))
    if section:
        print("[serve] fleet robustness:")
        for line in section.splitlines():
            print(f"[serve]   {line}")
    rsection = recovery_section(server.timeline("serve-fleet"))
    if rsection:
        print("[serve] KV-migration recovery:")
        for line in rsection.splitlines():
            print(f"[serve]   {line}")
    latencies = [
        r.latency_s for r in stats.results if r.status == "completed"
    ]
    summary = latency_summary(latencies) if latencies else {}
    summary.update(
        {
            "tokens_per_s": stats.throughput_tps,
            "fleet_workers": float(stats.num_workers),
            "rounds": float(stats.rounds),
            "completed": float(stats.completed),
            "failed": float(stats.failed),
            "rejected": float(stats.rejected),
            "deaths": float(stats.deaths),
            "requeued": float(stats.requeued),
            "hedged": float(stats.hedged),
            "duplicate_commits": float(stats.duplicate_commits),
            "goodput": stats.goodput,
            "max_degrade_level": float(stats.max_degrade_level),
            "migrated": float(stats.migrated),
            "migrated_tokens": float(stats.migrated_tokens),
            "recomputed_prefill_tokens": float(
                stats.recomputed_prefill_tokens),
            "bytes_moved": float(stats.bytes_moved),
            "checkpoints_saved": float(stats.checkpoints_saved),
            "checksum_failures": float(stats.checksum_failures),
            "drains": float(stats.drains),
            "joins": float(stats.joins),
        }
    )
    if stats.recovery_s:
        summary["recovery_max_s"] = max(stats.recovery_s)
    return summary, stats.total_tokens, stats.wall_s


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the architecture's small CPU test config "
                         "(default); --no-reduced serves its published "
                         "widths with weights and KV pages in bfloat16")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: serve the first N layers at unchanged "
                         "widths, one chip's share of a deployment that "
                         "pipelines the rest over further chips (0 = every "
                         "layer)")
    ap.add_argument("--backend", default=None,
                    choices=["pallas", "flash", "ref"],
                    help="attention kernels (default: the platform's own — "
                         "pallas on a TPU, flash elsewhere; flash and ref "
                         "are oracles)")
    ap.add_argument(
        "--engine", "--mode", dest="engine", default="static",
        choices=["static", "continuous", "paged"],
    )
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate-hz", type=float, default=20.0)
    ap.add_argument("--engine-batch", type=int, default=4)
    ap.add_argument("--batch-timeout-ms", type=float, default=10.0)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged engine)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="global KV page pool size (0 = num_slots * max_pages)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill tokens per decode boundary (0 = 4 pages)")
    ap.add_argument("--prefill-mode", default="packed",
                    choices=["packed", "chunked"],
                    help="packed: one token-packed varlen launch per boundary "
                         "(one compile); chunked: legacy per-slot chunks")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="packed-prefill tokens per decode boundary "
                         "(0 = 4x prefill chunk); bounds decode latency")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft depth: prompt-lookup proposes up "
                         "to k tokens per slot per boundary, one paged "
                         "verify launch scores all k+1 (0 = disabled)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="prompt-lookup n-gram match length for drafting")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="paged admission overcommit factor (>1 admits past "
                         "worst-case page commitment; preemption is the valve)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: heads split over the "
                         "first N of the process's devices (the chips of a "
                         "TPU host; on CPU, N forced host devices via "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--rs-block-outputs", action="store_true",
                    help="reduce-scatter block outputs instead of all-reduce "
                         "on seq-shardable (prefill) launches")
    ap.add_argument("--kv-dtype", default="",
                    choices=["", "float32", "bfloat16", "int8", "fp8"],
                    help="paged KV pool storage dtype: int8/fp8 store "
                         "quantized pages + per-page-per-head scales and "
                         "fuse dequantization into the attention kernels "
                         "for 2-4x effective pool capacity (empty = full "
                         "precision, bit-identical to before the flag)")
    ap.add_argument("--prefix-cache", default="on", choices=["on", "off"],
                    help="automatic prefix caching (paged engine): share "
                         "committed KV pages across requests with common "
                         "prompt prefixes (copy-on-write on append)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared-prefix workload: tokens of common prompt "
                         "prefix (0 = independent random prompts)")
    ap.add_argument("--prefix-share", type=float, default=0.75,
                    help="fraction of requests reusing a shared prefix")
    ap.add_argument("--prefix-groups", type=int, default=1,
                    help="distinct shared prefixes in the workload")
    ap.add_argument("--fleet", type=int, default=0,
                    help="fault-tolerant fleet: run N paged workers behind "
                         "the FleetRouter (load balancing, requeue-on-death, "
                         "graceful degradation; 0 = single engine)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request TTL from submit (fleet AND single-"
                         "engine paged): late completions fall out of "
                         "goodput, work expired before execution is "
                         "rejected with attribution (0 = none)")
    ap.add_argument("--tenants", default="",
                    help="multi-tenant serving mix: semicolon-separated "
                         "'name[,prio=T][,weight=W][,rate=TOK/S][,burst=TOK]"
                         "[,hz=QPS][,slo=MS][,prompt=N][,gen=N]' entries; "
                         "rate/burst arm a per-tenant token bucket, prio "
                         "picks the tier (0=best_effort 1=standard "
                         "2=premium), weight the fair share "
                         "(requires --engine paged)")
    ap.add_argument("--priority-mix", default="",
                    help="tier fractions for a single-tenant load, e.g. "
                         "'best_effort=0.25,standard=0.5,premium=0.25' "
                         "(ignored when --tenants is set)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request latency SLO for goodput accounting "
                         "and SLO-aware admission shedding (0 = none; "
                         "distinct from --deadline-ms, which is a hard TTL)")
    ap.add_argument("--fairness", default="on", choices=["on", "off"],
                    help="tenant-fair scheduling (token buckets + priority "
                         "tiers + weighted fair dequeue); off = pure FIFO "
                         "baseline for A/B comparison")
    ap.add_argument("--retries", type=int, default=2,
                    help="fleet requeues per request after a worker death "
                         "before the request is failed")
    ap.add_argument("--lease-ttl-s", type=float, default=30.0,
                    help="fleet worker heartbeat lease TTL; a worker that "
                         "misses renewal past the TTL is treated as dead")
    ap.add_argument("--fault-plan", default="",
                    help="scripted fault injection, e.g. "
                         "'crash@1:2,stall@0:3:0.5,pressure@2:1:8x4' "
                         "(kind@worker:step[:arg]; corrupt@W:S flips bytes "
                         "in worker W's latest KV checkpoint at step S; "
                         "empty = no faults)")
    ap.add_argument("--recovery", default="migrate",
                    choices=["replay", "migrate"],
                    help="fleet orphan recovery: migrate restores the "
                         "latest KV checkpoint on a survivor (O(bytes) "
                         "failover, bit-identical continuation); replay "
                         "re-prefills from the prompt (also the fallback "
                         "when no checkpoint exists or checksums fail)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="decode steps between KV page checkpoints on each "
                         "fleet worker (0 = none: only planned drains "
                         "migrate; requires --recovery migrate to matter)")
    ap.add_argument("--drain-at", default="",
                    help="planned elasticity: comma-separated worker:step "
                         "items, e.g. '1:4' drains worker 1 at boundary "
                         "step 4 — every live slot migrates with zero "
                         "recompute before the worker is removed")
    ap.add_argument("--evaldb", default="")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the command line."""
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.prefix_len > 0 and args.prefix_len >= args.prompt_len:
        ap.error(
            f"--prefix-len {args.prefix_len} must be smaller than "
            f"--prompt-len {args.prompt_len} (the shared prefix is a strict "
            f"prefix; every prompt keeps a unique tail)"
        )
    if args.tp > 1 and args.engine != "paged":
        ap.error("--tp > 1 requires --engine paged")
    if args.kv_dtype and args.engine != "paged":
        ap.error("--kv-dtype requires --engine paged (only the paged pool "
                 "stores quantized KV pages)")
    if args.fleet > 0 and args.engine != "paged":
        ap.error("--fleet requires --engine paged (the fleet routes over "
                 "paged workers)")
    if args.tenants and args.engine != "paged":
        ap.error("--tenants requires --engine paged (tenant-aware admission "
                 "lives in the paged engine and the fleet router)")
    if args.tenants:
        try:
            _parse_tenants(args.tenants)
        except ValueError as e:
            ap.error(str(e))
    if args.priority_mix:
        try:
            _parse_priority_mix(args.priority_mix)
        except ValueError as e:
            ap.error(f"bad --priority-mix {args.priority_mix!r}: {e}")
    try:
        depth_cut(get_config(args.arch, reduced=args.reduced), args.layers)
    except ValueError as e:
        ap.error(str(e))
    return args


def make_rules(args):
    """Tensor-parallel sharding rules over the first ``--tp`` devices, or
    None for a single device."""
    if args.tp <= 1:
        return None
    from ..sharding.specs import serve_rules
    from .mesh import make_serve_mesh

    return serve_rules(
        make_serve_mesh(tp=args.tp), rs_block_outputs=args.rs_block_outputs
    )


def serve_dtype(args) -> str:
    """Weights and KV pages: bfloat16 at published widths (what a chip
    serves), float32 for the reduced CPU config (what the tests compare
    bit for bit)."""
    return "float32" if args.reduced else "bfloat16"


def load_model(args, rules=None):
    """(cfg, model, params): the configured architecture, cut to
    ``--layers``, with random weights from seed 0.  The weights are made on
    the device by one jitted init, already laid out under ``rules`` — a
    model larger than one chip never lands whole on the first device."""
    cfg = depth_cut(get_config(args.arch, reduced=args.reduced), args.layers)
    model = build_model(cfg, backend=args.backend)
    shardings = None
    if rules is not None:
        shardings = jax.tree.map(
            lambda spec: NamedSharding(rules.mesh, spec),
            param_pspecs(model.param_defs(), rules),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    init = jax.jit(model.init, static_argnums=1, out_shardings=shardings)
    params = init(jax.random.PRNGKey(0), jnp.dtype(serve_dtype(args)))
    return cfg, model, params


def make_engine(args, model, params, rules=None) -> ServingEngine:
    return ServingEngine(
        model, params, max_batch=args.engine_batch, max_seq=args.max_seq,
        cache_dtype=serve_dtype(args), page_size=args.page_size, rules=rules,
        kv_dtype=args.kv_dtype or None,
    )


def make_workload(args, cfg):
    """(load, prompts): arrivals and prompt token ids drawn from seed 0."""
    rng = np.random.default_rng(0)
    if args.tenants:
        # multi-tenant mix: superposed per-tenant Poisson streams carrying
        # tenant identity / tier / SLO / token shape in each request's tags
        tenant_dicts = _parse_tenants(args.tenants)
        for t in tenant_dicts:
            t.setdefault("rate_hz", args.rate_hz / len(tenant_dicts))
            t.setdefault("slo_ms", args.slo_ms)
            t.setdefault("prompt_len", args.prompt_len)
            t.setdefault("gen_tokens", args.max_new_tokens)
        load = list(
            MultiTenantLoad(args.requests, tenant_dicts, seed=0).requests()
        )
        prompts = [
            rng.integers(
                0, cfg.vocab_size,
                (int(r.tags.get("prompt_len") or args.prompt_len),),
            ).astype(np.int32)
            for r in load
        ]
    elif args.prefix_len > 0:
        # shared-prefix serving mix: same-group prompts share their first
        # prefix_len tokens bit-for-bit — the workload the prefix cache eats
        load = list(
            SharedPrefixLoad(
                args.requests, rate_hz=args.rate_hz,
                prefix_len=args.prefix_len,
                suffix_len=args.prompt_len - args.prefix_len,
                share_ratio=args.prefix_share,
                num_groups=args.prefix_groups, seed=0,
            ).requests()
        )
        prompts = shared_prefix_prompts(load, cfg.vocab_size, seed=0)
    else:
        load = list(PoissonLoad(args.requests, args.rate_hz, seed=0).requests())
        prompts = [
            rng.integers(0, cfg.vocab_size, (args.prompt_len,)).astype(np.int32)
            for _ in load
        ]

    if args.priority_mix and not args.tenants:
        # stamp tiers onto a single-tenant load by fraction (seeded draw)
        import random as _random

        mix = _parse_priority_mix(args.priority_mix)
        tiers = [PRIORITY_TIERS.index(k) if k in PRIORITY_TIERS else int(k)
                 for k in mix]
        weights = [float(v) for v in mix.values()]
        mrng = _random.Random(0)
        for r in load:
            r.tags["priority"] = mrng.choices(tiers, weights)[0]
    return load, prompts


def serve(args, engine, cfg, load, prompts, model=None, params=None,
          rules=None):
    """Run the workload through the ``--engine`` executor (or the fleet);
    returns (summary, generated tokens, wall seconds)."""
    if args.fleet > 0:
        # workers share model+params (weights are read-only under serving);
        # each gets its own engine => its own KV page pool + slot state
        engines = [engine] + [
            make_engine(args, model, params, rules)
            for _ in range(args.fleet - 1)
        ]
        return _serve_fleet(engines, cfg, args, load, prompts)
    if args.engine == "continuous":
        return _serve_continuous(engine, cfg, args, load, prompts)
    if args.engine == "paged":
        return _serve_paged(engine, cfg, args, load, prompts)
    return _serve_static(engine, cfg, args, load, prompts)


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    rules = make_rules(args)
    cfg, model, params = load_model(args, rules)
    engine = make_engine(args, model, params, rules)
    # report header: the engine knobs this evaluation ran under, so the run
    # is self-describing (same block lands in the evaldb record)
    knobs = EngineKnobs(
        engine=args.engine,
        kv_dtype=args.kv_dtype or engine.cache_dtype,
        page_size=args.page_size if args.engine == "paged" else 0,
        spec_k=args.spec_k if args.engine == "paged" else 0,
        prefix_cache=args.engine == "paged" and args.prefix_cache == "on",
        tp=engine.tp,
        # recovery knobs are fleet-level: single-engine runs keep the
        # pre-fleet header byte-for-byte
        recovery=args.recovery if args.fleet else "replay",
        checkpoint_every=args.checkpoint_every if args.fleet else 0,
    )
    dev = jax.devices()[0]
    print(f"[serve] {cfg.name} ({cfg.num_layers} layers): {model.backend} "
          f"kernels on {jax.device_count()} x {dev.platform} "
          f"({dev.device_kind})")
    print(f"[serve] {knobs.describe()}")
    if args.tp > 1:
        print(f"[serve] tensor parallelism: requested tp={args.tp}, "
              f"effective tp={engine.tp} "
              f"({'heads split' if engine.tp > 1 else 'replication fallback'})")
    load, prompts = make_workload(args, cfg)
    summary, generated, wall = serve(
        args, engine, cfg, load, prompts, model, params, rules
    )

    print(f"[serve] {len(load)} requests, {generated} tokens in {wall:.2f}s")
    for k, v in summary.items():
        print(f"[serve]   {k:24s} {v:.2f}")
    if args.evaldb:
        EvalDB(args.evaldb).insert(
            EvaluationRecord(
                model=cfg.name, model_version="1.0.0", backend=model.backend,
                backend_version="1.0.0", system="local",
                scenario=f"serve-fleet{args.fleet}" if args.fleet > 0
                else f"serve-{args.engine}",
                batch_size=args.engine_batch, trace_level="NONE",
                agent_id="serve-driver",
                metrics={**summary, "engine_knobs": knobs.to_dict()},
            )
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

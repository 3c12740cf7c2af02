"""Tensor-parallel paged serving at a FIXED per-shard page budget.

The point of heads-splitting the KV pool: each page holds ``kv/tp`` heads
per shard, so the SAME per-device HBM budget (``pages_per_shard`` pages
here) funds a pool of ``pages_per_shard x tp`` logical pages.  This sweep
serves one deterministic workload at tp in {1, 2, 4} on forced host
devices, scaling ``num_pages`` with the effective tp exactly as a fixed
HBM budget would, and reports

* effective pool capacity (pages, = per-shard budget x tp) and the
  capacity ratio vs tp=1 — deterministic, CI-gated;
* servable peak concurrency at that budget (admission is keyed on free
  pages, so concurrency rises with the pool) and its ratio vs tp=1 —
  deterministic, CI-gated;
* greedy-token bit-identity vs the tp=1 run (1.0/0.0) — CI-gated;
* decode tokens/sec, TTFT p50/p99 and the analytic collective ledger
  (psum bytes moved) — recorded for trajectory, not gated (host-device
  shard_map on one CPU adds orchestration overhead, not speedup).

The model is the reduced glm4-9b with heads widened to 8/4 so tp=4
genuinely splits (the stock reduced config has 2 kv heads and would fall
back to replication).  The sweep runs on 8 forced CPU host devices, so it
says nothing about a chip interconnect.  Unless this process was started
pinned to them (``JAX_PLATFORMS=cpu`` and
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), the benchmark
re-execs itself in a child so pinned — decided from the environment alone,
so the parent never touches JAX and never holds a chip the child would
need (jax fixes the platform and device count at backend init, so an
in-process retry can't work).

Emits ``name,us_per_call,derived`` CSV rows plus ``BENCH_tp.json`` (seed +
git rev recorded).  ``--smoke`` keeps the same workload so baseline and CI
numbers compare one-to-one.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys

import numpy as np

from .common import bench_meta, emit

TP_SWEEP = (1, 2, 4)
NEEDED_DEVICES = 8
_CHILD_ENV = "REPRO_BENCH_TP_CHILD"


def _pinned_host_devices() -> int:
    """Forced CPU host devices this process was started with (0 unless it
    is pinned to the CPU) — read from the environment, not from JAX."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return 0
    counts = re.findall(
        r"--xla_force_host_platform_device_count=(\d+)",
        os.environ.get("XLA_FLAGS", ""),
    )
    return int(counts[-1]) if counts else 0


def _reexec_with_devices(smoke: bool, seed: int) -> dict:
    """Re-run this benchmark in a child pinned to forced CPU host devices."""
    if os.environ.get(_CHILD_ENV):
        raise RuntimeError(
            f"still only saw < {NEEDED_DEVICES} devices after forcing "
            f"host devices; is another XLA_FLAGS value overriding it?"
        )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={NEEDED_DEVICES}"
    ).strip()
    env[_CHILD_ENV] = "1"
    env.setdefault("PYTHONPATH", "src")
    cmd = [sys.executable, "-m", "benchmarks.bench_tp", "--seed", str(seed)]
    if smoke:
        cmd.append("--smoke")
    subprocess.run(cmd, env=env, check=True)
    with open("BENCH_tp.json") as f:
        return json.load(f)


def run(smoke: bool = False, seed: int = 0) -> dict:
    if _pinned_host_devices() < NEEDED_DEVICES:
        return _reexec_with_devices(smoke, seed)
    import jax

    from repro.configs import get_config
    from repro.core.analysis import percentile, tp_summary
    from repro.core.manifest import EngineKnobs
    from repro.core.tracing import Tracer, TracingServer
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve.engine import ServeRequest, ServingEngine
    from repro.sharding.specs import serve_rules

    pages_per_shard, page_size, num_slots = 10, 8, 8
    num_requests, prompt_len, gen_tokens = 12, 24, 6
    max_seq = 64

    # widen the reduced config's heads to 8 q / 4 kv so every sweep point
    # genuinely splits (stock reduced glm4-9b has 2 kv heads -> tp=4 would
    # replicate); pages_needed(24 + 6) = 4 pages per request, so the
    # 10-page tp=1 budget caps concurrency at 2 and the sweep has headroom
    cfg = dataclasses.replace(
        get_config("glm4-9b", reduced=True),
        name="glm4-9b-reduced-tp", num_heads=8, num_kv_heads=4,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        for _ in range(num_requests)
    ]

    def serve(tp: int, tracer=None):
        rules = serve_rules(make_serve_mesh(tp=tp)) if tp > 1 else None
        engine = ServingEngine(
            model, params, max_batch=num_slots, max_seq=max_seq,
            page_size=page_size, rules=rules,
        )
        # +1: page 0 is reserved scratch, so ALLOCATABLE capacity is exactly
        # pages_per_shard x tp and the capacity ratio lands on whole numbers
        num_pages = pages_per_shard * engine.tp + 1
        reqs = [
            ServeRequest(request_id=i, prompt=p, max_new_tokens=gen_tokens)
            for i, p in enumerate(prompts)
        ]
        engine.serve_paged(                       # warm the compile caches
            reqs[:2], num_slots=2, page_size=page_size, num_pages=num_pages,
        )
        reqs = [
            ServeRequest(request_id=i, prompt=p, max_new_tokens=gen_tokens)
            for i, p in enumerate(prompts)
        ]
        stats = engine.serve_paged(
            reqs, num_slots=num_slots, page_size=page_size,
            num_pages=num_pages, tracer=tracer,
        )
        return stats

    out = {
        "bench": "tp",
        "smoke": smoke,
        **bench_meta(seed, EngineKnobs(engine="paged", page_size=page_size,
                                       tp=TP_SWEEP[-1])),
        "devices": jax.device_count(),
        "pages_per_shard": pages_per_shard,
        "page_size": page_size,
        "num_slots": num_slots,
        "num_requests": num_requests,
        "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "heads": cfg.num_heads,
        "kv_heads": cfg.num_kv_heads,
    }
    base = None
    for tp in TP_SWEEP:
        server = TracingServer()
        tracer = Tracer(f"bench-tp{tp}", server)
        stats = serve(tp, tracer=tracer)
        if base is None:
            base = stats
        by_id = {r.request_id: r for r in base.results}
        identical = all(
            np.array_equal(r.tokens, by_id[r.request_id].tokens)
            for r in stats.results
        )
        ttfts = [r.ttft_s for r in stats.results]
        comm = tp_summary(server.timeline(f"bench-tp{tp}"))
        row = {
            "requested_tp": tp,
            "effective_tp": stats.tp,
            "num_pages": stats.num_pages,
            "capacity_ratio": stats.num_pages / base.num_pages,
            "peak_concurrency": stats.peak_slot_occupancy,
            "concurrency_ratio": (
                stats.peak_slot_occupancy / max(base.peak_slot_occupancy, 1)
            ),
            "tokens_identical": 1.0 if identical else 0.0,
            "decode_tokens_per_s": stats.total_tokens / max(stats.decode_s, 1e-12),
            "tokens_per_s": stats.throughput_tps,
            "ttft_p50_ms": percentile(ttfts, 50.0) * 1e3,
            "ttft_p99_ms": percentile(ttfts, 99.0) * 1e3,
            "wall_s": stats.wall_s,
            "preemptions": stats.preemptions,
            "psum_count": comm.get("psum_count", 0.0),
            "moved_bytes": comm.get("total_moved_bytes", 0.0),
        }
        out[f"tp{tp}"] = row
        emit(
            f"tp/{tp}", stats.wall_s,
            f"eff={stats.tp};pages={stats.num_pages};"
            f"capacity={row['capacity_ratio']:.1f}x;"
            f"peak_conc={stats.peak_slot_occupancy};"
            f"identical={int(identical)};"
            f"ttft_p99={row['ttft_p99_ms']:.1f}ms",
        )
        assert identical, f"tp={tp}: greedy tokens diverged from tp=1"

    for tp in TP_SWEEP[1:]:
        row = out[f"tp{tp}"]
        assert row["capacity_ratio"] == float(tp), (
            f"tp={tp}: pool capacity must scale with the heads split"
        )
        assert row["concurrency_ratio"] > 1.0, (
            f"tp={tp}: bigger pool must admit more concurrent requests"
        )

    with open("BENCH_tp.json", "w") as f:
        json.dump(out, f, indent=2)
    return out


if __name__ == "__main__":
    from .common import bench_main

    # re-exec'd child: the parent already printed the CSV header
    bench_main(run, "tp", suppress_header_env=_CHILD_ENV)

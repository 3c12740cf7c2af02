"""Run one cell of ``BENCHMARK.json`` once on the chip it is started on.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start): JAX on the accelerator, the
persistent compile cache in the checkout, the weights made on the device
from the seed by one jitted call, and a warm-up that compiles every shape
the cell's traffic can reach.  The window then drives ``serve_paged`` in
burst rounds: each round submits one burst of requests at once and runs
until every request has finished; rounds repeat until they have taken
``--seconds`` in all, and the window is the whole time of the rounds it
ran.  With ``--trace 1`` the first round runs under the profiler and the
per-layer metrics are printed instead of the end-to-end ones.

After the window the program's state is freed and a sample of the served
requests is compared with the float32 reference (``check``).  The last
line of standard output is the JSON result; everything else goes to
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits 1 and prints no result.

A cell over N chips runs one model over all of them with the program's
own tensor parallelism: the engine under ``serve_rules(make_serve_mesh(N))``
and every weight made in the sharding those rules give it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chipbench"

from chipbench import check, files, peaks, stats, traffic  # noqa: E402
from chipbench import trace as tracemod  # noqa: E402
from chipbench import weights as W  # noqa: E402

sys.path.insert(0, str(files.CHECKOUT / "src"))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Recorder:
    """The program's tracer hook: keeps every event ``serve_paged``
    publishes (name, host-clock start and end, tags)."""

    def __init__(self) -> None:
        self.events: List[Tuple[str, float, float, Dict[str, Any]]] = []

    def event(self, name, begin, end, **tags):
        self.events.append((name, begin, end, tags))


@dataclass
class Round:
    t0: float
    t1: float
    requests: List[Tuple[Any, int]]
    stats: Any
    events: List[Tuple[str, float, float, Dict[str, Any]]]


@dataclass
class Run:
    """What a per-layer metric reader gets."""

    cell: files.Cell
    dims: W.Dims
    slots: int
    rounds: List[Round]
    window_s: float
    peak: Dict[str, float]
    trace: Optional[tracemod.Reduction] = None
    traced_round: Optional[Round] = None
    # the engine's tensor-parallel degree: each chip's kernels hold
    # kv_heads // tp of the kv heads
    tp: int = 1


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run", description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def device_info(jax, chips: int, allow_cpu: bool) -> Dict[str, Any]:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU; JAX found platform {d.platform!r} "
                     f"({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def program_config(cell: files.Cell, d: W.Dims):
    """The program's configuration for the cell's file, checked against
    the sizes the file states: the file is the configuration as run."""
    from repro.configs import depth_cut, get_config

    pc = cell.config["program"]
    cfg = depth_cut(get_config(pc["arch"], reduced=bool(pc.get("reduced"))),
                    d.layers)
    got = {
        "layers": cfg.num_layers, "d_model": cfg.d_model, "d_ff": cfg.d_ff,
        "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim, "vocab": cfg.vocab_size,
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
    }
    want = {k: getattr(d, k) for k in got}
    plain = (cfg.family == "dense" and not cfg.tie_embeddings
             and not cfg.qk_norm and not cfg.post_norms
             and not cfg.scale_embed and cfg.attn_softcap == 0
             and cfg.logit_softcap == 0 and cfg.sliding_window == 0)
    if got != want or not plain:
        raise ValueError(f"{cell.config['name']}: the program's "
                         f"{pc['arch']} is {got} (plain dense: {plain}); "
                         f"the file states {want}")
    return cfg


def warmup_sets(slots: int, page: int, budget: int, pmin: int, pmax: int,
                max_seq: int) -> List[List[Tuple[int, int]]]:
    """Request sets, as (prompt length, output length), that together
    reach every decode ``pages_bound`` bucket, every packed-prefill context
    bucket and every mirror-patch size the cell's traffic can reach, in
    few prefill launches (each launch costs the same whatever it holds)."""
    from repro.serve.engine import bucket_pow2

    def pages(n):
        return -(-n // page)

    cap = pages(max_seq)
    sets: List[List[Tuple[int, int]]] = []
    counts = [1 << i for i in range(slots.bit_length()) if 1 << i <= slots]
    # decode: c requests of one length finish their prefill in one launch
    # (a mirror patch of c slots), then take one decode step with
    # pages(live) == b
    lo = bucket_pow2(pages(pmin + 1), cap=cap)
    hi = bucket_pow2(pages(max_seq), cap=cap)
    b = lo
    while True:
        live = min(b * page, max_seq - 1)
        if bucket_pow2(pages(live), cap=cap) != b:
            raise ValueError(f"no warm-up reaches decode bucket {b}")
        fit = [c for c in counts if c * (live - 1) <= budget]
        c = max(fit) if fit else 1
        if c in counts:
            counts.remove(c)
        sets.append([(live - 1, 2)] * c)
        if b >= hi:
            break
        b = min(2 * b, cap)
    for c in counts:
        sets.append([(page, 2)] * c)
    # packed prefill: a launch whose deepest chunk starts at s.  In a chain
    # R0, R1, ... each request fills the rest of the launch its
    # predecessor ends in, so launch j holds exactly one chunk that starts
    # at s_j; a start past the budget takes a set of its own
    top = bucket_pow2(max(pages(pmax) - 1, 1), cap=cap)
    starts = []
    b = 1
    while b <= top:
        s = min(b * page, (pages(pmax) - 1) * page)
        if bucket_pow2(pages(s), cap=cap) != b:
            raise ValueError(f"no warm-up reaches prefill context bucket {b}")
        starts.append(s)
        b *= 2
    short = [s for s in starts if s < budget]
    for i in range(0, len(short), max(slots - 1, 1)):
        chain = short[i:i + max(slots - 1, 1)]
        reqs = [(budget - chain[0], 2)]
        reqs += [(s + budget - t, 2) for s, t in zip(chain, chain[1:])]
        reqs.append((chain[-1] + page, 2))
        sets.append(reqs)
    for s in starts:
        if s >= budget:
            sets.append([(budget - s % budget, 2), (s + page, 2)])
    return sets


def serve_kwargs(cell: files.Cell) -> Dict[str, Any]:
    s = cell.serve["serve"]
    return dict(
        num_slots=int(cell.serve["slots"]),
        prefill_mode="packed",
        prefill_budget=int(s["prefill_budget"]),
        overcommit=float(s["overcommit"]),
        spec_k=int(s["spec_k"]),
        prefix_cache=bool(s["prefix_cache"]),
    )


def to_requests(burst):
    from repro.serve.engine import ServeRequest

    return [ServeRequest(request_id=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(burst)]


@dataclass
class Setup:
    jax: Any
    info: Dict[str, Any]
    dims: W.Dims
    engine: Any
    devices: List[Any]          # the cell's chips
    shardings: Any              # the weights' shardings; None on one chip
    kw: Dict[str, Any]
    compile_times: List[float]
    cache_dir: Optional[str]
    marks: Dict[str, float]


def tp_layout(model, mesh):
    """The program's serving rules over ``mesh`` and the weights'
    shardings under them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.sharding.specs import param_pspecs, serve_rules

    rules = serve_rules(mesh)
    shardings = jax.tree.map(
        lambda p: NamedSharding(rules.mesh, p),
        param_pspecs(model.param_defs(), rules),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    return rules, shardings


def build(cell: files.Cell, seed: int, allow_cpu: bool = False,
          fault=None) -> Setup:
    """JAX on the chip, the compile cache, the weights from ``seed`` and
    the engine, as the cell's files state them."""
    import jax

    info = device_info(jax, cell.chips, allow_cpu)
    marks = {"device": time.perf_counter() - T_PROCESS}
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_serve_mesh
    from repro.models import build_model
    from repro.serve.engine import ServingEngine

    cache_dir = None
    if info["platform"] == "tpu":
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compile_times: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: compile_times.append(time.perf_counter())
        if name == COMPILE_EVENT else None
    )
    d = W.dims(cell.config)
    model = build_model(program_config(cell, d))
    rules, shardings = (tp_layout(model, make_serve_mesh(cell.chips))
                        if cell.chips > 1 else (None, None))
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        model.param_specs(cell.config["dtype"]))
    params = W.make_params(d, seed, dtype=cell.config["dtype"],
                           shardings=shardings)
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if got != want:
        raise ValueError("the benchmark's weight layout no longer matches the "
                         "program's param_specs")
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter() - T_PROCESS
    engine = ServingEngine(
        model, params, max_batch=int(cell.serve["slots"]),
        max_seq=traffic.max_len(cell.traffic), cache_dtype=cell.config["dtype"],
        page_size=int(cell.serve["serve"]["page_size"]), rules=rules,
    )
    if fault is not None:
        fault(engine)
    devices = list(rules.mesh.devices.flat) if rules else jax.devices()[:1]
    return Setup(jax, info, d, engine, devices, shardings, serve_kwargs(cell),
                 compile_times, cache_dir, marks)


def warm_up(s: Setup, cell: files.Cell) -> int:
    """Serve the warm-up sets; returns how many there were."""
    mix = cell.traffic
    sets = warmup_sets(int(cell.serve["slots"]),
                       int(cell.serve["serve"]["page_size"]),
                       s.kw["prefill_budget"], int(mix["prompt"]["min"]),
                       int(mix["prompt"]["max"]), traffic.max_len(mix))
    rng = np.random.default_rng(0)
    for ws in sets:
        s.engine.serve_paged(to_requests(
            [(rng.integers(0, s.dims.vocab, size=p, dtype=np.int32), n)
             for p, n in ws]
        ), **s.kw)
    return len(sets)


def serve_round(s: Setup, cell: files.Cell, index: int, seed: int,
                trace_dir: Optional[str] = None) -> Round:
    """One burst through one ``serve_paged`` call; with ``trace_dir`` the
    call runs under the profiler, inside the host span the trace
    reduction takes as its window."""
    jax = s.jax
    burst = traffic.burst(cell.traffic, int(cell.serve["burst"]), index, seed,
                          s.dims.vocab)
    rec = Recorder()
    if trace_dir is None:
        t0 = time.perf_counter()
        st = s.engine.serve_paged(to_requests(burst), tracer=rec, **s.kw)
        return Round(t0, time.perf_counter(), burst, st, rec.events)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracemod.WINDOW):
            st = s.engine.serve_paged(to_requests(burst), tracer=rec, **s.kw)
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    return Round(t0, t1, burst, st, rec.events)


def longest_gap(rd: Round) -> float:
    """Longest time between two step boundaries of a round, from the
    page pool's occupancy events (one per boundary)."""
    t = [rd.t0] + [b for name, b, _, _ in rd.events
                   if name == "pages:occupancy"] + [rd.t1]
    return max(b - a for a, b in zip(t, t[1:]))


def measure(args, cell: files.Cell, allow_cpu: bool,
            fault=None) -> Dict[str, Any]:
    s = build(cell, args.seed, allow_cpu, fault)
    jax, info, d = s.jax, s.info, s.dims
    n_sets = warm_up(s, cell)
    variants = s.engine.compile_stats()
    setup_s = time.perf_counter() - T_PROCESS
    say(f"device {info['platform']} {info['kind']} x{info['count']} "
        f"(tp {s.engine.tp}); set-up "
        f"{setup_s:.3f} s (device ready {s.marks['device']:.3f} s, weights "
        f"{s.marks['weights']:.3f} s, then {n_sets} warm-up sets), "
        f"{len(s.compile_times)} backend compiles, cache {s.cache_dir}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    gc_pauses: List[float] = []
    gc_start: List[float] = []

    def on_gc(phase, info):
        if phase == "start":
            gc_start[:] = [time.perf_counter()]
        elif gc_start:
            gc_pauses.append(time.perf_counter() - gc_start[0])

    gc.callbacks.append(on_gc)
    rounds: List[Round] = []
    n_compiles = len(s.compile_times)
    # the window is the time of its rounds, each from its submission to its
    # last token: the profiler's start and stop around a traced round, and
    # the making of the next burst, lie outside it
    window_s = 0.0
    while not rounds or window_s < args.seconds:
        rounds.append(serve_round(s, cell, len(rounds), args.seed,
                                  trace_dir if not rounds else None))
        window_s += rounds[-1].t1 - rounds[-1].t0
    gc.callbacks.remove(on_gc)
    attempted = sum(len(rd.requests) for rd in rounds)
    new_variants = {k: v - variants.get(k, 0)
                    for k, v in s.engine.compile_stats().items()
                    if v != variants.get(k, 0)}
    say(f"window {window_s:.3f} s, {len(rounds)} rounds "
        f"({', '.join(f'{rd.t1 - rd.t0:.3f}' for rd in rounds)} s; longest "
        f"step boundary gap {', '.join(f'{longest_gap(rd):.3f}' for rd in rounds)} s), "
        f"{attempted} requests; compiles inside the window: "
        f"{len(s.compile_times) - n_compiles} backend, "
        f"new engine variants {new_variants or 'none'}; {len(gc_pauses)} "
        f"garbage collections, longest {max(gc_pauses, default=0.0):.3f} s")

    each = [int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for dev in s.devices]
    info["memory_peak_bytes"] = max(each)
    info["memory_peak_bytes_each"] = each

    results = [r for rd in rounds for r in rd.stats.results]
    failed = sum(
        1 for rd in rounds for (p, n), r in zip(rd.requests, rd.stats.results)
        if r.status != "completed" or len(r.tokens) != n
    )
    served = [check.Served(p, r.tokens)
              for rd in rounds for (p, n), r in zip(rd.requests, rd.stats.results)
              if r.status == "completed" and len(r.tokens) == n]

    run = Run(cell, d, int(cell.serve["slots"]), rounds, window_s, {},
              tp=s.engine.tp)
    reduction = None
    if trace_dir is not None:
        try:
            reduction = tracemod.reduce(tracemod.load(
                tracemod.find_xplane(trace_dir),
                devices=[dev.id for dev in s.devices]))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        info["busy_s"] = reduction.busy_s
        info["window_s"] = reduction.window_s
    if info["platform"] == "tpu" or not allow_cpu:
        run.peak = peaks.peaks(info["kind"])
    run.trace, run.traced_round = reduction, rounds[0] if reduction else None

    if args.trace:
        metrics = {}
        for m in cell.per_layer:
            v = files.load_metric(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = stats.end_to_end(results, window_s)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    # the program's state goes before the reference runs on the chip
    s.engine = None
    gc.collect()
    t_ref = time.perf_counter()
    items = check.sample(served, args.seed, int(cell.serve["check"]["requests"]))
    cmp = check.compare(d, args.seed, items)
    say(f"reference: {len(items)} requests, {int(cmp['tokens'])} served tokens "
        f"compared in {time.perf_counter() - t_ref:.3f} s")
    limits = cell.serve["check"]["limits"]
    numbers = {
        "served_gap": {"value": cmp["served_gap"], "limit": limits["served_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": info,
    }
    if reduction is not None:
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in tracemod.top_ops(reduction)],
            "idle_gaps": [[n, t] for n, t in reduction.gaps],
        }
    result["check"] = numbers
    for name, v in numbers.items():
        print(f"[chipbench] check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None, allow_cpu: bool = False, root: Path = files.HERE,
         benchmark: Optional[Path] = None, fault=None) -> int:
    """``allow_cpu`` and ``fault`` are for the tests: they skip the look
    for a chip, and break the engine underneath, respectively."""
    args = parse_args(argv)
    cell = files.load_cell(args.workload, root=root, benchmark=benchmark)
    try:
        result = measure(args, cell, allow_cpu, fault)
    except NoChip as e:
        say(f"FAIL: {e}")
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

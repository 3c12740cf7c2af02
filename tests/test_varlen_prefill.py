"""Packed varlen prefill: kernel sweeps vs the host-loop oracle, the packed
serving pipeline vs the chunked path (bit-identical greedy tokens), the
prefill token-budget ledger, and per-run compile accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.analysis import (
    prefill_saturation_section,
    prefill_saturation_summary,
)
from repro.core.tracing import Span, TraceLevel
from repro.kernels import kvquant, ops, ref
from repro.kernels.varlen_prefill import _item_starts, work_items
from repro.kernels.varlen_prefill import varlen_prefill as pallas_varlen
from repro.models import build_model
from repro.serve.engine import ServeRequest, ServingEngine
from repro.serve.scheduler import PrefillBudget

_RNG = np.random.default_rng(42)

PAGE = 8


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=5e-5, atol=5e-5)


def _pack(chunks, T, kvh=2, h=4, d=16, max_pages=6, num_pages=24,
          dtype=jnp.float32):
    """Build a packed workload: ``chunks`` is a list of (real_len,
    ctx_pages); spans are page-aligned, T may leave a buffer tail pad."""
    C = len(chunks)
    cu, lens, pos0 = [0], [], []
    tables = np.zeros((C, max_pages), np.int32)
    nxt = 1
    for c, (n, cp) in enumerate(chunks):
        cu.append(cu[-1] + (n + PAGE - 1) // PAGE * PAGE)
        lens.append(n)
        pos0.append(cp * PAGE)
        for j in range(cp):
            tables[c, j] = nxt
            nxt += 1
    assert cu[-1] <= T and nxt <= num_pages
    mk = lambda shape: jnp.asarray(_RNG.normal(size=shape), dtype)
    return (
        mk((T, h, d)), mk((T, kvh, d)), mk((T, kvh, d)),
        mk((num_pages, PAGE, kvh, d)), mk((num_pages, PAGE, kvh, d)),
        jnp.asarray(np.array(cu, np.int32)),
        jnp.asarray(np.array(lens, np.int32)),
        jnp.asarray(np.array(pos0, np.int32)),
        jnp.asarray(tables),
    )


CASES = [
    # (chunks [(real_len, ctx_pages)], T): ragged lengths, non-divisible
    # chunk tails, empty chunk rows, context pages, buffer tail pad
    ([(5, 0), (8, 2), (3, 1)], 32),
    ([(13, 1), (0, 0), (7, 0)], 24),
    ([(8, 3), (16, 0), (2, 2), (5, 1)], 40),
    ([(21, 2)], 24),
]


@pytest.mark.parametrize("chunks,T", CASES)
@pytest.mark.parametrize("window", [None, 5])
def test_varlen_jnp_vs_oracle(chunks, T, window):
    args = _pack(chunks, T)
    a = ref.varlen_prefill(*args, window=window)
    f = ops.varlen_prefill_jnp(*args, window=window)
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(f, np.float32), **_tol(jnp.float32)
    )


@pytest.mark.parametrize("chunks,T", CASES)
@pytest.mark.parametrize("window", [None, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_varlen_vs_oracle(chunks, T, window, dtype):
    args = _pack(chunks, T, dtype=dtype)
    a = ref.varlen_prefill(*args, window=window)
    p = pallas_varlen(*args, window=window)
    assert p.dtype == args[0].dtype
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(p, np.float32), **_tol(dtype)
    )


def test_varlen_softcap_and_dispatch():
    args = _pack([(6, 1), (9, 0)], 24)
    a = ref.varlen_prefill(*args, softcap=11.0)
    f = ops.varlen_prefill(*args, softcap=11.0, backend="flash")
    p = ops.varlen_prefill(*args, softcap=11.0, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(f, np.float32), **_tol(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(p, np.float32), **_tol(jnp.float32)
    )


def test_varlen_pages_bound_exact():
    """A pages_bound covering every chunk's committed context is exact."""
    args = _pack([(8, 2), (8, 1)], 16)
    full = pallas_varlen(*args)
    bounded = pallas_varlen(*args, pages_bound=2)
    np.testing.assert_allclose(np.asarray(full), np.asarray(bounded), atol=1e-6)
    via_ops = ops.varlen_prefill(*args, backend="flash", pages_bound=2)
    oracle = ref.varlen_prefill(*args)
    np.testing.assert_allclose(
        np.asarray(oracle, np.float32), np.asarray(via_ops, np.float32),
        **_tol(jnp.float32),
    )


# Packings for the work list: (chunks, T, pages_bound, kwargs, items).
# ``items`` is counted by hand: per chunk of L real q blocks and P context
# pages, L * min(P, bound) context items and L (L + 1) / 2 causal intra
# items, plus one item for each q block that holds no real token.
WORK_CASES = {
    # one 5-token chunk in an 8-block buffer: 1 live block, 7 pad blocks
    "short_chunk_in_large_buffer": ([(5, 0)], 64, None, {}, 1 + 7),
    # 2 context pages under a bound of 4: L=2, 2*2 + 3
    "context_below_bound": ([(16, 2)], 16, 4, {}, 4 + 3),
    # L = 1, 2, 1 with P = 0, 2, 0; one pad block
    "several_chunks_one_with_context":
        ([(5, 0), (11, 2), (3, 0)], 40, None, {}, 1 + (4 + 3) + 1 + 1),
    # no real token anywhere: one item per block
    "all_pad": ([(0, 0)], 16, None, {}, 2),
    # L = 2, 1 with P = 2, 1
    "window_with_softcap":
        ([(13, 2), (6, 1)], 32, None, dict(window=5, softcap=11.0),
         (4 + 3) + (1 + 1) + 1),
    # L = 2, 1 with P = 2, 1, context pages from an int8 pool
    "quantized_pool": ([(9, 2), (4, 1)], 32, None, dict(quantized=True),
                       (4 + 3) + (1 + 1) + 1),
}


@pytest.mark.parametrize("case", sorted(WORK_CASES))
def test_pallas_varlen_work_list(case):
    """The work-list kernel against the oracle on packings whose grid is
    mostly dead: pad rows come back exactly zero, and the kernel iterates
    exactly the hand-counted live items, as the host helper counts them."""
    chunks, T, bound, kw, items = WORK_CASES[case]
    kw = dict(kw)
    args = _pack(chunks, T)
    q, k, v, kp, vp, cu, lens, pos0, tables = args
    oracle_pools, scales = (kp, vp), {}
    if kw.pop("quantized", False):
        (kq, ks), (vq, vs) = (kvquant.quantize(x, jnp.int8) for x in (kp, vp))
        oracle_pools = kvquant.dequantize(kq, ks), kvquant.dequantize(vq, vs)
        kp, vp, scales = kq, vq, dict(k_scales=ks, v_scales=vs)
    want = ref.varlen_prefill(q, k, v, *oracle_pools, cu, lens, pos0, tables, **kw)
    got = np.asarray(pallas_varlen(q, k, v, kp, vp, cu, lens, pos0, tables,
                                   pages_bound=bound, **kw, **scales))
    np.testing.assert_allclose(np.asarray(want, np.float32), got,
                               **_tol(jnp.float32))
    real = np.zeros(T, bool)
    for c, n in enumerate(np.asarray(lens)):
        real[int(cu[c]) : int(cu[c]) + n] = True
    assert np.all(got[~real] == 0.0)

    ctx_bound = max(min(bound or tables.shape[1], tables.shape[1]), 1)
    _, count, _, _ = _item_starts(cu, lens, pos0, nqb=T // PAGE, block=PAGE,
                                  ctx_bound=ctx_bound)
    live, rect = work_items(cu, lens, pos0, t_pack=T, block=PAGE,
                            pages_bound=ctx_bound)
    assert live == int(count) == items
    assert rect == (T // PAGE) * (ctx_bound + T // PAGE)


def test_varlen_jnp_non_aligned_chunk_boundaries():
    """A page-multiple buffer with NON-page-aligned chunk boundaries must
    take the exact per-token path (a block straddling two chunks would
    otherwise gather the wrong request's context pages)."""
    ps, kvh, h, d, num_pages = 8, 2, 4, 16, 12
    T = 16
    mk = lambda shape: jnp.asarray(_RNG.normal(size=shape), jnp.float32)
    args = (
        mk((T, h, d)), mk((T, kvh, d)), mk((T, kvh, d)),
        mk((num_pages, ps, kvh, d)), mk((num_pages, ps, kvh, d)),
        jnp.asarray([0, 10, 16], jnp.int32),      # boundary at 10: misaligned
        jnp.asarray([10, 6], jnp.int32),
        jnp.asarray([8, 0], jnp.int32),
        jnp.asarray([[1, 0, 0], [0, 0, 0]], jnp.int32),
    )
    a = ref.varlen_prefill(*args)
    f = ops.varlen_prefill_jnp(*args)
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(f, np.float32), **_tol(jnp.float32)
    )


def test_varlen_pad_rows_are_zero():
    """Chunk-pad and buffer-tail rows must come back exactly zero (they feed
    the rest of the packed forward)."""
    chunks, T = [(5, 0), (11, 1)], 32
    args = _pack(chunks, T)
    for out in (ops.varlen_prefill_jnp(*args), pallas_varlen(*args)):
        o = np.asarray(out)
        assert np.all(o[5:8] == 0.0)        # chunk 0 pad
        assert np.all(o[8 + 11 : 24] == 0.0)  # chunk 1 pad
        assert np.all(o[24:] == 0.0)        # buffer tail


def test_varlen_no_cross_chunk_leakage():
    """Perturbing one chunk's tokens must not change another chunk's output
    (the packed buffer is attention-isolated per request)."""
    chunks, T = [(8, 0), (8, 0)], 16
    q, k, v, kp, vp, cu, lens, pos0, tables = _pack(chunks, T)
    base = np.asarray(ops.varlen_prefill_jnp(q, k, v, kp, vp, cu, lens, pos0, tables))
    k2 = k.at[8:].add(3.7)
    v2 = v.at[8:].add(-1.9)
    pert = np.asarray(ops.varlen_prefill_jnp(q, k2, v2, kp, vp, cu, lens, pos0, tables))
    np.testing.assert_array_equal(base[:8], pert[:8])
    assert np.abs(base[8:] - pert[8:]).max() > 1e-3


# ---------------------------------------------------------------------------
# Packed serving pipeline
# ---------------------------------------------------------------------------
def _engine(max_seq=32, num_slots=3):
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, ServingEngine(model, params, max_batch=num_slots, max_seq=max_seq)


def test_serve_paged_packed_matches_chunked():
    """Greedy tokens from the packed varlen-prefill pipeline are
    bit-identical to the PR 2 chunked path (and both to serve_continuous)."""
    cfg, engine = _engine()
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (5, 9, 7, 4)
    ]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=m)
        for i, (p, m) in enumerate(zip(prompts, (6, 4, 8, 3)))
    ]
    cont = engine.serve_continuous(reqs(), num_slots=2)
    chunked = engine.serve_paged(
        reqs(), num_slots=3, page_size=4, prefill_chunk=8, prefill_mode="chunked"
    )
    packed = engine.serve_paged(
        reqs(), num_slots=3, page_size=4, prefill_chunk=8,
        prefill_mode="packed", prefill_budget=16,
    )
    by_id = {r.request_id: r for r in cont.results}
    for r in chunked.results + packed.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)
    assert packed.prefill_mode == "packed"
    assert packed.prefill_budget == 16
    assert packed.prefill_tokens == sum(len(p) for p in prompts)
    # coalescing: fewer launches than chunks, budget ledger consistent
    assert packed.prefill_launches < packed.prefill_chunks + len(prompts)
    assert packed.prefill_launches <= chunked.prefill_launches
    assert packed.prefill_budget_stats["granted_tokens"] == packed.prefill_tokens


class _Tags:
    """A tracer that keeps each event's tags by name."""

    def __init__(self):
        self.tags = []

    def event(self, name, begin, end, **tags):
        self.tags.append((name, tags))


@pytest.mark.parametrize("backend", ["pallas", "flash"])
def test_serve_paged_counts_live_kernel_items(backend):
    """``prefill_kv_live`` / ``prefill_kv_rect`` sum each packed launch's
    live (q block, key stage) items and its whole grid, counted by hand:
    pages of 4 tokens, an 8-token buffer (2 q blocks).
    Launch 1: request 0's first 8 tokens, no context (bound 1): 1 + 2
    intra items of 2 x (1 + 2).  Launch 2: request 0's last 2 tokens over
    2 context pages (bound 2), 2 + 1 items, and request 1's 3 tokens, 1
    item, of 2 x (2 + 2).  The launches' ``prefill:packed`` tags add up to
    the same sums.  A backend that does not run the varlen kernel counts
    nothing."""
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg, backend=backend)
    engine = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                           max_batch=2, max_seq=32)
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (10, 3)]
    tracer = _Tags()
    stats = engine.serve_paged(
        [ServeRequest(request_id=i, prompt=p, max_new_tokens=2)
         for i, p in enumerate(prompts)],
        num_slots=2, page_size=4, prefill_mode="packed", prefill_budget=8,
        tracer=tracer,
    )
    assert stats.prefill_launches == 2
    want = (3 + 4, 6 + 8) if backend == "pallas" else (0, 0)
    assert (stats.prefill_kv_live, stats.prefill_kv_rect) == want
    launches = [tags for name, tags in tracer.tags if name == "prefill:packed"]
    assert (sum(t["live"] for t in launches),
            sum(t["rect"] for t in launches)) == want


def test_serve_paged_packed_budget_caps_boundary_tokens():
    """A tight prefill budget spreads one long prompt over several packed
    launches, each granting at most ``prefill_budget`` real tokens."""
    cfg, engine = _engine()
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (20,)).astype(np.int32)
    stats = engine.serve_paged(
        [ServeRequest(request_id=0, prompt=prompt, max_new_tokens=2)],
        num_slots=2, page_size=4, prefill_mode="packed", prefill_budget=8,
    )
    assert stats.prefill_budget == 8
    assert stats.prefill_launches >= 3          # 20 tokens / 8-token budget
    assert stats.prefill_budget_stats["granted_tokens"] == 20.0
    # no launch can exceed the budget: utilization is total/steps*budget
    assert stats.prefill_budget_stats["budget_utilization"] <= 1.0
    # tokens left waiting at full boundaries are recorded as starvation
    assert stats.prefill_budget_stats["starved_tokens"] > 0


def test_serve_paged_packed_preemption_identical_tokens():
    """Packed prefill under page pressure (overcommit + preemption) still
    produces the chunked path's exact greedy tokens."""
    cfg, engine = _engine()
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (9, 8, 7, 5)
    ]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=m)
        for i, (p, m) in enumerate(zip(prompts, (10, 8, 12, 6)))
    ]
    cont = engine.serve_continuous(reqs(), num_slots=2)
    packed = engine.serve_paged(
        reqs(), num_slots=3, page_size=4, num_pages=7, prefill_chunk=4,
        overcommit=10.0, prefill_mode="packed", prefill_budget=8,
    )
    assert packed.preemptions > 0
    by_id = {r.request_id: r for r in cont.results}
    for r in packed.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)


def test_serve_paged_packed_single_compile():
    """However ragged the prompt mix, the packed pipeline compiles ONE
    prefill variant per (buffer, chunk-rows, table, ctx-bucket) shape —
    not one per chunk length x offset like the chunked path."""
    cfg, engine = _engine(max_seq=64, num_slots=4)
    rng = np.random.default_rng(5)
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        for n in (3, 11, 17, 6, 9, 14)
    ]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=2)
        for i, p in enumerate(prompts)
    ]
    packed = engine.serve_paged(
        reqs(), num_slots=4, page_size=4, prefill_mode="packed",
        prefill_budget=16,
    )
    # ctx-pages pow2 buckets are the only extra variants (log, not per-shape)
    assert packed.compile_stats["packed_prefill"] <= 3
    assert packed.compile_stats["paged_prefill"] == 0
    chunked = engine.serve_paged(
        reqs(), num_slots=4, page_size=4, prefill_chunk=8,
        prefill_mode="chunked",
    )
    assert chunked.compile_stats["paged_prefill"] > packed.compile_stats["packed_prefill"]


def test_compile_stats_per_instance_and_per_run():
    """Engines built in one process never see each other's compile counts,
    and a run's PagedStats reports only its own delta (a warmed second run
    reports zero new compiles)."""
    cfg, e1 = _engine()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32) for _ in range(2)]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=2)
        for i, p in enumerate(prompts)
    ]
    first = e1.serve_paged(reqs(), num_slots=2, page_size=4, prefill_budget=8)
    assert sum(first.compile_stats.values()) > 0
    second = e1.serve_paged(reqs(), num_slots=2, page_size=4, prefill_budget=8)
    assert sum(second.compile_stats.values()) == 0   # cache warm: no new jits
    assert sum(e1.compile_stats().values()) == sum(first.compile_stats.values())
    _, e2 = _engine()
    assert all(v == 0 for v in e2.compile_stats().values())


# ---------------------------------------------------------------------------
# PrefillBudget ledger
# ---------------------------------------------------------------------------
def test_prefill_budget_ledger():
    b = PrefillBudget(16)
    with pytest.raises(ValueError):
        PrefillBudget(0)
    b.begin_step()
    assert b.grant(10) == 10
    assert b.grant(10) == 6                  # capped by the remaining budget
    assert b.grant(5) == 0
    with pytest.raises(ValueError):
        b.grant(-1)
    b.begin_step()
    assert b.remaining == 16                 # fresh window per boundary
    assert b.grant(4) == 4
    b.defer(7)                               # demand left waiting this step
    with pytest.raises(ValueError):
        b.defer(-1)
    s = b.stats()
    assert s["steps"] == 2.0
    assert s["granted_tokens"] == 20.0
    assert s["requested_tokens"] == 36.0
    assert s["starved_tokens"] == 16.0
    assert s["budget_utilization"] == pytest.approx(20 / 32)
    assert b.granted_series == [(0, 16), (1, 4)]


# ---------------------------------------------------------------------------
# Prefill-saturation analysis
# ---------------------------------------------------------------------------
def _prefill_span(begin, end, **tags):
    return Span(
        name="prefill:packed", level=TraceLevel.SYSTEM, trace_id="t",
        begin=begin, end=end, tags=tags,
    )


def test_prefill_saturation_summary_and_section():
    spans = [
        _prefill_span(0.0, 0.1, tokens=48, padding=16, chunks=3, buffer=64, budget=64),
        _prefill_span(0.2, 0.3, tokens=32, padding=32, chunks=1, buffer=64, budget=64),
        Span(name="pages:occupancy", level=TraceLevel.SYSTEM, trace_id="t"),
    ]
    s = prefill_saturation_summary(spans)
    assert s["launches"] == 2.0
    assert s["buffer_tokens"] == 64.0
    assert s["prefill_tokens"] == 80.0
    assert s["mean_chunks_per_launch"] == 2.0
    assert s["mean_buffer_utilization"] == pytest.approx(80 / 128)
    assert s["peak_buffer_utilization"] == pytest.approx(48 / 64)
    assert s["pad_fraction"] == pytest.approx(48 / 128)
    assert s["prefill_tokens_per_s"] == pytest.approx(80 / 0.2, rel=1e-6)
    section = prefill_saturation_section(spans)
    assert "mean_buffer_utilization" in section
    assert prefill_saturation_section([]) == ""


def test_serve_paged_packed_emits_prefill_events():
    from repro.core.tracing import Tracer, TracingServer

    cfg, engine = _engine()
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32) for _ in range(2)]
    server = TracingServer()
    tracer = Tracer("t", server)
    stats = engine.serve_paged(
        [ServeRequest(request_id=i, prompt=p, max_new_tokens=2)
         for i, p in enumerate(prompts)],
        num_slots=2, page_size=4, prefill_budget=8, tracer=tracer,
    )
    summary = prefill_saturation_summary(server.timeline("t"))
    assert summary["launches"] == float(stats.prefill_launches)
    assert summary["prefill_tokens"] == float(stats.prefill_tokens)

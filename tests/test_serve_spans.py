"""Spans and counters of ``serve_paged``: the boundary spans nest as the
loop runs them, the decode and prefill times are the sums of their spans,
each request's three spans tile its life, per-token times agree with the
request's own metrics, and nothing of it changes the served tokens.
The same names sit on the profiler's clock."""
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.analysis import percentile
from repro.models import build_model
from repro.serve.engine import ServeRequest, ServingEngine

BOUNDARY = ("sched:retire", "sched:admit", "prefill:packed", "prefill:wait",
            "prefill:first_tokens", "pages:grow", "decode:step",
            "decode:patch", "decode:fetch")
INNER = {"prefill:wait": "prefill:packed",
         "prefill:first_tokens": "prefill:packed",
         "decode:patch": "decode:step", "decode:fetch": "decode:step"}
REQUEST = ("request:queued", "request:prefill", "request:decode")

# name -> serve_paged settings: a queue of five requests into two slots; an
# overcommitted pool that preempts; speculative decoding
RUNS = {
    "queue": dict(num_slots=2, page_size=4, prefill_budget=16),
    "preempt": dict(num_slots=3, page_size=4, num_pages=7, overcommit=10.0,
                    prefill_budget=8),
    "spec": dict(num_slots=2, page_size=4, prefill_budget=16, spec_k=2),
}
LENGTHS = ((9, 10), (8, 8), (7, 12), (5, 6), (11, 3))


class Recorder:
    def __init__(self):
        self.events = []

    def event(self, name, begin, end, **tags):
        self.events.append((name, begin, end, tags))

    def named(self, name):
        return [e for e in self.events if e[0] == name]


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ServingEngine(model, params, max_batch=3, max_seq=32)


def _requests(vocab):
    rng = np.random.default_rng(5)
    return [ServeRequest(request_id=10 + i,
                         prompt=rng.integers(0, vocab, (p,)).astype(np.int32),
                         max_new_tokens=n)
            for i, (p, n) in enumerate(LENGTHS)]


@pytest.fixture(scope="module", params=sorted(RUNS))
def served(request, engine):
    kw = RUNS[request.param]
    vocab = engine.model.cfg.vocab_size
    plain = engine.serve_paged(_requests(vocab), **kw)      # compiles too
    rec = Recorder()
    t0 = time.perf_counter()
    stats = engine.serve_paged(_requests(vocab), tracer=rec, **kw)
    t1 = time.perf_counter()
    return request.param, plain, stats, rec, (t0, t1)


def _inside(inner, outer):
    return outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_boundary_spans_nest_inside_the_call(served):
    name, _, stats, rec, (t0, t1) = served
    seen = {e[0] for e in rec.events}
    assert {"sched:retire", "sched:admit", "prefill:packed", "prefill:wait",
            "prefill:first_tokens", "decode:step", "decode:fetch"} <= seen
    for e in rec.events:
        if e[0] in BOUNDARY:
            assert t0 <= e[1] <= e[2] <= t1, e
            assert "step" in e[3]
    for child, parent in INNER.items():
        outers = rec.named(parent)
        for e in rec.named(child):
            assert any(_inside(e, o) for o in outers), (child, e)
    # one retire and one admission span per loop iteration
    assert len(rec.named("sched:retire")) == stats.boundaries
    assert len(rec.named("sched:admit")) == stats.boundaries
    assert len(rec.named("decode:step")) == stats.steps
    assert len(rec.named("decode:fetch")) == stats.steps
    assert len(rec.named("prefill:packed")) == stats.prefill_launches


def test_step_times_are_the_sums_of_their_spans(served):
    _, _, stats, rec, _ = served
    decode = 0.0
    for _, b, e, _ in rec.named("decode:step"):
        decode += e - b
    prefill = 0.0
    for _, b, e, _ in rec.named("prefill:packed"):
        prefill += e - b
    assert decode == stats.decode_s
    assert prefill == stats.prefill_s


def test_span_tags(served):
    name, _, stats, rec, _ = served
    for _, _, _, t in rec.named("prefill:packed"):
        assert {"tokens", "padding", "chunks", "buffer", "budget",
                "decoding"} <= set(t)
        assert 0 <= t["decoding"] <= RUNS[name]["num_slots"]
    assert sum(t["n"] for *_, t in rec.named("prefill:first_tokens")) >= len(LENGTHS)
    for _, _, _, t in rec.named("decode:step"):
        assert 1 <= t["slots"] <= RUNS[name]["num_slots"]
        assert t["window"] in (1, RUNS[name].get("spec_k", 0) + 1)
    assert sum(t["admitted"] for *_, t in rec.named("sched:admit")) \
        == len(LENGTHS) + stats.preemptions
    assert sum(t["retired"] for *_, t in rec.named("sched:retire")) == len(LENGTHS)
    assert rec.named("sched:admit")[0][3]["queued"] == len(LENGTHS) - \
        rec.named("sched:admit")[0][3]["admitted"]
    if name == "preempt":
        assert stats.preemptions > 0


def test_request_spans_tile_each_request(served):
    _, _, stats, rec, (t0, _) = served
    by_id = {}
    for kind in REQUEST:
        for _, b, e, t in rec.named(kind):
            by_id.setdefault(t["request"], {}).setdefault(kind, []).append((b, e))
    assert sorted(by_id) == sorted(r.request_id for r in stats.results)
    for r in stats.results:
        spans = by_id[r.request_id]
        assert all(len(spans[k]) == 1 for k in REQUEST)
        (qb, qe), = spans["request:queued"]
        (pb, pe), = spans["request:prefill"]
        (db, de), = spans["request:decode"]
        assert t0 <= qb and qe == pb and pe == db
        assert qe - qb == r.queue_s
        assert pe - qb == r.ttft_s
        assert de - qb == r.latency_s


def test_token_times(served):
    _, _, stats, _, _ = served
    gaps = []
    for r in stats.results:
        t = r.token_times_s
        assert len(t) == len(r.tokens)
        assert all(b >= a for a, b in zip(t, t[1:]))
        assert t[0] == r.ttft_s
        assert 0.0 <= r.queue_s <= r.ttft_s <= t[-1] <= r.latency_s
        g = [b - a for a, b in zip(t, t[1:])]
        assert r.itl_p50_s == percentile(g, 50.0)
        assert r.itl_p99_s == percentile(g, 99.0)
        gaps += g
    assert stats.itl_p50_ms == percentile(gaps, 50.0) * 1e3
    assert stats.itl_p99_ms == percentile(gaps, 99.0) * 1e3
    # five requests into two slots: the last ones wait for a slot
    assert max(r.queue_s for r in stats.results) > 0.0


def test_host_time_and_boundaries(served):
    _, _, stats, rec, _ = served
    assert 0.0 < stats.host_s <= stats.wall_s
    assert stats.boundaries >= stats.steps > 0
    waits = sum(e - b for n, b, e, _ in rec.events
                if n in ("prefill:wait", "decode:fetch"))
    assert stats.host_s + waits <= stats.wall_s


def test_tracer_does_not_change_the_tokens(served):
    _, plain, stats, _, _ = served
    assert plain.boundaries == stats.boundaries and plain.steps == stats.steps
    for a, b in zip(plain.results, stats.results):
        assert a.request_id == b.request_id
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert len(a.token_times_s) == len(a.tokens)


def test_spans_reach_the_profiler(engine, tmp_path):
    """The loop's span names appear on the profiler's host line, inside the
    span the caller opened around the call."""
    from jax.profiler import ProfileData

    reqs = _requests(engine.model.cfg.vocab_size)[:2]
    kw = RUNS["queue"]
    engine.serve_paged(reqs, **kw)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test:window"):
            engine.serve_paged(_requests(engine.model.cfg.vocab_size)[:2], **kw)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    data = ProfileData.from_file(found[0])
    for plane in data.planes:
        for line in plane.lines:
            names = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events]
            window = [n for n in names if n[0] == "test:window"]
            if window:
                _, w0, w1 = window[0]
                inside = {n for n, a, b in names if w0 <= a <= b <= w1}
                assert {"sched:retire", "sched:admit", "prefill:packed",
                        "prefill:wait", "decode:step", "decode:fetch"} <= inside
                return
    pytest.fail("no host line holds the caller's span")

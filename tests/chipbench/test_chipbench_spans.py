"""The scheduler readers (queue wait, host time per iteration, prefill
stalls) on rounds served on the CPU at the program's reduced glm4-9b
sizes: each gives a value in range, None where its denominator is empty,
and None on a program that records none of what it reads."""
import math
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import files, stats  # noqa: E402
from chipbench import run as bench  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**33 + 7
READERS = ("sched.queue_wait_p95_ms", "sched.host_ms",
           "sched.prefill_stall_share")


def _read(name, run):
    return files.load_metric(name).read(run)


@pytest.fixture(scope="module")
def served():
    cell = files.load_cell("tiny.decode", root=DATA,
                           benchmark=DATA / "bench.json")
    s = bench.build(cell, SEED, allow_cpu=True)
    rounds = [bench.serve_round(s, cell, i, SEED) for i in range(2)]
    window = sum(rd.t1 - rd.t0 for rd in rounds)
    run = bench.Run(cell, s.dims, int(cell.serve["slots"]), rounds, window, {})

    def one_round(burst):
        rec = bench.Recorder()
        st = s.engine.serve_paged(bench.to_requests(burst), tracer=rec, **s.kw)
        return bench.Round(0.0, st.wall_s, burst, st, rec.events)

    rng = np.random.default_rng(0)
    single = one_round([(rng.integers(0, s.dims.vocab, 12, dtype=np.int32), 1)
                        for _ in range(3)])
    empty = one_round([])

    def alone(rd):
        return bench.Run(cell, s.dims, run.slots, [rd], rd.t1 - rd.t0, {})

    return run, alone(single), alone(empty)


def test_each_reader_gives_a_value_in_range(served):
    run = served[0]
    q = _read("sched.queue_wait_p95_ms", run)
    h = _read("sched.host_ms", run)
    share = _read("sched.prefill_stall_share", run)
    assert all(math.isfinite(v) for v in (q, h, share))
    # eight requests into four slots: the second half waits for a slot
    assert q > 0.0
    assert 0.0 < h <= 1e3 * run.window_s / sum(rd.stats.steps for rd in run.rounds)
    assert 0.0 <= share <= 100.0


def test_queue_wait_is_within_the_ttft_tail(served):
    run = served[0]
    done = [r for rd in run.rounds for r in rd.stats.results]
    ttft_p95 = stats.end_to_end(done, run.window_s)["ttft_p95_ms"]
    assert _read("sched.queue_wait_p95_ms", run) <= ttft_p95


def test_stall_share_counts_the_requests_held_by_each_launch(served):
    run = served[0]
    stalled = decode = 0.0
    for rd in run.rounds:
        for name, b, e, tags in rd.events:
            if name == "prefill:packed":
                stalled += (e - b) * tags["decoding"]
            elif name == "request:decode":
                decode += e - b
    assert decode > 0.0
    assert _read("sched.prefill_stall_share", run) == pytest.approx(
        100.0 * stalled / decode, rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_no_decode_step_reads_none(served, name):
    _, single, empty = served
    assert sum(rd.stats.steps for rd in empty.rounds) == 0
    assert _read(name, empty) is None
    # one token a request: no decode step, no time between tokens to stall
    assert sum(rd.stats.steps for rd in single.rounds) == 0
    if name == "sched.prefill_stall_share":
        assert _read(name, single) is None
    else:
        assert math.isfinite(_read(name, single))


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_counters_reads_none(name):
    """Rounds of a program that records no queue time, no host time and
    untagged launches, as the benchmark reads an older program."""
    res = SimpleNamespace(status="completed", tokens=[1, 2, 3], ttft_s=0.1,
                          latency_s=0.5)
    st = SimpleNamespace(results=[res], steps=2, decode_s=0.2)
    rd = bench.Round(0.0, 0.5, [], st, [("prefill:packed", 0.0, 0.1,
                                         {"tokens": 8})])
    run = bench.Run(None, None, 4, [rd], 0.5, {})
    assert _read(name, run) is None

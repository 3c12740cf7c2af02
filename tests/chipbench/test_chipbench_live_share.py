"""The varlen kernel's live-share reader: the summed counters of every
round, and None where no launch was counted, which includes rounds served
on the CPU at the program's reduced glm4-9b sizes (their packed launches
run the pure-JAX path, not the kernel)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import files  # noqa: E402
from chipbench import run as bench  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**33 + 11
NAME = "varlen_prefill.live_share"


def _read(run):
    return files.load_metric(NAME).read(run)


def _run(*stats):
    rounds = [bench.Round(0.0, 0.5, [], st, []) for st in stats]
    return bench.Run(None, None, 4, rounds, 0.5 * len(rounds), {})


@pytest.fixture(scope="module")
def served():
    cell = files.load_cell("tiny.decode", root=DATA,
                           benchmark=DATA / "bench.json")
    s = bench.build(cell, SEED, allow_cpu=True)
    rounds = [bench.serve_round(s, cell, i, SEED) for i in range(2)]
    window = sum(rd.t1 - rd.t0 for rd in rounds)
    return bench.Run(cell, s.dims, int(cell.serve["slots"]), rounds, window, {})


def test_live_share_is_the_summed_launch_counts():
    # a 161-token refill (bound 1) and eight context chunks (bound 256) at
    # T 2,048, page 16, as the kernel counts them
    run = _run(SimpleNamespace(prefill_kv_live=183, prefill_kv_rect=16512),
               SimpleNamespace(prefill_kv_live=13568, prefill_kv_rect=49152))
    assert _read(run) == pytest.approx(100.0 * (183 + 13568) / (16512 + 49152),
                                       rel=1e-12)


def test_live_share_none_where_the_kernel_does_not_run(served):
    assert sum(rd.stats.prefill_launches for rd in served.rounds) > 0
    assert sum(rd.stats.prefill_kv_rect for rd in served.rounds) == 0
    assert _read(served) is None


@pytest.mark.parametrize("stats", [
    # an older program's rounds: no counters at all
    SimpleNamespace(steps=2, decode_s=0.2),
    # a round with no packed launch
    SimpleNamespace(steps=0, prefill_kv_live=0, prefill_kv_rect=0),
], ids=["no_counters", "no_launch"])
def test_reads_none_without_a_counted_launch(stats):
    assert _read(_run(stats)) is None

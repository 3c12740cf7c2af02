"""Readings for the limit on ``served_gap``: the program's own and the
control's, at a cell's own size and load, over many seeds in one process.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 [--rounds 1]

For each seed the cell's weights are made from the seed, the engine
serves the cell's first burst(s), and the comparison of ``check`` runs on
the same sample a benchmark run takes: the program's ``served_gap`` and
the float8 control's ``control_gap``.  One JSON line per seed on
standard output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chipbench"

from chipbench import check, files  # noqa: E402
from chipbench import run as bench  # noqa: E402
from chipbench import weights as W  # noqa: E402


def readings(cell: files.Cell, seeds, rounds: int = 1, allow_cpu: bool = False,
             fault=None):
    """Yield one dict of readings per seed."""
    s = None
    for seed in seeds:
        t0 = time.perf_counter()
        if s is None:
            s = bench.build(cell, seed, allow_cpu, fault)
            layer0 = s.jax.jit(W.layer, static_argnums=(2, 3))(
                W.root_key(seed), 0, s.dims, s.engine.params["embed"].dtype)
            same = s.jax.tree.map(
                lambda a, b: bool((a[0] == b).all()),
                s.engine.params["blocks"], layer0)
            if not all(s.jax.tree.leaves(same)):
                raise AssertionError("the stacked weights differ from the "
                                     "reference's layer-by-layer weights")
        else:
            s.engine.params = W.make_params(s.dims, seed,
                                            dtype=cell.config["dtype"],
                                            shardings=s.shardings)
        served = []
        for i in range(rounds):
            rd = bench.serve_round(s, cell, i, seed)
            served += [check.Served(p, r.tokens)
                       for (p, n), r in zip(rd.requests, rd.stats.results)
                       if r.status == "completed" and len(r.tokens) == n]
        s.engine.params = None
        gc.collect()
        t1 = time.perf_counter()
        items = check.sample(served, seed, int(cell.serve["check"]["requests"]))
        out = check.compare(s.dims, seed, items, control=True)
        out.update(seed=seed, serve_s=t1 - t0, compare_s=time.perf_counter() - t1)
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--rounds", type=int, default=1)
    args = ap.parse_args(argv)
    cell = files.load_cell(args.workload)
    seeds = [int(x) for x in args.seeds.split(",")]
    try:
        for out in readings(cell, seeds, args.rounds):
            print(json.dumps(out), flush=True)
    except bench.NoChip as e:
        print(f"[chipbench] FAIL: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

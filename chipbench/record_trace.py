"""Record a small profiler trace of the paged serving path on the chip,
for the trace reduction's tests, and print what the trace holds.

    python3 -m chipbench.record_trace --out <dir>

A cell's configuration cut to two layers serves eight short requests
inside the host span the reduction takes as its window; the
``.xplane.pb`` is copied to ``<dir>/decode_2l.xplane.pb``.
"""
from __future__ import annotations

import argparse
import copy
import json
import shutil
import sys
import tempfile
from collections import Counter
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chipbench"

from chipbench import files  # noqa: E402
from chipbench import run as bench  # noqa: E402
from chipbench import trace as tracemod  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", default="glm4-9b-16l.decode")
    args = ap.parse_args(argv)
    cell = files.load_cell(args.workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config[cell.config["keys"]["layers"]] = 2
    cell.serve = dict(cell.serve, slots=8)
    s = bench.build(cell, 1)
    import numpy as np

    rng = np.random.default_rng(1)
    reqs = bench.to_requests([(rng.integers(0, s.dims.vocab, size=int(p), dtype=np.int32), 8)
                              for p in (64, 96, 128, 160, 192, 224, 256, 288)])
    s.engine.serve_paged(reqs, **s.kw)          # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="chipbench-rec-")
    opts = s.jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    s.jax.profiler.start_trace(tmp, profiler_options=opts)
    with s.jax.profiler.TraceAnnotation(tracemod.WINDOW):
        s.engine.serve_paged(reqs, **s.kw)
    s.jax.profiler.stop_trace()
    src = tracemod.find_xplane(tmp)
    Path(args.out).mkdir(parents=True, exist_ok=True)
    dst = Path(args.out) / "decode_2l.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(dst))
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("PLANE", plane.name, lines)
        if plane.name.startswith(tracemod.DEVICE_PREFIX):
            for ln in plane.lines:
                names = Counter()
                sample = {}
                for e in ln.events:
                    names[e.name] += 1
                    sample.setdefault(e.name, {k: v for k, v in e.stats})
                print("  LINE", ln.name)
                for n, c in names.most_common(25):
                    print("    ", c, n, json.dumps(sample[n], default=str)[:600])
    r = tracemod.reduce(tracemod.load(str(dst)))
    print("REDUCTION", json.dumps({"window_s": r.window_s, "busy_s": r.busy_s,
                                   "top": tracemod.top_ops(r), "gaps": r.gaps}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Cells over several chips, on the CPU.

A child process started with four virtual CPU devices (the device count
is fixed before JAX starts, so it cannot be this process) runs the
tensor-parallel test cell ``tiny.tp2`` through ``chipbench.run.main``
and makes the weights in their tp=2 and tp=4 shardings; the tests here
read what it reports.  The roofline readers' per-shard shapes and the
trace's choice of planes need no devices."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import files, peaks, trace, work  # noqa: E402
from chipbench import run as bench  # noqa: E402
from chipbench import weights as W  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**33 + 13
RECORDED = ROOT / "chipbench" / "testdata" / "decode_2l.xplane.pb"

CHILD = r"""
import contextlib, io, json, sys
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import jax
import numpy as np
from chipbench import files, run as bench, weights as W
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model

DATA = Path(sys.argv[2])
SEED = int(sys.argv[3])
report = {"devices": jax.device_count()}
made = []
make_params = W.make_params

def keep(*a, **k):
    made.append(make_params(*a, **k))
    return made[-1]

def look(engine):
    leaves = jax.tree.leaves(engine.params)
    report["param_devices"] = sorted(
        {d.id for a in leaves for d in a.sharding.device_set})
    report["embed_rows"] = sorted(
        s.data.shape[0] for s in engine.params["embed"].addressable_shards)
    report["moved"] = sum(a is not b for a, b in
                          zip(leaves, jax.tree.leaves(made[-1])))

# each row-parallel matmul (attention out, MLP down) summed over chip 0's
# heads and ffn columns only: what chip 0 holds where the all-reduce after
# it is left out
def drop_exchange(engine):
    def first_half(a):
        n = a.shape[1] // 2
        return a.at[:, n:].set(0)

    p = engine.params
    b = p["blocks"]
    engine.params = dict(p, blocks=dict(
        b, attn=dict(b["attn"], wo=first_half(b["attn"]["wo"])),
        mlp=dict(b["mlp"], w_down=first_half(b["mlp"]["w_down"]))))

def serve(fault):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench.main(
            ["--workload", "tiny.tp2", "--seed", str(SEED), "--seconds",
             "0.3", "--trace", "0"], allow_cpu=True, root=DATA,
            benchmark=DATA / "bench.json", fault=fault)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1])

W.make_params = keep
report["rc"], report["result"] = serve(look)
W.make_params = make_params
report["dropped"] = serve(drop_exchange)[1]

cell = files.load_cell("tiny.tp2", root=DATA, benchmark=DATA / "bench.json")
d = W.dims(cell.config)
model = build_model(bench.program_config(cell, d))
for dtype in ("float32", "bfloat16"):
    plain = jax.tree.leaves(W.make_params(d, SEED, dtype=dtype))
    for tp in (2, 4):
        _, shardings = bench.tp_layout(model, make_serve_mesh(tp))
        got = W.make_params(d, SEED, dtype=dtype, shardings=shardings)
        report[f"{dtype}.tp{tp}"] = {
            "equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                         for a, b in zip(jax.tree.leaves(got), plain)),
            "placed": all(a.sharding == s for a, s in zip(
                jax.tree.leaves(got), jax.tree.leaves(shardings))),
            "embed_rows": sorted(s.data.shape[0]
                                 for s in got["embed"].addressable_shards),
            "head_cols": sorted(s.data.shape[1]
                                for s in got["lm_head"].addressable_shards),
        }
print(json.dumps(report))
"""


@pytest.fixture(scope="module")
def child():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), str(DATA), str(SEED)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_tp_cell_is_correct_over_its_two_devices(child):
    assert child["devices"] == 4 and child["rc"] == 0
    res = child["result"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["count"] == 2
    # one peak a chip of the cell, the largest reported
    each = res["device"]["memory_peak_bytes_each"]
    assert len(each) == 2 and res["device"]["memory_peak_bytes"] == max(each)


def test_tp_cell_weights_span_both_devices_and_stay_put(child):
    assert child["param_devices"] == [0, 1]
    # the vocabulary's 256 rows, half on each device
    assert child["embed_rows"] == [128, 128]
    # made in the engine's shardings: its own placement moved no leaf
    assert child["moved"] == 0


def test_tp_cell_without_the_exchange_between_chips_is_not_correct(child):
    res = child["dropped"]
    gap = res["check"]["served_gap"]
    assert res["correct"] is False and gap["value"] > gap["limit"]


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_sharded_weights_equal_the_unsharded_draw(child, dtype, tp):
    r = child[f"{dtype}.tp{tp}"]
    assert r["equal"] and r["placed"]
    assert r["embed_rows"] == [256 // tp] * tp
    assert r["head_cols"] == [256 // tp] * tp


# -- roofline readers per shard -----------------------------------------------
GLM = W.dims(files.read_json(files.HERE / "configs" / "glm4-9b-16l.json"))
PEAK = peaks.peaks("TPU v5 lite")
REQUESTS = [(161, 40), (300, 12), (1024, 90)]


def _kernel(shape, a, b):
    text = (f"%closed_call.1 = bf16[{shape}]{{3,2,1,0}} custom-call() "
            f"custom_call_target=tpu_custom_call")
    return trace.Event("closed_call.1", a, b, text)


def _run(tp, planes):
    """A traced round of ``REQUESTS`` at glm4-9b-16l's widths, 16 slots and
    a 2,048-token budget, whose trace holds ``planes``."""
    tr = trace.Trace(devices=planes, host=[], window=(0.0, 10.0))
    results = [SimpleNamespace(status="completed", tokens=np.zeros(n))
               for _, n in REQUESTS]
    rd = bench.Round(0.0, 10.0, [(np.zeros(p), n) for p, n in REQUESTS],
                     SimpleNamespace(results=results), [])
    cell = SimpleNamespace(serve={"serve": {"prefill_budget": 2048}})
    return bench.Run(cell, GLM, 16, [rd], 10.0, PEAK, trace.reduce(tr), rd,
                     tp=tp)


def _share(kernel, chip_share):
    """Share (%) of its roofline each chip reached, doing ``chip_share``
    of the work in 1 s."""
    w = work.Work()
    for p, n in REQUESTS:
        w += kernel(GLM, p, n)
    w = work.Work(w.flops * chip_share, w.bytes * chip_share)
    least, _ = work.roofline_s(w, PEAK["bf16_flops_per_s"],
                               PEAK["hbm_bytes_per_s"])
    return 100.0 * least / 1.0


READERS = {
    "paged_attention_roofline": (work.paged_attention, "16,{kv},16,128"),
    "varlen_prefill_roofline": (lambda d, p, n: work.varlen_prefill(d, p),
                                "{kv},32768,128"),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_roofline_reader_at_tp1_reads_the_whole_kernel(name):
    kernel, shape = READERS[name]
    read = files.load_metric(name).read
    run = _run(1, {"/device:TPU:0": [_kernel(shape.format(kv=2), 0.0, 1.0)]})
    assert read(run) == pytest.approx(_share(kernel, 1.0), rel=1e-12)
    # a per-shard kernel is not this cell's
    assert read(_run(1, {"/device:TPU:0": [
        _kernel(shape.format(kv=1), 0.0, 1.0)]})) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_roofline_reader_at_tp2_reads_each_chips_share(name):
    kernel, shape = READERS[name]
    read = files.load_metric(name).read
    # each chip runs its half of the kernel (1 of the 2 kv heads) in 1 s;
    # the trace sums the two chips' time, and the reader's whole work over
    # 2 s is each chip's half over its own 1 s
    planes = {f"/device:TPU:{i}": [_kernel(shape.format(kv=1), 0.0, 1.0)]
              for i in range(2)}
    assert read(_run(2, planes)) == pytest.approx(_share(kernel, 0.5), rel=1e-12)
    # the whole-model shape does not exist on a chip at tp=2
    whole = {f"/device:TPU:{i}": [_kernel(shape.format(kv=2), 0.0, 1.0)]
             for i in range(2)}
    assert read(_run(2, whole)) is None


def test_recorded_trace_reads_alike_with_the_cells_chips():
    # one chip: naming the cell's chip keeps the trace as it was
    a = trace.reduce(trace.load(str(RECORDED)))
    b = trace.reduce(trace.load(str(RECORDED), devices=[0]))
    assert (a.busy_s, a.window_s, a.op_s, a.gaps) == (b.busy_s, b.window_s,
                                                      b.op_s, b.gaps)
    with pytest.raises(ValueError, match="no 'XLA Ops' line"):
        trace.load(str(RECORDED), devices=[1])

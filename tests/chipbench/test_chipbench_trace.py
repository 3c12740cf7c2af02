"""The trace reduction: busy time, per-operation time and labelled idle
gaps, on hand-made traces and on a small trace recorded on a TPU v5e
(``chipbench/testdata/decode_2l.xplane.pb``: glm4-9b widths cut to two
layers serving eight short requests)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402
from chipbench import work  # noqa: E402

RECORDED = ROOT / "chipbench" / "testdata" / "decode_2l.xplane.pb"


def _ev(name, a, b, text=""):
    return trace.Event(name, a, b, text or name)


def _trace(ops, host, window=(0.0, 10.0)):
    return trace.Trace(devices={"/device:TPU:0": ops}, host=host, window=window)


def test_busy_is_the_union_of_overlapping_ops():
    tr = _trace([_ev("a", 1, 3), _ev("b", 2, 4), _ev("c", 6, 7)], [])
    r = trace.reduce(tr)
    assert r.busy_s == pytest.approx(4.0)
    assert r.window_s == 10.0
    assert r.idle_share == pytest.approx(0.6)


def test_ops_are_clipped_to_the_window():
    tr = _trace([_ev("a", -2, 1), _ev("b", 9, 12)], [], window=(0.0, 10.0))
    r = trace.reduce(tr)
    assert r.busy_s == pytest.approx(2.0)
    assert r.op_s == {"a": pytest.approx(1.0), "b": pytest.approx(1.0)}


def test_busy_is_averaged_over_devices():
    tr = trace.Trace(devices={"/device:TPU:0": [_ev("a", 0, 4)],
                              "/device:TPU:1": [_ev("a", 0, 2)]},
                     host=[], window=(0.0, 4.0))
    assert trace.reduce(tr).busy_s == pytest.approx(3.0)


def test_gaps_are_labelled_by_the_innermost_host_event():
    host = [_ev(trace.WINDOW, 0, 10), _ev("PjitFunction(step)", 4.5, 5.5),
            _ev("sched", 7.2, 7.4)]
    tr = _trace([_ev("a", 0, 4), _ev("b", 6, 7), _ev("c", 8, 10)], host)
    r = trace.reduce(tr)
    assert [(n, pytest.approx(s)) for n, s in r.gaps] == [
        ("PjitFunction(step)", 2.0), ("sched", 1.0)]


def test_kernel_time_matches_name_or_stats():
    tr = _trace([_ev("fusion.1", 0, 1, "fusion.1 tf_op=jit(step)/dot"),
                 _ev("custom-call.3", 1, 3, "custom-call.3 kernel=paged_attn"),
                 _ev("custom-call.4", 3, 4, "custom-call.4 kernel=rmsnorm")], [])
    r = trace.reduce(tr)
    assert r.seconds_matching(lambda t: "kernel=paged_attn" in t) == pytest.approx(2.0)
    assert r.seconds_matching(lambda t: "nothing" in t) == 0.0
    assert trace.top_ops(r, 2) == [("custom-call.3", pytest.approx(2.0)),
                                   ("fusion.1", pytest.approx(1.0))]


def test_a_reader_finds_nothing_without_kernel_events():
    from types import SimpleNamespace

    r = trace.reduce(_trace([_ev("fusion.1", 0, 1)], []))
    run = SimpleNamespace(trace=r, traced_round=object(), peak={"x": 1})
    assert work.kernel_roofline(run, work.paged_attention,
                                lambda t: "custom-call" in t) is None


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.is_file():
        pytest.fail(f"missing {RECORDED}")
    return trace.reduce(trace.load(str(RECORDED)))


def test_recorded_trace_has_busy_and_idle_time(recorded):
    assert 0 < recorded.busy_s < recorded.window_s
    assert 0 < recorded.idle_share < 1
    # leaf operations run one at a time: their union is nearly their sum
    assert recorded.busy_s == pytest.approx(sum(recorded.op_s.values()), rel=0.05)


def test_recorded_trace_gaps_are_attributed(recorded):
    assert recorded.gaps
    assert all(n != "(no host event)" for n, _ in recorded.gaps)
    assert all(s > 0 for _, s in recorded.gaps)
    lengths = [s for _, s in recorded.gaps]
    assert lengths == sorted(lengths, reverse=True)


def test_an_op_holding_others_is_not_counted_twice():
    tr = _trace([_ev("while", 0, 6), _ev("a", 1, 2), _ev("b", 3, 5)], [])
    r = trace.reduce(tr)
    assert r.op_s == {"a": pytest.approx(1.0), "b": pytest.approx(2.0)}
    assert r.busy_s == pytest.approx(3.0)


def test_hlo_names_are_grouped_by_opcode_and_shape():
    name = ("%closed_call.38 = bf16[64,2,16,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
            "custom-call(s32[64,128]{1,0:T(8,128)S(1)} %slice.79)")
    assert trace.label(name) == "custom-call bf16[64,2,16,128]"
    assert trace.label("fusion.3") == "fusion.3"


def test_recorded_trace_finds_both_attention_kernels(recorded):
    # two layers, eight slots: the paged kernel's grouped query block and
    # the varlen kernel's packed block of a 2048-token budget
    paged = recorded.seconds_matching(
        lambda t: "tpu_custom_call" in t and "= bf16[8,2,16,128]" in t)
    varlen = recorded.seconds_matching(
        lambda t: "tpu_custom_call" in t and "= bf16[2,32768,128]" in t)
    assert paged > 0 and varlen > 0
    assert paged + varlen < recorded.busy_s

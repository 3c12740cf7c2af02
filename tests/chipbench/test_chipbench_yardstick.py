"""The benchmark's yardstick on the CPU: traffic, weights, work counts,
peaks, metric arithmetic, and the benchmark file against its contract."""
import json
import math
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import files, peaks, stats, traffic, work  # noqa: E402
from chipbench import weights as W  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
GLM = W.dims(files.read_json(files.HERE / "configs" / "glm4-9b-16l.json"))
SHAREGPT = files.read_json(files.HERE / "traffic" / "sharegpt.json")
MIXES = sorted(p.stem for p in (files.HERE / "traffic").glob("*.json"))


# -- traffic -----------------------------------------------------------------
@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_gives_identical_traffic(mix):
    m = files.read_json(files.HERE / "traffic" / f"{mix}.json")
    a = traffic.burst(m, 32, 1, 2**33 + 7, 1000)
    b = traffic.burst(m, 32, 1, 2**33 + 7, 1000)
    assert [(p.tolist(), n) for p, n in a] == [(p.tolist(), n) for p, n in b]


@pytest.mark.parametrize("mix", MIXES)
def test_seed_changes_tokens_not_sizes(mix):
    m = files.read_json(files.HERE / "traffic" / f"{mix}.json")
    a = traffic.burst(m, 32, 0, 1, 1000)
    b = traffic.burst(m, 32, 0, 2, 1000)
    assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in b]
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))


def test_rounds_hold_the_same_sizes_in_another_order():
    a = traffic.burst(SHAREGPT, 128, 0, 5, 1000)
    b = traffic.burst(SHAREGPT, 128, 1, 5, 1000)
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in b)
    assert sorted(n for _, n in a) == sorted(n for _, n in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]


def test_lengths_follow_the_mix():
    prompts, outputs = traffic.burst_lengths(SHAREGPT, 128)
    assert prompts.min() >= 4 and prompts.max() <= 1024
    assert outputs.min() >= 4 and outputs.max() <= 1024
    assert abs(np.median(prompts) - 101) <= 4
    assert abs(np.median(outputs) - 236) <= 8
    assert traffic.max_len(SHAREGPT) == 2048
    lu = {"dist": "loguniform", "min": 1024, "max": 4096}
    assert traffic.quantile(lu, 0.5) == 2048


@pytest.mark.parametrize("mix", MIXES)
def test_every_mix_names_its_source(mix):
    m = files.read_json(files.HERE / "traffic" / f"{mix}.json")
    assert m["name"] == mix and m["source"] and m["assumed"]


def test_sharegpt_keeps_the_published_means():
    # vLLM paper, Fig. 11: ShareGPT prompts average 161.31 tokens, outputs
    # 337.99; the medians are solved for those means after clipping
    prompts, outputs = traffic.burst_lengths(SHAREGPT, 4096)
    assert prompts.mean() == pytest.approx(161.31, rel=0.01)
    assert outputs.mean() == pytest.approx(337.99, rel=0.01)


def test_token_ids_stay_in_the_vocabulary():
    for p, _ in traffic.burst(SHAREGPT, 16, 3, 9, 151552):
        assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 151552


# -- weights -----------------------------------------------------------------
TINY = W.Dims(3, 64, 128, 4, 2, 16, 256, 1e-6, 1e4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_stacked_weights_equal_the_references_layers(dtype):
    import jax

    p = W.make_params(TINY, 2**40 + 3, dtype=dtype)
    key = W.root_key(2**40 + 3)
    make = jax.jit(W.layer, static_argnums=(2, 3))
    for l in range(TINY.layers):
        same = jax.tree.map(lambda a, b: bool((a[l] == b).all()),
                            p["blocks"], make(key, l, TINY, dtype))
        assert all(jax.tree.leaves(same))
    table = jax.jit(W.table, static_argnums=(1, 2, 3, 4))
    assert bool((table(key, "embed", 256, 64, dtype) == p["embed"]).all())
    assert bool((table(key, "lm_head", 256, 64, dtype).T == p["lm_head"]).all())


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_table_blocks_are_the_same_draw_over_any_shards(shards):
    import jax
    import jax.numpy as jnp

    key = W.root_key(2**40 + 5)
    table = jax.jit(W.table, static_argnums=(1, 2, 3, 4, 5))
    got = table(key, "embed", 256, 64, jnp.bfloat16, shards)
    # block b, drawn alone, is rows [16 b, 16 b + 16) whatever the shards
    want = jnp.concatenate([W._leaf(key, "embed", b, (16, 64), W.STD_IN,
                                    jnp.bfloat16)
                            for b in range(W.TABLE_BLOCKS)])
    assert bool((got == want).all())


def test_weights_depend_on_the_seed():
    a = W.make_params(TINY, 1)
    b = W.make_params(TINY, 2)
    assert not bool((a["embed"] == b["embed"]).all())


def test_config_files_state_the_published_widths():
    assert (GLM.d_model, GLM.d_ff, GLM.heads, GLM.kv_heads, GLM.head_dim,
            GLM.vocab) == (4096, 13696, 32, 2, 128, 151552)
    ds = W.dims(files.read_json(files.HERE / "configs" / "deepseek-67b-6l.json"))
    assert (ds.d_model, ds.d_ff, ds.heads, ds.kv_heads, ds.head_dim,
            ds.vocab, ds.layers) == (8192, 22016, 64, 8, 128, 102400, 6)


# -- work --------------------------------------------------------------------
def test_glm4_layer_parameters_by_hand():
    # q and o: 4096 x 32 x 128 each; k and v: 4096 x 2 x 128 each;
    # gate, up, down: 4096 x 13696 each
    attn = 2 * 4096 * 32 * 128 + 2 * 4096 * 2 * 128
    mlp = 3 * 4096 * 13696
    assert work.layer_matmul_params(GLM) == attn + mlp == 203_948_032


def test_glm4_prefill_flops_by_hand():
    # one 384-token prompt: 2 FLOPs per parameter and token in 16 layers,
    # 4 * 32 * 128 FLOPs per (query, key) pair, 384 * 385 / 2 causal pairs,
    # and the head (2 * 4096 * 151552) for the first served token
    per_layer = 2 * 203_948_032 * 384 + 4 * 32 * 128 * 73_920
    assert work.prefill_flops(GLM, 384) == 16 * per_layer + 1_241_513_984
    assert work.prefill_flops(GLM, 384) == 2_526_732_615_680


def test_glm4_decode_work_by_hand():
    # 128 served tokens after a 384-token prompt: 127 decode steps at
    # positions 384..510, attending 385..511 keys (56,896 in all)
    assert work.decode_flops(GLM, 384, 128) == (
        16 * (2 * 203_948_032 * 127 + 4 * 32 * 128 * 56_896)
        + 2 * 4096 * 151552 * 127)
    w = work.paged_attention(GLM, 384, 128)
    assert w.flops == 16 * 4 * 32 * 128 * 56_896 == 14_914_945_024
    # K and V: 2 kv heads x 128 x 2 bytes per key; q and o: 32 x 128 x 2 bytes
    assert w.bytes == 16 * (2 * 2 * 128 * 2 * 56_896 + 2 * 32 * 128 * 2 * 127)
    assert w.bytes == 965_476_352


def test_glm4_varlen_prefill_work_by_hand():
    w = work.varlen_prefill(GLM, 2048)
    assert w.flops == 16 * 4 * 32 * 128 * (2048 * 2049 // 2)
    assert w.bytes == 16 * (32 + 2 + 2 + 32) * 128 * 2 * 2048


def test_one_served_token_needs_no_decode():
    assert work.decode_flops(GLM, 100, 1) == 0
    assert work.paged_attention(GLM, 100, 1).bytes == 0


def test_roofline_takes_the_larger_bound():
    t, bound = work.roofline_s(work.Work(197e12, 819e9), 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.roofline_s(work.Work(1e12, 819e9 * 2), 197e12, 819e9)
    assert (t, bound) == (2.0, "memory")


# -- peaks -------------------------------------------------------------------
def test_peak_table_knows_the_v5e():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5 Lite"])
def test_peak_table_refuses_an_unknown_device(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks(kind)


# -- metric arithmetic -------------------------------------------------------
def _res(ttft, latency, n, status="completed"):
    return SimpleNamespace(ttft_s=ttft, latency_s=latency,
                           tokens=np.zeros(n, np.int32), status=status)


def test_tails_pool_every_request_of_every_round():
    fast = [_res(0.1, 1.1, 11)] * 19
    slow = [_res(5.0, 6.0, 11)]
    m = stats.end_to_end(fast + slow, 10.0)
    # 1 of 20 requests at 5 s: the pooled 95th percentile sits above every
    # fast request, where a mean of per-round percentiles would not
    assert 100.0 < m["ttft_p95_ms"] <= 5000.0
    assert m["ttft_p95_ms"] == pytest.approx(np.percentile([100.0] * 19 + [5000.0], 95))
    assert m["tpot_p95_ms"] == pytest.approx(100.0)


def test_a_stalled_round_moves_rate_and_tail():
    rounds = [_res(0.1, 1.1, 11)] * 40
    steady = stats.end_to_end(rounds, 2.0)
    stalled = stats.end_to_end(rounds + [_res(3.0, 4.0, 11)] * 40, 8.0)
    assert stalled["output_tok_s"] < steady["output_tok_s"]
    assert stalled["ttft_p95_ms"] > steady["ttft_p95_ms"]


def test_rates_count_completed_requests_only():
    m = stats.end_to_end([_res(0.1, 1.0, 10), _res(0.0, 0.5, 0, "rejected")], 2.0)
    assert m["output_tok_s"] == 5.0


# -- the benchmark file ------------------------------------------------------
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(e["name"] for e in BENCH["end_to_end"] + BENCH["per_layer"])) == \
        len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_every_named_file_exists():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert (files.HERE / "cells" / f"{w['name']}.json").is_file()
        assert (files.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] == 1
    for m in BENCH["per_layer"]:
        mod = files.load_metric(m["name"])
        assert callable(mod.read)


def test_bounds_and_check_budget():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cells_load_by_name(cell):
    c = files.load_cell(cell)
    assert c.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s", "output_tok_s"}
    assert c.per_layer
    assert c.serve["check"]["limits"]["served_gap"] > 0
    assert math.isfinite(c.serve["check"]["limits"]["served_gap"])

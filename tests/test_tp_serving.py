"""Tensor-parallel paged serving over a host-device mesh.

These tests need forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_tp_serving.py

Without the flag (plain tier-1 runs) every mesh-hungry test skips; the
tp=1 / fallback tests always run.  Coverage:

* the three serving kernels (paged_attention / varlen_prefill /
  spec_verify) under shard_map head splits at tp in {1, 2, 4}, against
  their ``ref.py`` oracles AND bit-exactly against the unsharded dispatch
  (heads never mix inside attention, so head-split blocks are exact) —
  ragged lengths, page-straddling contexts, bf16 pools;
* end-to-end ``serve_paged`` greedy-token bit-identity, tp=2 vs tp=1,
  across packed/chunked x spec_k 0/2 x prefix-cache on/off x preemption;
* ``make_serve_mesh`` and the non-divisible-heads replication fallback.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model
from repro.serve.engine import ServeRequest, ServingEngine
from repro.sharding.specs import (
    heads_shard_axis,
    serve_rules,
    set_activation_rules,
    tp_degree,
)


def requires_devices(n):
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices (XLA_FLAGS="
               f"--xla_force_host_platform_device_count={n})",
    )


def _tol(dtype):
    return (
        dict(rtol=2e-2, atol=2e-2)
        if dtype == jnp.bfloat16
        else dict(rtol=1e-5, atol=5e-5)
    )


def _rules_for(tp):
    return serve_rules(make_serve_mesh(tp=tp))


# ---------------------------------------------------------------------------
# kernel workloads: ragged lengths, page-straddling contexts
# ---------------------------------------------------------------------------
H, KVH, DH = 8, 4, 16
PAGE = 8


def _pools(rng, num_pages, dtype):
    k = jnp.asarray(rng.standard_normal((num_pages, PAGE, KVH, DH)), dtype)
    v = jnp.asarray(rng.standard_normal((num_pages, PAGE, KVH, DH)), dtype)
    return k, v


def _paged_decode_case(dtype):
    rng = np.random.default_rng(0)
    k_pages, v_pages = _pools(rng, 24, dtype)
    b, max_pages = 4, 4
    q = jnp.asarray(rng.standard_normal((b, 1, H, DH)), dtype)
    table = jnp.asarray(
        rng.permutation(np.arange(1, 24))[: b * max_pages].reshape(b, max_pages),
        jnp.int32,
    )
    # ragged: mid-page, page-straddling, single token, near-full
    lengths = jnp.asarray([5, 13, 1, 27], jnp.int32)
    return q, k_pages, v_pages, table, lengths


def _varlen_case(dtype):
    rng = np.random.default_rng(1)
    k_pages, v_pages = _pools(rng, 24, dtype)
    C, max_pages = 4, 4
    # page-aligned spans (the packed layout contract): 16 + 8 + 24 + 16 = 64
    spans = [16, 8, 24, 16]
    T = sum(spans)
    cu = np.zeros((C + 1,), np.int32)
    cu[1:] = np.cumsum(spans)
    chunk_lens = np.asarray([13, 8, 21, 10], np.int32)      # ragged real tokens
    chunk_pos0 = np.asarray([0, 16, 8, 0], np.int32)        # page-aligned starts
    tables = rng.permutation(np.arange(1, 24))[: C * max_pages].reshape(
        C, max_pages
    ).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((T, H, DH)), dtype)
    k = jnp.asarray(rng.standard_normal((T, KVH, DH)), dtype)
    v = jnp.asarray(rng.standard_normal((T, KVH, DH)), dtype)
    return (
        q, k, v, k_pages, v_pages,
        jnp.asarray(cu), jnp.asarray(chunk_lens), jnp.asarray(chunk_pos0),
        jnp.asarray(tables),
    )


def _spec_case(dtype):
    rng = np.random.default_rng(2)
    k_pages, v_pages = _pools(rng, 24, dtype)
    b, W, max_pages = 4, 3, 4
    q = jnp.asarray(rng.standard_normal((b, W, H, DH)), dtype)
    table = jnp.asarray(
        rng.permutation(np.arange(1, 24))[: b * max_pages].reshape(b, max_pages),
        jnp.int32,
    )
    # window starts are NOT page-aligned; row 2 is idle (window_len 0)
    lengths = jnp.asarray([5, 14, 3, 26], jnp.int32)
    window_lens = jnp.asarray([3, 1, 0, 2], jnp.int32)
    return q, k_pages, v_pages, table, lengths, window_lens


KERNEL_TPS = [1, 2, 4]


@pytest.mark.parametrize("tp", KERNEL_TPS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_tp_matches_oracle(tp, dtype):
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices")
    q, kp, vp, table, lengths = _paged_decode_case(dtype)
    want = ref.paged_attention(q, kp, vp, table, lengths)
    base = ops.paged_attention(q, kp, vp, table, lengths)
    with set_activation_rules(_rules_for(tp)):
        got = ops.paged_attention(q, kp, vp, table, lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )
    # head-split blocks never mix heads: sharding must be EXACT vs unsharded
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("tp", KERNEL_TPS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_varlen_prefill_tp_matches_oracle(tp, dtype):
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices")
    args = _varlen_case(dtype)
    want = ref.varlen_prefill(*args)
    base = ops.varlen_prefill(*args)
    with set_activation_rules(_rules_for(tp)):
        got = ops.varlen_prefill(*args)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@pytest.mark.parametrize("tp", KERNEL_TPS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spec_verify_tp_matches_oracle(tp, dtype):
    if jax.device_count() < tp:
        pytest.skip(f"needs {tp} devices")
    q, kp, vp, table, lengths, wlens = _spec_case(dtype)
    want = ref.spec_verify(q, kp, vp, table, lengths, wlens)
    base = ops.spec_verify(q, kp, vp, table, lengths, wlens)
    with set_activation_rules(_rules_for(tp)):
        got = ops.spec_verify(q, kp, vp, table, lengths, wlens)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


@requires_devices(2)
def test_paged_attention_tp_pages_bound():
    """The static pages_bound slice composes with the shard_map wrap."""
    q, kp, vp, table, lengths = _paged_decode_case(jnp.float32)
    lengths = jnp.minimum(lengths, 2 * PAGE)      # live pages fit the bound
    want = ops.paged_attention(q, kp, vp, table, lengths, pages_bound=2)
    with set_activation_rules(_rules_for(2)):
        got = ops.paged_attention(q, kp, vp, table, lengths, pages_bound=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# mesh + rules plumbing
# ---------------------------------------------------------------------------
def test_make_host_mesh_defaults_single_device():
    mesh = make_serve_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == 1 and mesh.shape["data"] == 1


@requires_devices(2)
def test_make_host_mesh_tp_axis():
    mesh = make_serve_mesh(tp=2)
    assert mesh.shape["model"] == 2 and mesh.shape["data"] == 1


def test_make_host_mesh_rejects_oversized_tp():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        make_serve_mesh(tp=10 * jax.device_count())
    with pytest.raises(ValueError):
        make_serve_mesh(tp=0)


@requires_devices(2)
def test_heads_shard_axis_requires_common_axis():
    rules = _rules_for(2)
    with set_activation_rules(rules):
        assert heads_shard_axis(8, 4) == (rules.mesh, "model")
        # kv heads that don't divide fall back to replication as a UNIT:
        # splitting q-heads but not kv would break GQA grouping
        assert heads_shard_axis(8, 3) is None
        assert heads_shard_axis(3, 3) is None
    assert heads_shard_axis(8, 4) is None         # no rules active


@requires_devices(4)
def test_tp_degree_replication_fallback():
    cfg = get_config("glm4-9b", reduced=True)     # heads=4, kv=2
    assert tp_degree(_rules_for(2), cfg.num_heads, cfg.num_kv_heads) == 2
    assert tp_degree(_rules_for(4), cfg.num_heads, cfg.num_kv_heads) == 1
    assert tp_degree(None, cfg.num_heads, cfg.num_kv_heads) == 1


# ---------------------------------------------------------------------------
# end-to-end: serve_paged tp=2 bit-identical to tp=1
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def _served_model():
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, shared_prefix=False):
    rng = np.random.default_rng(7)
    if shared_prefix:
        prefix = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
        prompts = [
            np.concatenate([prefix, rng.integers(0, cfg.vocab_size, (n,))
                            .astype(np.int32)])
            for n in (5, 3, 7, 2)
        ]
    else:
        prompts = [
            rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in (5, 9, 13, 4)
        ]
    return [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=m)
        for i, (p, m) in enumerate(zip(prompts, (6, 4, 8, 3)))
    ]


@requires_devices(2)
@pytest.mark.parametrize("prefill_mode", ["packed", "chunked"])
@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_serve_paged_tp2_bit_identical(_served_model, prefill_mode, spec_k,
                                       prefix_cache):
    cfg, model, params = _served_model
    kwargs = dict(
        num_slots=3, page_size=8, num_pages=40, prefill_mode=prefill_mode,
        spec_k=spec_k, prefix_cache=prefix_cache,
    )
    base_eng = ServingEngine(model, params, max_batch=3, max_seq=64)
    base = base_eng.serve_paged(_requests(cfg, prefix_cache), **kwargs)
    eng = ServingEngine(
        model, params, max_batch=3, max_seq=64, rules=_rules_for(2)
    )
    assert eng.tp == 2
    got = eng.serve_paged(_requests(cfg, prefix_cache), **kwargs)
    assert got.tp == 2 and base.tp == 1
    by_id = {r.request_id: r for r in base.results}
    for r in got.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)
    if prefix_cache:
        assert got.saved_prefill_tokens == base.saved_prefill_tokens


@requires_devices(2)
def test_serve_paged_tp2_preemption_bit_identical(_served_model):
    """Page pressure (overcommitted tiny pool) preempts and recovers under
    tp=2 exactly as at tp=1 — same preemptions, same greedy tokens."""
    cfg, model, params = _served_model
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        for n in (9, 8, 7, 5)
    ]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=m)
        for i, (p, m) in enumerate(zip(prompts, (10, 8, 12, 6)))
    ]
    kwargs = dict(num_slots=3, page_size=4, num_pages=7, prefill_chunk=4,
                  overcommit=10.0)
    base_eng = ServingEngine(model, params, max_batch=3, max_seq=32)
    base = base_eng.serve_paged(reqs(), **kwargs)
    assert base.preemptions > 0
    eng = ServingEngine(
        model, params, max_batch=3, max_seq=32, rules=_rules_for(2)
    )
    got = eng.serve_paged(reqs(), **kwargs)
    assert got.preemptions == base.preemptions
    by_id = {r.request_id: r for r in base.results}
    for r in got.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)


@requires_devices(2)
def test_serve_paged_tp2_emits_collective_events(_served_model):
    from repro.core.analysis import tp_summary
    from repro.core.tracing import Tracer, TracingServer

    cfg, model, params = _served_model
    server = TracingServer()
    tracer = Tracer("tp-e2e", server)
    eng = ServingEngine(
        model, params, max_batch=3, max_seq=64, rules=_rules_for(2)
    )
    eng.serve_paged(_requests(cfg), num_slots=3, page_size=8, num_pages=40,
                    tracer=tracer)
    summary = tp_summary(server.timeline("tp-e2e"))
    assert summary["tp"] == 2.0
    assert summary["sharded_launches"] > 0
    # every collective here is a psum (no rs_block_outputs): ring all-reduce
    # moves 2(tp-1)/tp of the payload -> equal at tp=2
    assert summary["psum_count"] > 0
    assert summary["psum_moved_bytes"] == summary["psum_payload_bytes"]
    assert summary["total_moved_bytes"] == summary["psum_moved_bytes"]


@requires_devices(2)
def test_serve_paged_tp2_reduce_scatter_lever(_served_model):
    """rs_block_outputs keeps tokens bit-identical and halves the analytic
    wire traffic on seq-shardable (prefill) launches."""
    from repro.core.analysis import tp_summary
    from repro.core.tracing import Tracer, TracingServer

    cfg, model, params = _served_model
    base_eng = ServingEngine(model, params, max_batch=3, max_seq=64)
    base = base_eng.serve_paged(_requests(cfg), num_slots=3, page_size=8,
                                num_pages=40)
    server = TracingServer()
    tracer = Tracer("tp-rs", server)
    rules = serve_rules(make_serve_mesh(tp=2), rs_block_outputs=True)
    eng = ServingEngine(model, params, max_batch=3, max_seq=64, rules=rules)
    got = eng.serve_paged(_requests(cfg), num_slots=3, page_size=8,
                          num_pages=40, tracer=tracer)
    by_id = {r.request_id: r for r in base.results}
    for r in got.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)
    summary = tp_summary(server.timeline("tp-rs"))
    assert summary.get("reduce_scatter_count", 0) > 0
    assert (summary["reduce_scatter_moved_bytes"]
            == summary["reduce_scatter_payload_bytes"] / 2)


@requires_devices(4)
def test_serve_paged_tp4_fallback_still_identical(_served_model):
    """glm4-9b reduced has 2 kv heads: tp=4 can't split them, so the rules
    fall back to replication (effective tp 1) — and tokens still match."""
    cfg, model, params = _served_model
    base_eng = ServingEngine(model, params, max_batch=3, max_seq=64)
    base = base_eng.serve_paged(_requests(cfg), num_slots=3, page_size=8,
                                num_pages=40)
    eng = ServingEngine(
        model, params, max_batch=3, max_seq=64, rules=_rules_for(4)
    )
    assert eng.tp == 1
    got = eng.serve_paged(_requests(cfg), num_slots=3, page_size=8,
                          num_pages=40)
    by_id = {r.request_id: r for r in base.results}
    for r in got.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)


@requires_devices(2)
def test_serve_paged_tp2_int8_bit_identical(_served_model):
    """Quantized pools shard their scale pools with the kv heads: the int8
    engine at tp=2 must produce the same greedy tokens as int8 at tp=1
    (quantization happens per kv head, so the heads split changes nothing)."""
    cfg, model, params = _served_model
    kwargs = dict(num_slots=3, page_size=8, num_pages=40)
    base_eng = ServingEngine(
        model, params, max_batch=3, max_seq=64, kv_dtype="int8"
    )
    base = base_eng.serve_paged(_requests(cfg), **kwargs)
    eng = ServingEngine(
        model, params, max_batch=3, max_seq=64, rules=_rules_for(2),
        kv_dtype="int8",
    )
    assert eng.tp == 2
    got = eng.serve_paged(_requests(cfg), **kwargs)
    assert got.kv_dtype == "int8" and base.kv_dtype == "int8"
    by_id = {r.request_id: r for r in base.results}
    for r in got.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)


def test_bench_tp_decides_from_env_without_jax(monkeypatch):
    """The tp sweep picks parent-vs-child from the environment alone (a
    parent that touched JAX would hold a chip its child needs), and only a
    process pinned to the CPU counts its forced host devices."""
    from benchmarks.bench_tp import _pinned_host_devices

    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert _pinned_host_devices() == 8
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=2 "
        "--xla_force_host_platform_device_count=8",
    )
    assert _pinned_host_devices() == 8              # the last flag wins
    monkeypatch.delenv("JAX_PLATFORMS")
    assert _pinned_host_devices() == 0
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS")
    assert _pinned_host_devices() == 0

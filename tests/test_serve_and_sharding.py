"""Serving engine + sharding-rule unit tests."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro.configs import get_config
from repro.models import build_model
from repro.models.params import P
from repro.serve.engine import ServingEngine
from repro.sharding.specs import ShardingRules, default_rules, param_pspecs


# ---------------------------------------------------------------------------
# Serving engine
# ---------------------------------------------------------------------------
def test_engine_generate_matches_stepwise_forward():
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_seq=32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32) for _ in range(2)]
    res = engine.generate(prompts, max_new_tokens=4)
    assert res.tokens.shape == (2, 4)
    assert res.tokens_per_s > 0
    # greedy check against explicit forward for row 0 first new token
    batch = {"tokens": jnp.asarray(np.stack(prompts))}
    logits, _ = model.forward(params, batch)
    expected_first = int(jnp.argmax(logits[0, -1]))
    assert int(res.tokens[0, 0]) == expected_first


def test_engine_continuous_batching_slot_reuse():
    """A queued prompt is admitted into the slot freed by a finished
    sequence, at a decode-step boundary (fake clock: no real sleeps)."""
    from repro.serve.engine import ServeRequest

    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_seq=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32) for _ in range(3)]
    reqs = [
        ServeRequest(request_id=0, prompt=prompts[0], max_new_tokens=2),
        ServeRequest(request_id=1, prompt=prompts[1], max_new_tokens=6),
        ServeRequest(request_id=2, prompt=prompts[2], max_new_tokens=3),
    ]

    class VT:
        t = 0.0

        def clock(self):
            self.t += 1.0
            return self.t

    stats = engine.serve_continuous(reqs, num_slots=2, clock=VT().clock)
    by_id = {r.request_id: r for r in stats.results}
    # requests 0 and 1 are admitted immediately; 2 waits for a free slot
    assert by_id[0].admit_step == 0 and by_id[1].admit_step == 0
    assert by_id[2].admit_step == by_id[0].finish_step  # admitted when 0 frees
    assert by_id[2].admit_step > 0
    assert by_id[2].slot == by_id[0].slot               # the freed slot is reused
    for r in stats.results:
        assert len(r.tokens) == reqs[r.request_id].max_new_tokens
        assert r.ttft_s > 0 and r.latency_s >= r.ttft_s
    assert stats.total_tokens == 2 + 6 + 3
    assert 1.0 <= stats.mean_slot_occupancy <= 2.0


def test_engine_continuous_single_token_budget():
    """A request whose whole budget is the prefill token retires without a
    decode step appending a spurious second token."""
    from repro.serve.engine import ServeRequest

    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_seq=32)
    prompt = np.arange(4, dtype=np.int32)
    stats = engine.serve_continuous(
        [ServeRequest(request_id=0, prompt=prompt, max_new_tokens=1)], num_slots=2
    )
    assert len(stats.results[0].tokens) == 1
    assert stats.total_tokens == 1


def test_engine_continuous_rejects_encdec():
    from repro.serve.engine import ServeRequest

    cfg = get_config("whisper-large-v3", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_seq=32)
    with pytest.raises(NotImplementedError, match="encoder-decoder"):
        engine.serve_continuous(
            [ServeRequest(request_id=0, prompt=np.arange(4, dtype=np.int32),
                          max_new_tokens=2)]
        )


def test_engine_continuous_matches_static_generate():
    """Greedy tokens from the continuous path equal the static batched path
    (same left-padding, masked vs uniform cache writes are equivalent)."""
    from repro.serve.engine import ServeRequest

    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_seq=32)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32) for _ in range(2)]
    static = engine.generate(prompts, max_new_tokens=4)
    reqs = [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=4)
        for i, p in enumerate(prompts)
    ]
    cont = engine.serve_continuous(reqs, num_slots=2)
    for i, r in enumerate(cont.results):
        np.testing.assert_array_equal(r.tokens, static.tokens[i])


def test_engine_paged_matches_continuous():
    """serve_paged (chunked prefill + paged KV + Pallas-style page tables)
    emits exactly the tokens of serve_continuous for the same seeded
    requests — the paged layout is bit-compatible with the dense path."""
    from repro.serve.engine import ServeRequest

    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=3, max_seq=32)
    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (5, 9, 7, 4)
    ]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=m)
        for i, (p, m) in enumerate(zip(prompts, (6, 4, 8, 3)))
    ]
    cont = engine.serve_continuous(reqs(), num_slots=2)
    paged = engine.serve_paged(
        reqs(), num_slots=3, page_size=4, prefill_chunk=8
    )
    by_id = {r.request_id: r for r in cont.results}
    for r in paged.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)
    assert paged.total_tokens == cont.total_tokens == 6 + 4 + 8 + 3
    assert paged.prefill_chunks >= len(prompts)  # every prompt chunk-prefilled
    assert paged.preemptions == 0                # default admission reserves


def test_engine_paged_preemption_under_page_pressure():
    """With an overcommitted pool the youngest request is preempted
    (recompute-style) and still finishes with identical greedy tokens."""
    from repro.serve.engine import ServeRequest

    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=3, max_seq=32)
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32) for n in (9, 8, 7, 5)
    ]
    reqs = lambda: [
        ServeRequest(request_id=i, prompt=p, max_new_tokens=m)
        for i, (p, m) in enumerate(zip(prompts, (10, 8, 12, 6)))
    ]
    cont = engine.serve_continuous(reqs(), num_slots=2)
    # 6 allocatable pages of 4 tokens = 24 live tokens; worst case needs 19
    # per request, so overcommitted admission forces page-pressure evictions
    paged = engine.serve_paged(
        reqs(), num_slots=3, page_size=4, num_pages=7, prefill_chunk=4,
        overcommit=10.0,
    )
    assert paged.preemptions > 0
    by_id = {r.request_id: r for r in cont.results}
    for r in paged.results:
        np.testing.assert_array_equal(r.tokens, by_id[r.request_id].tokens)
    assert paged.peak_pages_in_use <= paged.num_pages == 6


def test_engine_prefill_bucketing_bounds_compiles():
    """Distinct prompt lengths map to one power-of-two prefill bucket, so
    the engine stops recompiling per length (counted in compile stats)."""
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=2, max_seq=64)
    rng = np.random.default_rng(0)
    first = None
    for n in (3, 5, 9, 14):     # all bucket to 16 (floor page_size=16)
        p = rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
        res = engine.generate([p], max_new_tokens=2)
        # bucketing must stay numerically exact: right-padding + causal
        # attention means the first token matches the unpadded forward
        logits, _ = model.forward(params, {"tokens": jnp.asarray(p[None])})
        assert int(res.tokens[0, 0]) == int(jnp.argmax(logits[0, -1]))
        if first is None:
            first = engine.compile_stats()["prefill"]
    stats = engine.compile_stats()
    assert stats["prefill"] == first == 1
    assert stats["decode"] >= 1


def test_page_pool_and_table_bookkeeping():
    from repro.serve.page_table import PagePool, PageTable, pages_needed

    assert pages_needed(0, 8) == 0
    assert pages_needed(1, 8) == 1
    assert pages_needed(17, 8) == 3
    pool = PagePool(6, 8, reserved=1)    # pages 1..5 allocatable
    assert pool.capacity == 5
    a = pool.alloc(3)
    assert sorted(a) == [1, 2, 3] and pool.num_in_use == 3
    assert pool.alloc(3) is None         # atomic: all-or-nothing
    b = pool.alloc(2)
    assert pool.num_free == 0 and pool.peak_in_use == 5
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free([a[0]])                # double free
    table = PageTable(2, 4)
    table.assign(0, b)
    with pytest.raises(ValueError):
        table.assign(0, [1])             # slot already holds pages
    table.append(0, 1)
    assert table.num_pages_of(0) == 3
    mask = np.array([False, True])
    assert (table.rows_for(mask)[0] == 0).all()  # masked row -> scratch page
    assert table.clear(0) == b + [1]
    assert table.num_pages_of(0) == 0


def test_engine_rejects_oversize():
    cfg = get_config("glm4-9b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, max_batch=1, max_seq=8)
    with pytest.raises(ValueError):
        engine.generate([np.zeros(4, np.int32)] * 2, max_new_tokens=1)
    with pytest.raises(ValueError):
        engine.generate([np.zeros(7, np.int32)], max_new_tokens=5)


# ---------------------------------------------------------------------------
# Sharding rules (pure spec logic — uses a stub mesh, no devices needed)
# ---------------------------------------------------------------------------
def _stub_mesh(shape_dict):
    return SimpleNamespace(shape=shape_dict, axis_names=tuple(shape_dict))


def _norm(spec):
    """Normalize PartitionSpec entries: 'x' and ('x',) are the same sharding
    (older jax canonicalized these as equal; newer versions compare raw)."""
    return tuple(
        None if e is None else ((e,) if isinstance(e, str) else tuple(e))
        for e in spec
    )


def test_divisibility_fallback():
    mesh = _stub_mesh({"data": 16, "model": 16})
    rules = default_rules(mesh)
    # divisible: sharded
    assert rules.mesh_axes_for("heads", 32) == "model"
    # not divisible: dropped to replication
    assert rules.mesh_axes_for("heads", 20) is None
    assert rules.mesh_axes_for("vocab", 50280) is None
    assert rules.mesh_axes_for("vocab", 102400) == "model"
    # batch composes pod+data when present
    mesh3 = _stub_mesh({"pod": 2, "data": 16, "model": 16})
    rules3 = default_rules(mesh3)
    assert rules3.mesh_axes_for("batch", 256) == ("pod", "data")
    assert rules3.mesh_axes_for("batch", 16) == "pod"  # drops trailing axes
    assert rules3.mesh_axes_for("batch", 1) is None


def test_param_pspecs_from_logical_axes():
    mesh = _stub_mesh({"data": 16, "model": 16})
    rules = default_rules(mesh, fsdp=True)
    defs = {
        "wq": P((4, 8192, 64, 128), axes=("layer", "embed", "heads", "head_dim")),
        "norm": P((8192,), axes=("embed",)),
    }
    specs = param_pspecs(defs, rules)
    assert _norm(specs["wq"]) == _norm(PartitionSpec(None, ("data",), "model", None))
    # fsdp shards norm's embed dim over data
    assert _norm(specs["norm"]) == _norm(PartitionSpec(("data",)))
    rules_nofsdp = default_rules(mesh, fsdp=False)
    specs2 = param_pspecs(defs, rules_nofsdp)
    assert _norm(specs2["wq"]) == _norm(PartitionSpec(None, None, "model", None))


def test_moe_expert_specs_no_duplicate_axes():
    mesh = _stub_mesh({"data": 16, "model": 16})
    rules = default_rules(mesh, fsdp=True)
    defs = {
        "w_gate": P((24, 128, 5120, 8192),
                    axes=("layer", "experts", "embed", "expert_ffn")),
    }
    spec = param_pspecs(defs, rules)["w_gate"]
    assert _norm(spec) == _norm(PartitionSpec(None, "model", ("data",), None))
    flat = [a for dim in spec for a in ((dim,) if isinstance(dim, str) else (dim or ()))]
    assert len(flat) == len(set(flat))  # no mesh axis used twice


def test_rank_mismatch_raises():
    mesh = _stub_mesh({"data": 2, "model": 2})
    rules = default_rules(mesh)
    with pytest.raises(ValueError):
        param_pspecs({"bad": P((2, 2), axes=("embed",))}, rules)


# ---------------------------------------------------------------------------
# launch/serve.py: published widths, depth cut, kernel default, compile cache
# ---------------------------------------------------------------------------
def test_depth_cut_keeps_widths_and_whole_periods():
    from repro.configs import depth_cut

    glm = get_config("glm4-9b")
    cut = depth_cut(glm, 16)
    assert cut.num_layers == 16 and cut.name == "glm4-9b-16L"
    assert (cut.d_model, cut.num_heads, cut.num_kv_heads, cut.d_ff,
            cut.vocab_size) == (glm.d_model, glm.num_heads,
                                glm.num_kv_heads, glm.d_ff, glm.vocab_size)
    assert depth_cut(glm, 0) is glm and depth_cut(glm, 40) is glm
    gemma = get_config("gemma2-27b")               # local/global pairs
    assert depth_cut(gemma, 4).num_layers == 4
    with pytest.raises(ValueError, match="pattern"):
        depth_cut(gemma, 5)


def test_serve_cli_reduced_switch_and_depth():
    from repro.launch import serve

    args = serve.parse_args([])
    assert args.reduced and args.backend is None
    assert serve.serve_dtype(args) == "float32"
    args = serve.parse_args(["--no-reduced", "--layers", "16"])
    assert not args.reduced and args.layers == 16
    assert serve.serve_dtype(args) == "bfloat16"
    with pytest.raises(SystemExit):
        serve.parse_args(["--arch", "gemma2-27b", "--no-reduced",
                          "--layers", "5"])


def test_serve_cli_builds_reduced_model_through_its_functions():
    from repro.kernels import ops
    from repro.launch import serve

    args = serve.parse_args(["--layers", "2", "--engine", "paged"])
    cfg, model, params = serve.load_model(args)
    assert cfg.num_layers == 2
    # off a TPU the platform's kernels are the chunked pure-JAX ones
    assert model.backend == ops.default_backend() == "flash"
    assert params["embed"].dtype == jnp.float32
    engine = serve.make_engine(args, model, params)
    assert engine.cache_dtype == "float32"


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert compile_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
        assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "src").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

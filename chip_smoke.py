"""On-chip smoke test: glm4-9b at its published widths, served on a TPU
through the paged engine and the Pallas kernels.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # tensor parallelism on a 4-chip host

One process drives every phase, through ``repro.launch.serve``'s own
functions.  Each phase prints its lines as it runs:

  a. device      platform, device kind and count; the platform's default
                 kernels must be the Pallas ones
  b. kernels     each serving kernel against its ``kernels/ref.py`` oracle
                 on the chip, with bf16 and with int8 KV pools
  c. serve       8 requests of 512 prompt tokens and 32 new tokens through
                 ``--engine paged --backend pallas`` (packed prefill, prefix
                 cache on), then again with ``--spec-k 3 --prefix-len 256``
  d. agreement   the first prefill's logits and a few cached decode steps'
                 logits, Pallas path against the ``flash`` path on the same
                 weights; the lowered decode step must hold Mosaic kernels

``--four-chips`` runs only the tensor-parallel path and what it is compared
with: the depth-cut model at tp=2 against tp=1 on one chip, then the whole
40-layer model at tp=2, which one chip cannot hold.

The model is cut to its first LAYERS layers (every width as published,
random weights from seed 0).  Timings are smoke figures from one run, not
benchmarks.  On success the last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``.  Without a TPU, or without the
repository's ``src/`` next to this file, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from functools import partial
from pathlib import Path

# 16 of glm4-9b's 40 uniform layers: bf16 weights plus the serving pool
# take 8.46 GiB (memory_analysis() of the decode step compiled for v5e),
# leaving ~7 GiB of the chip's 16 for KV pages and activations
LAYERS = 16
PUBLISHED_LAYERS = 40
PAGE = 16

# kernel vs oracle, bf16 outputs: both round q/k/v to bf16, accumulate in
# f32 and round the output to bf16 (a step of 2^-8 = 3.9e-3 relative), but
# in different orders (online softmax per page vs one softmax); 2e-2 is
# about five output ulps, while a wrong page, head group or mask moves an
# element by O(1)
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)
# logits, Pallas vs flash (and tp=2 vs tp=1), as a fraction of the largest
# |logit|: each path rounds attention outputs and the residual stream to
# bf16 at different points in each of 16 layers, 16 x 3.9e-3 ~ 6e-2 bounds
# the drift; a broken kernel moves logits by their whole scale
LOGIT_TOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[smoke] {phase}: {msg}", flush=True)


def base_argv(layers: int = LAYERS):
    """Flags shared by every serve run: published widths cut to
    ``layers``, Pallas kernels, paged engine, packed prefill (one prompt
    per 512-token launch) and the prefix cache, with a pool large enough
    that cached prompt pages are not evicted within a run."""
    return [
        "--arch", "glm4-9b", "--no-reduced", "--layers", str(layers),
        "--engine", "paged", "--backend", "pallas",
        "--page-size", str(PAGE), "--num-pages", "1024",
        "--prefill-mode", "packed", "--prefill-budget", "512",
        "--prefix-cache", "on", "--rate-hz", "1000",
    ]


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------
def phase_device(jax):
    from repro.kernels import ops
    from repro.configs import get_config
    from repro.launch import serve as launch
    from repro.models import build_model

    devs = jax.devices()
    d = devs[0]
    say("a/device", f"platform={d.platform} kind={d.device_kind} "
                    f"count={len(devs)}")
    check(ops.default_backend() == "pallas",
          f"platform default kernels are {ops.default_backend()!r}")
    cfg = get_config("glm4-9b")
    check(launch.parse_args([]).backend is None
          and build_model(cfg).backend == "pallas",
          "repro.launch.serve's default backend is not pallas on this chip")
    say("a/device", "default kernels: pallas (ops.default_backend and "
                    "repro.launch.serve's --backend default)")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


# ---------------------------------------------------------------------------
# b. kernels vs oracles
# ---------------------------------------------------------------------------
def phase_kernels(jax, jnp, np):
    from repro.kernels import kvquant, ops, ref

    H, KVH, D = 32, 2, 128                  # glm4-9b attention widths
    num_pages, b, max_pages = 128, 8, 12
    rng = np.random.default_rng(0)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    kp, vp = normal((num_pages, PAGE, KVH, D)), normal((num_pages, PAGE, KVH, D))
    kq, ks = kvquant.quantize(kp, jnp.int8)
    vq, vs = kvquant.quantize(vp, jnp.int8)
    pools = {
        "bf16": (kp, vp, {}),
        "int8": (kq, vq, {"k_scales": ks, "v_scales": vs}),
    }
    table = jnp.asarray(
        rng.permutation(np.arange(1, num_pages))[: b * max_pages]
        .reshape(b, max_pages), jnp.int32,
    )
    # ragged: one token, page edges either side, mid-page, near-full
    lengths = jnp.asarray([1, 15, 16, 17, 64, 100, 150, 192], jnp.int32)
    q1 = normal((b, 1, H, D))
    W = 4                                   # spec_k = 3 drafts + 1
    qw = normal((b, W, H, D))
    committed = jnp.asarray([0, 10, 16, 33, 60, 100, 150, 188], jnp.int32)
    wlens = jnp.asarray([4, 4, 1, 0, 3, 4, 2, 4], jnp.int32)
    spans = [64, 16, 48, 128]               # page-aligned packed spans
    T = sum(spans)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(spans)]), jnp.int32)
    chunk_lens = jnp.asarray([60, 16, 33, 128], jnp.int32)
    chunk_pos0 = jnp.asarray([0, 32, 16, 64], jnp.int32)
    tables_c = table[:4]
    qt, kt, vt = normal((T, H, D)), normal((T, KVH, D)), normal((T, KVH, D))

    def compare(name, got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
        err = float(np.max(np.abs(got - want)))
        try:
            np.testing.assert_allclose(got, want, **KERNEL_TOL)
        except AssertionError as e:
            raise SmokeFailure(f"{name}: kernel differs from oracle "
                               f"(max |err| {err:.3g}):\n{e}") from None
        return err

    for mode, (k_pages, v_pages, sc) in pools.items():
        errs = {}
        with jax.default_matmul_precision("highest"):
            want = {
                "paged_attention": ref.paged_attention(
                    q1, k_pages, v_pages, table, lengths, **sc),
                "spec_verify": ref.spec_verify(
                    qw, k_pages, v_pages, table, committed, wlens, **sc),
                "varlen_prefill": ref.varlen_prefill(
                    qt, kt, vt, k_pages, v_pages, cu, chunk_lens,
                    chunk_pos0, tables_c, **sc),
            }
        got = {
            "paged_attention": ops.paged_attention(
                q1, k_pages, v_pages, table, lengths, backend="pallas",
                pages_bound=max_pages, **sc),
            "spec_verify": ops.spec_verify(
                qw, k_pages, v_pages, table, committed, wlens,
                backend="pallas", pages_bound=max_pages, **sc),
            "varlen_prefill": ops.varlen_prefill(
                qt, kt, vt, k_pages, v_pages, cu, chunk_lens, chunk_pos0,
                tables_c, backend="pallas", pages_bound=max_pages, **sc),
        }
        for name in want:
            errs[name] = compare(f"{name}/{mode}", got[name], want[name])
        say("b/kernels", f"{mode} pool: " + ", ".join(
            f"{k} max|err| {v:.3g}" for k, v in errs.items()
        ) + f" (tolerance rtol {KERNEL_TOL['rtol']} atol {KERNEL_TOL['atol']})")


# ---------------------------------------------------------------------------
# c. paged serving through repro.launch.serve
# ---------------------------------------------------------------------------
def phase_serve(jax, launch):
    args = launch.parse_args(base_argv() + [
        "--requests", "8", "--engine-batch", "8", "--prompt-len", "512",
        "--max-new-tokens", "32", "--max-seq", "576",
    ])
    t0 = time.perf_counter()
    cfg, model, params = launch.load_model(args)
    jax.block_until_ready(params)
    say("c/serve", f"{cfg.name}: {cfg.num_layers} of {PUBLISHED_LAYERS} "
                   f"layers at published widths (d_model {cfg.d_model}, "
                   f"{cfg.num_heads} q / {cfg.num_kv_heads} kv heads x "
                   f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
                   f"{cfg.vocab_size}), {launch.serve_dtype(args)} weights "
                   f"and KV pages, "
                   f"{model.backend} kernels; weight init "
                   f"{time.perf_counter() - t0:.1f} s (smoke figure)")
    check(model.backend == "pallas", f"model runs {model.backend} kernels")
    engine = launch.make_engine(args, model, params)

    def run(run_args, load, prompts, label):
        summary, generated, wall = launch.serve(
            run_args, engine, cfg, load, prompts
        )
        n = len(prompts)
        check(summary.get("completed") == n and summary.get("rejected") == 0,
              f"{label}: {summary.get('completed')} of {n} requests "
              f"completed ({summary.get('rejected')} rejected)")
        check(generated == n * run_args.max_new_tokens,
              f"{label}: {generated} tokens generated, expected "
              f"{n * run_args.max_new_tokens}")
        say("c/serve", f"{label}: {n}/{n} requests completed, {generated} "
                       f"tokens in {wall:.2f} s incl. compiles; prefill "
                       f"{summary['prefill_s']:.2f} s, decode "
                       f"{summary['decode_s']:.2f} s, "
                       f"{summary['tokens_per_s']:.1f} tok/s (smoke figures)")
        return summary

    load, prompts = launch.make_workload(args, cfg)
    run(args, load, prompts, "packed prefill + prefix cache")

    spec_args = launch.parse_args(base_argv() + [
        "--requests", "8", "--engine-batch", "4", "--prompt-len", "512",
        "--max-new-tokens", "32", "--max-seq", "576",
        "--spec-k", "3", "--prefix-len", "256",
    ])
    load, prompts = launch.make_workload(spec_args, cfg)
    for p in prompts:
        # repeat a phrase inside each prompt so prompt-lookup drafting
        # finds n-gram matches and the verify kernel launches
        p[384:512] = p[256:384]
    # a retried request: admitted after the first wave, its whole prompt is
    # cached, so its first append copies the shared last page (COW)
    prompts[-1] = prompts[0].copy()
    summary = run(spec_args, load, prompts,
                  "spec-k 3 + 256-token shared prefix")
    check(summary.get("spec_launches", 0) > 0,
          "speculative run launched no verify step")
    check(summary.get("cow_copies", 0) > 0,
          "speculative run made no copy-on-write page copy")
    check(summary.get("saved_prefill_tokens", 0) > 0,
          "prefix cache served no prompt tokens")
    say("c/serve", f"verify launches {summary['spec_launches']:.0f}, drafts "
                   f"accepted {summary['draft_accepted']:.0f}/"
                   f"{summary['draft_proposed']:.0f}, copy-on-write copies "
                   f"{summary['cow_copies']:.0f}, prompt tokens served "
                   f"from cache {summary['saved_prefill_tokens']:.0f}")
    return cfg, model, params


# ---------------------------------------------------------------------------
# d. logits agreement (and tp=2 vs tp=1 in --four-chips)
# ---------------------------------------------------------------------------
def paged_logits(jax, np, model, params, prompt, steps, feed=None,
                 rules=None, device=None, timings=None):
    """Logits of one request through the paged entry points: one packed
    prefill launch of the whole prompt, then ``steps`` cached decode steps.
    Decode consumes ``feed`` when given (so two paths see the same tokens),
    else its own greedy tokens.  Returns (list of (V,) logits, tokens)."""
    from repro.sharding.specs import set_activation_rules

    T = len(prompt)
    max_pages = (T + steps) // PAGE + 1
    row = np.arange(1, max_pages + 1, dtype=np.int32)  # page 0: scratch
    cache = model.init_paged_cache(max_pages + 1, PAGE, dtype="bfloat16")
    if rules is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        pspecs = model.paged_cache_pspecs(rules, max_pages + 1, PAGE,
                                          dtype="bfloat16")
        cache = jax.device_put(cache, jax.tree.map(
            lambda s: NamedSharding(rules.mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        ))
    elif device is not None:
        cache = jax.device_put(cache, device)
    pos = np.arange(T, dtype=np.int32)
    batch = {
        "tokens": np.asarray(prompt, np.int32)[None],
        "tok_pos": pos,
        "dst_page": row[pos // PAGE],
        "dst_off": (pos % PAGE).astype(np.int32),
        "cu_seqlens": np.asarray([0, T], np.int32),
        "chunk_lens": np.asarray([T], np.int32),
        "chunk_pos0": np.asarray([0], np.int32),
        "page_tables": row[None],
        "last_idx": np.asarray([T - 1], np.int32),
    }

    def ruled(fn):
        def wrapped(*a):
            with set_activation_rules(rules):
                return fn(*a)
        return wrapped if rules is not None else fn

    prefill = jax.jit(ruled(partial(model.prefill_packed, pages_bound=1)))
    decode = jax.jit(ruled(partial(model.decode_paged, pages_bound=max_pages)))
    table = row[None]
    dec_args = (params, np.zeros((1,), np.int32), cache, table,
                np.asarray([T], np.int32))
    if timings is not None:
        t0 = time.perf_counter()
        prefill = prefill.lower(params, batch, cache).compile()
        t1 = time.perf_counter()
        lowered = decode.lower(*dec_args)
        timings["decode_hlo"] = lowered.as_text()
        decode = lowered.compile()
        timings["prefill_compile_s"] = t1 - t0
        timings["decode_compile_s"] = time.perf_counter() - t1
    logits, cache = prefill(params, batch, cache)
    out = [np.asarray(logits[0], np.float32)]
    toks = []
    for i in range(steps):
        tok = int(np.argmax(out[-1])) if feed is None else int(feed[i])
        toks.append(tok)
        logits, cache = decode(params, np.asarray([tok], np.int32), cache,
                               table, np.asarray([T + i], np.int32))
        out.append(np.asarray(logits[0], np.float32))
    return out, toks


def compare_logits(np, label, got, want):
    worst = 0.0
    for i, (g, w) in enumerate(zip(got, want)):
        check(np.isfinite(g).all() and np.isfinite(w).all(),
              f"{label}: non-finite logits at step {i}")
        scale = float(np.max(np.abs(w)))
        check(scale > 0 and float(np.std(w)) > 0,
              f"{label}: degenerate reference logits at step {i}")
        err = float(np.max(np.abs(g - w))) / scale
        worst = max(worst, err)
        check(err <= LOGIT_TOL,
              f"{label}: step {i} max |dlogit| / max |logit| = {err:.3g} "
              f"> {LOGIT_TOL}")
    top1 = sum(int(np.argmax(g) == np.argmax(w)) for g, w in zip(got, want))
    return worst, top1


def phase_agreement(jax, np, cfg, params):
    from repro.models import build_model

    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (512,)).astype(np.int32)
    steps = 4
    timings = {}
    pallas = build_model(cfg, backend="pallas")
    flash = build_model(cfg, backend="flash")
    got, toks = paged_logits(jax, np, pallas, params, prompt, steps,
                             timings=timings)
    check("tpu_custom_call" in timings["decode_hlo"],
          "the lowered Pallas decode step holds no Mosaic kernel")
    want, _ = paged_logits(jax, np, flash, params, prompt, steps,
                           feed=toks)
    worst, top1 = compare_logits(np, "pallas vs flash", got, want)
    say("d/agreement", f"prefill + {steps} decode steps: max |dlogit| / max "
                       f"|logit| {worst:.3g} (tolerance {LOGIT_TOL}), top-1 "
                       f"equal {top1}/{steps + 1}; lowered decode step holds "
                       f"tpu_custom_call")
    say("d/agreement", f"compile (smoke figures): packed prefill "
                       f"{timings['prefill_compile_s']:.1f} s, decode "
                       f"{timings['decode_compile_s']:.1f} s")


# ---------------------------------------------------------------------------
# --four-chips: tensor parallelism
# ---------------------------------------------------------------------------
def sharded_over(leaf, devices) -> bool:
    """True when ``leaf`` is split (not replicated) across exactly
    ``devices``."""
    shards = leaf.addressable_shards
    return (
        {s.device for s in shards} == set(devices)
        and all(s.data.shape != leaf.shape for s in shards)
    )


def phase_four_chips(jax, np, launch):
    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    tp_args = launch.parse_args(base_argv() + [
        "--tp", "2", "--requests", "4", "--engine-batch", "4",
        "--prompt-len", "128", "--max-new-tokens", "16", "--max-seq", "256",
    ])
    rules = launch.make_rules(tp_args)
    mesh_devs = list(rules.mesh.devices.flat)
    cfg, model, params = launch.load_model(tp_args, rules)
    engine = launch.make_engine(tp_args, model, params, rules)
    check(engine.tp == 2, f"requested tp=2, effective tp={engine.tp}")
    wq = params["blocks"]["attn"]["wq"]
    check(sharded_over(wq, mesh_devs),
          f"attention weights are not split over {mesh_devs}: "
          f"{[(s.device, s.data.shape) for s in wq.addressable_shards]}")
    pool = jax.device_put(
        model.init_paged_cache(64, PAGE, dtype="bfloat16"),
        jax.tree.map(
            lambda s: jax.sharding.NamedSharding(rules.mesh, s),
            model.paged_cache_pspecs(rules, 64, PAGE, dtype="bfloat16"),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        ),
    )
    check(sharded_over(pool["k_pages"], mesh_devs),
          "the KV pool is not split by heads over the mesh")
    del pool
    say("tp", f"{cfg.name}: requested tp=2, effective tp={engine.tp}; "
              f"weights and KV pool split over {[d.id for d in mesh_devs]}")

    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (512,)).astype(np.int32)
    steps = 4
    got, toks = paged_logits(jax, np, model, params, prompt, steps,
                             rules=rules)
    lone = next(d for d in devs if d not in mesh_devs)
    params1 = jax.device_put(params, lone)
    want, _ = paged_logits(jax, np, model, params1, prompt, steps,
                           feed=toks, device=lone)
    worst, top1 = compare_logits(np, "tp=2 vs tp=1", got, want)
    say("tp", f"depth-cut model, prefill + {steps} decode steps, tp=2 vs "
              f"tp=1 on device {lone.id}: max |dlogit| / max |logit| "
              f"{worst:.3g} (tolerance {LOGIT_TOL}), top-1 equal "
              f"{top1}/{steps + 1}")
    del params, params1, engine

    whole_args = launch.parse_args(base_argv(layers=0) + [
        "--tp", "2", "--requests", "4", "--engine-batch", "4",
        "--prompt-len", "128", "--max-new-tokens", "16", "--max-seq", "256",
    ])
    t0 = time.perf_counter()
    cfg, model, params = launch.load_model(whole_args, rules)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    check(cfg.num_layers == PUBLISHED_LAYERS,
          f"whole model has {cfg.num_layers} layers")
    engine = launch.make_engine(whole_args, model, params, rules)
    check(engine.tp == 2, f"whole model: effective tp={engine.tp}")
    load, prompts = launch.make_workload(whole_args, cfg)
    summary, generated, wall = launch.serve(
        whole_args, engine, cfg, load, prompts
    )
    n = len(prompts)
    check(summary.get("completed") == n,
          f"whole model: {summary.get('completed')} of {n} requests completed")
    check(generated == n * whole_args.max_new_tokens,
          f"whole model: {generated} tokens generated")
    say("tp", f"{cfg.name}, all {cfg.num_layers} layers at tp=2: {n}/{n} "
              f"requests completed, {generated} tokens in {wall:.2f} s incl. "
              f"compiles, weight init {init_s:.1f} s (smoke figures)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel phase (4-chip host)")
    opts = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"[smoke] FAIL: the repository's src/repro is not next to "
              f"{Path(__file__).name}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    first = jax.devices()[0]
    if first.platform != "tpu":
        print(f"[smoke] FAIL: needs a TPU; JAX found platform "
              f"{first.platform!r} ({first.device_kind})", file=sys.stderr)
        return 1
    from repro.launch import serve as launch

    say("setup", f"compile cache: {cache_dir}")
    try:
        t0 = time.perf_counter()
        device = phase_device(jax)
        if opts.four_chips:
            phase_four_chips(jax, np, launch)
        else:
            phase_kernels(jax, jnp, np)
            cfg, _, params = phase_serve(jax, launch)
            phase_agreement(jax, np, cfg, params)
        say("done", f"all phases passed in {time.perf_counter() - t0:.1f} s "
                    f"(smoke figure)")
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        print("[smoke] FAIL: a phase raised", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

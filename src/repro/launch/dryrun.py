import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()
# ^ the required first lines: set BEFORE any jax-importing import below.
"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

The FIRST LINES above run before any other import (jax locks the platform
and the device count at first init): the dry-run is a CPU program over 512
forced host devices, so it pins itself to the CPU — it never holds a chip,
and neither do the per-cell processes ``--all`` starts, which inherit the
pin.  The device-count flag is appended to any ``XLA_FLAGS`` already set.
Do not import this module from code that needs real device topology.

For every cell this lowers the right step function (train_step for
``train_*`` shapes, prefill/decode for serving shapes) with
ShapeDtypeStruct inputs (no allocation), compiles for the production mesh,
and records ``memory_analysis()`` / ``cost_analysis()`` / the parsed HLO
roofline terms to a JSON file — the §Dry-run + §Roofline data source.

Usage::

    python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh pod
    python -m repro.launch.dryrun --all --out results/dryrun   # full sweep
                                                               # (subprocess per cell)
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..configs import ARCH_IDS, SHAPES, ShapeSpec, get_config, list_archs, shape_applicable
from ..models.lm import build_model
from ..models.params import param_specs as specs_of, tree_map_defs, P
from ..roofline.model import TPU_V5E, model_flops_for, roofline_from_compiled
from ..sharding.specs import (
    ShardingRules,
    batch_axes,
    default_rules,
    param_pspecs,
    set_activation_rules,
)
from ..train.optimizer import OptimizerConfig, opt_state_defs
from ..train.step import make_train_step
from .mesh import make_production_mesh, mesh_name

SERVE_DTYPE = "bfloat16"

# Per-arch policies: dtypes/microbatching chosen so every cell fits 16 GB/chip
# (napkin math in EXPERIMENTS.md §Dry-run). fsdp shards weight embed-dims over
# the data axes (ZeRO-3-style); optimizer states inherit it (ZeRO-1).
DEFAULT_TRAIN = dict(
    param_dtype="float32", microbatches=16, m_dtype="float32",
    v_dtype="float32", accum_dtype="float32", fsdp=True, remat=True,
)
TRAIN_POLICY: Dict[str, Dict[str, Any]] = {
    "llama4-maverick-400b-a17b": dict(
        param_dtype="bfloat16", microbatches=16, m_dtype="bfloat16",
        v_dtype="bfloat16", accum_dtype="bfloat16", fsdp=True, remat=True,
    ),
    "deepseek-67b": dict(DEFAULT_TRAIN, microbatches=32),
    "granite-20b": dict(DEFAULT_TRAIN, microbatches=32),
    "chameleon-34b": dict(DEFAULT_TRAIN, microbatches=32),
    "mamba2-130m": dict(DEFAULT_TRAIN, microbatches=8, fsdp=False),
    # whisper: 20 heads defeat 16-way TP, so weights replicate across the
    # model axis unless FSDP shards their embed dims over data
    "whisper-large-v3": dict(DEFAULT_TRAIN, microbatches=8, fsdp=True),
    "zamba2-2.7b": dict(DEFAULT_TRAIN, microbatches=16, fsdp=False),
}
SERVE_POLICY: Dict[str, Dict[str, Any]] = {
    # 400B weights exceed 16-way TP capacity -> FSDP-style sharding at serve
    "llama4-maverick-400b-a17b": dict(fsdp=True),
    # 67B bf16 = 8.4 GB/chip at TP-16; + a 6 GB 32k cache leaves no headroom
    "deepseek-67b": dict(fsdp=True),
}


def rules_for(cfg, mesh, fsdp: bool, train: bool = False, opts=None) -> ShardingRules:
    rules = default_rules(mesh, fsdp=fsdp)
    if opts:
        rules.opts.update(opts)
    model_size = mesh.shape["model"]
    if cfg.num_kv_heads and cfg.num_kv_heads % model_size != 0:
        # KV heads can't split the model axis -> shard the cache's seq dim
        rules.rules["kv_seq"] = "model"
    if train:
        # Megatron-style sequence parallelism: the residual stream (and thus
        # the remat-saved per-layer activations) shards its seq dim over
        # "model"; GSPMD inserts the all-gather/reduce-scatter pairs around
        # attention. Cuts saved-activation memory by the model-axis size.
        rules.rules["seq"] = "model"
    return rules


def clamp_microbatches(mb: int, global_batch: int, rules: ShardingRules) -> int:
    """Largest mb <= requested s.t. each microbatch still shards the batch
    axes evenly (a microbatch smaller than the batch sharding under-shards)."""
    shards = rules.axis_size(rules.mesh_axes_for("batch", global_batch))
    mb = max(1, min(mb, global_batch // max(shards, 1)))
    while mb > 1 and (global_batch % mb or (global_batch // mb) % shards):
        mb -= 1
    return mb


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def batch_specs(cfg, shape: ShapeSpec, rules: ShardingRules):
    """ShapeDtypeStructs + PartitionSpecs for the model inputs of one cell."""
    mesh = rules.mesh
    b_ax = rules.mesh_axes_for("batch", shape.global_batch)
    gb, seq = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        if cfg.family == "encdec":
            dec = seq - cfg.encoder_seq
            spec = {
                "frames": jax.ShapeDtypeStruct((gb, cfg.encoder_seq, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((gb, dec), jnp.int32),
            }
            pspec = {
                "frames": PartitionSpec(b_ax, None, None),
                "tokens": PartitionSpec(b_ax, None),
            }
        else:
            spec = {"tokens": jax.ShapeDtypeStruct((gb, seq), jnp.int32)}
            pspec = {"tokens": PartitionSpec(b_ax, None)}
        return spec, pspec
    if shape.kind == "prefill":
        if cfg.family == "encdec":
            dec = seq - cfg.encoder_seq
            spec = {
                "frames": jax.ShapeDtypeStruct((gb, cfg.encoder_seq, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((gb, dec), jnp.int32),
            }
            pspec = {
                "frames": PartitionSpec(b_ax, None, None),
                "tokens": PartitionSpec(b_ax, None),
            }
        else:
            spec = {"tokens": jax.ShapeDtypeStruct((gb, seq), jnp.int32)}
            pspec = {"tokens": PartitionSpec(b_ax, None)}
        return spec, pspec
    # decode: one token per sequence
    spec = {"tokens": jax.ShapeDtypeStruct((gb,), jnp.int32)}
    pspec = {"tokens": PartitionSpec(b_ax)}
    return spec, pspec


def input_specs(arch: str, shape_name: str = "train_4k", multi_pod: bool = False):
    """Public helper: ShapeDtypeStruct stand-ins for every model input."""
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(cfg, mesh, fsdp=False)
    spec, _ = batch_specs(cfg, SHAPES[shape_name], rules)
    return spec


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    overrides: Optional[Dict[str, Any]] = None,
):
    """Lower + compile one cell; returns (compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return None, {"skipped": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 1
    for a in mesh.axis_names:
        chips *= mesh.shape[a]
    overrides = overrides or {}

    if shape.kind == "train":
        policy = dict(TRAIN_POLICY.get(arch, DEFAULT_TRAIN))
        policy.update(overrides)
        rules = rules_for(cfg, mesh, fsdp=policy["fsdp"], train=True,
                          opts=overrides.get("opts"))
        policy["microbatches"] = clamp_microbatches(
            int(policy["microbatches"]), shape.global_batch, rules
        )
        compute = overrides["compute_dtype"] if "compute_dtype" in overrides else "bfloat16"
        model = build_model(cfg, backend=overrides.get("backend", "flash"),
                            compute_dtype=compute)
        defs = model.param_defs()
        p_specs = specs_of(defs, dtype=policy["param_dtype"])
        p_pspecs = param_pspecs(defs, rules)
        opt_cfg = OptimizerConfig(
            m_dtype=policy["m_dtype"], v_dtype=policy["v_dtype"]
        )
        o_defs = opt_state_defs(defs, opt_cfg)
        o_specs = specs_of(o_defs)
        o_pspecs = param_pspecs(o_defs, rules)
        b_specs, b_pspecs = batch_specs(cfg, shape, rules)
        opts = overrides.get("opts") or {}
        step = make_train_step(
            model, opt_cfg,
            microbatches=policy["microbatches"],
            remat=policy["remat"],
            accum_dtype=policy["accum_dtype"],
            grad_shardings=named(mesh, p_pspecs) if opts.get("rs_grads") else None,
            cast_params_once=bool(opts.get("cast_params_once")),
        )
        with set_activation_rules(rules):
            jitted = jax.jit(
                step,
                in_shardings=(
                    named(mesh, p_pspecs), named(mesh, o_pspecs), named(mesh, b_pspecs)
                ),
                # matching out shardings -> donated params/opt alias in place
                out_shardings=(named(mesh, p_pspecs), named(mesh, o_pspecs), None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(p_specs, o_specs, b_specs)
            compiled = lowered.compile()
        meta = {"kind": "train", "policy": policy, "chips": chips,
                "mesh": mesh_name(mesh)}
        return compiled, meta

    # serving shapes
    policy = dict(SERVE_POLICY.get(arch, {"fsdp": False}))
    policy.update(overrides)
    rules = rules_for(cfg, mesh, fsdp=policy.get("fsdp", False),
                      opts=overrides.get("opts"))
    compute = overrides["compute_dtype"] if "compute_dtype" in overrides else "bfloat16"
    model = build_model(cfg, backend=overrides.get("backend", "flash"),
                        compute_dtype=compute)
    defs = model.param_defs()
    p_specs = specs_of(defs, dtype=overrides.get("param_dtype", SERVE_DTYPE))
    p_pspecs = param_pspecs(defs, rules)
    cache_dtype = overrides.get("cache_dtype", SERVE_DTYPE)
    cache_defs = model.cache_defs(shape.global_batch, shape.seq_len, dtype=cache_dtype)
    c_specs = specs_of(cache_defs)
    c_pspecs = param_pspecs(cache_defs, rules)
    b_specs, b_pspecs = batch_specs(cfg, shape, rules)

    if shape.kind == "prefill":
        fn = lambda p, b, c: model.prefill(p, b, c)
    else:
        fn = lambda p, t, c: model.decode(p, t["tokens"], c)
    args = (p_specs, b_specs, c_specs)
    shardings = (named(mesh, p_pspecs), named(mesh, b_pspecs), named(mesh, c_pspecs))
    # matching output shardings let XLA alias the donated cache in place
    out_shardings = (None, named(mesh, c_pspecs))
    with set_activation_rules(rules):
        jitted = jax.jit(
            fn, in_shardings=shardings, out_shardings=out_shardings,
            donate_argnums=(2,),
        )
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    meta = {"kind": shape.kind, "policy": policy, "chips": chips,
            "mesh": mesh_name(mesh)}
    return compiled, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: Optional[Dict[str, Any]] = None,
             note: str = "") -> Dict[str, Any]:
    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    compiled, meta = lower_cell(arch, shape_name, multi_pod, overrides)
    if compiled is None:
        return {
            "arch": arch, "shape": shape_name,
            "mesh": mesh_name(make_production_mesh(multi_pod=multi_pod)),
            "status": "skip", "reason": meta["skipped"],
        }
    mem = compiled.memory_analysis()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mf = model_flops_for(
        cfg.param_count(active_only=True), tokens,
        "train" if shape.kind == "train" else "serve",
    )
    report = roofline_from_compiled(
        compiled,
        arch=arch, shape=shape_name, mesh_name=meta["mesh"], chips=meta["chips"],
        model_flops=mf, note=note,
    )
    peak = int(
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    out = {
        "arch": arch, "shape": shape_name, "mesh": meta["mesh"],
        "status": "ok", "kind": meta["kind"], "policy": {
            k: str(v) for k, v in meta["policy"].items()
        },
        "compile_s": time.time() - t0,
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_per_device_bytes": peak,
        },
        "roofline": report.to_dict(),
    }
    # CPU-backend artifact correction: the CPU compiler normalizes bf16 dots
    # to f32, materializing f32 copies of bf16 tensors (caches, saved
    # activations) that do NOT exist on TPU. For over-budget cells, re-lower
    # everything in f32 (artifact-free: single dtype) — half its temp is the
    # TPU-bf16 estimate; arguments (params/opt/cache) are taken at their real
    # policy dtypes from the raw run.
    if peak > 16 * 2**30:
        try:
            f32_over = dict(overrides or {})
            f32_over.update(param_dtype="float32", cache_dtype="float32",
                            accum_dtype="float32", compute_dtype=None)
            compiled2, _ = lower_cell(arch, shape_name, multi_pod, f32_over)
            m2 = compiled2.memory_analysis()
            est = int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + m2.temp_size_in_bytes / 2
            )
            out["memory"]["tpu_estimate_bytes"] = est
        except Exception as e:  # noqa: BLE001 - estimate is best-effort
            out["memory"]["tpu_estimate_error"] = str(e)
    return out


# ---------------------------------------------------------------------------
# CLI driver
# ---------------------------------------------------------------------------
def cell_path(out_dir: str, arch: str, shape: str, mesh_kind: str) -> str:
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true", help="sweep all cells via subprocesses")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--note", default="")
    ap.add_argument("--override", default="", help="JSON policy overrides")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        failures = []
        for arch in list_archs():
            for shape in SHAPES:
                for mesh_kind in ("pod", "multipod"):
                    path = cell_path(args.out, arch, shape, mesh_kind)
                    if os.path.exists(path) and not args.force:
                        continue
                    cmd = [
                        sys.executable, "-m", "repro.launch.dryrun",
                        "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                        "--out", args.out,
                    ]
                    if args.force:
                        cmd.append("--force")
                    print(f"[dryrun] {arch} × {shape} × {mesh_kind} ...", flush=True)
                    rc = subprocess.run(cmd).returncode
                    if rc != 0:
                        failures.append((arch, shape, mesh_kind))
                        print(f"[dryrun]   FAILED rc={rc}", flush=True)
        print(f"[dryrun] sweep done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    overrides = json.loads(args.override) if args.override else None
    path = cell_path(args.out, args.arch, args.shape, args.mesh)
    if os.path.exists(path) and not args.force:
        print(f"[dryrun] cached: {path}")
        return 0
    try:
        result = run_cell(
            args.arch, args.shape, args.mesh == "multipod",
            overrides=overrides, note=args.note,
        )
    except Exception:
        result = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "status": "error", "traceback": traceback.format_exc(),
        }
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
        print(result["traceback"], file=sys.stderr)
        return 1
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    if result["status"] == "ok":
        r = result["roofline"]
        print(
            f"[dryrun] {args.arch} × {args.shape} × {result['mesh']}: "
            f"peak/dev={result['memory']['peak_per_device_bytes']/2**30:.2f} GiB "
            f"terms(s): compute={r['compute_term_s']:.4f} "
            f"memory={r['memory_term_s']:.4f} collective={r['collective_term_s']:.4f} "
            f"dominant={r['dominant']} frac={r['roofline_fraction']:.3f}"
        )
    else:
        print(f"[dryrun] {args.arch} × {args.shape}: {result['status']} ({result.get('reason','')})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

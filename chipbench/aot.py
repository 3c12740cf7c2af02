"""Compile a cell's decode and packed-prefill steps for a described TPU v5e
and print their ``memory_analysis()``; nothing runs.

    JAX_PLATFORMS=cpu python3 -m chipbench.aot --workload <cell> [--benchmark <file>]

The steps are the program's ``decode_paged`` (with the on-device argmax
the engine fuses into it) and ``prefill_packed``, at the cell's slots,
page pool, packed budget and deepest context bucket, with bfloat16
weights and pages, and the benchmark's call that makes the weights
(``weights.params_fn``).  A cell over N chips compiles over the first
N devices of a described ``v5e:2x2`` under the program's serving rules,
with the weights and the page pool in the shardings those rules give
them; every byte count is then one chip's.  ``--benchmark`` names a
``BENCHMARK.json`` other than the checkout's, for a cell not yet in it.
On a machine without the chip JAX reports the CPU as its backend, so the
kernels would take their interpret path: the compile runs with
``jax.default_backend`` reporting ``tpu``, which is what the chip's
process sees.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
from pathlib import Path
from unittest import mock

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chipbench"

from chipbench import files, traffic  # noqa: E402
from chipbench import weights as W  # noqa: E402

sys.path.insert(0, str(files.CHECKOUT / "src"))


def analysis(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          - out["alias_size_in_bytes"]
                          + out["temp_size_in_bytes"])
    return out


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def compile_into(out: dict, name: str, lower) -> None:
    """``out[name]``: the memory analysis of ``lower().compile()``, the
    collectives in it and whether a Mosaic kernel is there; or the
    compiler's refusal, which is a finding too (a kernel that does not
    fit the chip's fast memory is refused here as on the chip)."""
    import jax

    try:
        compiled = lower().compile()
    except jax.errors.JaxRuntimeError as e:
        out[name] = {"error": str(e).splitlines()[0][:600]}
        return
    text = compiled.as_text()
    out[name] = analysis(compiled)
    out[name]["has_kernel"] = "tpu_custom_call" in text
    out[name]["collectives"] = {c: text.count(f" {c}(") for c in COLLECTIVES
                                if f" {c}(" in text}


def chip_bytes(tree) -> int:
    """Bytes of one chip's share of a tree of sharded shapes."""
    import jax

    return sum(math.prod(s.sharding.shard_shape(s.shape)) * s.dtype.itemsize
               for s in jax.tree.leaves(tree))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--benchmark", type=Path, default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from chipbench.run import program_config, tp_layout
    from repro.models import build_model
    from repro.serve.engine import bucket_pow2
    from repro.sharding.specs import set_activation_rules

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cell = files.load_cell(args.workload, benchmark=args.benchmark)
    d = W.dims(cell.config)
    cfg = program_config(cell, d)
    slots = int(cell.serve["slots"])
    page = int(cell.serve["serve"]["page_size"])
    budget = int(cell.serve["serve"]["prefill_budget"])
    max_seq = traffic.max_len(cell.traffic)
    max_pages = -(-max_seq // page)
    num_pages = slots * max_pages + 1
    dtype = cell.config["dtype"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # one chip is the tp = 1 mesh: a one-device sharding compiles the same
    # program as the single-device placement the engine uses there
    mesh = Mesh(np.array(topo.devices[:cell.chips]).reshape(1, cell.chips),
                ("data", "model"))
    replicated = NamedSharding(mesh, PartitionSpec())

    def spec(shape, dt, sharding=replicated):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)

    def placed(shapes, shardings):
        return jax.tree.map(lambda s, sh: spec(s.shape, s.dtype, sh),
                            shapes, shardings)

    out = {"workload": cell.name, "tp": cell.chips, "slots": slots,
           "num_pages": num_pages, "max_pages": max_pages}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = build_model(cfg, backend="pallas")
        pool = jax.eval_shape(lambda: model.init_paged_cache(num_pages, page,
                                                             dtype=dtype))
        rules = shardings = None
        weight_shardings = jax.tree.map(lambda _: replicated,
                                        model.param_specs(dtype))
        pool_shardings = jax.tree.map(lambda _: replicated, pool)
        if cell.chips > 1:
            rules, shardings = tp_layout(model, mesh)
            weight_shardings = shardings
            pool_shardings = jax.tree.map(
                lambda p: NamedSharding(mesh, p),
                model.paged_cache_pspecs(rules, num_pages, page, dtype=dtype),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
        params = placed(model.param_specs(dtype), weight_shardings)
        cache = placed(pool, pool_shardings)
        out["weight_bytes"] = chip_bytes(params)
        out["pool_bytes"] = chip_bytes(cache)
        # every program is traced under the rules, as the engine traces them
        with set_activation_rules(rules):
            key = jax.ShapeDtypeStruct((), W.root_key(0).dtype,
                                       sharding=replicated)
            compile_into(out, "weights", lambda: W.params_fn(
                d, jnp.dtype(dtype), shardings).lower(key))
            bound = bucket_pow2(max_pages, cap=max_pages)

            def decode(params, nxt, cache, table, pos, mask):
                logits, cache = model.decode_paged(params, nxt, cache, table,
                                                   pos, pages_bound=bound)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (tok, jnp.where(mask, tok, nxt),
                        jnp.where(mask, pos + 1, pos), cache)

            i32 = jnp.int32
            compile_into(out, "decode", lambda: jax.jit(
                decode, donate_argnums=(1, 2, 4)).lower(
                params, spec((slots,), i32), cache,
                spec((slots, max_pages), i32), spec((slots,), i32),
                spec((slots,), jnp.bool_)))
            top = -(-int(cell.traffic["prompt"]["max"]) // page) - 1
            ctx = bucket_pow2(max(top, 1), cap=max_pages)
            batch = {
                "tokens": spec((1, budget), i32),
                "tok_pos": spec((budget,), i32),
                "dst_page": spec((budget,), i32), "dst_off": spec((budget,), i32),
                "cu_seqlens": spec((slots + 1,), i32),
                "chunk_lens": spec((slots,), i32),
                "chunk_pos0": spec((slots,), i32),
                "page_tables": spec((slots, max_pages), i32),
                "last_idx": spec((slots,), i32),
            }
            out["prefill_pages_bound"] = ctx
            compile_into(out, "prefill", lambda: jax.jit(
                lambda p, b, c: model.prefill_packed(p, b, c, pages_bound=ctx),
                donate_argnums=(2,)).lower(params, batch, cache))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

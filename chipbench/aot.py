"""Compile a cell's decode and packed-prefill steps for a described TPU v5e
and print their ``memory_analysis()``; nothing runs.

    JAX_PLATFORMS=cpu python3 -m chipbench.aot --workload <cell>

The steps are the program's ``decode_paged`` (with the on-device argmax
the engine fuses into it) and ``prefill_packed``, at the cell's slots,
page pool, packed budget and deepest context bucket, with bfloat16
weights and pages.  On a machine without the chip JAX reports the CPU as
its backend, so the kernels would take their interpret path: the compile
runs with ``jax.default_backend`` reporting ``tpu``, which is what the
chip's process sees.
"""
from __future__ import annotations

import argparse
import json
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from chipbench.run import program_config
    from repro.models import build_model
    from repro.serve.engine import bucket_pow2

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    cell = files.load_cell(args.workload)
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
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one)

    out = {"workload": cell.name, "slots": slots, "num_pages": num_pages,
           "max_pages": max_pages}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        model = build_model(cfg, backend="pallas")
        params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                              model.param_specs(dtype))
        cache = jax.tree.map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init_paged_cache(num_pages, page, dtype=dtype)))
        out["weight_bytes"] = sum(int(s.size) * s.dtype.itemsize
                                  for s in jax.tree.leaves(params))
        out["pool_bytes"] = sum(int(s.size) * s.dtype.itemsize
                                for s in jax.tree.leaves(cache))
        bound = bucket_pow2(max_pages, cap=max_pages)

        def decode(params, nxt, cache, table, pos, mask):
            logits, cache = model.decode_paged(params, nxt, cache, table, pos,
                                               pages_bound=bound)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return tok, jnp.where(mask, tok, nxt), jnp.where(mask, pos + 1, pos), cache

        i32 = jnp.int32
        dec = jax.jit(decode, donate_argnums=(1, 2, 4)).lower(
            params, spec((slots,), i32), cache, spec((slots, max_pages), i32),
            spec((slots,), i32), spec((slots,), jnp.bool_)).compile()
        out["decode"] = analysis(dec)
        out["decode_has_kernel"] = "tpu_custom_call" in dec.as_text()
        ctx = bucket_pow2(max(-(-int(cell.traffic["prompt"]["max"]) // page) - 1, 1),
                          cap=max_pages)
        batch = {
            "tokens": spec((1, budget), i32), "tok_pos": spec((budget,), i32),
            "dst_page": spec((budget,), i32), "dst_off": spec((budget,), i32),
            "cu_seqlens": spec((slots + 1,), i32),
            "chunk_lens": spec((slots,), i32),
            "chunk_pos0": spec((slots,), i32),
            "page_tables": spec((slots, max_pages), i32),
            "last_idx": spec((slots,), i32),
        }
        pre = jax.jit(lambda p, b, c: model.prefill_packed(p, b, c, pages_bound=ctx),
                      donate_argnums=(2,)).lower(params, batch, cache).compile()
        out["prefill"] = analysis(pre)
        out["prefill_pages_bound"] = ctx
        out["prefill_has_kernel"] = "tpu_custom_call" in pre.as_text()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

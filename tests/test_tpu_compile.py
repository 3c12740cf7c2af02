"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode (every other kernel test) accepts block shapes that the
chip's compiler refuses; this file asks that compiler.  Each kernel is
lowered with ``interpret=False`` at glm4-9b's published attention widths
(32 query heads, 2 kv heads, head_dim 128, 16-token pages, bf16) against a
``v5e:2x2`` topology that is described, not attached, and the compiled
program must contain the Mosaic kernel (``tpu_custom_call``).  Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.spec_verify import spec_verify
from repro.kernels.varlen_prefill import varlen_prefill

# glm4-9b attention widths (configs/glm4_9b.py) and a serving-sized pool
H, KVH, D, D_MODEL = 32, 2, 128, 4096
PAGE, NUM_PAGES, MAX_PAGES, BATCH = 16, 1024, 64, 8
SPEC_W = 4                    # spec_k = 3 drafts + the pending token
PACKED_T, CHUNKS = 256, 8     # packed-prefill buffer and chunk rows
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_hlo(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _named_kernel(hlo: str, name: str) -> bool:
    """The Mosaic kernel's instruction carries the kernel's own name, so a
    trace names it whatever shape its result takes."""
    return re.search(
        rf'^\s*(ROOT )?%{name}(\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        hlo, re.M,
    ) is not None


def _pool_args(one_chip, pool_dtype):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pools = [spec((NUM_PAGES, PAGE, KVH, D), pool_dtype)] * 2
    scales = (
        [spec((NUM_PAGES, PAGE, KVH), jnp.float32)] * 2
        if pool_dtype == jnp.int8 else []
    )
    return spec, pools, scales


def _with_scales(kernel, n_lead, **kw):
    """Call ``kernel`` on its ``n_lead`` positional operands, passing any
    trailing operands as the quantized pool's k/v scale pools."""
    def fn(*args):
        lead, scales = args[:n_lead], args[n_lead:]
        if scales:
            kw.update(k_scales=scales[0], v_scales=scales[1])
        return kernel(*lead, interpret=False, **kw)
    return fn


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_paged_attention_compiles_for_v5e(one_chip, pool):
    spec, pools, scales = _pool_args(one_chip, POOL_DTYPES[pool])
    hlo = _compile_hlo(
        _with_scales(paged_attention, 5, pages_bound=MAX_PAGES),
        spec((BATCH, 1, H, D), jnp.bfloat16), *pools,
        spec((BATCH, MAX_PAGES), jnp.int32), spec((BATCH,), jnp.int32),
        *scales,
    )
    assert "tpu_custom_call" in hlo
    assert _named_kernel(hlo, "paged_attention")


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_spec_verify_compiles_for_v5e(one_chip, pool):
    spec, pools, scales = _pool_args(one_chip, POOL_DTYPES[pool])
    hlo = _compile_hlo(
        _with_scales(spec_verify, 6, pages_bound=MAX_PAGES),
        spec((BATCH, SPEC_W, H, D), jnp.bfloat16), *pools,
        spec((BATCH, MAX_PAGES), jnp.int32), spec((BATCH,), jnp.int32),
        spec((BATCH,), jnp.int32), *scales,
    )
    assert "tpu_custom_call" in hlo


def _compile_varlen(one_chip, pool, pages_bound, t=PACKED_T, chunks=CHUNKS,
                    max_pages=MAX_PAGES) -> str:
    spec, pools, scales = _pool_args(one_chip, POOL_DTYPES[pool])
    return _compile_hlo(
        _with_scales(varlen_prefill, 9, pages_bound=pages_bound),
        spec((t, H, D), jnp.bfloat16),
        spec((t, KVH, D), jnp.bfloat16),
        spec((t, KVH, D), jnp.bfloat16),
        *pools,
        spec((chunks + 1,), jnp.int32), spec((chunks,), jnp.int32),
        spec((chunks,), jnp.int32), spec((chunks, max_pages), jnp.int32),
        *scales,
    )


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_varlen_prefill_compiles_for_v5e(one_chip, pool):
    hlo = _compile_varlen(one_chip, pool, MAX_PAGES)
    assert "tpu_custom_call" in hlo
    assert _named_kernel(hlo, "varlen_prefill")


@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_varlen_prefill_one_context_page_compiles_for_v5e(one_chip, pool):
    """The smallest context bound (a refill with no context): the per-block
    item starts and the dynamic grid bound still lower, under the kernel's
    name."""
    hlo = _compile_varlen(one_chip, pool, 1)
    assert "tpu_custom_call" in hlo
    assert _named_kernel(hlo, "varlen_prefill")


@pytest.mark.parametrize("t", [2048, 4096])
@pytest.mark.parametrize("pool", sorted(POOL_DTYPES))
def test_varlen_prefill_long_context_compiles_for_v5e(one_chip, pool, t):
    """Packed buffers of 2,048 and 4,096 tokens over 32K-token contexts
    (2,048 pages a chunk, 16 chunks): what the kernel keeps in scalar
    memory grows with the q blocks, not with the context bound, so it
    still fits."""
    hlo = _compile_varlen(one_chip, pool, 2048, t=t, chunks=16, max_pages=2048)
    assert "tpu_custom_call" in hlo
    assert _named_kernel(hlo, "varlen_prefill")


def test_flash_attention_compiles_for_v5e(one_chip):
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    hlo = _compile_hlo(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        spec((2, 512, H, D)), spec((2, 512, KVH, D)), spec((2, 512, KVH, D)),
    )
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles_for_v5e(one_chip):
    spec = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip
    )
    hlo = _compile_hlo(
        lambda q, k, v, n: decode_attention(
            q, k, v, n, kv_bound=1024, interpret=False
        ),
        spec((BATCH, 1, H, D)), spec((BATCH, 2048, KVH, D)),
        spec((BATCH, 2048, KVH, D)), spec((BATCH,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles_for_v5e(one_chip):
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    hlo = _compile_hlo(
        lambda x, w: rmsnorm(x, w, interpret=False),
        spec((PACKED_T, D_MODEL)), spec((D_MODEL,)),
    )
    assert "tpu_custom_call" in hlo

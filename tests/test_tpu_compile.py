"""The attention kernels of the served path compile for a TPU v5e.

Interpret mode (every other kernel test) checks results, not what Mosaic
accepts: block shapes, tiling-aligned DMA slices, VMEM use. These tests
compile each kernel at qwen3-8b widths (chunk 2048, 32 query / 8 kv heads,
head_dim 128, bf16) for a described ``v5e:2x2`` chip — no chip attached,
nothing runs. The topology is described inside a module fixture (never at
import: only one process may load the TPU library at a time).
"""
import os

import pytest

C, H, KVH, D = 2048, 32, 8, 128
SLOTS = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    import jax
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    # compiles for a described device cannot be read back from a persistent
    # cache; keep them out of it
    jax.config.update("jax_enable_compilation_cache", False)

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("variant", ["plain", "return_state", "int8"])
def test_chunk_attention_compiles_for_v5e(spec, variant):
    import jax.numpy as jnp
    from repro.kernels import chunk_attn as ca
    kv_dt = jnp.int8 if variant == "int8" else jnp.bfloat16
    q = spec((1, H, C, D), jnp.bfloat16)
    kv = spec((1, KVH, C, D), kv_dt)
    args = [q, kv, kv]
    kw = {"return_state": variant == "return_state"}
    if variant == "int8":
        sc = spec((1, KVH, C, 1), jnp.float32)
        args += [sc, sc]

    def fn(q, k, v, ks=None, vs=None):
        return ca.chunk_attention_pallas(q, k, v, k_scale=ks, v_scale=vs,
                                         **kw)
    _compile(fn, *args)


def test_pool_attention_compiles_for_v5e(spec):
    import jax.numpy as jnp
    from repro.kernels import chunk_attn as ca
    q = spec((1, H, C, D), jnp.bfloat16)
    kv = spec((SLOTS, 1, KVH, C, D), jnp.bfloat16)
    valid = spec((SLOTS,), jnp.int32)
    _compile(ca.pool_attention_pallas, q, kv, kv, valid)


@pytest.mark.parametrize("kv_dtype,page_tokens", [("bfloat16", C),
                                                  ("int8", 256)])
def test_pool_attention_paged_compiles_for_v5e(spec, kv_dtype, page_tokens):
    import jax.numpy as jnp
    from repro.kernels import chunk_attn as ca
    ppc = C // page_tokens
    pages = (SLOTS + 1) * ppc
    q = spec((1, H, C, D), jnp.bfloat16)
    kv = spec((pages, 1, KVH, page_tokens, D), jnp.dtype(kv_dtype))
    handles = spec((SLOTS * ppc,), jnp.int32)
    valid = spec((SLOTS,), jnp.int32)
    args = [q, kv, kv, handles, valid]
    if kv_dtype == "int8":
        sc = spec((pages, KVH), jnp.float32)
        args += [sc, sc]

    def fn(q, k, v, handles, valid, ks=None, vs=None):
        return ca.pool_attention_paged_pallas(q, k, v, handles, valid,
                                              ppc=ppc, k_scale=ks,
                                              v_scale=vs)
    _compile(fn, *args)

"""The attention kernels of the served path compile for a TPU v5e.

Interpret mode (every other kernel test) checks results, not what Mosaic
accepts: block shapes, tiling-aligned DMA slices, VMEM use. These tests
compile each kernel at qwen3-8b widths (chunk 2048, 32 query / 8 kv heads,
head_dim 128, bf16; the self-block and paged pool kernels also at
mistral-large widths and a 256-token chunk) for a described ``v5e:2x2``
chip — no chip attached, nothing runs; one more compiles the four-stage pipeline at reduced widths
and checks the names a profiler trace will show. The topology is described
inside a module fixture (never at import: only one process may load the
TPU library at a time).
"""
import os
import re

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


@pytest.mark.parametrize("variant,widths,offset", [
    ("plain", (H, KVH, C), 0), ("return_state", (H, KVH, C), 0),
    ("int8", (H, KVH, C), 0), ("return_state", (96, 8, C), 0),
    ("return_state", (H, KVH, 256), 0), ("int8", (H, KVH, C), C)],
    ids=["plain", "return_state", "int8", "mistral-return_state",
         "bucket256-return_state", "full-visibility-int8"])
def test_chunk_attention_compiles_for_v5e(spec, variant, widths, offset):
    """The self-block kernel at the tiles ``self_tiles`` picks for the
    served shapes — qwen3-8b (g 4), mistral-large (96/8 heads, g 12), the
    smallest mixed-open bucket (256-token chunks) and MBKR's fetched chunk
    (every key visible, int8 payloads) — compiles with the whole kv head
    resident, within the default scoped VMEM: the kernel asks for no
    limit of its own."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import chunk_attn as ca
    h, kvh, c = widths
    kv_dt = jnp.int8 if variant == "int8" else jnp.bfloat16
    heads, block_q, block_k = ca.self_tiles(h // kvh, c, c, D, offset)
    rows, kv_bytes = heads * block_q, jnp.dtype(kv_dt).itemsize
    quantized = variant == "int8"
    assert ca.self_span(c, rows, block_k, D, kv_bytes, 2, quantized) == c
    assert ca.self_vmem_bytes(rows, block_k, c, D, kv_bytes, 2,
                              quantized) <= ca.PAGED_VMEM_BYTES
    q = spec((1, h, c, D), jnp.bfloat16)
    kv = spec((1, kvh, c, D), kv_dt)
    args = [q, kv, kv]
    kw = {"return_state": variant == "return_state",
          "causal_offset": offset}
    if quantized:
        sc = spec((1, kvh, c, 1), jnp.float32)
        args += [sc, sc]

    def fn(q, k, v, ks=None, vs=None):
        return ca.chunk_attention_pallas(q, k, v, k_scale=ks, v_scale=vs,
                                         **kw)
    lowered = jax.jit(fn).lower(*args).as_text()
    # a kernel that sets vmem_limit_bytes carries a scoped memory config
    assert lowered.count("tpu_custom_call") == 1
    assert "scoped_memory_configs" not in lowered
    _compile(fn, *args)


def test_pool_attention_compiles_for_v5e(spec):
    import jax.numpy as jnp
    from repro.kernels import chunk_attn as ca
    q = spec((1, H, C, D), jnp.bfloat16)
    kv = spec((SLOTS, 1, KVH, C, D), jnp.bfloat16)
    valid = spec((SLOTS,), jnp.int32)
    _compile(ca.pool_attention_pallas, q, kv, kv, valid)


@pytest.mark.parametrize("kv_dtype,page_tokens,widths", [
    ("bfloat16", C, (H, KVH, C)), ("int8", 256, (H, KVH, C)),
    ("bfloat16", C, (96, 8, C)), ("bfloat16", 256, (H, KVH, 256))],
    ids=["bfloat16-2048", "int8-256", "mistral-bfloat16-2048",
         "bucket256-bfloat16-256"])
def test_pool_attention_paged_compiles_for_v5e(spec, kv_dtype, page_tokens,
                                               widths):
    """The paged pool kernel at the tiles ``paged_tiles`` picks for the
    served shapes — qwen3-8b (g 4), mistral-large (96/8 heads, g 12) and
    the smallest mixed-open bucket (256-token chunks of one page) — compiles
    within the default scoped VMEM."""
    import jax.numpy as jnp
    from repro.kernels import chunk_attn as ca
    h, kvh, c = widths
    ppc = c // page_tokens
    pages = (SLOTS + 1) * ppc
    kv_dt = jnp.dtype(kv_dtype)
    heads, block_q, block_k = ca.paged_tiles(h // kvh, c, page_tokens)
    assert ca.paged_vmem_bytes(heads * block_q, block_k, page_tokens, D,
                               kv_dt.itemsize) <= ca.PAGED_VMEM_BYTES
    q = spec((1, h, c, D), jnp.bfloat16)
    kv = spec((pages, 1, kvh, page_tokens, D), kv_dt)
    handles = spec((SLOTS * ppc,), jnp.int32)
    valid = spec((SLOTS,), jnp.int32)
    args = [q, kv, kv, handles, valid]
    if kv_dtype == "int8":
        sc = spec((pages, kvh), jnp.float32)
        args += [sc, sc]

    def fn(q, k, v, handles, valid, ks=None, vs=None):
        return ca.pool_attention_paged_pallas(q, k, v, handles, valid,
                                              ppc=ppc, k_scale=ks,
                                              v_scale=vs)
    _compile(fn, *args)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_ssd_compiles_for_v5e(spec, impl, monkeypatch):
    """Both SSD paths of ``models.ssm.SSD_IMPLS`` (the TPU default,
    ``ssd_impl("auto", "tpu")``, is one of them) at granite-4.0-h-small's
    shapes: a 2048-token chunk, 128 heads of 64, d_state 128, one group,
    256-position blocks, the state carried in."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import ssm
    # the kernel wrapper asks the process's backend (the CPU here)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    assert ssm.ssd_impl("auto", "tpu") in ssm.SSD_IMPLS
    h, p, n = 128, 64, 128
    args = [spec((1, C, h, p), jnp.bfloat16), spec((1, C, h), jnp.float32),
            spec((h,), jnp.float32), spec((1, C, 1, n), jnp.bfloat16),
            spec((1, C, 1, n), jnp.bfloat16), spec((h,), jnp.float32),
            spec((1, h, p, n), jnp.float32)]

    def fn(x, dt, a, b, c, d, s0):
        return ssm.SSD_IMPLS[impl](x, dt, a, b, c, d, chunk=256,
                                   init_state=s0)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == (impl == "pallas")


def test_held_moe_compiles_for_v5e(spec):
    """The dropless held-expert layer at granite-4.0-h-small's widths (a
    2048-token chunk, d 4096, the router over 72 experts, top 10, 36 held
    experts of width 768), reading layer 3's experts from a stack of ten:
    both grouped matmuls lower to the TPU's own ragged dot, not to a dense
    product over every expert, and no layer's expert weights are copied
    (the temporaries stay under one layer's experts)."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    params = {"router": spec((4096, 72), jnp.bfloat16),
              "e_wgu": spec((10, 36, 4096, 2 * 768), jnp.bfloat16),
              "e_wd": spec((10, 36, 768, 4096), jnp.bfloat16)}
    x = spec((1, C, 4096), jnp.bfloat16)
    compiled = jax.jit(lambda p, x: L.held_moe(p, x, top_k=10, layer=3)
                       ).lower(params, x).compile()
    text = compiled.as_text()
    grouped = [n for n in re.findall(r"%([\w.\-]+) = ", text)
               if n.startswith("ragged-dot") and "metadata" not in n]
    assert len(grouped) == 2, grouped
    # the one other product is the router's
    assert text.count(" convolution(") == 1 and " dot(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 36 * 3 * 4096 * 768 * 2


def test_pipeline_names_for_v5e(topo):
    """The served four-stage pipeline (reduced widths, Pallas + paged pool,
    MBKR qship as served) compiled for four described chips: the kernels'
    custom calls keep their fixed names, and the device scopes name the
    ring shift, the pair exchanges and the kernels' layers."""
    import re
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.configs.base import RunConfig, get_config, replace
    from repro.core import pipeline as pp
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.models.topology import Topology
    from repro.obs.trace import hlo_op_scopes
    cfg = replace(get_config("qwen3-8b"), num_layers=8, d_model=512,
                  num_heads=4, num_kv_heads=2, d_ff=1024, vocab_size=1024,
                  head_dim=D, dtype="bfloat16")
    seq, m = 4096, 16
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    tp = Topology(mesh=mesh)
    plan = pp.build_plan(cfg, 4, seq, RunConfig(
        num_chunks=m, num_stages=4, attn_backend="pallas",
        pool_backend="paged"))
    shapes = jax.eval_shape(lambda: pp.stage_params(
        cfg, build_model(cfg).init(jax.random.key(0)), plan))
    staged = jax.tree.map(
        lambda a, p: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shapes, pp.stage_param_specs(cfg, plan, tp),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    on_tpu = ops._on_tpu
    ops._on_tpu = lambda: True      # the jitted step asks the CPU backend
    try:
        text = jax.jit(lambda s, t: pp.prefill_pipeline(
            cfg, s, t, plan, tp)).lower(staged, toks).compile().as_text()
    finally:
        ops._on_tpu = on_tpu
    kernels = {re.sub(r"\.\d+$", "", n) for n in re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)}
    assert kernels == {"chunk_attention", "pool_attention_paged"}, kernels
    scopes = hlo_op_scopes(text)
    coll = {n: s for n, s in scopes.items()
            if n.startswith("collective-permute-start")}
    assert sorted(set(coll.values())) == ["transport.pair_shift",
                                          "transport.ring_shift"], coll
    assert sum(s == "transport.ring_shift" for s in coll.values()) == 1
    for name, scope in scopes.items():
        if name.startswith("chunk_attention"):
            assert scope == "layer.attn_self"
        elif name.startswith("pool_attention_paged"):
            assert scope == "layer.attn_pool"


# --------------------------------------------- the served pp4 program vs a
# chip trace of it

_TYPE = r"\b[a-z]+[0-9]*\[[0-9,]*\](?:\{[^}]*\})?"
_ASYNC = ("async-start", "async-update", "async-done")


def _instruction(body):
    """(result type, opcode, operands, attributes) of an HLO instruction's
    text after ``name = ``, without its metadata. A trace writes each
    operand with its type and an async op unsugared; both are undone."""
    import re
    body = re.split(r", (?:metadata|backend_config|frontend_attributes)=",
                    body)[0]
    result, op, rest = re.match(r"^(.*?) ([a-z][\w\-]*)\((.*)$",
                                body).groups()
    rest = re.sub(_TYPE, "", re.sub(r"/\*index=\d+\*/", "", rest))
    prev = None
    while prev != rest:     # the tuple types' empty shells
        prev, rest = rest, re.sub(r"\(\s*(?:,\s*)*\)", "", rest)
    rest = re.sub(r"\s+", "", rest)
    operands, _, attrs = rest.partition(")")
    return result, op, operands, attrs


def _served_pp4_text(topo):
    """The compiled text of the program the benchmark's
    ``qwen3-8b-pp4.docs32k`` cell serves (``harness.build_engine``'s
    executor, 32768 tokens in 16 chunks), for four described chips."""
    import json
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "bench"))
    import harness
    from repro.configs.base import RunConfig
    from repro.core import pipeline as pp
    from repro.kernels import ops
    from repro.models.api import build_model
    from repro.models.topology import Topology
    c = json.load(open(os.path.join(harness.BENCH, "configs",
                                    "qwen3-8b-pp4.json")))
    t = json.load(open(os.path.join(harness.BENCH, "traffic",
                                    "docs32k.json")))
    s = c["serve"]
    cfg = harness.model_config(c)
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1),
                ("data", "model"))
    tp = Topology(mesh=mesh)
    seq = t["buckets"][0]
    plan = pp.build_plan(cfg, s["stages"], seq, RunConfig(
        num_chunks=t["num_chunks"], num_stages=s["stages"],
        attn_backend=s["attn_backend"], pool_backend=s["pool_backend"],
        kv_dtype=s["kv_dtype"]))
    shapes = jax.eval_shape(lambda: pp.stage_params(
        cfg, build_model(cfg).init(jax.random.key(0)), plan))
    staged = jax.tree.map(
        lambda a, p: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shapes, pp.stage_param_specs(cfg, plan, tp),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    toks = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    on_tpu = ops._on_tpu
    ops._on_tpu = lambda: True      # the jitted step asks the CPU backend
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(lambda st, tk: pp.prefill_pipeline(
            cfg, st, tk, plan, tp)).lower(staged, toks).compile().as_text()
    finally:
        ops._on_tpu = on_tpu


@pytest.mark.parametrize("recording,renumbered", [
    ("pipeline4", {"broadcast_in_dim.207", "squeeze.232"})])
def test_served_pp4_program_is_the_recorded_one(topo, recording,
                                                renumbered):
    """Every device op of a recorded pp4 chip trace is an instruction of
    this tree's pp4 program compiled for four described chips, with the
    same result shape, opcode, operands and attributes. ``pipeline4`` was
    recorded from a program built without device scopes: the scopes change
    metadata only, and the lowering numbers two instructions of the same
    kind otherwise (``renumbered``: the same instruction under another
    suffix)."""
    import collections
    import gzip
    import re
    from jax.profiler import ProfileData
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "testdata", recording + ".xplane.pb.gz")
    traced = {}
    with gzip.open(path) as f:
        space = ProfileData.from_serialized_xspace(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    name, _, body = e.name.partition(" = ")
                    traced[name.strip().lstrip("%")] = body
    compiled = collections.defaultdict(list)
    for line in _served_pp4_text(topo).splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", line)
        if m:
            compiled[m.group(1)].append(_instruction(m.group(2)))
    def same(want, haves):
        return any(have == want or (want[1] in _ASYNC
                                    and have[::2] == want[::2])
                   for have in haves)

    assert len(traced) > 150
    unmatched, renamed = [], set()
    for name, body in sorted(traced.items()):
        want = _instruction(body)
        if same(want, compiled.get(name, ())):
            continue
        kind = name.rpartition(".")[0]
        if name not in compiled and same(want, [
                have for other, haves in compiled.items()
                if other.rpartition(".")[0] == kind for have in haves]):
            renamed.add(name)
        else:
            unmatched.append((name, want, compiled.get(name)))
    assert not unmatched, unmatched[:4]
    assert renamed == renumbered

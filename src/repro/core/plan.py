"""Static pipeline planning: everything decided BEFORE tracing.

A ``PipelinePlan`` is the single immutable object threaded through the
execution stack (staging -> stage programs -> driver). It pins the pipeline
geometry (N stages x M chunks x C tokens), the MBKR slot plan and its static
lookup tables (numpy arrays that become HLO constants), the KV page store
layout (``repro.kvstore``: page size, slot->page table, storage codec), and
the runtime policy knobs every lower layer reads: ``remote_attn`` (fetch |
qship, see core.remote), ``attn_backend`` (jnp | pallas, core.attention)
and ``ssm_backend`` (jnp | pallas, kernels.ops.ssd).

Modes: ``mocap`` (pool + MBKR), ``terapipe`` (pool of M slots, no
reallocation), ``gpipe`` (microbatch pipeline: batch-split, full-sequence
chunks, no pool). See DESIGN.md §2 for the layering.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.core import mbkr
from repro.kvstore import pages as kvpages
from repro.kvstore import quant as kvquant


@dataclass(frozen=True)
class PipelinePlan:
    """Everything static about one pipeline lowering."""
    mode: str                 # mocap | terapipe | gpipe
    num_stages: int           # N
    num_chunks: int           # M
    chunk_len: int            # C (uniform); gpipe: microbatch size
    layers_per_stage: int     # lps (ceil(L / N)); hybrid: groups per stage
    num_slots: int            # KV pool size (excl. scratch)
    p2: int                   # spill threshold (chunks >= p2 spill); M if no MBKR
    remote_attn: str = "qship"   # fetch | qship
    attn_backend: str = "jnp"    # jnp | pallas (core.attention registry)
    transport: str = "jax"       # core.transport registry entry
    tp_lowering: str = "auto"    # auto (GSPMD) | manual (explicit
                                 # transport psums, all mesh axes manual)
    fetch_batch: str = "auto"    # auto | on | off: land fetched chunk-layers
                                 # in a staging buffer and run ONE
                                 # pool_attention launch ("auto" follows the
                                 # pool backend's batched_pool flag; resolved
                                 # at use time in core.remote)
    pool_backend: str = "jnp"    # backend for POOL-sourced partials (own
                                 # pool scan + fetch/qship); resolved from
                                 # RunConfig.pool_backend ("auto" follows
                                 # attn_backend; "paged" = gather-free
                                 # ragged pool kernel) — never "auto" here
    ssm_backend: str = "jnp"     # jnp | pallas (kernels.ops.ssd)
    spill_dtype: str = "bfloat16"  # int8 -> wire-only spill compression
    ship_dtype: str = "bfloat16"   # qship q/acc wire format (= model dtype)
    # KV page store (repro.kvstore): the pool holds fixed-size pages in the
    # codec's storage dtype; slot tables index pages through ``slot_pages``
    kv_dtype: str = "bfloat16"     # resolved storage knob (never "auto")
    page_tokens: int = 0           # tokens per page (0 only in gpipe mode)
    pages_per_chunk: int = 1
    # static tables (numpy; become HLO constants)
    own_slot: Any = None          # [M] chunk -> own slot (scratch if spilled)
    host_slot_a: Any = None       # [M] chunk -> host slot (first-half hosts)
    host_slot_b: Any = None
    slot_own_chunk: Any = None    # [slots+1] slot -> own chunk (-1 none)
    slot_host_chunk_a: Any = None  # [slots+1] slot -> hosted pair chunk (-1)
    slot_host_chunk_b: Any = None
    host_slots_used: Any = None   # [H] the (few) slots host tables touch —
                                  # the creditor-side scan visits ONLY these
    slot_pages: Any = None        # [slots+1, ppc] slot -> physical page ids

    @property
    def scratch(self) -> int:
        return self.num_slots

    @property
    def codec(self) -> kvquant.KVCodec:
        return kvquant.get_codec(self.kv_dtype)

    @property
    def page_geometry(self) -> kvpages.PageGeometry:
        return kvpages.PageGeometry(
            self.chunk_len, self.page_tokens, self.pages_per_chunk,
            self.num_slots, (self.num_slots + 1) * self.pages_per_chunk)

    @property
    def num_ticks(self) -> int:
        return self.num_chunks + self.num_stages - 1

    @property
    def pair_shift(self) -> int:
        return self.num_stages // 2


def _invert(table: np.ndarray, num_slots: int, lo: int, hi: int) -> np.ndarray:
    inv = np.full(num_slots + 1, -1, np.int32)
    for chunk in range(lo, hi):
        s = int(table[chunk])
        if s <= num_slots:
            inv[s] = chunk
    return inv


def build_plan(cfg: ModelConfig, num_stages: int, seq_len: int,
               run: RunConfig, *, mode: Optional[str] = None) -> PipelinePlan:
    """Derive the static pipeline plan for one (arch, shape, run) cell."""
    from repro import compat

    mode = mode or ("mocap" if run.mbkr else "terapipe")
    m = run.num_chunks
    pool_backend = (run.attn_backend if run.pool_backend in ("auto", "", None)
                    else run.pool_backend)
    tp_lowering = compat.resolve_tp_lowering(run.tp_lowering)
    if run.fetch_batch not in ("auto", "on", "off"):
        raise ValueError(f"unknown fetch_batch {run.fetch_batch!r}")
    if mode == "gpipe":
        return PipelinePlan(mode, num_stages, m, 0,
                            _layers_per_stage(cfg, num_stages), 0, m,
                            attn_backend=run.attn_backend,
                            pool_backend=pool_backend,
                            ssm_backend=run.ssm_backend,
                            transport=run.transport,
                            tp_lowering=tp_lowering,
                            fetch_batch=run.fetch_batch)
    assert seq_len % m == 0, f"seq_len {seq_len} must divide into {m} chunks"
    c = seq_len // m
    use_mbkr = mode == "mocap" and not cfg.attn_free and num_stages >= 2 and m >= 2
    spill = run.mbkr_spill_chunks
    if not 0 <= spill < m:
        raise ValueError(f"mbkr_spill_chunks {spill} must lie in [0, {m})")
    mp = mbkr.plan(m, num_stages, p2=m - spill if spill else None,
                   mbkr=use_mbkr)
    codec = kvquant.get_codec(run.kv_dtype, cfg.dtype)
    geom = kvpages.page_geometry(c, mp.num_slots, run.kv_page_tokens)
    slot_pages = kvpages.build_slot_pages(geom)
    kvpages.verify_page_plan(slot_pages, geom)
    return PipelinePlan(
        mode=mode, num_stages=num_stages, num_chunks=m, chunk_len=c,
        layers_per_stage=_layers_per_stage(cfg, num_stages),
        num_slots=mp.num_slots, p2=mp.p2,
        remote_attn=run.remote_attn,
        attn_backend=run.attn_backend,
        pool_backend=pool_backend,
        ssm_backend=run.ssm_backend,
        transport=run.transport,
        tp_lowering=tp_lowering,
        fetch_batch=run.fetch_batch,
        spill_dtype=run.kv_spill_dtype,
        ship_dtype=cfg.dtype,   # wire in model precision (bf16 in prod)
        kv_dtype=codec.name, page_tokens=geom.page_tokens,
        pages_per_chunk=geom.pages_per_chunk, slot_pages=slot_pages,
        own_slot=mp.own_slot, host_slot_a=mp.host_slot_a, host_slot_b=mp.host_slot_b,
        slot_own_chunk=_invert(mp.own_slot, mp.num_slots, 0, mp.p2),
        slot_host_chunk_a=_invert(mp.host_slot_a, mp.num_slots, mp.p2, m),
        slot_host_chunk_b=_invert(mp.host_slot_b, mp.num_slots, mp.p2, m),
        host_slots_used=np.unique(np.concatenate(
            [mp.host_slot_a[mp.p2:], mp.host_slot_b[mp.p2:]])).astype(np.int32)
        if mp.p2 < m else np.zeros((0,), np.int32),
    )


def _layers_per_stage(cfg: ModelConfig, n: int) -> int:
    if cfg.family == "hybrid":
        nl = cfg.hybrid.num_groups + 1  # +1 pseudo-group for the SSM tail
    else:
        nl = cfg.num_layers
    return -(-nl // n)

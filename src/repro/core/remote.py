"""Remote KV access: the spill / fetch / qship collectives (DESIGN.md §3.4).

MBKR spills chunks with index >= p2 at creation: one pairing permute by N/2
(the fixed cross-half stage pairing) moves their KV to the paired stage's
host slots. At attention time the debtor reaches its remote prefix one of
two ways:

- ``fetch``  (paper-faithful): re-read each spilled chunk from the pair, one
  chunk-layer slice per permute. The streamed order runs each landed chunk
  through the online-softmax combine as it arrives (residency = 1
  chunk-layer); with a ``batched_pool`` backend (and
  ``plan.fetch_batch != "off"``) the landed chunk-layers accumulate in a
  staging buffer instead and go through ONE ``pool_block`` launch — same
  wire traffic, O(1) attention launches per (layer, tick) instead of one
  per remote chunk (``ops.count_launches`` pins it). Under the PAGED pool
  backend the staging buffer is viewed as a page store with identity
  handles and the same ragged paged kernel consumes it
  (``PagedPallasBackend.pool_block`` — no extra copy for passthrough
  codecs, one small staging transpose for per-page-quantized stacks).
- ``qship``  (beyond-paper, TPU-native): ship the QUERY to the creditor,
  which computes partial flash attention over the chunks it hosts and ships
  back (acc, lse). Traffic O(q + out): cheaper whenever >= 2 chunks are
  remote under GQA, and one round-trip instead of n_remote transfers.

ALL wire movement goes through the pluggable transport
(``core.transport``): this module contains no raw collective calls. Every
function takes and returns the ``CollectiveLedger`` — per-category wire
bytes, charged from the actual shipped arrays (quantized codec compression
shows up automatically) and gated by the consumption predicate the §3.4
analytic model prices (a lockstep transfer whose payload is never read does
not count).

KV bytes live in the page store (``repro.kvstore``): slot tables resolve to
page handles through ``plan.slot_pages``, and with a quantized ``kv_dtype``
the spill AND fetch wires carry the encoded payload + per-head scales — the
creditor scatters raw pages under ITS page table (reallocation is handle
movement, and reallocation traffic shrinks by the codec's factor). With a
passthrough codec the legacy ``spill_dtype="int8"`` wire-only compression is
preserved bit-for-bit (quantize on the wire, dequantize into the pool).

All attention math inside both paths routes through the pluggable backend
(``core.attention``), so fetch/qship work identically under jnp and pallas.
The caller passes the plan's POOL backend (``plan.pool_backend``) — remote
partials are pool-sourced, so they follow the pool knob, not the self-block
one; under pallas the creditor-side qship scan is the batched slot-grid
kernel (one launch over ``host_slots_used``). The functions take the
per-trace stage context (``core.stagestep.StageCtx``) duck-typed to keep
this layer import-light.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.core.attention import (AttentionBackend, State, attn_combine,
                                  attn_init, pool_scan)
from repro.core import transport as tx
from repro.core.transport import Ledger
from repro.kvstore import pages as kvpages
from repro.kvstore import quant as kvquant
from repro.obs import telemetry as obs_t
from repro.obs.telemetry import StageTelemetry


def _rep(ctx) -> int:
    """Telemetry count replication under the manual TP lowering."""
    return ctx.mtp.tp if ctx.mtp is not None else 1


def pair_phase(ctx) -> jax.Array:
    """The chunk index my PAIR stage is computing this tick."""
    n2 = ctx.plan.pair_shift
    return jnp.where(ctx.first_half, ctx.phase - n2, ctx.phase + n2)


def spill_permute(ctx, kv: jax.Array, led: Ledger = None, *,
                  active=None):
    """Cross-half spill transfer for a PASSTHROUGH pool. int8 spill_dtype:
    the WIRE carries the int8 payload + one fp32 scale per (tensor, layer,
    kv head) — half the spill bytes; the pool stays in model dtype
    (dequantized at the creditor)."""
    plan, tr = ctx.plan, ctx.transport
    if plan.spill_dtype != "int8":
        return tr.pair_shift(kv, ctx.topo.stage_axis, ctx.pair_perm, led,
                             tag="spill", active=active)
    amax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=(-3, -1), keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(kv.astype(jnp.float32) / scale), -127, 127)
    q8, led = tr.pair_shift(q.astype(jnp.int8), ctx.topo.stage_axis,
                            ctx.pair_perm, led, tag="spill", active=active)
    s, led = tr.pair_shift(scale, ctx.topo.stage_axis, ctx.pair_perm, led,
                           tag="spill", active=active)
    return (q8.astype(jnp.float32) * s).astype(kv.dtype), led


def host_table(ctx) -> jax.Array:
    """chunk -> host slot table for MY half of the pairing."""
    plan = ctx.plan
    return jnp.where(ctx.first_half,
                     jnp.asarray(plan.host_slot_a),
                     jnp.asarray(plan.host_slot_b))


def _pool_layer(pool: kvpages.PagedPool, l_idx: jax.Array):
    """Slice one layer out of the paged pool: payloads [P, B, K, pt, D] +
    scales [P, B, K, 1, 1] (None when passthrough)."""
    sl = lambda a: jax.lax.dynamic_index_in_dim(a, l_idx, axis=1,
                                                keepdims=False)
    ks = sl(pool.k_scale) if pool.k_scale is not None else None
    vs = sl(pool.v_scale) if pool.v_scale is not None else None
    return sl(pool.k), sl(pool.v), ks, vs


def fetch_batched(ctx, backend: AttentionBackend) -> bool:
    """Resolve the batched-fetch knob against the pool backend: "auto"
    batches exactly when the backend fuses multi-slot stacks into one
    launch (``batched_pool``)."""
    fb = ctx.plan.fetch_batch
    return fb == "on" or (fb == "auto" and backend.batched_pool)


def fetch_remote(ctx, backend: AttentionBackend, qg, pool_l, st: State,
                 led: Ledger = None, tel: StageTelemetry = None):
    """Paper-faithful fetch wire: stream one chunk-layer per pairing permute.
    The slot *I* host for my pair at index j holds — after the symmetric
    cross-half exchange — my own chunk j. The wire carries the ENCODED pages
    (quantized codec: the fetch traffic shrinks by the same factor as the
    pool).

    Post-transfer attention order (``fetch_batched``): streamed = one
    online-softmax combine per landed chunk (the reference order, residency
    1 chunk-layer); batched = land every chunk-layer in a staging buffer and
    run ONE ``pool_block`` over the stack (a single slot-grid kernel launch
    under the pallas pool backend — the combine happens inside VMEM). The
    two orders agree to 1e-6 on float pages (``tests/test_transport.py``).
    """
    plan = ctx.plan
    host_tbl = host_table(ctx)
    slot_pages = jnp.asarray(plan.slot_pages)
    quantized = plan.codec.quantized
    js = jnp.arange(plan.p2, plan.num_chunks)

    def wire_one(led, tel, j):
        """Permute chunk j's encoded pages from the pair (ledger-charged
        iff the chunk is actually consumed this tick)."""
        pages = slot_pages[host_tbl[j]]
        kq, vq, ks, vs = kvpages.gather_chunk(*pool_l, pages)
        active = (j < ctx.phase) & (ctx.phase < plan.num_chunks)
        pk, led = ctx.transport.pair_shift(
            jnp.stack([kq, vq]), ctx.topo.stage_axis, ctx.pair_perm, led,
            tag="fetch", active=active)
        if quantized:
            ps, led = ctx.transport.pair_shift(
                jnp.stack([ks, vs]), ctx.topo.stage_axis, ctx.pair_perm, led,
                tag="fetch", active=active)
            ks, vs = ps[0], ps[1]
        # one telemetry event per CONSUMED chunk-layer (same gate as the
        # ledger — wire bytes = events x per_event_wire_bytes["fetch"])
        tel = obs_t.charge(tel, "fetch_events", 1.0, active, _rep(ctx))
        return (pk[0], pk[1], ks, vs), led, tel

    if fetch_batched(ctx, backend):
        def land(carry, j):
            led, tel = carry
            (kq, vq, ks, vs), led, tel = wire_one(led, tel, j)
            ys = (kq, vq, ks, vs) if quantized else (kq, vq)
            return (led, tel), ys

        (led, tel), landed = jax.lax.scan(land, (led, tel), js)
        if quantized:
            kqs, vqs, kss, vss = landed
        else:
            (kqs, vqs), kss, vss = landed, None, None
        valid = js < ctx.phase
        st = backend.pool_block(qg, kqs, vqs, kss, vss, valid, ctx.scale, st)
        tel = obs_t.charge(tel, "launches", 1.0, None, _rep(ctx))
        return st, led, tel

    def fetch_body(carry, j):
        stc, led, tel = carry
        (kq, vq, ks, vs), led, tel = wire_one(led, tel, j)
        stc = backend.chunk_block_q(qg, kq, vq, ks, vs, j < ctx.phase,
                                    ctx.scale, stc)
        tel = obs_t.charge(tel, "launches", 1.0, None, _rep(ctx))
        return (stc, led, tel), None

    (st, led, tel), _ = jax.lax.scan(fetch_body, (st, led, tel), js)
    return st, led, tel


def qship_remote(ctx, backend: AttentionBackend, qg, pool_l, st: State,
                 led: Ledger = None, tel: StageTelemetry = None):
    """Beyond-paper qship: ship my Q to the creditor, which runs the backend
    over ONLY the host slots it holds for me, then ships back (m, l, acc).
    With a ``batched_pool`` backend the creditor-side scan is ONE slot-grid
    kernel launch over the host-slot subset (``pool_scan`` handles both)."""
    plan, tr = ctx.plan, ctx.transport
    b, c, kvh, g, d = qg.shape
    sd = jnp.dtype(plan.ship_dtype)
    # useful iff I actually have a remote prefix this tick (phase > p2)
    active = (ctx.phase > plan.p2) & (ctx.phase < plan.num_chunks)
    q_pair, led = tr.pair_shift(qg.astype(sd), ctx.topo.stage_axis,
                                ctx.pair_perm, led, tag="qship_q",
                                active=active)
    q_pair = q_pair.astype(qg.dtype)
    host_chunk = jnp.where(ctx.first_half,
                           jnp.asarray(plan.slot_host_chunk_a),
                           jnp.asarray(plan.slot_host_chunk_b))
    pair_limit = pair_phase(ctx)  # pair needs chunks [p2, pair_phase)
    st_r = attn_init(b, c, kvh, g, d)
    # creditor-side scan visits ONLY the host slots (compute win)
    st_r = pool_scan(backend, q_pair, pool_l, plan.slot_pages, host_chunk,
                     pair_limit, ctx.scale, st_r,
                     slots=plan.host_slots_used)
    # ship (m, l) packed fp32 + acc in the wire dtype
    ml, led = tr.pair_shift(jnp.stack([st_r[0], st_r[1]]),
                            ctx.topo.stage_axis, ctx.pair_perm, led,
                            tag="qship_state", active=active)
    a_r, led = tr.pair_shift(st_r[2].astype(sd), ctx.topo.stage_axis,
                             ctx.pair_perm, led, tag="qship_state",
                             active=active)
    # one event per useful round-trip; launches = the creditor-side scan
    tel = obs_t.charge(tel, "qship_events", 1.0, active, _rep(ctx))
    tel = obs_t.charge(tel, "launches",
                       1.0 if backend.batched_pool
                       else float(len(plan.host_slots_used)),
                       None, _rep(ctx))
    return attn_combine(st, (ml[0], ml[1], a_r.astype(jnp.float32))), led, tel


def write_pools(ctx, pool: kvpages.PagedPool, stage_k, stage_v,
                led: Ledger = None, tel: StageTelemetry = None):
    """End-of-tick page writes: encode the fresh chunk once, scatter its
    pages to the own slot (phase < p2) or ship the payload cross-half and
    scatter under the creditor's page table. Inactive phases write to the
    scratch slot's pages (write-garbage land, never read).

    With the prefix path armed (``ctx.prefix_chunks = k > 0``) the first
    ``k`` phases ALSO redirect to scratch: the pool was seeded with the
    cached prefix KV (``kvstore.prefix.DeviceSeedCache``), so the fresh
    recompute of a hit chunk must not clobber the authoritative pages —
    copy-on-write at the device. Each redirected store charges the
    ``prefix_hit`` saved-bytes category (ledger: the chunk's stored bytes;
    telemetry: one event), pinned against ``obs.telemetry.
    prefix_saved_model``. ``k`` is STATIC: the disarmed program is
    byte-identical to pre-prefix builds."""
    plan = ctx.plan
    codec = plan.codec
    slot_pages = jnp.asarray(plan.slot_pages)
    phase, active = ctx.phase, (ctx.phase >= 0) & (ctx.phase < plan.num_chunks)
    pidx = jnp.clip(phase, 0, plan.num_chunks - 1)

    own_tbl = jnp.asarray(plan.own_slot)
    own_slot = jnp.where(active & (phase < plan.p2), own_tbl[pidx], plan.scratch)
    if ctx.prefix_chunks > 0:
        hit = active & (phase < ctx.prefix_chunks)
        own_slot = jnp.where(hit, plan.scratch, own_slot)
        lps, b, c, kvh, hd = stage_k.shape
        led = tx.charge(led, "prefix_hit",
                        obs_t.chunk_stored_bytes(plan, lps, b, c, kvh, hd),
                        hit)
        tel = obs_t.charge(tel, "prefix_hit", 1.0, hit, _rep(ctx))
    kq, ksc = kvquant.encode(codec, stage_k, pages=plan.pages_per_chunk)
    vq, vsc = kvquant.encode(codec, stage_v, pages=plan.pages_per_chunk)
    pool = kvpages.scatter_chunk_raw(pool, slot_pages[own_slot],
                                     kq, vq, ksc, vsc)

    if plan.p2 < plan.num_chunks and plan.mode == "mocap":
        pp = pair_phase(ctx)  # the chunk index my pair just computed
        host_tbl = host_table(ctx)
        ppc = jnp.clip(pp, 0, plan.num_chunks - 1)
        hslot = jnp.where((pp >= plan.p2) & (pp < plan.num_chunks),
                          host_tbl[ppc], plan.scratch)
        # I ship MY chunk; it is useful iff MY phase needs hosting
        ship_active = (phase >= plan.p2) & (phase < plan.num_chunks)
        tel = obs_t.charge(tel, "spill_events", 1.0, ship_active, _rep(ctx))
        if codec.quantized:
            # the wire carries the already-encoded pages + scales
            sq, led = ctx.transport.pair_shift(
                jnp.stack([kq, vq]), ctx.topo.stage_axis, ctx.pair_perm,
                led, tag="spill", active=ship_active)
            ss, led = ctx.transport.pair_shift(
                jnp.stack([ksc, vsc]), ctx.topo.stage_axis, ctx.pair_perm,
                led, tag="spill", active=ship_active)
            pool = kvpages.scatter_chunk_raw(pool, slot_pages[hslot],
                                             sq[0], sq[1], ss[0], ss[1])
        else:
            spill, led = spill_permute(ctx, jnp.stack([stage_k, stage_v]),
                                       led, active=ship_active)
            pool = kvpages.scatter_chunk_raw(pool, slot_pages[hslot],
                                             spill[0].astype(pool.k.dtype),
                                             spill[1].astype(pool.v.dtype),
                                             None, None)
    return pool, led, tel

"""Pluggable transport layer: every cross-stage / cross-rank collective the
pipeline issues, behind one protocol (DESIGN.md §3.6).

MOCAP's premise is that WSC interconnect makes MBKR reallocation traffic
cheap — which makes the COMMUNICATION layer the part worth orchestrating.
Before this module, raw ``ppermute``/``psum`` calls were hard-coded in four
files; now the pipeline path goes through a ``Transport``:

- ``ring_shift``      stage-boundary activation advance (+1 on the stage
                      axis — the paper's 1-hop D2D transfer),
- ``pair_shift``      the fixed cross-half MBKR pairing permute (spill
                      wires, fetch chunk-layer streams, qship q/state ships),
- ``stage_psum``      stage-axis reduction (final hidden-state collect),
- ``tp_psum`` / ``tp_reduce_scatter`` / ``tp_all_gather``
                      tensor-parallel collectives for the MANUAL TP lowering
                      (``RunConfig.tp_lowering="manual"``: explicit psums in
                      the stage programs instead of GSPMD partial-auto).

Transports are registered like attention backends (``register_transport``),
so future comm optimizations — TPU-native qship DMA, in-pipeline cold
streaming — plug into the registry instead of another monolith. The default
``jax`` transport lowers to ``jax.lax`` collectives.

The **CollectiveLedger** rides along: a carry-threaded pytree of per-category
wire-byte counters (``ring / collect / spill / fetch / qship_q / qship_state
/ tp``). Every transport call charges the bytes IT PUT ON THE WIRE from this
chip, gated by a traced ``active`` predicate (SPMD lockstep runs every
collective every tick; the ledger counts the *useful* bytes — the ones the
§3.4 traffic model prices). Byte counts come from the actual shipped arrays,
so a quantized codec's compression (``repro.kvstore``) is reflected
automatically — payload at storage-dtype width plus the fp32 scale rows.
``ledger_collect`` psums the per-chip counters over the mapped axes at the
end of the pipeline body; ``analytic_wire_bytes`` computes the same totals
in closed form from the plan (DESIGN.md §3.4/§3.6) — dryrun records it and
``tests/test_transport.py`` pins runtime-vs-analytic agreement to <1%.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# "prefix_hit" is SAVED bytes, not wire bytes: the stored KV a prefix-cache
# hit did not re-materialize (one chunk-store per (stage, hit phase); closed
# form in obs.telemetry.prefix_saved_model). The key exists unconditionally —
# same pytree, same psum count whether the prefix path is armed or not — so
# the disabled lowering stays bit-identical with zero extra collectives.
LEDGER_KEYS = ("ring", "collect", "spill", "fetch", "qship_q", "qship_state",
               "tp", "prefix_hit")

Ledger = Optional[Dict[str, jax.Array]]


def ledger_init() -> Dict[str, jax.Array]:
    """Fresh per-chip ledger: one fp32 byte counter per traffic category."""
    return {k: jnp.zeros((), jnp.float32) for k in LEDGER_KEYS}


def nbytes(x: jax.Array) -> float:
    """Wire bytes of one array as shipped (static: shape x itemsize)."""
    return float(np.prod(x.shape) * jnp.dtype(x.dtype).itemsize)


def charge(led: Ledger, key: str, amount: float, active=None) -> Ledger:
    """Add ``amount`` bytes to ``led[key]``, gated by the traced ``active``
    predicate (None = unconditional). No-op on a None ledger."""
    if led is None or amount == 0.0:
        return led
    if active is None:
        add = jnp.float32(amount)
    else:
        add = jnp.where(active, jnp.float32(amount), 0.0)
    out = dict(led)
    out[key] = led[key] + add
    return out


def ledger_collect(led: Ledger, axis_names) -> Ledger:
    """Sum the per-chip counters over the mapped ``axis_names`` (stage + any
    manual TP axes) — after this every chip holds the global totals."""
    if led is None:
        return None
    return {k: jax.lax.psum(v, axis_names) for k, v in led.items()}


def ledger_to_dict(led) -> Dict[str, float]:
    return {k: float(np.asarray(v)) for k, v in led.items()}


# ========================================================== the protocol

class Transport:
    """One way to move bytes between chips. Methods take and return the
    ledger (carry-threaded pytree; None disables accounting) so call sites
    inside ``lax.scan`` bodies stay functional."""

    name = "abstract"

    # -- stage-axis movement -------------------------------------------
    def ring_shift(self, x, axis, perm, led: Ledger = None, *,
                   active=None) -> Tuple[jax.Array, Ledger]:
        """Activation advance to the next stage (ring +1)."""
        raise NotImplementedError

    def pair_shift(self, x, axis, perm, led: Ledger = None, *,
                   tag: str, active=None) -> Tuple[jax.Array, Ledger]:
        """Cross-half MBKR pairing permute. ``tag`` picks the ledger
        category (spill | fetch | qship_q | qship_state)."""
        raise NotImplementedError

    def stage_psum(self, x, axis, led: Ledger = None, *,
                   active=None) -> Tuple[jax.Array, Ledger]:
        """All-reduce over the stage axis (final hidden-state collect)."""
        raise NotImplementedError

    # -- tensor-parallel collectives (manual TP lowering) --------------
    def tp_psum(self, x, axes, led: Ledger = None, *,
                active=None) -> Tuple[jax.Array, Ledger]:
        raise NotImplementedError

    def tp_reduce_scatter(self, x, axes, led: Ledger = None, *,
                          scatter_axis: int = 0,
                          active=None) -> Tuple[jax.Array, Ledger]:
        raise NotImplementedError

    def tp_all_gather(self, x, axes, led: Ledger = None, *,
                      concat_axis: int = 0,
                      active=None) -> Tuple[jax.Array, Ledger]:
        raise NotImplementedError


class JaxCollectiveTransport(Transport):
    """Default transport: ``jax.lax`` collectives, ring-algorithm byte model.

    Wire-byte charges (per CHIP, per call — ``ledger_collect`` sums chips):
      permute (ring/pair):   nbytes(x)                 one send per chip
      all-reduce (psum):     2 * (k-1)/k * nbytes(x)   ring all-reduce
      reduce-scatter:        (k-1)/k * nbytes(x)
      all-gather:            (k-1) * nbytes(x_local)
    """

    name = "jax"

    @staticmethod
    def _axis_size(axes) -> int:
        sizes = jax.lax.psum(1, axes)
        return int(sizes)

    def ring_shift(self, x, axis, perm, led: Ledger = None, *, active=None):
        out = jax.lax.ppermute(x, axis, perm)
        return out, charge(led, "ring", nbytes(x), active)

    def pair_shift(self, x, axis, perm, led: Ledger = None, *,
                   tag: str, active=None):
        out = jax.lax.ppermute(x, axis, perm)
        return out, charge(led, tag, nbytes(x), active)

    def stage_psum(self, x, axis, led: Ledger = None, *, active=None):
        k = self._axis_size(axis)
        out = jax.lax.psum(x, axis)
        return out, charge(led, "collect", 2.0 * (k - 1) / k * nbytes(x),
                            active)

    def tp_psum(self, x, axes, led: Ledger = None, *, active=None):
        k = self._axis_size(axes)
        out = jax.lax.psum(x, axes)
        return out, charge(led, "tp", 2.0 * (k - 1) / k * nbytes(x), active)

    def tp_reduce_scatter(self, x, axes, led: Ledger = None, *,
                          scatter_axis: int = 0, active=None):
        k = self._axis_size(axes)
        out = jax.lax.psum_scatter(x, axes, scatter_dimension=scatter_axis,
                                   tiled=True)
        return out, charge(led, "tp", (k - 1) / k * nbytes(x), active)

    def tp_all_gather(self, x, axes, led: Ledger = None, *,
                      concat_axis: int = 0, active=None):
        k = self._axis_size(axes)
        out = jax.lax.all_gather(x, axes, axis=concat_axis, tiled=True)
        return out, charge(led, "tp", (k - 1) * nbytes(x), active)


# =========================================================== the registry

_TRANSPORTS: Dict[str, Callable[[], Transport]] = {}


def register_transport(name: str, factory: Callable[[], Transport]) -> None:
    _TRANSPORTS[name] = factory


def get_transport(name: str) -> Transport:
    if name not in _TRANSPORTS:
        raise KeyError(f"unknown transport {name!r}; "
                       f"registered: {sorted(_TRANSPORTS)}")
    return _TRANSPORTS[name]()


def available_transports() -> Tuple[str, ...]:
    return tuple(sorted(_TRANSPORTS))


register_transport("jax", JaxCollectiveTransport)


# ================================================== §3.4 analytic model

def analytic_wire_bytes(plan, cfg, b: int, *,
                        dtype_bytes: Optional[float] = None,
                        resident_pages=None) -> Dict[str, float]:
    """Closed-form §3.4 traffic totals for one ``prefill_pipeline`` call of a
    TRANSFORMER-family plan — the model the runtime ledger is validated
    against (``tests/test_transport.py``, <1%).

    Logical bytes, whole run, all stages, useful-gated exactly like the
    ledger: a transfer counts when its payload is consumed (fetch chunk j at
    phase p counts iff j < p; qship counts iff p > p2; spill counts iff the
    shipped chunk index is in [p2, M)). Per-chip TP sharding divides each
    chip's share but the psum over chips restores these logical totals, so
    the model is lowering-independent (auto vs manual TP) except for the
    ``tp`` category, which only the manual lowering puts on the wire (the
    stage programs charge it at the call site; it is not modeled here).

    ``resident_pages``: optional per-chunk RESIDENT page counts ([M] ints,
    each <= pages_per_chunk) — the ragged-occupancy variant for the paged
    pool path (DESIGN.md §3.7), where a chunk's spill/fetch wire carries
    only its resident pages instead of the padded slot stack. ``None`` (or
    all-full) reproduces the dense closed form exactly; today's uniform-
    chunk runtime ships full chunks, so the ledger pins against the dense
    case, and the ragged model prices what partial chunks will save."""
    n, m, c = plan.num_stages, plan.num_chunks, plan.chunk_len
    lps = plan.layers_per_stage
    kvh, hd, h = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
    dt = dtype_bytes or float(jnp.dtype(cfg.dtype).itemsize)
    codec = plan.codec
    sto = float(codec.bytes_per_el)
    ppc = plan.pages_per_chunk
    pt = c // ppc
    if resident_pages is None:
        resident_pages = [ppc] * m
    rp = [int(min(max(p, 0), ppc)) for p in resident_pages]
    assert len(rp) == m, (len(rp), m)
    out = {k: 0.0 for k in LEDGER_KEYS}

    # ring: stage s < N-1 forwards its chunk output once per active phase
    out["ring"] = (n - 1) * m * (b * c * cfg.d_model) * dt
    # collect: one [B, d] fp32 all-reduce over the stage axis
    out["collect"] = 2.0 * (n - 1) * (b * cfg.d_model) * 4.0

    if plan.mode != "mocap" or plan.p2 >= m or cfg.attn_free:
        return out

    # --- spill: every stage ships each chunk in [p2, M) once (all lps
    # layers in one end-of-tick permute). Quantized codec: the wire carries
    # the encoded RESIDENT pages + fp32 scales; passthrough + int8
    # spill_dtype: int8 payload + one fp32 scale per (tensor, layer, kv
    # head).
    def spill_wire(pages: int) -> float:
        payload = 2 * lps * b * (pages * pt) * kvh * hd  # k and v elements
        if codec.quantized:
            return payload * sto + 2 * pages * lps * b * kvh * 4.0
        if plan.spill_dtype == "int8":
            return payload * 1.0 + 2 * lps * b * kvh * 4.0
        return payload * dt

    out["spill"] = n * sum(spill_wire(rp[j]) for j in range(plan.p2, m))

    if plan.remote_attn == "fetch":
        # one chunk-layer permute per (stage, layer, phase, remote chunk
        # consumed): chunk j is consumed at every phase p with j < p
        def fetch_wire(pages: int) -> float:
            payload = 2 * b * (pages * pt) * kvh * hd
            if codec.quantized:
                return payload * sto + 2 * pages * b * kvh * 4.0
            return payload * sto
        out["fetch"] = n * lps * sum(
            fetch_wire(rp[j])
            for p in range(m) for j in range(plan.p2, min(p, m)))
    else:
        # qship: one q ship + one (m, l, acc) return per (stage, layer,
        # phase with p > p2)
        phases = max(0, m - 1 - plan.p2)
        ship = float(jnp.dtype(plan.ship_dtype).itemsize)
        out["qship_q"] = n * lps * phases * (b * c * h * hd) * ship
        out["qship_state"] = n * lps * phases * (
            2 * (b * kvh * (h // kvh) * c) * 4.0        # (m, l) fp32 packed
            + (b * kvh * (h // kvh) * c * hd) * ship)   # acc in wire dtype
    return out

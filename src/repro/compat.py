"""Thin JAX helpers shared across the repo.

- ``AxisType`` / ``make_mesh`` / ``set_mesh`` / ``shard_map``: the jax entry
  points, re-exported so call sites import one module.
- ``axis_types_kw``: ``{"axis_types": (Auto,) * n}`` for ``jax.make_mesh``.
- ``ensure_host_devices``: fake host-platform devices for CPU runs.
- ``resolve_tp_lowering``: validates ``RunConfig.tp_lowering``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType  # noqa: F401  (re-export)

make_mesh = jax.make_mesh
set_mesh = jax.set_mesh
shard_map = jax.shard_map


def axis_types_kw(n: int) -> dict:
    """``{"axis_types": (Auto,)*n}``: every mesh axis GSPMD-auto."""
    return {"axis_types": (AxisType.Auto,) * n}


def ensure_host_devices(n: int = 8) -> None:
    """Give the CPU platform ``n`` fake host devices (the multi-stage
    pipeline path on a CPU host). No-op when the flag is already set. Must
    run before the first jax backend use (device queries, array ops);
    importing jax is fine. Callers invoke it only when the platform is the
    CPU — the flag never touches an accelerator."""
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def resolve_tp_lowering(requested: str = "auto") -> str:
    """Validate ``RunConfig.tp_lowering``: "auto" (GSPMD partial-auto
    shard_map over the TP axis) or "manual" (all mesh axes manual, explicit
    transport psums in the stage programs — DESIGN.md §3.6)."""
    requested = requested or "auto"
    if requested not in ("auto", "manual"):
        raise ValueError(f"unknown tp_lowering {requested!r}; "
                         "choose 'auto' or 'manual'")
    return requested

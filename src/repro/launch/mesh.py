"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; smoke tests
see the real single device).

Axis semantics (DESIGN.md §3):
  "data"  — 16-wide: chunked-pipeline STAGE axis for prefill; batch/FSDP axis
            for train and decode shapes.
  "model" — 16-wide: tensor parallelism inside a stage (Megatron split).
  "pod"   — multi-pod replica axis (independent request streams / data
            parallel across pods); gradients all-reduce over it in training.
"""
from __future__ import annotations


import jax

from repro.compat import axis_types_kw as _axis_kw
from repro.models.topology import Topology


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))


def make_topology(*, multi_pod: bool = False) -> Topology:
    mesh = make_production_mesh(multi_pod=multi_pod)
    batch = ("pod", "data") if multi_pod else ("data",)
    return Topology(mesh=mesh, batch_axes=batch, tp_axis="model",
                    stage_axis="data")


def make_test_topology(num_stages: int = 4, tp: int = 2) -> Topology:
    """Small mesh over however many (fake) devices the process has."""
    mesh = jax.make_mesh((num_stages, tp), ("data", "model"), **_axis_kw(2))
    return Topology(mesh=mesh)

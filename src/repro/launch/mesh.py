"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches JAX device state — the dry-run driver must set
--xla_force_host_platform_device_count *before* the first jax call.

Topology: TPU v5e pods of 16×16 = 256 chips; the multi-pod mesh adds a
leading "pod" axis (2 pods = 512 chips).  Axis roles:
  pod   — slowest (DCN-connected) dimension: pure data parallelism.
  data  — intra-pod data parallel / FSDP shard axis.
  model — tensor/expert/sequence parallel axis (16-way).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh", "auto_mesh", "dp_axes",
           "DP_AXES", "MODEL_AXIS"]

MODEL_AXIS = "model"


def auto_mesh(shape, axes):
    """`jax.make_mesh` with every axis in Auto sharding mode.  JAX 0.9 makes
    new meshes Explicit by default, under which the model's un-annotated ops
    (cache writes, concatenations) reject sharded operands; this repo places
    shardings with jit in/out specs and shard_map, which is Auto mode."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples).

    ``model`` carves a model-parallel axis out of the host devices (the
    sharded-serving parity tests run an 8-device host mesh as (4, 2) —
    `XLA_FLAGS=--xla_force_host_platform_device_count=8`); the default is
    the degenerate all-data mesh.
    """
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"model={model} does not divide the "
                         f"{n} available devices")
    return auto_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axis names of a mesh (('pod',)+('data',) or ('data',))."""
    names = tuple(mesh.axis_names)
    return tuple(a for a in names if a != MODEL_AXIS)


DP_AXES = ("pod", "data")

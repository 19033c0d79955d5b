"""Runtime setup: production meshes, multi-host initialization, scheduler
options and the persistent compilation cache.

Defined as FUNCTIONS (never module-level meshes) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before the
first jax device query.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

from .. import CHECKOUT_DIR

DEFAULT_COMPILE_CACHE = CHECKOUT_DIR / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (gitignored): a path built from nothing
    variable, so every run from one checkout finds the previous run's
    executables."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


# XLA TPU options that let the scheduler overlap the bucketed gradient
# collectives (distributed/overlap.py) with backward compute.  Passed per
# compilation (``jax.jit(..., compiler_options=...)``): the TPU compiler
# rejects an option it does not know with an error at compile time, and
# nothing leaks into the process environment.
TPU_OVERLAP_OPTIONS = {
    "xla_tpu_enable_latency_hiding_scheduler": True,
    "xla_tpu_enable_async_collective_fusion": True,
    "xla_tpu_enable_async_collective_fusion_fuse_all_gather": True,
    "xla_tpu_overlap_compute_collective_tc": True,
}


def overlap_compiler_options(mesh) -> dict:
    """Compiler options for a step compiled over ``mesh``: the TPU overlap
    options when the mesh spans more than one TPU device, else none (one
    device has no collectives to overlap, and the CPU thunk runtime already
    runs independent per-bucket collective chains concurrently)."""
    if mesh is None or mesh.devices.size < 2:
        return {}
    if mesh.devices.flat[0].platform != "tpu":
        return {}
    return dict(TPU_OVERLAP_OPTIONS)


def initialize_distributed(coordinator: str, num_processes: int,
                           process_id: int) -> None:
    """Multi-process jax runtime init (idempotent-ish: call once, before
    any jax device use).

    On CPU the default collectives implementation cannot cross processes;
    gloo can, and must be selected *before* ``jax.distributed.initialize``
    touches the backend.  Platform detection is env-only
    (``JAX_PLATFORMS``) because querying the backend here would initialize
    it pre-distributed — the exact bug this helper exists to prevent.  The
    2-process localhost tier (tests/test_multiprocess.py) runs this path."""
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def make_host_spanning_mesh(shape, axes):
    """Mesh over ALL global devices (every process's), for multi-host
    data parallelism.  Identical to :func:`make_mesh` on one process —
    ``jax.devices()`` is the global list either way — but kept as a named
    entry point so call sites document their multi-host intent."""
    return make_mesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16x16 = 256 chips (data, model); multi-pod adds a leading
    pod axis: 2 x 16 x 16 = 512 chips (pod, data, model).

    Scaling pods is a shape change only: every PartitionSpec in the tree
    uses the composite ("pod", "data") axis, so (8, 16, 16) = 2048 chips
    works unchanged.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes, devices=None):
    """Generic helper for tests/examples (e.g. (2, 2) on 4 host devices).

    ``devices`` restricts the mesh to an explicit device subset — the
    elastic driver uses this to rebuild a smaller mesh after losing nodes
    (e.g. 8 -> 4 devices) without restarting the process."""
    if devices is not None:
        import numpy as np
        from jax.sharding import Mesh
        return Mesh(np.asarray(devices).reshape(tuple(shape)), tuple(axes))
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))

"""Bucketed, backward-overlapped compressed data-parallel reduction.

PR 2's in-collective int8 compression ran each engine shard through ONE
``shard_map`` — a monolithic reduce-scatter → quantize → all-gather after
the full backward pass, leaving every microsecond of comm time exposed.
This module re-expresses the same reduction as a pipeline of independent
per-bucket collectives:

    shard  =  [bucket 0 | bucket 1 | ... | bucket B-1]     (static slices,
                                                            256-block- and
                                                            segment-aligned)
    for each bucket:  reduce-scatter(fp32) -> int8 quantize (+EF view)
                      -> all-gather(int8 + scales) -> dequantize

Each bucket is its own ``shard_map`` call, so the compiled HLO contains B
*independent* collective chains instead of one monolithic chain.  That is
exactly the shape XLA's latency-hiding scheduler (flags threaded through
``launch/mesh.py``) needs to start early buckets' collectives while later
buckets' inputs are still being produced by backward compute — and on the
CPU thunk runtime, independent chains execute concurrently with compute
without any flags at all.

Bucket geometry — device-major under a mesh, contiguous without one:

    mesh-less     bucket j  =  global elements [start_j, stop_j)
    under a mesh  bucket j  =  each device's LOCAL elements
                               [start_j/ndev, stop_j/ndev) of its segment,
                               i.e. global {d*seg + start_j/ndev ... } for
                               every device d

The mesh form matters: the gradient and error-feedback buffers arrive
sharded ``P(fsdp)``, so a *contiguous* global slice [start, stop) crosses
device boundaries and SPMD partitioning has to insert collective-permutes
to reshard every bucket (measured: +56% total collective bytes at 8
devices).  Slicing each device's own segment instead is comm-free — a
reshape to ``[ndev, seg]``, a column slice, and the inverse reassembly
(concatenate along columns) all stay device-local.

Numerical contract — the load-bearing property of this file:

    *any* bucketing dequantizes BIT-IDENTICALLY to the monolithic path.

Both the per-256-block fp32 scales and the stochastic-rounding noise are
functions of the **global element index** within the flat shard (PR 2's
device-count-invariance discipline: ``repro.quant._quantize(..., offset=)``
hashes ``offset + arange``).  Bucket boundaries are multiples of
``block * ndev``, so bucket-local runs land on the same scale blocks and
the same noise as the whole-shard call whichever geometry is in play —
the device-major form passes ``stride = seg`` to ``_allreduce_one`` so
device ``d``'s run still hashes ``d*seg + start/ndev + arange``.
``tests/test_overlap.py`` pins this across bucket sizes straddling block
boundaries.

Bucketing also fixes the monolithic path's peak comm buffer: the int8
all-gather buffer is O(bucket) instead of O(shard), and the fp32 gradient
only ever crosses the wire reduce-scattered, so peak per-collective bytes
are O(n/devices + bucket).  The 8-device HLO audit asserts this on the
compiled program.

Telemetry (``telemetry=True``) threads host timestamps around each
bucket's collective using *unordered* ``io_callback`` — ordering is
enforced purely by dataflow (the stamp consumes a probe of its
predecessor, the successor consumes the stamp), which is the only ordering
that is safe under multi-device jit.  ``time.perf_counter`` deltas exceed
f32 precision, so stamps are (2,) f32 ``[whole_seconds, fraction]`` pairs;
``delta_seconds`` recombines them.  On the chip, each bucket's collective
also sits in a ``comm_shard{i}_bucket{j}`` named scope, so a device
trace shows the per-bucket timeline.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

from ..core.engine import bucket_slices
from ..quant import _GOLDEN, _as_seed

__all__ = ["allreduce_shards_bucketed", "plan_buckets", "stamp",
           "delta_seconds"]


# ---------------------------------------------------------------------------
# bucket planning

def plan_buckets(shard_sizes: Sequence[int], ndev: int, *, block: int = 256,
                 bucket_elems: Optional[int] = None
                 ) -> Tuple[Tuple[Tuple[int, int], ...], ...]:
    """Static bucket plan: per shard, a tuple of ``(start, stop)`` slices.

    ``bucket_elems`` semantics (shared with ``allreduce_shards``):

      * ``None``  — auto: roofline-chosen size (``choose_bucket_elems``)
        when the reduction actually spans devices; monolithic when
        ``ndev <= 1`` (no collective to overlap, bucketing is pure
        overhead);
      * ``0``     — force the monolithic single-bucket path (PR 2 shape);
      * ``N > 0`` — explicit size, rounded up to ``block * ndev`` so every
        per-device segment of every bucket stays aligned with the
        quantization scale blocks.

    The plan is pure static metadata — slicing happens at trace time, so
    the compiled program sees fixed bucket shapes.
    """
    align = block * max(1, ndev)
    plans = []
    for n in shard_sizes:
        n = int(n)
        if bucket_elems is None:
            if ndev <= 1:
                b = 0
            else:
                from ..launch.roofline import choose_bucket_elems
                b = choose_bucket_elems(n, ndev, block=block)
        else:
            b = int(bucket_elems)
        plans.append(bucket_slices(n, b, align=align))
    return tuple(plans)


# ---------------------------------------------------------------------------
# host-timestamp telemetry (dataflow-ordered, multi-device safe)

def _host_stamp(_probe):
    t = time.perf_counter()
    whole = float(int(t))
    return np.asarray([whole, t - whole], np.float32)


def stamp(dep: jnp.ndarray):
    """Host timestamp ordered by dataflow: fires after ``dep`` exists.

    Returns ``(t, dep')`` where ``t`` is a (2,) f32 ``[whole, frac]``
    seconds pair and ``dep'`` equals ``dep`` but additionally depends on
    ``t`` — thread ``dep'`` (not ``dep``) into downstream compute so the
    stamp is pinned *between* producer and consumer.  The callback is
    deliberately unordered: ``ordered=True`` is unsupported/unsafe on
    multi-device programs, and dataflow gives the only ordering we need.
    """
    probe = (jnp.reshape(dep, (-1,))[0].astype(jnp.float32)
             if dep.size else jnp.float32(0))
    t = io_callback(_host_stamp, jax.ShapeDtypeStruct((2,), jnp.float32),
                    probe, ordered=False)
    dep = dep + (t[0] * 0).astype(dep.dtype)
    return t, dep


def delta_seconds(t0, t1):
    """Seconds between two :func:`stamp` pairs, f32-precision-safe."""
    return (t1[0] - t0[0]) + (t1[1] - t0[1])


# ---------------------------------------------------------------------------
# the bucketed pipeline

def allreduce_shards_bucketed(compressor, g_shards, state, rng, *,
                              mesh=None, axis=None,
                              bucket_elems: Optional[int] = None,
                              telemetry: bool = False):
    """Per-bucket compressed reduction over flat gradient shards.

    Entry point behind ``GradCompressor.allreduce_shards`` (see its
    docstring for the user-facing contract).  With ``telemetry=True``
    returns a third element ``{"comm_seconds", "comm_t0"}``:
    ``comm_seconds`` is the wall span of the comm *window* — earliest
    bucket pre-stamp to latest bucket post-stamp in actual execution order
    (buckets run out of program order under the latency-hiding scheduler,
    so min/max over stamps, not first/last) — and ``comm_t0`` is the
    absolute (2,) f32 reference stamp the window is measured from, for
    correlating with step-level stamps.
    """
    if mesh is None:
        from .sharding import activation_mesh
        mesh = activation_mesh()
    if axis is None and mesh is not None:
        from .sharding import fsdp_axis
        axis = fsdp_axis(mesh)
    axes = (axis,) if isinstance(axis, str) else tuple(axis or ())
    ndev = (int(np.prod([mesh.shape[a] for a in axes]))
            if (mesh is not None and axes) else 1)

    plans = plan_buckets([g.shape[0] for g in g_shards], ndev,
                         block=compressor.block, bucket_elems=bucket_elems)
    seed = _as_seed(rng)

    out_g, out_e = [], []
    pre_stamps, post_stamps = [], []
    for i, (g, e, plan) in enumerate(zip(g_shards, state.error, plans)):
        # rng None selects deterministic round-to-nearest (see _quantize)
        # — preserve it instead of xor-ing into a crash
        sseed = None if seed is None else \
            seed ^ jnp.uint32((_GOLDEN * (i + 1)) & 0xFFFFFFFF)
        n = int(g.shape[0])
        # device-major geometry (see module docstring): plan boundaries are
        # multiples of block*ndev whenever the plan has >1 bucket, so the
        # [ndev, seg] view and its column slices are always exact
        interleave = len(plan) > 1 and ndev > 1
        if interleave:
            seg = n // ndev
            g2 = g.reshape(ndev, seg)
            e2 = e.reshape(ndev, seg)
        deq_parts, err_parts = [], []
        for j, (start, stop) in enumerate(plan):
            if interleave:
                s0, s1 = start // ndev, stop // ndev
                g_b = g2[:, s0:s1].reshape(-1)
                e_b = e2[:, s0:s1].reshape(-1)
                off, stride = s0, seg
            else:
                g_b = g if len(plan) == 1 else g[start:stop]
                e_b = e if len(plan) == 1 else e[start:stop]
                off, stride = start, None
            if telemetry:
                t0, g_b = stamp(g_b)
                pre_stamps.append(t0)
            with jax.named_scope(f"comm_shard{i}_bucket{j}"):
                deq, err = compressor._allreduce_one(g_b, e_b, sseed, mesh,
                                                     axis, offset=off,
                                                     stride=stride)
            if telemetry:
                t1, deq = stamp(deq)
                post_stamps.append(t1)
            if interleave:
                deq = deq.reshape(ndev, -1)
                err = err.reshape(ndev, -1)
            deq_parts.append(deq)
            err_parts.append(err)
        if interleave:
            out_g.append(jnp.concatenate(deq_parts, axis=1).reshape(-1))
            out_e.append(jnp.concatenate(err_parts, axis=1).reshape(-1))
        else:
            out_g.append(deq_parts[0] if len(deq_parts) == 1
                         else jnp.concatenate(deq_parts))
            out_e.append(err_parts[0] if len(err_parts) == 1
                         else jnp.concatenate(err_parts))

    from .compression import FlatCompressionState
    new_state = FlatCompressionState(error=tuple(out_e))
    if telemetry:
        if pre_stamps:
            ref = pre_stamps[0]
            lo = jnp.stack([delta_seconds(ref, t) for t in pre_stamps]).min()
            hi = jnp.stack([delta_seconds(ref, t) for t in post_stamps]).max()
            tele = {"comm_seconds": hi - lo, "comm_t0": ref}
        else:
            tele = {"comm_seconds": jnp.float32(0),
                    "comm_t0": jnp.zeros((2,), jnp.float32)}
        return tuple(out_g), new_state, tele
    return tuple(out_g), new_state

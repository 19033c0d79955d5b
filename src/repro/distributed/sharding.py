"""Sharding rules: one table maps parameter-tree paths to PartitionSpecs.

Mesh axes (see launch/mesh.py):
    single-pod:  ("data", "model")            = (16, 16)
    multi-pod:   ("pod", "data", "model")     = (2, 16, 16)

``pod`` composes with ``data`` into the gradient/FSDP axis — specs use the
tuple ``("pod", "data")`` when the mesh has a pod axis, so the same rule
table serves both meshes (and any pod count).

Design:
  * tensor-parallel ("model") axis shards heads / MLP hidden / experts /
    vocab — the contraction patterns XLA turns into all-reduce or
    reduce-scatter per layer.
  * FSDP (ZeRO-3) optionally shards the *other* large axis of every weight
    over the data axis; optimizer states (Sophia m, h) inherit param specs,
    so Sophia trains with the same memory footprint as AdamW (paper Table 1)
    at any scale.
  * every rule is validated for divisibility; non-divisible dims fall back
    to replication (correct, just less sharded).
"""
from __future__ import annotations

import functools
import re
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

PyTree = Any


def fsdp_axis(mesh: Mesh):
    """The (composite) data axis: ("pod","data") on multi-pod meshes."""
    names = mesh.axis_names
    if "pod" in names:
        return ("pod", "data")
    return "data"


# ---------------------------------------------------------------------------
# activation-sharding context
#
# GSPMD propagation alone can drop the batch sharding mid-model (it may
# trade per-layer FSDP weight gathers for replicated activations, a
# catastrophic choice at 4k x 256).  Models therefore pin their residual
# streams / logits / expert buffers through ``constrain`` — a no-op unless
# the launcher installs a mesh via ``set_activation_mesh``.

_ACT_CTX = {"mesh": None, "seq_shard": False}


def set_activation_mesh(mesh: Optional[Mesh]) -> None:
    _ACT_CTX["mesh"] = mesh


def activation_mesh() -> Optional[Mesh]:
    return _ACT_CTX["mesh"]


def set_sequence_sharding(on: bool) -> None:
    """Megatron-style sequence parallelism: the residual stream between
    blocks is sharded over ("model") along the SEQUENCE dim.  Saved remat
    carries shrink by the model-axis size and the post-block all-reduce
    becomes reduce-scatter(+all-gather at the next attention) at half the
    volume.  Hillclimb lever; see EXPERIMENTS.md §Perf."""
    _ACT_CTX["seq_shard"] = on


def residual_axes():
    """Logical axes for the (B, S, D) residual stream."""
    if _ACT_CTX["seq_shard"]:
        return ("batch", "model", None)
    return ("batch", None, None)


def constrain(x, *axes):
    """with_sharding_constraint by logical axis name.

    axes: one entry per dim of x — "batch" (data axis), "model", or None.
    Dims that don't divide evenly fall back to unsharded.
    """
    mesh = _ACT_CTX["mesh"]
    if mesh is None:
        return x

    def resolve(ax, size):
        if ax is None:
            return None
        phys = batch_axis(mesh) if ax == "batch" else ax
        n = (int(np.prod([mesh.shape[a] for a in phys]))
             if isinstance(phys, tuple) else mesh.shape[phys])
        return phys if size % n == 0 else None

    spec = P(*[resolve(a, s) for a, s in zip(axes, x.shape)])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_axis(mesh: Mesh):
    return fsdp_axis(mesh)


def per_device(fn, in_specs, out_specs, batch: int):
    """Run ``fn`` per device under ``jax.shard_map`` over the installed
    activation mesh; ``fn`` itself when none (or a one-device mesh) is.

    XLA cannot partition a Pallas TPU (Mosaic) kernel, so a kernel entry
    point called under a mesh maps itself over the batch.  The specs are
    PartitionSpecs naming the logical axis ``"batch"``, which resolves to
    the mesh's batch axis when ``batch`` divides its size and to
    replication otherwise (every device then computes the whole call).
    ``fn`` receives the index of its batch shard as a leading argument (0
    when replicated)."""
    mesh = _ACT_CTX["mesh"]
    if mesh is None or mesh.size == 1:
        return functools.partial(fn, 0)
    ax = batch_axis(mesh)
    axes = ax if isinstance(ax, tuple) else (ax,)
    n = int(np.prod([mesh.shape[a] for a in axes]))
    phys = ax if batch % n == 0 else None

    def resolve(tree):
        return jax.tree.map(
            lambda sp: P(*[phys if a == "batch" else a for a in sp]), tree,
            is_leaf=lambda x: isinstance(x, P))

    def body(*args):
        idx = jnp.int32(0)
        if phys is not None:
            for a in axes:
                idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return fn(idx, *args)

    return jax.shard_map(body, mesh=mesh, in_specs=resolve(in_specs),
                         out_specs=resolve(out_specs), check_vma=False)


# ---------------------------------------------------------------------------
# rule table: (path regex, builder(dims, model_ax, fsdp_ax) -> P)
# paths look like: "['layers']['attn']['wq']" from jax.tree_util.keystr


def _rules(model="model"):
    M = model
    return [
        # embeddings: vocab over TP, d_model over FSDP
        (r"\['embed'\]\['tok'\]$",      lambda f: P(M, f)),
        (r"\['embed'\]\['unembed'\]$",  lambda f: P(f, M)),
        (r"\['embed'\]\['pos'\]$",      lambda f: P(None, f)),
        # attention
        (r"\['wq'\]$",                  lambda f: P(f, M)),
        (r"\['wk'\]$",                  lambda f: P(f, M)),
        (r"\['wv'\]$",                  lambda f: P(f, M)),
        (r"\['wo'\]$",                  lambda f: P(M, f)),
        (r"\['b[qkv]'\]$",              lambda f: P(M)),
        # dense MLP / shared experts / rwkv channel-mix
        (r"\['w_gate'\]$",              lambda f: P(f, M)),
        (r"\['w_up'\]$",                lambda f: P(f, M)),
        (r"\['w_down'\]$",              lambda f: P(M, f)),
        (r"\['b_up'\]$",                lambda f: P(M)),
        (r"\['b_down'\]$",              lambda f: P()),
        # MoE experts: E over TP (expert parallelism)
        (r"\['moe'\]\['router'\]$",     lambda f: P(f, None)),
        (r"\['moe'\]\['w_gate'\]$",     lambda f: P(M, f, None)),
        (r"\['moe'\]\['w_up'\]$",       lambda f: P(M, f, None)),
        (r"\['moe'\]\['w_down'\]$",     lambda f: P(M, None, f)),
        # rwkv time-mix
        (r"\['tm'\]\['w[rkvg]'\]$",     lambda f: P(f, M)),
        (r"\['tm'\]\['wo'\]$",          lambda f: P(M, f)),
        (r"\['tm'\]\['wa'\]$",          lambda f: P(f, None)),
        (r"\['tm'\]\['wb'\]$",          lambda f: P(None, M)),
        (r"\['tm'\]\['w0'\]$",          lambda f: P(M)),
        (r"\['tm'\]\['u'\]$",           lambda f: P(M, None)),
        (r"\['tm'\]\['mu'\]$",          lambda f: P(None, None)),
        (r"\['cm'\]\['wk'\]$",          lambda f: P(f, M)),
        (r"\['cm'\]\['wv'\]$",          lambda f: P(M, f)),
        (r"\['cm'\]\['wr'\]$",          lambda f: P(f, M)),
        # griffin RG-LRU
        (r"\['w_in'\]$",                lambda f: P(f, M)),
        (r"\['conv_k'\]$",              lambda f: P(None, M)),
        (r"\['conv_b'\]$",              lambda f: P(M)),
        (r"\['lam'\]$",                 lambda f: P(M)),
        (r"\['w_[ax]'\]$",              lambda f: P(None, M)),
        (r"\['b_[ax]'\]$",              lambda f: P(M)),
        (r"\['w_out'\]$",               lambda f: P(M, f)),
        # frontends
        (r"\['patch_proj'\]$",          lambda f: P(f, None)),
        (r"\['frame_proj'\]$",          lambda f: P(f, None)),
    ]


def _spec_for(path: str, shape, n_prefix: int, mesh: Mesh,
              fsdp: bool) -> P:
    """Match path against the rule table; prepend None for stacked axes;
    drop shardings that don't divide."""
    f_ax = fsdp_axis(mesh) if fsdp else None
    for pat, builder in _rules():
        if re.search(pat, path):
            spec = builder(f_ax)
            break
    else:
        spec = P()  # replicate (norm scales, biases, scalars)

    dims = list(spec) + [None] * (len(shape) - n_prefix - len(spec))
    dims = [None] * n_prefix + dims
    dims = dims[:len(shape)]

    # divisibility check: replicate any axis that doesn't divide
    def size_of(ax):
        if ax is None:
            return 1
        if isinstance(ax, tuple):
            return int(np.prod([mesh.shape[a] for a in ax]))
        return mesh.shape[ax]

    fixed = [ax if (ax is None or s % size_of(ax) == 0) else None
             for ax, s in zip(dims, shape)]
    while fixed and fixed[-1] is None:
        fixed.pop()
    return P(*fixed)


_STACK_KEYS = ("layers", "groups", "tail", "encoder", "decoder")


def partition_params(params_shape: PyTree, mesh: Mesh, *,
                     fsdp: bool = True) -> PyTree:
    """Map a (ShapeDtypeStruct or array) param tree to PartitionSpecs."""

    def spec(path_entries, leaf):
        path = jax.tree_util.keystr(path_entries)
        n_prefix = 1 if any(f"['{k}']" in path for k in _STACK_KEYS) else 0
        return _spec_for(path, leaf.shape, n_prefix, mesh, fsdp)

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def param_shardings(params_shape: PyTree, mesh: Mesh, *,
                    fsdp: bool = True) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        partition_params(params_shape, mesh, fsdp=fsdp))


# ---------------------------------------------------------------------------
# batch / activation / cache specs


def batch_specs(batch_shape: PyTree, mesh: Mesh) -> PyTree:
    """Shard the leading (batch) dim of every input over the data axis."""
    b_ax = batch_axis(mesh)

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        dims = [b_ax] + [None] * (leaf.ndim - 1)
        size = (np.prod([mesh.shape[a] for a in b_ax])
                if isinstance(b_ax, tuple) else mesh.shape[b_ax])
        if leaf.shape[0] % size != 0:
            return P(*([None] * leaf.ndim))
        return P(*dims)

    return jax.tree.map(spec, batch_shape)


def cache_specs(cache_shape: PyTree, mesh: Mesh) -> PyTree:
    """KV caches: (L, B, S, Hkv, hd) — batch over data, heads over model.

    MQA (Hkv=1) and rwkv/griffin states fall back per-dim on divisibility.
    """
    b_ax = batch_axis(mesh)

    def size_of(ax):
        return (np.prod([mesh.shape[a] for a in ax])
                if isinstance(ax, tuple) else mesh.shape[ax])

    def spec(leaf):
        if leaf.ndim == 0:
            return P()
        dims = [None] * leaf.ndim
        # find the batch dim: first dim after an optional layer-stack dim
        bdim = 1 if leaf.ndim >= 3 else 0
        if leaf.shape[bdim] % size_of(b_ax) == 0:
            dims[bdim] = b_ax
        # shard the first divisible dim after batch over "model":
        # attention caches (L,B,S,Hkv,hd) get SEQUENCE-sharded KV (the
        # production long-context layout; softmax over the sharded S axis
        # costs two tiny all-reduces), rwkv states get head-sharded,
        # griffin recurrences get width-sharded.
        for d in range(bdim + 1, leaf.ndim):
            if leaf.shape[d] % mesh.shape["model"] == 0:
                dims[d] = "model"
                break
        return P(*dims)

    return jax.tree.map(spec, cache_shape)


def to_shardings(specs: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda x: isinstance(x, P))

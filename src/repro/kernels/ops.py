"""Per-pytree wrappers around the fused kernels (test/bench harness).

Each leaf is flattened, zero-padded to the block size, streamed through the
Pallas kernel, and reshaped back.  Padding is benign for every fused op
(p=m=h=g=0 stays 0; clip counts on padding are masked out).

NOTE: the production train step does NOT go through these wrappers — the
per-leaf pad/unpad round-trip here is exactly what the flat-buffer engine
(core/engine.py) eliminates by raveling the whole tree into block-padded
dtype shards once at init.  These remain as the direct per-tensor harness
for kernel unit tests (tests/test_kernels.py) and micro-benchmarks.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import ref
from .sophia_update import (BLOCK, adamw_fused_block, hessian_ema_block,
                            sophia_fused_block)

PyTree = Any


def _flat_pad(x, block):
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def sophia_fused_apply(params: PyTree, m: PyTree, h: PyTree, grads: PyTree,
                       *, lr, beta1: float, gamma: float, eps: float,
                       weight_decay: float, clip_threshold: float = 1.0,
                       block: int = BLOCK, interpret: bool | None = None):
    """Fused Algorithm-3 apply over a whole parameter tree.

    Returns (new_params, new_m, clip_fraction)."""
    lr = jnp.asarray(lr, jnp.float32)
    total = 0
    clipped = []

    def one(p, m_, h_, g_):
        nonlocal total
        flat_p, pad = _flat_pad(p, block)
        flat_m, _ = _flat_pad(m_, block)
        flat_h, _ = _flat_pad(h_, block)
        flat_g, _ = _flat_pad(g_, block)
        np_, nm, nclip = sophia_fused_block(
            flat_p, flat_m, flat_h, flat_g, lr, beta1=beta1, gamma=gamma,
            eps=eps, weight_decay=weight_decay,
            clip_threshold=clip_threshold, block=block, interpret=interpret)
        n = p.size
        total += n
        # padding zeros: raw = 0/eps = 0 -> |raw| < rho -> never counted
        clipped.append(nclip.astype(jnp.float32).sum())
        return (np_[:n].reshape(p.shape).astype(p.dtype),
                nm[:n].reshape(p.shape).astype(m_.dtype))

    out = jax.tree.map(one, params, m, h, grads)
    new_p = jax.tree.map(lambda o: o[0], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda o: o[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    clip_fraction = (sum(clipped) / total).astype(jnp.float32)
    return new_p, new_m, clip_fraction


def hessian_ema_apply(h: PyTree, est: PyTree, *, beta2: float,
                      scale: float = 1.0, block: int = BLOCK,
                      interpret: bool | None = None) -> PyTree:
    """Fused EMA refresh of the diagonal-Hessian state (line 9)."""

    def one(h_, e_):
        flat_h, _ = _flat_pad(h_, block)
        flat_e, _ = _flat_pad(e_, block)
        out = hessian_ema_block(flat_h, flat_e, beta2=beta2, scale=scale,
                                block=block, interpret=interpret)
        return out[:h_.size].reshape(h_.shape).astype(h_.dtype)

    return jax.tree.map(one, h, est)


def adamw_fused_apply(params: PyTree, m: PyTree, v: PyTree, grads: PyTree,
                      *, lr, step, beta1: float, beta2: float, eps: float,
                      weight_decay: float, block: int = BLOCK,
                      interpret: bool | None = None):
    lr = jnp.asarray(lr, jnp.float32)
    step = jnp.asarray(step, jnp.float32)

    def one(p, m_, v_, g_):
        fp, _ = _flat_pad(p, block)
        fm, _ = _flat_pad(m_, block)
        fv, _ = _flat_pad(v_, block)
        fg, _ = _flat_pad(g_, block)
        np_, nm, nv = adamw_fused_block(fp, fm, fv, fg, lr, step,
                                        beta1=beta1, beta2=beta2, eps=eps,
                                        weight_decay=weight_decay,
                                        block=block, interpret=interpret)
        n = p.size
        return (np_[:n].reshape(p.shape).astype(p.dtype),
                nm[:n].reshape(p.shape).astype(m_.dtype),
                nv[:n].reshape(p.shape).astype(v_.dtype))

    out = jax.tree.map(one, params, m, v, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)

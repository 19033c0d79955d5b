"""Flat-buffer optimizer engine: one update path for every optimizer.

The paper's pitch is negligible per-step overhead (Section 4.3); the way to
keep that true in a production system is to make the optimizer update pure
streaming work.  This engine ravels the parameter pytree's *optimizer state*
once at init into a small set of dtype-homogeneous flat shards — one shard
per parameter dtype, tail-padded to a multiple of the kernel block — and
keeps it flat forever.  A static :class:`ShardLayout` (leaf offsets + shapes)
maps between the model-facing pytree view and the flat view, so each train
step does exactly:

    params, grads --ravel-->  one flat buffer per dtype shard
    one pallas_call grid sweep per shard (or the pure-jnp reference)
    flat params   --slice-->  parameter pytree

There is no per-leaf pad/unpad anywhere in the step: the single tail pad per
shard is fused into the ravel concatenate, and padded elements are fixed
points of every update rule here (p = m = h = g = 0 stays 0), so the pad is
paid once at init, never per step.  This mirrors how AdaHessian (Yao et al.,
2021) and distributed Shampoo (Anil et al., 2021) organize second-order
state, and makes the Sophia-vs-AdamW overhead comparison apples-to-apples:
both run through literally the same machinery.

Backends:
    * ``reference`` — pure jnp over the flat shards (kernels/ref.py math);
    * ``pallas``    — fused kernels (kernels/sophia_update.py), one grid
      sweep per shard, clip-fraction telemetry computed in-kernel.

Swapping one for the other is a one-line change and must agree to fp32
tolerance (tests/test_engine.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import per_device
from ..kernels import ref as kref
from ..kernels import sophia_update as kblk

PyTree = Any

BLOCK = kblk.BLOCK

#: trainer-level optimizer names -> engine family
FAMILIES = {
    "sophia_g": "sophia",
    "sophia_h": "sophia",
    "adamw": "adamw",
    "lion": "lion",
    "signgd": "signgd",
    "adahessian": "adahessian",
    "sgd": "sgd",
}

_CURVATURE_FAMILIES = ("sophia", "adamw", "adahessian")
_HESSIAN_AWARE = ("sophia", "adahessian")


def hessian_aware_optimizer(optimizer: str) -> bool:
    """True for trainer-level optimizer names whose curvature refreshes
    out-of-band (the Algorithm-3 cadence).  The single source of truth the
    trainer / drivers / benchmarks consult for the refresh flag — never a
    hardcoded optimizer-name tuple — without constructing an engine."""
    return FAMILIES.get(optimizer) in _HESSIAN_AWARE


# ---------------------------------------------------------------------------
# Static layout


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """Static map between a parameter pytree and its flat dtype shards."""

    treedef: Any
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    leaf_dtypes: Tuple[Any, ...]
    leaf_shard: Tuple[int, ...]    # which shard each leaf lives in
    leaf_offset: Tuple[int, ...]   # element offset of the leaf in its shard
    shard_dtypes: Tuple[Any, ...]
    shard_sizes: Tuple[int, ...]   # padded: multiples of ``block``
    shard_used: Tuple[int, ...]    # true element counts (pad excluded)
    block: int

    @property
    def n_shards(self) -> int:
        return len(self.shard_sizes)

    @property
    def n_params(self) -> int:
        return sum(self.shard_used)

    def bucket_slices(self, bucket_elems: int, *,
                      align: int = 256) -> Tuple[Tuple[Tuple[int, int], ...],
                                                 ...]:
        """Per-shard bucket views (see :func:`bucket_slices`): the static
        slice table the bucketed compressed all-reduce
        (distributed/overlap.py) iterates, one tuple of (start, stop)
        pairs per flat shard."""
        return tuple(bucket_slices(int(n), bucket_elems, align=align)
                     for n in self.shard_sizes)

    def manifest(self) -> dict:
        """JSON-serializable summary (stored in checkpoint manifests)."""
        return {
            "block": self.block,
            "n_leaves": len(self.leaf_shapes),
            "n_params": self.n_params,
            "shards": [
                {"dtype": str(jnp.dtype(d)), "size": int(s), "used": int(u)}
                for d, s, u in zip(self.shard_dtypes, self.shard_sizes,
                                   self.shard_used)
            ],
        }


def bucket_slices(n: int, bucket_elems: int, *,
                  align: int = 256) -> Tuple[Tuple[int, int], ...]:
    """Static (start, stop) views partitioning a flat shard into buckets.

    ``bucket_elems`` is rounded up to a multiple of ``align`` (the
    quantization scale block, possibly multiplied by the collective axis
    size so per-device segments stay block-aligned); the last bucket takes
    the remainder.  ``bucket_elems <= 0`` means one bucket — the monolithic
    view.  Every boundary is a multiple of ``align``, which is what keeps
    bucketed quantization bit-identical to whole-shard quantization: the
    per-256-block scales and the (seed, global element index) rounding hash
    never see the bucket structure (distributed/overlap.py)."""
    if n <= 0:
        return ()
    if bucket_elems <= 0 or bucket_elems >= n:
        return ((0, n),)
    b = -(-bucket_elems // align) * align
    if b <= 0 or n % align != 0:
        return ((0, n),)
    edges = list(range(0, n, b)) + [n]
    return tuple((edges[i], edges[i + 1]) for i in range(len(edges) - 1))


def build_layout(params: PyTree, *, block: int = BLOCK) -> ShardLayout:
    """Group leaves into dtype-homogeneous shards, assign static offsets."""
    leaves, treedef = jax.tree.flatten(params)
    leaf_shapes = tuple(tuple(l.shape) for l in leaves)
    leaf_dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    shard_dtypes: list = []
    used: list = []
    leaf_shard, leaf_offset = [], []
    for shape, dt in zip(leaf_shapes, leaf_dtypes):
        if dt not in shard_dtypes:
            shard_dtypes.append(dt)
            used.append(0)
        si = shard_dtypes.index(dt)
        leaf_shard.append(si)
        leaf_offset.append(used[si])
        used[si] += math.prod(shape)
    sizes = tuple(-(-u // block) * block for u in used)
    return ShardLayout(treedef=treedef, leaf_shapes=leaf_shapes,
                       leaf_dtypes=leaf_dtypes, leaf_shard=tuple(leaf_shard),
                       leaf_offset=tuple(leaf_offset),
                       shard_dtypes=tuple(shard_dtypes), shard_sizes=sizes,
                       shard_used=tuple(used), block=block)


def ravel_shards(layout: ShardLayout, tree: PyTree, *,
                 dtype=None) -> Tuple[jnp.ndarray, ...]:
    """Pytree -> flat shards.  One concatenate per shard; the tail pad is a
    constant-zeros operand of that concatenate, not a per-leaf pad op.

    ``dtype`` overrides the shard dtype (grads/estimates ravel to fp32)."""
    leaves = jax.tree.leaves(tree)
    parts: list = [[] for _ in layout.shard_sizes]
    for leaf, si in zip(leaves, layout.leaf_shard):
        tdt = dtype if dtype is not None else layout.shard_dtypes[si]
        parts[si].append(leaf.reshape(-1).astype(tdt))
    out = []
    for si, chunks in enumerate(parts):
        tdt = dtype if dtype is not None else layout.shard_dtypes[si]
        pad = layout.shard_sizes[si] - layout.shard_used[si]
        if pad:
            chunks = chunks + [jnp.zeros((pad,), tdt)]
        out.append(chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks))
    return tuple(out)


def unravel_shards(layout: ShardLayout,
                   shards: Tuple[jnp.ndarray, ...]) -> PyTree:
    """Flat shards -> pytree (static slices, no pad/unpad)."""
    leaves = []
    for shape, dt, si, off in zip(layout.leaf_shapes, layout.leaf_dtypes,
                                  layout.leaf_shard, layout.leaf_offset):
        n = math.prod(shape)
        leaves.append(shards[si][off:off + n].reshape(shape).astype(dt))
    return jax.tree.unflatten(layout.treedef, leaves)


# ---------------------------------------------------------------------------
# Engine state


class EngineState(NamedTuple):
    """Optimizer state over flat shards (lives flat across the whole run).

    ``m`` is the first-moment slot; ``h`` is the curvature / second-moment
    slot (Sophia's diagonal-Hessian EMA, AdamW's v, AdaHessian's EMA of
    squared estimates) — ``()`` for families that don't need one."""

    count: jnp.ndarray            # step counter t
    m: Tuple[jnp.ndarray, ...]
    h: Tuple[jnp.ndarray, ...]
    hess_count: jnp.ndarray       # number of Hessian refreshes so far
    clip_fraction: jnp.ndarray    # telemetry (paper Fig 9a); 0 if untracked


# ---------------------------------------------------------------------------
# The engine


class OptimizerEngine:
    """One update path for reference and fused optimizers over flat shards.

    Usage::

        eng = OptimizerEngine("sophia_g", hypers=dict(beta1=.96, beta2=.99,
                              gamma=.05, eps=1e-12, weight_decay=.2,
                              clip_threshold=1.0), backend="pallas")
        opt_state = eng.init(params)
        params, opt_state = eng.step(opt_state, params, grads, lr)
        # unified pipeline: refresh fused into the step, flag traced
        params, opt_state = eng.step_with_refresh(
            opt_state, params, g_sh, lr, est_shards, scale, do_refresh)
        # out-of-band form (tests/tooling):
        opt_state = eng.update_hessian(opt_state, est, scale=B, params=params)
    """

    def __init__(self, optimizer: str, *, hypers: dict,
                 backend: str = "reference", block: int = BLOCK,
                 state_dtype=jnp.float32,
                 interpret: Optional[bool] = None):
        if optimizer not in FAMILIES:
            raise ValueError(f"unknown optimizer {optimizer!r}")
        if backend not in ("reference", "pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self.optimizer = optimizer
        self.family = FAMILIES[optimizer]
        self.hypers = dict(hypers)
        self.backend = backend
        self.block = block
        self.state_dtype = jnp.dtype(state_dtype)
        self.interpret = interpret
        self._layouts: dict = {}

    # -- properties ---------------------------------------------------------

    @property
    def needs_curvature(self) -> bool:
        return self.family in _CURVATURE_FAMILIES

    @property
    def hessian_aware(self) -> bool:
        return self.family in _HESSIAN_AWARE

    @property
    def tracks_clip_fraction(self) -> bool:
        return self.family == "sophia"

    # -- layout -------------------------------------------------------------

    def layout(self, params: PyTree) -> ShardLayout:
        leaves = jax.tree.leaves(params)
        key = (jax.tree.structure(params),
               tuple(tuple(l.shape) for l in leaves),
               tuple(str(jnp.dtype(l.dtype)) for l in leaves))
        lay = self._layouts.get(key)
        if lay is None:
            lay = build_layout(params, block=self.block)
            self._layouts[key] = lay
        return lay

    def describe(self, params: PyTree) -> dict:
        return self.layout(params).manifest()

    # -- init ---------------------------------------------------------------

    def init(self, params: PyTree) -> EngineState:
        lay = self.layout(params)

        def zeros():
            return tuple(jnp.zeros((s,), self.state_dtype)
                         for s in lay.shard_sizes)

        # m and h get buffers of their own: the train step donates the
        # state, and a buffer donated twice is an error
        return EngineState(
            count=jnp.zeros((), jnp.int32),
            m=zeros(),
            h=zeros() if self.needs_curvature else (),
            hess_count=jnp.zeros((), jnp.int32),
            clip_fraction=jnp.zeros((), jnp.float32),
        )

    # -- the step -----------------------------------------------------------

    @jax.named_scope("optimizer.ravel")
    def ravel_grads(self, params: PyTree,
                    grads: PyTree) -> Tuple[jnp.ndarray, ...]:
        """Grads pytree -> fp32 flat shards in this engine's layout — the
        representation the compressed all-reduce (distributed/compression)
        and :meth:`step_shards` consume."""
        return ravel_shards(self.layout(params), grads, dtype=jnp.float32)

    def step(self, state: EngineState, params: PyTree, grads: PyTree,
             lr) -> tuple:
        """One optimizer step.  ``lr`` is a traced scalar (the trainer
        evaluates the schedule once, outside the engine).

        Returns ``(new_params, new_state)``."""
        return self.step_shards(state, params, self.ravel_grads(params, grads),
                                lr)

    @jax.named_scope("optimizer.update")
    def step_shards(self, state: EngineState, params: PyTree,
                    g_sh: Tuple[jnp.ndarray, ...], lr) -> tuple:
        """:meth:`step` with the gradients already raveled to flat fp32
        shards (the trainer ravels once, optionally runs the in-collective
        compression on the flat view, then lands here)."""
        return self._apply_shards(state, params, g_sh, lr,
                                  None, None, None)

    def _apply_shards(self, state: EngineState, params: PyTree, g_sh, lr,
                      e_sh, flag, scale) -> tuple:
        """Shared shard loop for the plain step (``e_sh is None``) and the
        fused update+refresh (``e_sh``/``flag``/``scale`` set)."""
        lay = self.layout(params)
        lr = jnp.asarray(lr, jnp.float32)
        c1 = (state.count + 1).astype(jnp.float32)  # bias-correction step
        p_sh = ravel_shards(lay, params)
        new_p, new_m, new_h = [], [], []
        nclip = jnp.zeros((), jnp.float32)
        for i in range(lay.n_shards):
            h_i = state.h[i] if self.needs_curvature else None
            e_i = e_sh[i] if e_sh is not None else None
            p_i, m_i, h_i, nclip_i = self._step_shard(
                p_sh[i], state.m[i], h_i, g_sh[i], e_i, lr, c1, flag, scale)
            new_p.append(p_i)
            new_m.append(m_i)
            if h_i is not None:
                new_h.append(h_i)
            if nclip_i is not None:
                nclip = nclip + nclip_i.astype(jnp.float32)
        clip_fraction = (nclip / lay.n_params if self.tracks_clip_fraction
                         else state.clip_fraction)
        kw = {} if flag is None else \
            dict(hess_count=state.hess_count + flag.astype(jnp.int32))
        new_state = state._replace(
            count=state.count + 1, m=tuple(new_m),
            h=tuple(new_h) if new_h else state.h,
            clip_fraction=jnp.asarray(clip_fraction, jnp.float32), **kw)
        return unravel_shards(lay, tuple(new_p)), new_state

    def _step_shard(self, p, m, h, g, e, lr, c1, flag, scale):
        """Dispatch one flat shard to the backend — the plain update when
        ``e`` is None, the fused update+refresh otherwise.  Returns
        (p', m', h' or None, n_clipped or None)."""
        hp = self.hypers
        fused = self.backend == "pallas"
        fam = self.family

        def sweep(kernel, vecs, scalars, **args):
            """A Pallas sweep, per device over its segment of the
            FSDP-sharded flat shards under an activation mesh (XLA cannot
            partition the kernel)."""
            spec = P("batch")
            fn = per_device(
                lambda _, v, sc: kernel(*v, *sc, **args, block=self.block,
                                        interpret=self.interpret),
                (spec, P()), spec, vecs[0].shape[0] // self.block)
            return fn(tuple(vecs), tuple(scalars))

        if fam == "sophia":
            args = dict(beta1=hp["beta1"], gamma=hp["gamma"], eps=hp["eps"],
                        weight_decay=hp["weight_decay"],
                        clip_threshold=hp["clip_threshold"])
            if e is not None:
                args["beta2"] = hp["beta2"]
                if fused:
                    p2, m2, h2, nclip = sweep(
                        kblk.sophia_refresh_fused_block, (p, m, h, g, e),
                        (lr, flag, scale), **args)
                    return p2, m2, h2, jnp.sum(nclip)
                p2, m2, h2, nclip = kref.sophia_step_refresh_ref(
                    p, m, h, g, e, lr=lr, flag=flag, scale=scale, **args)
                return p2, m2, h2, nclip
            if fused:
                p2, m2, nclip = sweep(kblk.sophia_fused_block,
                                      (p, m, h, g), (lr,), **args)
                return p2, m2, h, jnp.sum(nclip)
            p2, m2, nclip = kref.sophia_fused_ref(p, m, h, g, lr=lr, **args)
            return p2, m2, h, nclip
        if fam == "adahessian":
            args = dict(beta1=hp["beta1"], beta2=hp["beta2"], eps=hp["eps"],
                        weight_decay=hp["weight_decay"])
            if e is not None:
                if fused:
                    p2, m2, h2 = sweep(
                        kblk.adahessian_refresh_fused_block,
                        (p, m, h, g, e), (lr, flag, scale, c1), **args)
                else:
                    p2, m2, h2 = kref.adahessian_step_refresh_ref(
                        p, m, h, g, e, lr=lr, flag=flag, scale=scale,
                        step=c1, **args)
                return p2, m2, h2, None
            if fused:
                p2, m2 = sweep(kblk.adahessian_fused_block, (p, m, h, g),
                               (lr, c1), **args)
            else:
                p2, m2 = kref.adahessian_fused_ref(p, m, h, g, lr=lr,
                                                   step=c1, **args)
            return p2, m2, h, None
        if fam == "adamw":
            args = dict(beta1=hp["beta1"], beta2=hp["beta2"], eps=hp["eps"],
                        weight_decay=hp["weight_decay"])
            if fused:
                p2, m2, v2 = sweep(kblk.adamw_fused_block, (p, m, h, g),
                                   (lr, c1), **args)
            else:
                p2, m2, v2 = kref.adamw_fused_ref(p, m, h, g, lr=lr, step=c1,
                                                  **args)
            return p2, m2, v2, None
        if fam == "lion":
            args = dict(beta1=hp["beta1"], beta2=hp["beta2"],
                        weight_decay=hp["weight_decay"])
            if fused:
                p2, m2 = sweep(kblk.lion_fused_block, (p, m, g), (lr,),
                               **args)
            else:
                p2, m2 = kref.lion_fused_ref(p, m, g, lr=lr, **args)
            return p2, m2, None, None
        if fam == "signgd":
            args = dict(beta1=hp["beta1"], weight_decay=hp["weight_decay"])
            if fused:
                p2, m2 = sweep(kblk.signgd_fused_block, (p, m, g), (lr,),
                               **args)
            else:
                p2, m2 = kref.signgd_fused_ref(p, m, g, lr=lr, **args)
            return p2, m2, None, None
        if fam == "sgd":
            args = dict(momentum=hp.get("momentum", 0.0))
            if fused:
                p2, m2 = sweep(kblk.sgd_fused_block, (p, m, g), (lr,),
                               **args)
            else:
                p2, m2 = kref.sgd_fused_ref(p, m, g, lr=lr, **args)
            return p2, m2, None, None
        raise ValueError(self.family)

    # -- fused step + Hessian-EMA refresh (the unified curvature pipeline) --

    def _est_shards(self, lay: ShardLayout, est) -> Tuple[jnp.ndarray, ...]:
        """Estimate as flat fp32 shards: a tuple matching the layout passes
        through untouched (the flat estimators' output — no params-shaped
        curvature tree ever materializes); a pytree ravels once."""
        if (isinstance(est, tuple) and len(est) == lay.n_shards
                and all(getattr(e, "ndim", None) == 1
                        and e.shape[0] == s
                        for e, s in zip(est, lay.shard_sizes))):
            return tuple(e.astype(jnp.float32) for e in est)
        return ravel_shards(lay, est, dtype=jnp.float32)

    @jax.named_scope("optimizer.update")
    def step_with_refresh(self, state: EngineState, params: PyTree,
                          g_sh: Tuple[jnp.ndarray, ...], lr, est, scale,
                          do_refresh) -> tuple:
        """One optimizer step with the Hessian-EMA refresh fused in.

        ``do_refresh`` is a *traced* 0/1 flag: when set, the curvature shard
        absorbs ``scale * est`` (Algorithm 3 line 9) in the same grid sweep
        that applies the update — h is read and written exactly once either
        way, so the unified train step compiles to a single program whose
        refresh branch adds no extra h traffic.  ``est`` is a tuple of flat
        fp32 shards (or a pytree, raveled once); ``scale`` is the GNB batch
        factor B, still a traced scalar.

        Semantically identical to ``update_hessian(...)`` followed by
        ``step_shards(...)`` when the flag is set, and to ``step_shards``
        alone when clear (tests/test_unified_step.py pins both).

        Returns ``(new_params, new_state)``."""
        if not self.hessian_aware:
            raise ValueError(
                f"step_with_refresh requires a hessian-aware family, "
                f"got {self.family!r} (use step/step_shards)")
        flag = jnp.asarray(do_refresh).astype(jnp.float32)
        scale = jnp.asarray(scale, jnp.float32)
        e_sh = self._est_shards(self.layout(params), est)
        return self._apply_shards(state, params, g_sh, lr, e_sh, flag, scale)

    # -- Hessian-EMA refresh (Algorithm 3 line 9, out-of-band form) ---------

    def update_hessian(self, state: EngineState, est, *,
                       scale=1.0, params: PyTree) -> EngineState:
        """Fold a fresh diagonal-Hessian estimate into the curvature shards.

        ``est`` is either a params-shaped pytree (raveled once) or already a
        tuple of flat fp32 shards in this engine's layout — the flat
        estimators (core/estimators.py) hand shards in directly, so no
        params-shaped curvature tree materializes.  ``scale`` is the GNB
        batch factor B (a traced scalar — it depends on the step's
        valid-token mask), folded into the EMA in-kernel so the scaled
        estimate never materializes.  AdaHessian squares the scaled
        estimate (its state is an EMA of squared estimates).

        The unified train step fuses this into :meth:`step_with_refresh`;
        this standalone form remains for tests and offline tooling."""
        if not self.hessian_aware:
            return state
        lay = self.layout(params)
        e_sh = self._est_shards(lay, est)
        beta2 = self.hypers["beta2"]
        square = self.family == "adahessian"
        new_h = []
        for h, e in zip(state.h, e_sh):
            if self.backend == "pallas":
                new_h.append(kblk.hessian_ema_block(
                    h, e, beta2=beta2, scale=scale, square=square,
                    block=self.block, interpret=self.interpret))
            else:
                new_h.append(kref.hessian_ema_ref(h, e, beta2=beta2,
                                                  scale=scale, square=square))
        return state._replace(h=tuple(new_h),
                              hess_count=state.hess_count + 1)

    # -- debugging / telemetry views ---------------------------------------

    def state_as_trees(self, state: EngineState, params: PyTree) -> dict:
        """Unravel m/h back into params-shaped pytrees (inspection only)."""
        lay = self.layout(params)
        out = {"m": unravel_shards(lay, state.m)}
        if state.h:
            out["h"] = unravel_shards(lay, state.h)
        return out


def flat_shard_spec(a, mesh=None):
    """PartitionSpec for one 1-D flat shard: sharded over the ``data`` mesh
    axis when divisible (FSDP-style), else replicated.  Shared by the
    engine's m/h shards and the compressor's error-feedback shards."""
    from jax.sharding import PartitionSpec as P
    if (mesh is not None and "data" in mesh.shape
            and a.shape[0] % mesh.shape["data"] == 0):
        return P("data")
    return P()


def engine_partition_specs(opt_state: EngineState, mesh=None) -> EngineState:
    """PartitionSpecs for an EngineState: flat shards are sharded over the
    ``data`` mesh axis when divisible (FSDP-style), else replicated."""
    from jax.sharding import PartitionSpec as P
    scalar = P()
    return EngineState(count=scalar,
                       m=tuple(flat_shard_spec(a, mesh) for a in opt_state.m),
                       h=tuple(flat_shard_spec(a, mesh) for a in opt_state.h),
                       hess_count=scalar, clip_fraction=scalar)

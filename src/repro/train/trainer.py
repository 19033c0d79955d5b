"""Training loop: ONE jitted step, Algorithm 3 under a traced refresh flag.

The host calls a single compiled ``train_step(state, batch, do_refresh)``
every step and flips the flag at the Hessian cadence (t % k == 0).  The
estimator sub-graph — and the optimizer dispatch — live inside the step
under ``lax.cond``: the refresh branch draws the diagonal-Hessian estimate
(GNB / Hutchinson / empirical-Fisher) on the reduced sub-batch directly as
the engine's flat fp32 shards and folds its EMA into the *same* Pallas
grid sweep that applies the update (``engine.step_with_refresh`` — h read
and written exactly once); the other branch is the ordinary fused step, so
the hot path's HBM traffic is byte-identical to a never-refreshing run.
Because the flag is traced there is exactly one XLA program per mesh
configuration — the elastic driver no longer compiles and caches a hot
step *and* a refresh step.

Every step shares:
  grad accumulation (microbatch scan, aux metrics ride the carry) ->
  global-norm clip (threshold 1.0, trigger telemetry) -> ravel to flat fp32
  shards -> [optional in-collective int8 compression over the fsdp axis,
  error feedback persisted as flat shards] -> fused engine update
  (+ flag-gated Hessian-EMA refresh; the estimator sub-batch gradient can
  optionally ride the same int8 collective, stateless — no error feedback
  at refresh sparsity).

The optimizer update itself is one engine call for *every* optimizer: the
engine (core/engine.py) keeps m/h as flat dtype-homogeneous shards and
executes the whole update as a single fused Pallas grid sweep per shard
(``fused_kernel=True``) or the identical-layout pure-jnp reference.  The
LR schedule is evaluated once per step and handed to the engine as a
traced scalar; the GNB batch factor B stays a traced scalar too.

The hot-path LM loss is logits-free: ``loss_fn`` routes the trunk's
*pre-norm* hidden states through ``models.loss.lm_loss`` (the Pallas
fused kernel by default — autotuned block sizes, the final norm applied
in VMEM inside the sweep; ``fused_loss=False`` falls back to the chunked
jnp sweep), so the ``[B*T, V]`` logits tensor never materializes on
ordinary steps.  The GNB refresh branch is logits-free too by default:
``yhat ~ softmax(logits)`` is drawn *inside* the kernel's vocab sweep
(``sampled_loss_fn`` -> ``gnb_ghat_flat_from_loss``) and B = the sweep's
valid-position count folds into the fused Hessian-EMA as a traced
scalar; the chunked fallback's refresh materializes the estimator
*sub-batch*'s logits once via ``logits_fn`` (its single chunked sweep
eliminates the second fp32 ``log_softmax`` copy, not the buffer itself).
The Hutchinson refresh crosses the fused loss through its ``custom_jvp``
twin, so the HVP no longer falls back to the chunked path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..core import (OptimizerEngine, clip_by_global_norm,
                    empirical_fisher_ghat_flat, gnb_ghat_flat,
                    gnb_ghat_flat_from_loss, hessian_aware_optimizer,
                    hutchinson_estimator_flat, linear_warmup_cosine,
                    constant, subsample_batch)
from ..distributed.compression import GradCompressor
from ..models import ModelConfig, get_model
from .train_state import TrainState

PyTree = Any

# Per-purpose RNG stream tags.  Every consumer derives its stream as
# fold_in(fold_in(rng, TAG), step) — never an arithmetic offset of the bare
# step: the old ``step + (1 << 20)`` compression offset collided with the
# estimator stream as soon as step >= 2**20.
RNG_TAG_HESS = 1           # estimator label sampling / probe draws
RNG_TAG_COMPRESS = 2       # gradient-compression stochastic rounding
RNG_TAG_HESS_COMPRESS = 3  # estimator-compression stochastic rounding


def _fold_rng(state: TrainState, tag: int) -> jax.Array:
    """Domain-separated per-step stream: (purpose tag, then step)."""
    return jax.random.fold_in(jax.random.fold_in(state.rng, tag), state.step)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    optimizer: str = "sophia_g"
    peak_lr: float = 4e-4
    total_steps: int = 10_000
    warmup_steps: int = 2_000
    schedule: str = "cosine"           # cosine | constant
    weight_decay: float = 0.2
    beta1: float = 0.96
    beta2: float = 0.99
    gamma: float = 0.05
    eps: float = 1e-12
    hess_interval: int = 10            # k in Algorithm 3
    hess_subbatch: int = 240           # paper: 240/480 (G), 32/480 (H)
    estimator: str = "gnb"             # gnb | hutchinson | empirical_fisher
    grad_clip: float = 1.0
    clip_threshold: float = 1.0        # Sophia rho (1e9 = ablation: no clip)
    grad_accum: int = 1
    remat: str = "none"                # none | full | dots
    attn_impl: str = "auto"
    fused_attn: bool = True            # Pallas flash attention on the train
    #                                    path (kernels/flash_attention.py,
    #                                    autotuned blocks; the Hutchinson
    #                                    HVP rides its custom_jvp twin).
    #                                    Only consulted while attn_impl is
    #                                    "auto" — an explicit impl wins.
    fused_kernel: bool = False         # Pallas backend for the engine
    fused_loss: bool = True            # Pallas logits-free LM loss + GNB
    #                                    (kernels/fused_ce.py, autotuned
    #                                    block sizes); False falls back to
    #                                    the chunked jnp sweep — both keep
    #                                    the [B*T, V] logits out of HBM
    compress_grads: bool = False       # int8 + error feedback (beyond-paper)
    compress_hess: bool = False        # int8 for the estimator sub-batch
    #                                    gradient too (stateless: no error
    #                                    feedback at refresh sparsity)
    comm_bucket_elems: Optional[int] = None  # gradient-collective bucketing
    #                                    (distributed/overlap.py): None=auto
    #                                    (roofline; monolithic off-mesh),
    #                                    0=monolithic, N=explicit elements
    comm_telemetry: bool = False       # per-step comm/compute host stamps:
    #                                    metrics gain comm_seconds /
    #                                    step_seconds / exposed_comm_fraction
    state_dtype: str = "float32"       # optimizer m/h dtype ("bfloat16" at 400B)
    seed: int = 0


def make_schedule(tc: TrainerConfig):
    if tc.schedule == "constant":
        return constant(tc.peak_lr)
    return linear_warmup_cosine(tc.peak_lr, tc.total_steps, tc.warmup_steps)


def make_engine(tc: TrainerConfig) -> OptimizerEngine:
    """Engine for ``tc.optimizer`` with the paper's per-optimizer hypers."""
    name = tc.optimizer
    if name in ("sophia_g", "sophia_h"):
        hypers = dict(beta1=tc.beta1, beta2=tc.beta2, gamma=tc.gamma,
                      eps=tc.eps, weight_decay=tc.weight_decay,
                      clip_threshold=tc.clip_threshold)
    elif name == "adamw":
        hypers = dict(beta1=0.9, beta2=0.95, eps=1e-8,
                      weight_decay=tc.weight_decay)
    elif name == "lion":
        hypers = dict(beta1=0.95, beta2=0.98, weight_decay=tc.weight_decay)
    elif name == "signgd":
        hypers = dict(beta1=tc.beta1, weight_decay=tc.weight_decay)
    elif name == "adahessian":
        hypers = dict(beta1=0.92, beta2=0.99, eps=1e-8,
                      weight_decay=tc.weight_decay)
    elif name == "sgd":
        hypers = dict(momentum=0.0)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    sdt = jnp.bfloat16 if tc.state_dtype == "bfloat16" else jnp.float32
    return OptimizerEngine(name, hypers=hypers,
                           backend="pallas" if tc.fused_kernel
                           else "reference",
                           state_dtype=sdt)


# ---------------------------------------------------------------------------


def _accum_grads(loss_fn, params, batch, accum: int):
    """Microbatch gradient accumulation via scan (mean over microbatches).

    Aux metrics ride the scan carry alongside the loss and grads, so
    ``grad_accum > 1`` reports the same (averaged) metrics as
    ``grad_accum == 1`` — the old carry kept only the final microbatch's ce
    and zeroed aux, silently skewing logged metrics with accumulation on."""
    if accum <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    micro = jax.tree.map(
        lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
        batch)
    met0 = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(loss_fn, params,
                       jax.tree.map(lambda x: x[0], micro))[1])

    def body(carry, mb):
        loss_acc, met_acc, g_acc = carry
        (loss, met), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        return (loss_acc + loss,
                jax.tree.map(lambda a, b: a + b, met_acc, met),
                jax.tree.map(lambda a, b: a + b, g_acc, g)), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, mets, grads), _ = jax.lax.scan(
        body, (jnp.zeros(()), met0, zeros), micro)
    inv = 1.0 / accum
    return loss * inv, jax.tree.map(lambda m: m * inv, mets), \
        jax.tree.map(lambda g: g * inv, grads)


def make_train_fns(cfg: ModelConfig, tc: TrainerConfig):
    """Returns ``(init_fn, train_step)``.

    ``train_step(state, batch, do_refresh)`` is the single compiled program
    (jit-able with shardings by the launcher): the estimator sub-graph runs
    under ``lax.cond`` on the *traced* ``do_refresh`` flag and its EMA is
    fused into the optimizer update, so flipping the flag at the Algorithm-3
    cadence never triggers a second compilation.
    """
    model = get_model(cfg)
    engine = make_engine(tc)
    schedule = make_schedule(tc)
    clipper = clip_by_global_norm(tc.grad_clip)
    compressor = GradCompressor() if tc.compress_grads else None
    hess_compressor = GradCompressor() if tc.compress_hess else None

    loss_impl = "fused" if tc.fused_loss else None  # None -> module default
    # fused_attn only applies while attn_impl is "auto"; an explicit impl
    # ("full", "chunked", "flash", ...) always wins.  The Hutchinson HVP
    # cannot differentiate through custom_vjp, so it rides the custom_jvp
    # twin of the same kernel — mirroring fused_loss's "fused_jvp" route.
    attn_impl = (tc.attn_impl if tc.attn_impl != "auto"
                 else ("flash" if tc.fused_attn else "auto"))
    hvp_attn_impl = "flash_jvp" if attn_impl == "flash" else attn_impl

    def loss_fn(params, batch):
        return model.loss_fn(cfg, params, batch, remat=tc.remat,
                             attn_impl=attn_impl, loss_impl=loss_impl)

    def init_fn(rng) -> TrainState:
        p_rng, s_rng = jax.random.split(jax.random.PRNGKey(tc.seed)
                                        if rng is None else rng)
        params = model.init_params(cfg, p_rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=engine.init(params),
                          clip_state=clipper.init(params), rng=s_rng,
                          comp_state=(compressor.init_shards(
                              engine.layout(params))
                              if compressor is not None else ()))

    def _estimate_flat(params, batch, rng, crng):
        """(est_shards, scale): diagonal-Hessian estimate as flat fp32
        shards in the engine layout — the engine folds ``scale`` into the
        fused Hessian-EMA (GNB's batch factor B, Algorithm 2 line 6).

        With ``compress_hess``, the int8 collective quantizes the
        *gradient-valued* pieces — GNB/E-F's ghat BEFORE squaring (the
        quantity a real data-parallel reduction puts on the wire; squaring
        first would square the per-block dynamic range and zero every
        coordinate below ~max/16 of its scale block), and Hutchinson's
        u ⊙ Hu estimate (the HVP reduction's wire form, u replicated)."""
        lay = engine.layout(params)
        sub = subsample_batch(batch, tc.hess_subbatch) \
            if tc.hess_subbatch else batch
        compress = (hess_compressor.allreduce_shards_stateless
                    if hess_compressor is not None else lambda s, _: s)
        if tc.estimator == "gnb":
            if tc.fused_loss:
                # logits-free Algorithm 2: the label draw happens inside
                # the fused loss kernel's vocab sweep and B rides out as
                # the sweep's valid-position count
                def slf(p):
                    return model.sampled_loss_fn(
                        cfg, p, sub, rng, remat=tc.remat,
                        attn_impl=attn_impl, loss_impl="fused")
                g_sh, scale = gnb_ghat_flat_from_loss(slf, params, lay)
            else:
                def lf(p):
                    return model.logits_fn(cfg, p, sub, remat=tc.remat,
                                           attn_impl=attn_impl)
                g_sh, scale = gnb_ghat_flat(lf, params, rng, lay,
                                            mask=sub.get("mask"))
            g_sh = compress(g_sh, crng)
            return tuple(g * g for g in g_sh), scale
        if tc.estimator == "hutchinson":
            # forward-over-reverse HVP crosses the fused loss through its
            # custom_jvp twin ("fused_jvp": same Pallas forward, linear
            # tangent swept chunk-by-chunk — kernels/fused_ce.py); without
            # fused_loss the chunked jnp loss supports both modes natively
            hvp_impl = "fused_jvp" if tc.fused_loss else "chunked"
            def sf(p):
                return model.loss_fn(cfg, p, sub, remat=tc.remat,
                                     attn_impl=hvp_attn_impl,
                                     loss_impl=hvp_impl)[0]
            est = hutchinson_estimator_flat(sf, params, rng, lay)
            return compress(est, crng), 1.0
        if tc.estimator == "empirical_fisher":
            def sf(p):
                return model.loss_fn(cfg, p, sub, remat=tc.remat,
                                     attn_impl=attn_impl,
                                     loss_impl=loss_impl)[0]
            lead = jax.tree.leaves(sub)[0]
            n = lead.shape[0] * (lead.shape[1] if lead.ndim > 1 else 1)
            g_sh = compress(empirical_fisher_ghat_flat(sf, params, lay),
                            crng)
            return tuple(g * g for g in g_sh), float(n)
        raise ValueError(tc.estimator)

    def train_step(state: TrainState, batch, do_refresh=False):
        """One unified step (Algorithm 3 lines 6-13, refresh flag-gated)."""
        telemetry = tc.comm_telemetry
        t_step0 = None
        if telemetry:
            # stamp step start on a batch leaf and thread the stamped leaf
            # back in, so forward compute provably follows the stamp
            from ..distributed import overlap as _ov
            leaves, treedef = jax.tree.flatten(batch)
            t_step0, l0 = _ov.stamp(leaves[0])
            batch = jax.tree.unflatten(treedef, [l0] + leaves[1:])
        loss, metrics, grads = _accum_grads(loss_fn, state.params, batch,
                                            tc.grad_accum)
        metrics = {"loss": loss, **metrics}
        with jax.named_scope("train.grad_clip"):
            grads, clip_state = clipper.update(grads, state.clip_state)
        g_sh = engine.ravel_grads(state.params, grads)
        comp_state = state.comp_state
        comm_tele = None
        if compressor is not None:
            # in-collective int8 all-reduce over the flat shards: picks up
            # the fsdp axis from the launcher-installed activation mesh
            # (mesh-less runs use the identical math on the whole shard);
            # bucketed per comm_bucket_elems so the per-bucket collectives
            # can overlap backward compute (distributed/overlap.py)
            out = compressor.allreduce_shards(
                g_sh, comp_state, _fold_rng(state, RNG_TAG_COMPRESS),
                bucket_elems=tc.comm_bucket_elems, telemetry=telemetry)
            if telemetry:
                g_sh, comp_state, comm_tele = out
            else:
                g_sh, comp_state = out
        lr = schedule(state.opt_state.count)

        if engine.hessian_aware:
            # the whole engine dispatch sits under the cond, not just the
            # estimator: the hot branch runs the plain fused step (4 reads +
            # 2 writes per element) and only the refresh branch pays for the
            # estimate operand and the h write — inside that branch the
            # refresh flag is constant True, so the kernel's select folds
            # away and the fused sweep still touches h exactly once
            def _refresh_step():
                with jax.named_scope("sophia.estimate"):
                    est_sh, scale = _estimate_flat(
                        state.params, batch, _fold_rng(state, RNG_TAG_HESS),
                        _fold_rng(state, RNG_TAG_HESS_COMPRESS))
                return engine.step_with_refresh(
                    state.opt_state, state.params, g_sh, lr, est_sh,
                    jnp.asarray(scale, jnp.float32), True)

            def _plain_step():
                return engine.step_shards(state.opt_state, state.params,
                                          g_sh, lr)

            params, opt_state = jax.lax.cond(
                jnp.asarray(do_refresh, bool), _refresh_step, _plain_step)
        else:
            params, opt_state = engine.step_shards(state.opt_state,
                                                   state.params, g_sh, lr)

        metrics = dict(metrics,
                       grad_norm=clip_state.last_norm,
                       clip_triggers=clip_state.triggers,
                       lr=lr)
        if engine.tracks_clip_fraction:
            metrics["sophia_clip_fraction"] = opt_state.clip_fraction
        if telemetry:
            # step end stamped on an updated-params leaf (dataflow pins it
            # after the optimizer write).  comm_seconds is the wall span of
            # the comm *window* (first bucket issued -> last completed) —
            # an upper bound on exposed comm, exact when nothing overlaps;
            # the differential measurement lives in benchmarks/comm_overlap
            from ..distributed import overlap as _ov
            t_step1, _ = _ov.stamp(jax.tree.leaves(params)[0])
            step_s = _ov.delta_seconds(t_step0, t_step1)
            comm_s = (comm_tele["comm_seconds"] if comm_tele is not None
                      else jnp.float32(0))
            metrics["comm_seconds"] = comm_s
            metrics["step_seconds"] = step_s
            metrics["exposed_comm_fraction"] = \
                comm_s / jnp.maximum(step_s, jnp.float32(1e-9))
        return TrainState(step=state.step + 1, params=params,
                          opt_state=opt_state, clip_state=clip_state,
                          rng=state.rng, comp_state=comp_state), metrics

    return init_fn, train_step


def train_loop(cfg: ModelConfig, tc: TrainerConfig, source, *,
               num_steps: int, state: Optional[TrainState] = None,
               jit: bool = True, callback: Optional[Callable] = None,
               start_step: int = 0, donate: bool = False):
    """Single-host reference loop (tests/benchmarks; launch/train.py is the
    production multi-device driver).

    With ``donate=True`` (and a backend that implements donation — CPU
    doesn't), the input TrainState is donated to the jitted step: the flat
    params/m/h buffers update in place, halving optimizer-state peak
    memory.  Opt-in here because it consumes the caller's ``state``
    argument; the production driver always donates."""
    init_fn, train_step = make_train_fns(cfg, tc)
    if jit:
        dn = (0,) if donate and jax.default_backend() != "cpu" else ()
        train_step = jax.jit(train_step, donate_argnums=dn)
    if state is None:
        state = init_fn(jax.random.PRNGKey(tc.seed))
    # the engine registry knows which families refresh curvature
    # out-of-band — a hardcoded optimizer-name tuple here silently skipped
    # refresh for any newly registered curvature family
    needs_hess = hessian_aware_optimizer(tc.optimizer)
    k = tc.hess_interval
    history = []
    for t in range(start_step, start_step + num_steps):
        batch = {k2: jnp.asarray(v) for k2, v in source.batch_at(t).items()}
        flag = jnp.asarray(needs_hess and t % k == 0)
        state, metrics = train_step(state, batch, flag)
        history.append({k2: float(v) for k2, v in metrics.items()})
        if callback is not None:
            callback(t, state, metrics)
    return state, history

"""Production training driver — elastic, multi-host.

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-small \
        --opt sophia_g --steps 400 --global-batch 32 --seq-len 256 \
        --ckpt-dir /tmp/run1 --elastic

Multi-host: launch the SAME command on every host, adding

    --coordinator host0:1234 --num-processes N --process-id <rank>

``jax.distributed`` initializes before any device query, the auto mesh
spans every process's devices, checkpoint save/restore is collective
(process 0 writes, manifests cross-validated), and a dead peer surfaces as
``NodeLoss``: the survivors exit non-zero, get relaunched with
``--num-processes`` = the surviving count, and resume from the last
complete manifest.

Features: any registered arch (--smoke for the reduced config), any
optimizer, sharded execution over all visible devices (mesh auto-shaped),
Algorithm-3 hessian cadence, gradient accumulation, buffer donation on the
jitted step (flat params/m/h update in place), async checkpointing with
auto-resume, preemption-safe exit, and an elastic retry loop: every attempt
rebuilds the mesh from the *surviving* device set and re-shards the latest
checkpoint onto it (checkpoint -> shrink mesh -> resume), so node loss or a
persistent straggler degrades capacity instead of killing the run.

Profiling: ``--profile-dir DIR --profile-steps A:B`` writes a
``jax.profiler`` trace of steps A..B-1.  Each step is a ``train`` step
event holding the host spans ``data.batch_at``, ``train.put_batch``,
``train.dispatch`` and ``train.wait``, on the clock of the device ops, which
carry the step's layer scopes (``model.*``, ``loss.lm``, ``train.grad_clip``,
``optimizer.*``, ``sophia.estimate``) in their HLO metadata.  A log line
names any step that compiled again ("recompiled at step t").
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..configs import ARCHS, get_config

# NOTE: jax is imported lazily-at-top but devices must not be touched until
# main() has had the chance to run jax.distributed.initialize
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..data import DataConfig, make_source
from ..distributed.sharding import (batch_specs, partition_params,
                                    set_activation_mesh)
from ..train import TrainerConfig, checkpoint as ckpt, make_engine, \
    make_train_fns
from ..train.elastic import (MeshDegraded, NodeLoss, PreemptionGuard,
                             StragglerDetector, is_distributed_failure,
                             run_resumable)
from ..train.train_state import state_partition_specs
from .mesh import (enable_compile_cache, initialize_distributed,
                   make_mesh, overlap_compiler_options)


def build_mesh(devices=None):
    """Auto mesh: the data axis gets at least the model axis's width — it
    carries the gradient reduction, the FSDP flat shards and the
    in-collective compression, so it must not collapse to 1 (the old
    model-first shaping made ``--compress-grads`` silently inert on <= 8
    devices).  ``devices`` restricts to a subset (the elastic driver's
    shrunken mesh); TP-heavy layouts should pass an explicit mesh."""
    devs = list(jax.devices()) if devices is None else list(devices)
    n = len(devs)
    if n == 1:
        return None
    model = 1
    for m in (4, 2):
        if n % m == 0 and n // m >= m:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"), devices=devs)


def _put_tree(tree, sh_tree):
    """device_put a host pytree against target shardings.  Shardings that
    span other processes' devices (multi-host) need
    ``make_array_from_callback`` — every process holds the identical global
    host value (deterministic init / stateless data pipeline) and
    contributes its addressable slices."""
    if sh_tree is None:
        return tree

    def put(x, s):
        if getattr(s, "is_fully_addressable", True):
            return jax.device_put(x, s)
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, s, lambda idx: x[idx])

    return jax.tree.map(put, tree, sh_tree)


def _final_save(ckpt_dir, step, state, extra):
    """Sync save at exit; skips if the periodic async save already wrote this
    step (and drains it first — the tmp dir would otherwise be shared)."""
    ckpt.wait_for_pending()
    if ckpt.latest_step(ckpt_dir) != step:
        ckpt.save(ckpt_dir, step, state, extra=extra)


def compile_train_step(cfg, tc, mesh, sample_batch, state_shape=None):
    """Jit THE train step for ``mesh`` (explicit shardings + buffer
    donation) and return (train_step, init_fn, state_shardings,
    batch_shardings) — state/batch shardings are None on a mesh-less run.

    One program per mesh configuration: the Hessian refresh is a traced
    flag inside ``train_step(state, batch, do_refresh)``, so the elastic
    driver's per-device-set compile cache holds a single XLA executable
    where it used to hold a hot step *and* a refresh step.

    ``state_shape`` (an eval_shape of init_fn, mesh-independent) can be
    passed in to avoid re-tracing the model abstractly.  A multi-chip TPU
    mesh also gets the collective-overlap scheduler options
    (``mesh.overlap_compiler_options``)."""
    init_fn, train_step = make_train_fns(cfg, tc)
    # donate the TrainState: the flat params/m/h shards alias input->output,
    # halving optimizer-state peak memory (CPU has no donation; skip the
    # warning noise there)
    dn = (0,) if jax.default_backend() != "cpu" else ()
    set_activation_mesh(mesh)
    if mesh is None:
        return jax.jit(train_step, donate_argnums=dn), init_fn, None, None
    if state_shape is None:
        state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    pspecs = partition_params(state_shape.params, mesh, fsdp=True)
    sspecs = state_partition_specs(state_shape, pspecs, mesh)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    ssh = ns(sspecs)
    bsh = ns(batch_specs(sample_batch, mesh))
    return (jax.jit(train_step, in_shardings=(ssh, bsh, None),
                    out_shardings=(ssh, None), donate_argnums=dn,
                    compiler_options=overlap_compiler_options(mesh)),
            init_fn, ssh, bsh)


def _step_range(text):
    """``A:B`` -> the steps ``range(A, B)``."""
    a, b = (int(x) for x in text.split(":"))
    if not 0 <= a < b:
        raise argparse.ArgumentTypeError(f"need 0 <= A < B, got {text!r}")
    return range(a, b)


class _Profiler:
    """A ``jax.profiler`` trace of the steps in ``steps`` into ``log_dir``
    (host spans and device ops; no Python tracer, which would record every
    call of the host's loops and slow them)."""

    def __init__(self, log_dir, steps):
        self.log_dir, self.steps, self.on = log_dir, steps, False

    def before(self, t):
        if self.log_dir and t == self.steps.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.on = True

    def after(self, t):
        if self.on and t + 1 >= self.steps.stop:
            self.stop()

    def stop(self):
        if self.on:
            jax.profiler.stop_trace()
            self.on = False


class _CompileCounter:
    """Counts the backend compiles of this process (``jax.monitoring``), so
    that the loop can name a step that compiled again."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, name, secs, **kw):
        if name == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-small", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config")
    ap.add_argument("--opt", default="sophia_g")
    ap.add_argument("--estimator", default="gnb")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--peak-lr", type=float, default=4e-4)
    ap.add_argument("--weight-decay", type=float, default=0.2)
    ap.add_argument("--gamma", type=float, default=0.05)
    ap.add_argument("--hess-interval", type=int, default=10)
    ap.add_argument("--hess-subbatch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--fused-kernel", action="store_true")
    ap.add_argument("--fused-loss", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="Pallas logits-free LM loss + in-sweep GNB "
                         "sampling (kernels/fused_ce.py, autotuned block "
                         "sizes); --no-fused-loss falls back to the "
                         "chunked jnp sweep")
    ap.add_argument("--fused-attn", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="Pallas flash attention on the train path "
                         "(kernels/flash_attention.py, autotuned blocks; "
                         "the Hutchinson HVP rides its custom_jvp twin); "
                         "--no-fused-attn falls back to the reference "
                         "jnp attention")
    ap.add_argument("--retune", action="store_true",
                    help="re-run measured autotuning (fused-CE loss shape "
                         "and flash-attention shape) for this run before "
                         "training (ignores the on-disk caches; see README "
                         "'Fused loss' / 'Training attention')")
    ap.add_argument("--compress-grads", action="store_true",
                    help="in-collective int8 all-reduce over the fsdp axis")
    ap.add_argument("--comm-bucket-elems", type=int, default=None,
                    help="bucket size (elements) for the bucketed, "
                         "backward-overlapped gradient collective "
                         "(distributed/overlap.py): unset=auto (roofline), "
                         "0=monolithic, N=explicit")
    ap.add_argument("--comm-telemetry", action="store_true",
                    help="per-step comm/compute host stamps: logs "
                         "comm_seconds and exposed_comm_fraction")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0; presence turns on "
                         "multi-process jax.distributed")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--compress-hess", action="store_true",
                    help="int8-compress the estimator sub-batch gradient "
                         "too (stateless: no error feedback at refresh "
                         "sparsity)")
    ap.add_argument("--state-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="use only the first N visible devices")
    ap.add_argument("--elastic", action="store_true",
                    help="retry-with-restore on failure (run_resumable)")
    ap.add_argument("--max-restarts", type=int, default=None,
                    help="restart budget (default: 3 with --elastic, else 0)")
    ap.add_argument("--degrade-after", type=int, default=0,
                    help="with --elastic + --ckpt-dir: after N straggler "
                         "flags, checkpoint, halve the device set, and "
                         "resume on the smaller mesh (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace (.xplane.pb: device "
                         "ops named by the step's layers, host spans per "
                         "step) of --profile-steps into this directory")
    ap.add_argument("--profile-steps", type=_step_range,
                    default=range(2, 5), metavar="A:B",
                    help="the steps A..B-1 to profile (default 2:5, after "
                         "the first step's compile)")
    args = ap.parse_args(argv)

    # MUST precede every jax device query: distributed init after a device
    # query deadlocks
    enable_compile_cache()
    if args.coordinator:
        initialize_distributed(args.coordinator, args.num_processes,
                               args.process_id)
    p0 = jax.process_index() == 0

    cfg = get_config(args.arch, smoke=args.smoke)
    tc = TrainerConfig(
        optimizer=args.opt, estimator=args.estimator, peak_lr=args.peak_lr,
        total_steps=args.steps, warmup_steps=max(2, args.steps // 20),
        weight_decay=args.weight_decay, gamma=args.gamma,
        hess_interval=args.hess_interval, hess_subbatch=args.hess_subbatch,
        grad_accum=args.grad_accum, remat=args.remat,
        fused_kernel=args.fused_kernel, fused_loss=args.fused_loss,
        fused_attn=args.fused_attn,
        compress_grads=args.compress_grads,
        compress_hess=args.compress_hess,
        comm_bucket_elems=args.comm_bucket_elems,
        comm_telemetry=args.comm_telemetry,
        state_dtype=args.state_dtype, seed=args.seed)
    if args.retune and tc.fused_loss:
        # eager measured tuning for this run's exact hot-path loss shape;
        # the result persists to the on-disk cache so the jitted step's
        # trace picks it up (kernels/autotune.py)
        from ..kernels.autotune import tune_shape
        n_rows = (args.global_batch // max(1, args.grad_accum)) \
            * args.seq_len
        tuned = tune_shape(
            n_rows, cfg.d_model, cfg.padded_vocab, dtype=cfg.dtype,
            transpose_w=not cfg.tie_embeddings,
            softcap=cfg.final_logit_softcap, norm=cfg.norm_type,
            refresh=True)
        if p0:
            print(f"[retune] fused CE {n_rows}x{cfg.d_model}x"
                  f"{cfg.padded_vocab}: bn={tuned.bn} bv={tuned.bv} "
                  f"schedule={tuned.schedule} ({tuned.source})")
    if args.retune and tc.fused_attn and tc.attn_impl == "auto":
        from ..kernels.autotune import tune_attn_shape
        b_local = args.global_batch // max(1, args.grad_accum)
        tuned_a = tune_attn_shape(
            b_local, cfg.n_heads, cfg.n_kv_heads, args.seq_len,
            args.seq_len, cfg.hd, dtype=cfg.dtype, causal=True,
            softcap=cfg.attn_logit_softcap, refresh=True)
        if p0:
            print(f"[retune] flash attn B{b_local} H{cfg.n_heads} "
                  f"S{args.seq_len} hd{cfg.hd}: bq={tuned_a.bq} "
                  f"bk={tuned_a.bk} schedule={tuned_a.schedule} "
                  f"({tuned_a.source})")
    src = make_source(DataConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        vocab_size=cfg.vocab_size, seed=args.seed, source=args.data,
        path=args.data_path))
    sample = {k: jnp.asarray(v) for k, v in src.batch_at(0).items()}

    # The TrainState shape and the flat-shard layout are mesh-independent:
    # traced abstractly once, shared by every setup()/restore across mesh
    # reconfigurations.  The layout is recorded alongside every checkpoint
    # (the elastic restore verifies it, and offline tooling can rebuild the
    # unravel spec without the code).
    state_shape = jax.eval_shape(make_train_fns(cfg, tc)[0],
                                 jax.random.PRNGKey(args.seed))
    engine = make_engine(tc)
    layout_meta = dict(engine.describe(state_shape.params),
                       optimizer=args.opt, state_dtype=args.state_dtype,
                       compress_grads=bool(args.compress_grads))

    all_devices = list(jax.devices())
    ctx = {"devices": all_devices[:args.devices] if args.devices
           else all_devices}

    def setup():
        """(Re)build mesh + the single jitted step for the current device
        set.  A retry on an unchanged device set (transient failure, no
        degrade) keeps the compiled step — retraces cost minutes on real
        models."""
        key = tuple(ctx["devices"])
        if ctx.get("setup_key") == key:
            return
        mesh = build_mesh(ctx["devices"])
        sjit, init_fn, ssh, bsh = compile_train_step(cfg, tc, mesh, sample,
                                                     state_shape=state_shape)
        ctx.update(mesh=mesh, sjit=sjit, init_fn=init_fn,
                   ssh=ssh, bsh=bsh, setup_key=key)

    def make_state():
        setup()
        state = ctx["init_fn"](jax.random.PRNGKey(args.seed))
        return _put_tree(state, ctx["ssh"])

    def restore_latest():
        if not args.ckpt_dir or ckpt.latest_step(args.ckpt_dir) is None:
            return None
        prev = (ckpt.read_manifest(args.ckpt_dir).get("extra") or {})
        for field in ("optimizer", "state_dtype", "compress_grads"):
            # different optimizer families (and state dtypes) share the flat
            # (m, h) layout, so a silent restore would reinterpret the
            # curvature state; flipping compress_grads changes the
            # TrainState leaf count — refuse all three instead of dying in
            # restore (SystemExit is deliberately not retried by
            # run_resumable)
            if prev.get(field) not in (None, layout_meta[field]):
                raise SystemExit(
                    f"[resume] checkpoint in {args.ckpt_dir} was written "
                    f"with {field}={prev[field]!r}; refusing to resume with "
                    f"{layout_meta[field]!r} (use a fresh --ckpt-dir)")
        setup()
        state, start = ckpt.restore_resharded(
            args.ckpt_dir, state_shape, shardings=ctx["ssh"],
            expect_layout=layout_meta)
        if p0:
            print(f"[resume] restored step {start} from {args.ckpt_dir} "
                  f"onto {len(ctx['devices'])} device(s) / "
                  f"{jax.process_count()} process(es)")
        return state, start

    guard = PreemptionGuard()
    # the engine knows which families refresh curvature out-of-band (no
    # hardcoded optimizer-name tuple: a new curvature family would have
    # silently skipped its refresh cadence)
    needs_hess = engine.hessian_aware

    def dispatch_and_wait(state, batch, flag, t):
        try:
            with jax.profiler.TraceAnnotation("train.dispatch"):
                state, metrics = ctx["sjit"](state, batch, flag)
            # dispatch is asynchronous: wait, so the step time (and the
            # straggler detector) see device time, and a failed
            # execution raises inside this handler
            with jax.profiler.TraceAnnotation("train.wait"):
                jax.block_until_ready(metrics["loss"])
        except Exception as e:
            if jax.process_count() > 1 and is_distributed_failure(e):
                # a peer died: unrecoverable in-process — propagate as
                # NodeLoss so run_resumable exits instead of retrying
                # into a hang; the relauncher resumes the survivors
                # from the last manifest
                raise NodeLoss(
                    f"distributed failure at step {t}: {e}") from e
            raise
        return state, metrics

    def run(state, start):
        profiler = _Profiler(args.profile_dir, args.profile_steps)
        try:
            with _CompileCounter() as compiles:
                return loop(state, start, profiler, compiles)
        finally:
            profiler.stop()

    def loop(state, start, profiler, compiles):
        straggler = StragglerDetector()
        t_start = time.time()
        recompiled = []
        for t in range(start, args.steps):
            profiler.before(t)
            n_compiles = compiles.n
            with jax.profiler.StepTraceAnnotation("train", step_num=t):
                t0 = time.time()
                # every process computes the identical global batch
                # (stateless deterministic source) and contributes its
                # addressable slices
                host = src.batch_at(t)
                with jax.profiler.TraceAnnotation("train.put_batch"):
                    batch = _put_tree({k: jnp.asarray(v)
                                       for k, v in host.items()},
                                      ctx["bsh"])
                    flag = jnp.asarray(needs_hess
                                       and t % tc.hess_interval == 0)
                state, metrics = dispatch_and_wait(state, batch, flag, t)
            dt = time.time() - t0
            profiler.after(t)
            if t > start and compiles.n > n_compiles:
                recompiled.append(t)
            if straggler.observe(dt):
                if p0:
                    print(f"[straggler] step {t} took {dt:.2f}s "
                          f"(mean {straggler.mean:.2f}s)")
                if (args.elastic and args.degrade_after and args.ckpt_dir
                        and straggler.flagged >= args.degrade_after
                        and jax.process_count() == 1
                        and len(ctx["devices"]) > 1):
                    # checkpoint -> shrink mesh -> resume: drop the slow
                    # half of the device set and let run_resumable restore
                    # this exact step onto the smaller mesh
                    _final_save(args.ckpt_dir, t + 1, state, layout_meta)
                    ctx["devices"] = ctx["devices"][
                        :max(1, len(ctx["devices"]) // 2)]
                    raise MeshDegraded(
                        f"persistent straggler at step {t}; degrading to "
                        f"{len(ctx['devices'])} device(s)")
            if t % args.log_every == 0 and p0:
                loss = float(metrics["loss"])
                comm = ""
                if "comm_seconds" in metrics:
                    cs = float(metrics["comm_seconds"]) * 1e3
                    cf = float(metrics["exposed_comm_fraction"]) * 100
                    comm = f" comm {cs:.1f}ms ({cf:.0f}% of step)"
                again = (f" recompiled at step "
                         f"{', '.join(map(str, recompiled))}"
                         if recompiled else "")
                recompiled.clear()
                print(f"step {t:6d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt * 1e3:.0f}ms{comm}{again}", flush=True)
            if args.ckpt_dir and (t + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, t + 1, state, async_=True,
                          extra=layout_meta)
            if guard.requested:
                if p0:
                    print(f"[preempt] checkpointing at step {t + 1} "
                          "and exiting")
                if args.ckpt_dir:
                    _final_save(args.ckpt_dir, t + 1, state, layout_meta)
                return state
        if args.ckpt_dir:
            _final_save(args.ckpt_dir, args.steps, state, layout_meta)
        if p0:
            print(f"done: {args.steps - start} steps in "
                  f"{time.time() - t_start:.1f}s "
                  f"(straggler flags: {straggler.flagged})")
        return state

    max_restarts = args.max_restarts if args.max_restarts is not None \
        else (3 if args.elastic else 0)
    return run_resumable(make_state, run, restore_latest,
                         max_restarts=max_restarts)


if __name__ == "__main__":
    main()

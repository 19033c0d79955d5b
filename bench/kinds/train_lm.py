"""Runner for ``train_lm`` traffic: the program's training step, as the
launcher drives it, on synthetic token batches made from the seed.

Set-up compiles the step through ``launch.train.compile_train_step`` (the
launcher's mesh, shardings and donation), makes the weights and optimizer
state on the device from the seed, and drives that same compiled step
through its first ``check_steps`` steps with the window's own loop: step 0
refreshes the Hessian (when the optimizer does), the rest are plain, so
both branches of the one program are warm.  Those steps are the ones the
correctness check compares with the plain reference.  The window then
runs whole periods of ``hess_interval`` steps until ``--seconds`` have
passed.  After the window the program's state is freed and the reference
replays the checked steps.

Each step does what ``launch/train.py``'s loop does: ``batch_at(t)``, then
``_put_tree``, then the step with the refresh flag at ``t % k == 0``,
then ``block_until_ready(loss)``; each phase sits in a host span
(``bench.batch_at``, ``bench.put_tree``, ``bench.dispatch``,
``bench.wait``) that the profiler records on its own clock.
"""
from __future__ import annotations

import gc
import gzip
import json
import math
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from bench import check as checkmod
from bench import common, generator, trace as tracemod

SPANS = ("bench.batch_at", "bench.put_tree", "bench.dispatch", "bench.wait")


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def jax_key(seed: int):
    """A JAX key from a seed of any size: low 32 bits, then the rest."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf_norms(tree) -> dict:
    """{leaf path: float32 norm} of a pytree, on the device."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def _host(d: dict) -> dict:
    return {k: float(v) for k, v in d.items()}


def _leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): x for p, x in flat}


def _cosines(delta: dict, other: dict) -> dict:
    """{leaf: cosine between two changes}, leaf by leaf on the device;
    ``other`` holds host arrays.  A change of norm 0 has cosine 0."""
    @jax.jit
    def cos(a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        na = jnp.sqrt(jnp.sum(a * a))
        nb = jnp.sqrt(jnp.sum(b * b))
        return jnp.where(na * nb > 0, jnp.sum(a * b) / (na * nb), 0.0)

    return {k: float(cos(v, jnp.asarray(other[k])))
            for k, v in delta.items()}


#: the trace-time kernel counters (``repro.kernels.fused_ce.KERNEL_CALLS``)
#: that the step must show for each trainer setting it turns on
PATH = (("fused_loss", ("fwd",)), ("fused_loss", ("bwd_fused", "bwd_split")),
        ("fused_attn", ("attn_fwd",)), ("fused_attn", ("attn_bwd_dq",)),
        ("fused_attn", ("attn_bwd_dkv",)))


class Program:
    """The system under test, built for one cell: the compiled step, its
    state, the data source and the loop that feeds it."""

    def __init__(self, spec, seed, devices, faults=()):
        from repro.data import DataConfig, make_source
        from repro.launch.train import _put_tree, build_mesh, \
            compile_train_step
        from repro.models import ModelConfig
        from repro.train import TrainerConfig, make_engine

        wl, tr = spec["workload"], spec["traffic"]
        self.cfg = ModelConfig(name=spec["config"]["name"],
                               **spec["config"]["model"])
        self.tc = TrainerConfig(**tr["trainer"], seed=seed & 0x7FFFFFFF)
        self.engine = make_engine(self.tc)
        self.k = self.tc.hess_interval
        self.needs_hess = self.engine.hessian_aware
        self.tokens_per_step = tr["seq_len"] * tr["global_batch"]
        self.src = make_source(DataConfig(
            seq_len=tr["seq_len"], global_batch=tr["global_batch"],
            vocab_size=self.cfg.vocab_size, seed=seed, source=tr["source"],
            zipf_a=tr["zipf_a"]))
        self._put_tree = _put_tree
        mesh = build_mesh(devices) if len(devices) > 1 else None
        sample = self.src.batch_at(0)
        t0 = time.perf_counter()
        sjit, init_fn, ssh, self.bsh = compile_train_step(
            self.cfg, self.tc, mesh, sample)
        self.init = jax.jit(init_fn, out_shardings=ssh)
        self.key = jax_key(seed)
        self.state = self.init(self.key)
        batch = self.put(sample)
        from repro.kernels import fused_ce
        fused_ce.reset_kernel_calls()
        t1 = time.perf_counter()
        self.step_fn = sjit.lower(self.state, batch,
                                  jnp.asarray(True)).compile()
        self.compile_s = time.perf_counter() - t1
        self.build_s = t1 - t0
        self.kernel_calls = fused_ce.kernel_calls()
        # the trace names a kernel's op by its HLO instruction; this maps
        # each instruction to the kernel it runs
        self.kernel_names = tracemod.kernel_names(self.step_fn.as_text())
        self._check_path()
        for fault in faults:
            self.step_fn = fault(self.step_fn)
        self.records = []

    def _check_path(self):
        """The step holds the kernels its settings name: the trace-time
        counters of the fused loss and flash attention, the update sweep's
        backend, and the sampling forward where GNB refreshes."""
        tr = self.tc
        need = [alts for flag, alts in PATH if getattr(tr, flag)]
        if self.needs_hess and tr.estimator == "gnb" and tr.fused_loss:
            need.append(("fwd_sample",))
        missing = [a for a in need
                   if not any(self.kernel_calls.get(k) for k in a)]
        backend = self.engine.backend
        _log(f"path: kernel calls {self.kernel_calls}; update sweep "
             f"backend {backend}; kernels in the step "
             f"{sorted(set(self.kernel_names.values()))}")
        if missing or (tr.fused_kernel and backend != "pallas"):
            raise RuntimeError(
                f"the compiled step lacks kernels its settings name: "
                f"{missing}, update sweep backend {backend!r}")

    def put(self, host_batch):
        return self._put_tree({k: jnp.asarray(v)
                               for k, v in host_batch.items()}, self.bsh)

    def step(self, t):
        """One step of the launcher's loop; returns the loss (on device)."""
        h0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.batch_at"):
            host = self.src.batch_at(t)
        h1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.put_tree"):
            batch = self.put(host)
            flag = jnp.asarray(self.needs_hess and t % self.k == 0)
        h2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.state, metrics = self.step_fn(self.state, batch, flag)
        h3 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(metrics["loss"])
        h4 = time.perf_counter()
        self.records.append({
            "t": t, "refresh": bool(self.needs_hess and t % self.k == 0),
            "batch_at": h1 - h0, "put_tree": h2 - h1, "dispatch": h3 - h2,
            "wait": h4 - h3, "total": h4 - h0})
        return metrics["loss"]

    def first_steps(self, n):
        """Drive the first ``n`` steps; returns the program's readings."""
        hypers = self.engine.hypers
        beta1 = hypers["beta1"]
        engine = self.engine

        @jax.jit
        def after_first(state):
            trees = engine.state_as_trees(state.opt_state, state.params)
            out = {"grad": {k: v / (1.0 - beta1) for k, v in
                            _leaf_norms(trees["m"]).items()}}
            if engine.family == "sophia":
                out["hess"] = _leaf_norms(trees["h"])
            return out

        @jax.jit
        def change(params, p0):
            delta = jax.tree.map(jnp.subtract, params, p0)
            return _leaf_norms(delta), _leaves(delta)

        losses = []
        for t in range(n):
            losses.append(self.step(t))
            if t == 0:
                first = after_first(self.state)
        # the starting weights again, from the same compiled init and key:
        # a copy kept on the device through the steps would not fit
        upd, delta = change(self.state.params, self.init(self.key).params)
        out = {"loss": [float(x) for x in losses],
               "grad": _host(first["grad"]),
               "update": _host(upd),
               # kept on the host for the reference's direction reading
               "delta": jax.device_get(delta)}
        if "hess" in first:
            out["hess"] = _host(first["hess"])
        return out

    def free(self):
        self.state = None
        self.step_fn = None
        gc.collect()


def reference_readings(spec, seed, n, *, quant=None, half_batch=False,
                       against=None, keep_delta=False):
    """The plain reference over the first ``n`` steps of the same seed:
    the same weights' draws and label-sampling stream, and batches from
    the benchmark's own generator.  With ``against`` (the other side's
    per-leaf change, host arrays) it also reads ``update_cos``; with
    ``keep_delta`` it returns its own change as host arrays under
    ``delta``."""
    mod = common.reference(spec["config"]["reference"])
    m = spec["config"]["model"]
    tr = spec["traffic"]
    opt = tr["trainer"]
    src = generator.make(tr, vocab_size=m["vocab_size"], seed=seed)
    p_key, s_key = jax.random.split(jax_key(seed))
    params = jax.jit(lambda k: mod.init_params(m, k))(p_key)
    p0 = jax.tree.map(lambda x: jnp.array(x, copy=True), params)
    mom = jax.tree.map(jnp.zeros_like, params)
    h = jax.tree.map(jnp.zeros_like, params)
    step = mod.make_step(m, opt, quant=quant, half_batch=half_batch)
    k = opt["hess_interval"]
    sophia = opt["optimizer"] == "sophia_g"
    out = {"loss": []}
    for t in range(n):
        b = src.batch_at(t)
        seed_t = jax.random.bits(
            jax.random.fold_in(jax.random.fold_in(s_key, 1), t), (2,),
            jnp.uint32)
        params, mom, h, loss, grads = step(
            params, mom, h, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
            jnp.float32(mod.lr_at(t, opt)), jnp.int32(t + 1), seed_t,
            refresh=bool(sophia and t % k == 0))
        out["loss"].append(float(loss))
        if t == 0:
            out["grad"] = _host(jax.jit(_leaf_norms)(grads))
            if sophia:
                out["hess"] = _host(jax.jit(_leaf_norms)(h))
        del grads
    delta = _leaves(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        params, p0))
    del params, p0, mom, h
    out["update"] = _host(jax.jit(lambda d: {
        k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in d.items()})(delta))
    if against is not None:
        out["update_cos"] = _cosines(delta, against)
    if keep_delta:
        out["delta"] = jax.device_get(delta)
    return out


def _step_summary(records) -> str:
    """Host-clock step times of the window, for reading its noise: per
    kind of step the median and the largest, and the host's share."""
    out = []
    for kind in (False, True):
        ts = sorted(r["total"] for r in records if r["refresh"] == kind)
        if ts:
            out.append(f"{'refresh' if kind else 'plain'} x{len(ts)} median "
                       f"{1e3 * ts[len(ts) // 2]:.2f} ms, max "
                       f"{1e3 * ts[-1]:.2f} ms")
    host = sum(r["batch_at"] + r["put_tree"] for r in records)
    out.append(f"batch_at + put_tree {1e3 * host:.1f} ms in all, largest "
               f"{1e3 * max(r['batch_at'] + r['put_tree'] for r in records):.2f}"
               f" ms")
    return "; ".join(out)


def _memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(spec, *, seed, seconds, trace, devices, t_start, faults=(),
        keep_trace=None):
    """One run of a training cell; returns the result dict (without the
    ``checks`` printing, which run.py does)."""
    wl = spec["workload"]
    n_check = wl["check_steps"]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)

    prog = Program(spec, seed, devices, faults)
    _log(f"setup: build {prog.build_s:.3f} s, step compile "
         f"{prog.compile_s:.3f} s")
    side = prog.first_steps(n_check)
    k = prog.k

    # the window: whole periods of k steps from the first unchecked step
    n_compiles0 = len(compiles)
    # set-up's objects stay out of the collections the window's own
    # allocations trigger, so that none of them scans the whole heap
    gc.collect()
    gc.freeze()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # host spans and device ops only: the Python tracer would record
        # every call of batch_at's loop and slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t = n_check
    losses = []
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            losses.append(prog.step(t))
            t += 1
            if (t - n_check) % k == 0 and time.perf_counter() - w0 >= seconds:
                break
    w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    gc.unfreeze()
    window_s = w1 - w0
    setup_s = w0 - t_start
    in_window = len(compiles) - n_compiles0
    window_records = prog.records[n_check:]
    n_steps = len(window_records)
    failed = sum(1 for x in losses if not math.isfinite(float(x)))
    mem_peak = _memory_peak(devices)
    _log(f"window: {n_steps} steps ({n_steps // k} periods of {k}) in "
         f"{window_s:.3f} s; compiles in window {in_window}; setup "
         f"{setup_s:.3f} s; memory peak {mem_peak}")
    _log("window steps: " + _step_summary(window_records))
    prog.free()

    ctx = {
        "spec": spec, "records": window_records, "window_s": window_s,
        "tokens": n_steps * prog.tokens_per_step, "k": k,
        "compile_s": prog.compile_s, "setup_s": setup_s,
        "chips": len(devices), "device_kind": devices[0].device_kind,
        "peaks": common.peaks(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
        "trace": None,
    }
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    breakdown = None
    if trace:
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            path = tracemod.find_xplane(tdir)
            shutil.copy(path, os.path.join(keep_trace, "trace.xplane.pb"))
            with open(os.path.join(keep_trace, "summary.txt"), "w") as f:
                f.write(tracemod.describe(path))
        tr = tracemod.load(tdir, n_devices=len(devices),
                           kernels=prog.kernel_names)
        shutil.rmtree(tdir, ignore_errors=True)
        if keep_trace:
            with gzip.open(os.path.join(keep_trace, "record.json.gz"),
                           "wt") as f:
                json.dump(tracemod.to_record(tr), f)
        ctx["trace"] = tr
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_by_span(SPANS, 10)}

    r0 = time.perf_counter()
    ref = reference_readings(spec, seed, n_check, against=side.pop("delta"))
    _log(f"reference: {n_check} steps in {time.perf_counter() - r0:.3f} s")
    correct, checks = checkmod.judge(side, ref, wl["limits"])
    correct = correct and failed == 0

    metrics = {}
    names = spec["per_layer"] if trace else spec["end_to_end"]
    for m in names:
        if trace:
            value = common.metric_reader(m["name"]).read(ctx)
        else:
            value = {"tokens_per_s": ctx["tokens"] / window_s,
                     "setup_s": setup_s}[m["name"]]
        if value is None and trace:
            # every per-layer metric in spec lists this cell: a reader
            # that finds nothing here means a kernel left the path or
            # was renamed, which must not pass silently
            raise RuntimeError(f"per-layer metric {m['name']!r} found "
                               f"nothing to read in cell {spec['cell']!r}")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": n_steps,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result

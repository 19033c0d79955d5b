"""Chip smoke: drive Sophia training and serving once on a TPU at the full
width of ``gpt2-small`` (d=768, 12 layers, 12 heads, vocab 50304, context
1024) with random weights and synthetic data made from ``--seed``, and
check what comes out.

    python chip_smoke.py             # one chip: train and serve phases
    python chip_smoke.py --chips 4   # the sharded train step on a 4-chip
                                     # mesh against the same steps on one

One process; it starts no children.  Phases (one chip):

* train: Sophia-G with the GNB estimator at seq 1024, global batch 8, with
  the Pallas fused loss, flash attention and update sweep; one Hessian
  refresh step, then plain steps.  Checks: finite losses, the kernels'
  trace-time counters, and the step-0 loss against the reference path
  (jnp loss, jnp attention, jnp engine).
* serve: greedy requests through ``ServeEngine`` with the Pallas decode
  kernel and with the XLA decode path, for bf16 and int8 KV; the decode
  logits of both paths on the same cache must agree.

Exits non-zero, printing no result, when JAX finds no TPU or a phase
fails.  The last line of stdout is ``{"ok": true, "device": {...}}``.
The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "gpt2-small"
SEQ_LEN = 1024
GLOBAL_BATCH = 8
TRAIN_STEPS = 4
HESS_INTERVAL = 2          # refresh at steps 0 and 2, plain steps 1 and 3
# TPU matmuls run bf16 passes, so the fused and reference paths (and the
# sharded and one-device steps) round differently: losses must agree to
# this relative tolerance.
LOSS_RTOL = 2e-3
# decode logits, Pallas kernel vs XLA path on the same cache: the kernel
# computes attention in fp32 from bf16 operands, the XLA path in bf16
DECODE_ATOL = 5e-2
N_REQUESTS = 4
PROMPT_LENS = (200, 137, 64, 255)
MAX_NEW = 32
CACHE_LEN = 512
PAGE_LEN = 128
TRAIN_KERNELS = ("attn_fwd", "attn_bwd_dq", "attn_bwd_dkv", "fwd",
                 "fwd_sample")


class PhaseFailed(Exception):
    pass


def _check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def _trainer_config(*, steps, fused, compress=False, seed=0):
    """The launch/train.py CLI defaults for everything not named here.
    The reference path saves every layer's (S, S) attention scores for the
    backward, 8 GB beyond a 16 GB chip at this size, so it recomputes each
    layer in the backward instead (``remat="full"``: same loss)."""
    from repro.train import TrainerConfig
    return TrainerConfig(
        optimizer="sophia_g", estimator="gnb", peak_lr=4e-4,
        total_steps=steps, warmup_steps=max(2, steps // 20),
        weight_decay=0.2, gamma=0.05, hess_interval=HESS_INTERVAL,
        hess_subbatch=8, remat="none" if fused else "full",
        fused_kernel=fused, fused_loss=fused, fused_attn=fused,
        compress_grads=compress, seed=seed)


def run_train(cfg, tc, *, seq_len, batch, steps, mesh=None, label=""):
    """Compile the train step through ``launch.train.compile_train_step``
    and run ``steps`` steps on synthetic data.  Returns (losses,
    step_seconds, compile_seconds)."""
    import jax
    import jax.numpy as jnp

    from repro.data import DataConfig, make_source
    from repro.launch.train import _put_tree, compile_train_step

    src = make_source(DataConfig(seq_len=seq_len, global_batch=batch,
                                 vocab_size=cfg.vocab_size, seed=tc.seed))

    def batch_at(t):
        return _put_tree({k: jnp.asarray(v)
                          for k, v in src.batch_at(t).items()}, bsh)

    sjit, init_fn, ssh, bsh = compile_train_step(cfg, tc, mesh,
                                                 src.batch_at(0))
    state = jax.jit(init_fn, out_shardings=ssh)(jax.random.PRNGKey(tc.seed))
    flag = jnp.asarray(True)
    t0 = time.perf_counter()
    step = sjit.lower(state, batch_at(0), flag).compile()
    compile_s = time.perf_counter() - t0
    print(f"train{label}: compiled in {compile_s:.2f} s", flush=True)
    losses, times = [], []
    for t in range(steps):
        b = batch_at(t)
        refresh = t % tc.hess_interval == 0
        t0 = time.perf_counter()
        state, metrics = step(state, b, jnp.asarray(refresh))
        loss = float(metrics["loss"])
        dt = time.perf_counter() - t0
        losses.append(loss)
        times.append(dt)
        print(f"train{label} step {t} refresh={int(refresh)} loss {loss:.6f} "
              f"gnorm {float(metrics['grad_norm']):.4f} "
              f"{dt * 1e3:.1f} ms", flush=True)
    del state
    gc.collect()
    return losses, times, compile_s


def train_phase(cfg, *, seq_len, batch, steps, seed):
    """Fused path (Pallas loss, attention and update sweep) against the
    reference path at step 0, plus the kernel counters."""
    from repro.kernels.fused_ce import kernel_calls, reset_kernel_calls

    ref_losses, _, ref_c = run_train(
        cfg, _trainer_config(steps=1, fused=False, seed=seed),
        seq_len=seq_len, batch=batch, steps=1, label=" reference")
    reset_kernel_calls()
    losses, times, c = run_train(
        cfg, _trainer_config(steps=steps, fused=True, seed=seed),
        seq_len=seq_len, batch=batch, steps=steps, label=" fused")
    calls = kernel_calls()
    print(f"train kernel calls (trace time): {calls}")
    _check(all(math.isfinite(x) for x in losses + ref_losses),
           f"non-finite loss: fused {losses} reference {ref_losses}")
    missing = [k for k in TRAIN_KERNELS if not calls.get(k)]
    if not any(v for k, v in calls.items() if k.startswith("bwd_")):
        missing.append("bwd_*")
    _check(not missing, f"kernels not traced into the step: {missing}")
    rel = abs(losses[0] - ref_losses[0]) / abs(ref_losses[0])
    print(f"train step-0 loss fused {losses[0]:.6f} reference "
          f"{ref_losses[0]:.6f} rel diff {rel:.3e} (tol {LOSS_RTOL:g})")
    _check(rel <= LOSS_RTOL, f"step-0 loss rel diff {rel:.3e} > {LOSS_RTOL}")
    warm = times[1:] or times
    print(f"train fused step time (steps 1..): min {min(warm) * 1e3:.1f} ms "
          f"max {max(warm) * 1e3:.1f} ms")
    return c + ref_c


def _requests(cfg, seed, prompt_lens, max_new):
    import numpy as np

    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i, tokens=rng.integers(0, cfg.vocab_size, size=(n,),
                                               dtype=np.int32),
                    max_new=max_new)
            for i, n in enumerate(prompt_lens)]


def serve_phase(cfg, params, *, seed, prompt_lens, max_new, cache_len,
                page_len):
    """Greedy requests through ServeEngine with the Pallas decode kernel
    and the XLA path, bf16 and int8 KV; decode logits compared on the
    Pallas engine's final cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import get_model
    from repro.models.layers import set_decode_attn_impl
    from repro.serve import ServeEngine

    model = get_model(cfg)
    for kv in ("bf16", "int8"):
        out, eng = {}, None
        for impl in ("xla", "pallas"):
            set_decode_attn_impl(impl)
            eng = ServeEngine(cfg, params, n_slots=len(prompt_lens),
                              cache_len=cache_len, page_len=page_len,
                              kv_dtype=kv, seed=seed)
            for r in _requests(cfg, seed, prompt_lens, max_new):
                eng.submit(r)
            t0 = time.perf_counter()
            res = eng.run()
            dt = time.perf_counter() - t0
            out[impl] = {r.uid: list(r.tokens) for r in res}
            n_tok = sum(len(v) for v in out[impl].values())
            print(f"serve kv={kv} decode={impl}: {len(res)} requests, "
                  f"{n_tok} tokens in {dt:.2f} s (compile included)",
                  flush=True)
            _check(len(res) == len(prompt_lens)
                   and all(len(v) == max_new for v in out[impl].values()),
                   f"serve kv={kv} decode={impl}: incomplete results")
        match = sum(a == b for uid in out["xla"]
                    for a, b in zip(out["xla"][uid], out["pallas"][uid]))
        total = sum(len(v) for v in out["xla"].values())

        # one decode step on the Pallas engine's final cache, both paths
        toks = jnp.asarray([[out["pallas"][i][-1]]
                            for i in range(len(prompt_lens))], jnp.int32)
        pos = jnp.asarray([n + max_new - 1 for n in prompt_lens], jnp.int32)
        logits = {}
        for impl in ("xla", "pallas"):
            set_decode_attn_impl(impl)
            fn = jax.jit(lambda p, s, t, q: model.decode_slots(
                eng.cfg, p, s, t, q)[0])
            logits[impl] = np.asarray(fn(params, eng.state, toks, pos),
                                      np.float32)
        set_decode_attn_impl("xla")
        diff = float(np.max(np.abs(logits["xla"] - logits["pallas"])))
        scale = float(np.max(np.abs(logits["xla"])))
        print(f"serve kv={kv}: greedy tokens pallas==xla {match}/{total}; "
              f"decode logits max|diff| {diff:.3e} (max|logit| {scale:.3e}, "
              f"tol {DECODE_ATOL:g})", flush=True)
        _check(np.all(np.isfinite(logits["pallas"])),
               f"serve kv={kv}: non-finite decode logits")
        _check(diff <= DECODE_ATOL,
               f"serve kv={kv}: decode logits differ by {diff:.3e}")
        del eng
        gc.collect()


def _memory_line(devices):
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:in_use={st.get('bytes_in_use', 0) / 2**20:.0f}"
                     f"MiB,peak={st.get('peak_bytes_in_use', 0) / 2**20:.0f}"
                     "MiB")
    return " ".join(parts)


def four_chip_phase(cfg, devices, *, seq_len, batch, steps, seed):
    """The sharded step (FSDP flat shards, bucketed int8 all-reduce) on a
    4-device mesh against the same steps on one device."""
    from repro.launch.train import build_mesh

    mesh = build_mesh(devices)
    print(f"mesh {dict(mesh.shape)} over devices "
          f"{[d.id for d in mesh.devices.flat]}")
    tc = _trainer_config(steps=steps, fused=True, compress=True, seed=seed)
    sharded, _, c4 = run_train(cfg, tc, seq_len=seq_len, batch=batch,
                               steps=steps, mesh=mesh, label=" 4-chip")
    print(f"memory after sharded run: {_memory_line(devices)}")
    single, _, c1 = run_train(cfg, tc, seq_len=seq_len, batch=batch,
                              steps=steps, label=" 1-chip")
    print(f"memory after one-device run: {_memory_line(devices)}")
    _check(all(math.isfinite(x) for x in sharded + single),
           f"non-finite loss: sharded {sharded} one-device {single}")
    rels = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    print(f"4-chip vs 1-chip loss rel diff per step: "
          f"{[f'{r:.3e}' for r in rels]} (tol {LOSS_RTOL:g})")
    _check(max(rels) <= LOSS_RTOL,
           f"sharded vs one-device losses differ: {max(rels):.3e}")
    return c4 + c1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.configs import get_config
        from repro.launch.mesh import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    cache = enable_compile_cache()
    print(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{cache}", flush=True)

    cfg = get_config(ARCH)
    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            compile_s = four_chip_phase(cfg, devices, seq_len=SEQ_LEN,
                                        batch=GLOBAL_BATCH,
                                        steps=TRAIN_STEPS, seed=args.seed)
        else:
            compile_s = train_phase(cfg, seq_len=SEQ_LEN, batch=GLOBAL_BATCH,
                                    steps=TRAIN_STEPS, seed=args.seed)
            from repro.models import get_model
            params = get_model(cfg).init_params(
                cfg, jax.random.PRNGKey(args.seed))
            serve_phase(cfg, params, seed=args.seed, prompt_lens=PROMPT_LENS,
                        max_new=MAX_NEW, cache_len=CACHE_LEN,
                        page_len=PAGE_LEN)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"train step compile total {compile_s:.2f} s; smoke wall "
          f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

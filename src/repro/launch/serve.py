"""Serving driver: a request stream over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --requests 8 --slots 4 --prompt-len 32 --max-new 32 --mixed

Submits ``--requests`` generation requests (mixed prompt/output lengths
with ``--mixed``) to a :class:`repro.serve.ServeEngine` and reports
steady-state throughput.  A warmup pass is timed separately so compile
time never pollutes tok/s; per-token p50/p95 latency, TTFT/TPOT/queue-wait
percentiles and slot utilization come from the engine's telemetry.

Serving tier-2 knobs: ``--prefix-cache/--no-prefix-cache`` turns on
shared-prefix KV page reuse (pair with ``--shared-prefix N`` to give the
stream a common preamble), and ``--kv-dtype int8`` switches the KV cache
to int8 payloads + fp32 per-token scales.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import ARCHS, get_config
from ..models import get_model
from ..models.layers import set_decode_attn_impl
from ..serve import Request, ServeEngine
from .mesh import enable_compile_cache

ENC_SRC_LEN = 16  # synthetic frame-stream length for encdec requests


def _make_requests(cfg, n, prompt_len, max_new, mixed, seed,
                   shared_prefix=0):
    """Deterministic request stream; --mixed varies both lengths;
    ``shared_prefix`` prepends a common preamble (exercises the prefix
    cache the way a shared system prompt would)."""
    prefix = (np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 7), (shared_prefix,), 0, cfg.vocab_size))
        if shared_prefix else None)
    reqs = []
    for i in range(n):
        if mixed:
            sp = max(1, prompt_len // 2 + (i * 7) % prompt_len)
            mn = max(1, max_new // 2 + (i * 5) % max_new)
        else:
            sp, mn = prompt_len, max_new
        if cfg.family == "encdec":
            frames = jax.random.normal(jax.random.PRNGKey(seed + 100 + i),
                                       (ENC_SRC_LEN, cfg.d_model))
            reqs.append(Request(uid=i, tokens=np.zeros((1,), np.int32),
                                max_new=mn, frames=frames))
        else:
            toks = np.asarray(jax.random.randint(
                jax.random.PRNGKey(seed + 100 + i), (sp,), 0,
                cfg.vocab_size))
            if prefix is not None:
                toks = np.concatenate([prefix, toks])
            reqs.append(Request(uid=i, tokens=toks, max_new=mn))
    return reqs


def _new_engine(cfg, params, args):
    return ServeEngine(cfg, params, n_slots=args.slots,
                       cache_len=2 * (args.prompt_len + args.shared_prefix
                                      + args.max_new),
                       page_len=args.page_len,
                       steps_per_tick=args.steps_per_tick, seed=args.seed,
                       src_len=ENC_SRC_LEN if cfg.family == "encdec" else 0,
                       prefix_cache=args.prefix_cache,
                       prefix_pool_pages=args.prefix_pool_pages,
                       kv_dtype=args.kv_dtype)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--mixed", action="store_true",
                    help="vary prompt/output lengths across requests")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="common preamble tokens prepended to every prompt")
    ap.add_argument("--page-len", type=int, default=16)
    ap.add_argument("--steps-per-tick", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--decode-kernel", default="xla",
                    choices=["xla", "pallas"])
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="shared-prefix KV page reuse (dense/moe only)")
    ap.add_argument("--prefix-pool-pages", type=int, default=0,
                    help="device pool size in pages (0 = 4 * slots)")
    ap.add_argument("--kv-dtype", default=None, choices=["bf16", "int8"],
                    help="KV cache dtype; int8 stores 1-byte payloads "
                         "with fp32 per-token scales")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    set_decode_attn_impl(args.decode_kernel)
    cfg = get_config(args.arch, smoke=args.smoke)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(args.seed))

    # --- warmup: compile prefill + decode-burst programs off the clock ---
    t0 = time.perf_counter()
    warm = _new_engine(cfg, params, args)
    for r in _make_requests(cfg, min(2, args.requests), args.prompt_len,
                            args.max_new, args.mixed, args.seed + 999,
                            args.shared_prefix):
        warm.submit(r)
    warm.run()
    compile_s = time.perf_counter() - t0

    # --- measured request stream (steady state: programs already built) ---
    eng = _new_engine(cfg, params, args)
    reqs = _make_requests(cfg, args.requests, args.prompt_len, args.max_new,
                          args.mixed, args.seed, args.shared_prefix)
    for r in reqs:
        r.temperature = args.temperature
        eng.submit(r)
    t0 = time.perf_counter()
    results = eng.run()
    dt = time.perf_counter() - t0

    stats = eng.stats()
    toks = stats["tokens_emitted"]
    print(f"arch={cfg.name} slots={args.slots} requests={args.requests} "
          f"page_len={args.page_len} kernel={args.decode_kernel} "
          f"kv_dtype={eng.cfg.kv_dtype} prefix_cache={args.prefix_cache}")
    print(f"warmup (compile) {compile_s:.2f}s — excluded from tok/s")
    print(f"steady state: {toks} tokens in {dt:.2f}s = {toks / dt:.1f} tok/s")
    print(f"per-token latency p50={stats['token_lat_p50_s'] * 1e3:.2f}ms "
          f"p95={stats['token_lat_p95_s'] * 1e3:.2f}ms  "
          f"slot_utilization={stats['slot_utilization']:.2f}")
    print(f"mean request latency {stats['mean_request_latency_s']:.3f}s  "
          f"mean ttft {stats['mean_ttft_s']:.3f}s")
    print(f"ttft p50/p95/p99 {stats['ttft_p50_s']:.3f}/"
          f"{stats['ttft_p95_s']:.3f}/{stats['ttft_p99_s']:.3f}s  "
          f"tpot p50/p99 {stats['tpot_p50_s'] * 1e3:.2f}/"
          f"{stats['tpot_p99_s'] * 1e3:.2f}ms  "
          f"queue wait p99 {stats['queue_wait_p99_s']:.3f}s")
    if args.prefix_cache:
        print(f"prefix cache: hit_rate={stats['prefix_hit_rate']:.2f} "
              f"pages_reused={stats['prefix_pages_reused']} "
              f"inserts={stats['prefix_inserts']} "
              f"evictions={stats['prefix_evictions']} "
              f"pool={stats['prefix_pool_used']}/"
              f"{stats['prefix_pool_pages']}")
    # results arrive in completion order; sample request 0 specifically
    by_uid = {r.uid: r for r in results}
    print("sample (uid 0):", by_uid[0].tokens[:16])
    return results


if __name__ == "__main__":
    main()

"""Benchmark entry point: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads, builds and warms up the cell (set-up), measures for ``--seconds``
(rounded up to whole refresh periods), checks what the timed path produced
against the plain reference, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit, also printed as the last lines of standard
error.

Refuses to run, printing no result and exiting non-zero, where JAX finds no
TPU or fewer chips than the cell asks for.  JAX's persistent compilation
cache is ``<checkout>/.jax_cache`` whatever the environment says, so every
run of a cell after the first in a checkout finds its programs there.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _setup_paths():
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def _enable_cache(jax):
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"(worst at {c['at']}) {verdict}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="with --trace 1: copy the raw trace and a "
                         "hand-readable summary of it into DIR")
    args = ap.parse_args(argv)

    _setup_paths()
    try:
        from bench import common
        spec = common.cell_spec(args.workload)
        runner = common.runner(spec["traffic"]["kind"])
        import jax
        import repro  # noqa: F401  the system under test
    except (ImportError, KeyError, FileNotFoundError) as e:
        print(f"bench: cannot set up cell {args.workload!r}: {e!r}",
              file=sys.stderr)
        return 2
    _enable_cache(jax)

    devices = jax.devices()
    chips = spec["entry"]["chips"]
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); the "
              f"benchmark runs only on a TPU", file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"bench: cell {args.workload!r} needs {chips} chips, JAX "
              f"finds {len(devices)}", file=sys.stderr)
        return 1

    result = runner.run(spec, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), devices=devices[:chips],
                        t_start=T_START, keep_trace=args.keep_trace)
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

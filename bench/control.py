"""Upper readings of a cell's correctness numbers, for setting its limits.

    python bench/control.py --workload <cell> --seeds 11 12 13 \
        [--program-seeds 21 22 ...]

For each seed, at the cell's own sizes, runs the plain reference and, in the
program's place, (a) the control: the same reference with every matrix
product's operands and cotangents rounded to float8 e4m3 (one scale per
tensor), the precision below the configuration's bfloat16; and (b) the
fault "half of the batch left out, the mean taken over the rest".  Prints
each number of ``bench/check.py`` for both, one JSON line per seed and
variant.  A state left unchanged reads 1 on the leaf numbers by their
definition and needs no run.  With ``--program-seeds``, first reads the
program itself on those seeds (its lower readings), each a run of the
cell with a window of one refresh period, all in this one process.  Runs
on whatever JAX finds; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(spec, seed):
    from bench import check, common
    from bench.kinds.train_lm import reference_readings
    mod = common.reference(spec["config"]["reference"])
    n = spec["workload"]["check_steps"]
    ref = reference_readings(spec, seed, n, keep_delta=True)
    delta = ref.pop("delta")
    out = {}
    for name, kw in (("control_fp8", {"quant": mod.fp8_quant}),
                     ("fault_half_batch", {"half_batch": True})):
        side = reference_readings(spec, seed, n, against=delta, **kw)
        out[name] = {k: v for k, (v, _) in check.gaps(side, ref).items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    from bench import common
    spec = common.cell_spec(args.workload)
    from bench.kinds import train_lm
    for seed in args.program_seeds:
        t0 = time.perf_counter()
        res = train_lm.run(spec, seed=seed, seconds=0, trace=False,
                           devices=jax.devices()[:spec["entry"]["chips"]],
                           t_start=t0)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "variant": "program", "correct": res["correct"],
                          **{k: c["value"] for k, c in
                             res["checks"].items()}}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings(spec, seed)
        for name, nums in out.items():
            print(json.dumps({"cell": args.workload, "seed": seed,
                              "variant": name, **nums}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

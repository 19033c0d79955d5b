"""Compile each cell's train step at its real size for a described TPU v5e,
with no chip attached, and print what the compiler says of its memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

For a one-chip cell the step is compiled for one device of a described
``v5e:2x2`` with its state donated, as the launcher compiles it on a TPU;
a four-chip cell would be compiled over the described 2x2 mesh.  The
kernels' interpret-mode switch and the autotuners' peaks are steered to
the described chip (this host's backend is the CPU).  Nothing runs: the
numbers are the compiler's ``memory_analysis()``, not a chip run.  The
persistent compilation cache is off (a TPU executable written here could
not be read back).
"""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30


def compile_cell(spec, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import (decode_attention, flash_attention, fused_ce,
                               sophia_update)
    from repro.launch import roofline
    from repro.models import ModelConfig
    from repro.train import TrainerConfig, make_train_fns

    kind = topo.devices[0].device_kind
    roofline.device_peaks = lambda k=None: roofline.CHIP_PEAKS[kind]
    for mod in (fused_ce, flash_attention, decode_attention, sophia_update):
        mod._interpret_default = lambda: False
    if spec["entry"]["chips"] != 1:
        raise NotImplementedError("four-chip cells: compile over "
                                  "Mesh(topo.devices) with the launcher's "
                                  "shardings")
    cfg = ModelConfig(name=spec["config"]["name"], **spec["config"]["model"])
    tc = TrainerConfig(**spec["traffic"]["trainer"])
    init_fn, train_step = make_train_fns(cfg, tc)
    dev = SingleDeviceSharding(topo.devices[0])
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=dev),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)))
    B, S = spec["traffic"]["global_batch"], spec["traffic"]["seq_len"]
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=dev)
    flag = jax.ShapeDtypeStruct((), jnp.bool_, sharding=dev)
    t0 = time.perf_counter()
    compiled = jax.jit(train_step, donate_argnums=(0,)).lower(
        state, {"tokens": tok, "labels": tok}, flag).compile()
    return compiled, time.perf_counter() - t0


def main(argv=None):
    for p in (ROOT, os.path.join(ROOT, "src")):
        sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench import common
    jax.config.update("jax_enable_compilation_cache", False)
    cells = argv or [w["name"] for w in common.benchmark()["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for cell in cells:
        compiled, secs = compile_cell(common.cell_spec(cell), topo)
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        text = compiled.as_text()
        print(f"{cell}: compiled for a described "
              f"{topo.devices[0].device_kind} in {secs:.1f} s; "
              f"arguments {ma.argument_size_in_bytes / GIB:.3f} GiB, "
              f"outputs {ma.output_size_in_bytes / GIB:.3f} GiB, "
              f"aliased {ma.alias_size_in_bytes / GIB:.3f} GiB, "
              f"temp {ma.temp_size_in_bytes / GIB:.3f} GiB, "
              f"total {total / GIB:.3f} GiB; "
              f"tpu_custom_call x{text.count('tpu_custom_call')}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

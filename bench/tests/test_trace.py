"""The reduction from a profiler trace to metrics (bench/trace.py), checked
on hand-made events with known answers."""
import os
from types import SimpleNamespace

import pytest

from bench import trace


def _trace():
    # one chip, window [0, 10): ops cover [1, 3), [2, 4) and [6, 7); a
    # collective at [6.5, 8) is half hidden under the op at [6, 7)
    ops = [[("fusion.1", 1.0, 3.0), ("_fwd_kernel", 2.0, 4.0),
            ("_ce_fwd_kernel", 6.0, 7.0), ("all-reduce.3", 6.5, 8.0),
            ("late", 11.0, 12.0)]]
    spans = [("bench.window", 0.0, 10.0), ("bench.batch_at", 0.0, 1.0),
             ("bench.wait", 4.0, 5.5), ("bench.dispatch", 8.0, 9.0)]
    return trace.Trace(ops, spans, (0.0, 10.0))


def test_busy_union_and_window():
    tr = _trace()
    assert tr.window_s() == 10.0
    # [1, 4) + [6, 8) = 5 s; the op after the window is left out
    assert tr.busy_s() == pytest.approx(5.0)


def test_kernel_sums_by_exact_name():
    tr = _trace()
    assert tr.op_s(["_fwd_kernel"]) == pytest.approx(2.0)
    assert tr.op_s(["_ce_fwd_kernel"]) == pytest.approx(1.0)


def test_collective_exposure():
    tr = _trace()
    assert tr.has_collectives()
    assert tr.collective_exposed_s() == pytest.approx(1.0)


def test_idle_gaps_named_by_host_span():
    tr = _trace()
    # idle: [0, 1) batch_at, [4, 6) of which [4, 5.5) wait, [8, 10) of
    # which [8, 9) dispatch
    got = dict((k, v) for k, v in tr.idle_by_span(
        ("bench.batch_at", "bench.put_tree", "bench.dispatch",
         "bench.wait"), 10))
    assert got["bench.batch_at"] == pytest.approx(1.0)
    assert got["bench.wait"] == pytest.approx(1.5)
    assert got["bench.dispatch"] == pytest.approx(1.0)
    assert got["other"] == pytest.approx(1.5)
    assert "bench.put_tree" not in got


def test_top_ops():
    top = _trace().top_ops(2)
    assert [n for n, _ in top] == ["fusion.1", "_fwd_kernel"]


def test_op_names():
    ev = SimpleNamespace
    kernels = {"closed_call.178": "_fwd_kernel", "jvp__.2": "_ce_fwd_kernel"}
    assert trace.op_name(ev(name="fusion.12"), kernels) == "fusion"
    assert trace.op_name(ev(name="closed_call.178"), kernels) == \
        "_fwd_kernel"
    assert trace.op_name(ev(name="%jvp__.2"), kernels) == "_ce_fwd_kernel"
    # an instruction the compiled step does not name keeps its own name
    assert trace.op_name(ev(name="closed_call.7"), kernels) == "closed_call"
    assert trace.op_name(ev(name="closed_call.178")) == "closed_call"


@pytest.fixture(scope="module")
def described_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the cache here could not be read back
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  no TPU compiler to describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_kernel_names_from_the_compiled_hlo(described_chip):
    """Each Pallas call of a program compiled for a TPU is named by its
    kernel function, whatever code surrounds the call."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _double_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2

    def _shift_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 3

    def call(kernel, x):
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            x.shape, x.dtype))(x)

    def f(x):
        y = jax.named_scope("outer")(lambda v: call(_double_kernel, v))(x)
        y = jax.lax.scan(lambda c, _: (call(_shift_kernel, c), None), y,
                         None, length=2)[0]
        return jnp.sin(call(_shift_kernel, y))

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32, sharding=described_chip)
    text = jax.jit(f).lower(x).compile().as_text()
    names = trace.kernel_names(text)
    assert sorted(names.values()) == ["_double_kernel", "_shift_kernel",
                                      "_shift_kernel"]
    for instr in names:
        assert f"%{instr} = " in text
    assert not any(n.endswith("_kernel") for n in names)


def test_record_keeps_what_the_metrics_read():
    tr = _trace()
    back = trace.from_record(trace.to_record(tr))
    assert back.window_s() == tr.window_s()
    assert back.busy_s() == pytest.approx(tr.busy_s())
    assert back.op_s(["_fwd_kernel"]) == pytest.approx(2.0)
    assert dict(back.idle_by_span(("bench.wait",), 3)) == \
        pytest.approx(dict(tr.idle_by_span(("bench.wait",), 3)))

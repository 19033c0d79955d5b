"""The trace reduction (bench/trace.py) on a trace recorded on the chip.

``data/gpt2s.sophia_g.s1024.period.json.gz`` is one refresh period (ten
steps, one of them a refresh) of ``gpt2s.sophia_g.s1024`` on one TPU v5e:
the ``XLA Ops`` line of the chip, kernels named from the compiled step's
HLO (``trace.kernel_names``), and the benchmark's host spans, written by
``bench/run.py --trace 1 --keep-trace DIR`` (``record.json.gz``) and cut to
the period's ten steps.  One chip runs no collective; their overlap is
checked on hand-made events in ``test_trace.py``.
"""
import gzip
import json
from pathlib import Path

import pytest

from bench import common, trace

RECORD = Path(__file__).with_name("data") / \
    "gpt2s.sophia_g.s1024.period.json.gz"
CELL = "gpt2s.sophia_g.s1024"
LAYERS = 12
STEPS, REFRESH = 10, 1


@pytest.fixture(scope="module")
def tr():
    with gzip.open(RECORD, "rt") as f:
        return trace.from_record(json.load(f))


def _covered(intervals, lo, hi):
    """Length of the union by counting how many intervals cover each
    stretch between consecutive end points."""
    edges = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    depth, last, total = 0, None, 0.0
    for t, d in edges:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_busy_union(tr):
    ops = [(s, e) for _, s, e in tr.ops[0]]
    assert tr.busy_s() == pytest.approx(_covered(ops, *tr.window), rel=1e-12)
    assert 0.5 * tr.window_s() < tr.busy_s() < tr.window_s()


def test_kernel_sums_by_exact_name(tr):
    """Every kernel of the step shows as often as the period runs it, and
    its sum takes only the events of that exact name."""
    calls = {
        # forward and backward once per layer, and again on the refresh's
        # sub-batch
        "_fwd_kernel": LAYERS * (STEPS + REFRESH),
        "_dq_kernel": LAYERS * (STEPS + REFRESH),
        "_dkv_kernel": LAYERS * (STEPS + REFRESH),
        "_ce_fwd_kernel": STEPS,
        "_ce_fwd_sample_kernel": REFRESH,
        "_ce_bwd_dh_kernel": STEPS + REFRESH,
        "_ce_bwd_dw_kernel": STEPS + REFRESH,
        "_sophia_kernel": STEPS - REFRESH,
        "_sophia_refresh_kernel": REFRESH,
    }
    for name, n in calls.items():
        events = [(s, e) for m, s, e in tr.ops[0] if m == name]
        assert len(events) == n, name
        assert tr.op_s([name]) == pytest.approx(
            sum(e - s for s, e in events), rel=1e-12)
    # "_fwd_kernel" is not a suffix match: the CE forward stays apart
    both = tr.op_s(["_fwd_kernel", "_ce_fwd_kernel"])
    assert both == pytest.approx(tr.op_s(["_fwd_kernel"])
                                 + tr.op_s(["_ce_fwd_kernel"]), rel=1e-12)


def test_own_times_add_up_to_the_busy_time(tr):
    """Ops nest (a while over the layers holds each layer's ops), so the
    own times of all ops add up to the busy union, and no container leads
    the breakdown."""
    own = sum(v for _, v in tr.top_ops(10 ** 6))
    assert own == pytest.approx(tr.busy_s(), rel=1e-3)
    top = [n for n, _ in tr.top_ops(10)]
    assert not any(n in ("while", "conditional") for n in top)


def test_idle_gaps_named_by_host_span(tr):
    spans = ("bench.batch_at", "bench.put_tree", "bench.dispatch",
             "bench.wait")
    named = tr.idle_by_span(spans, 10)
    assert sum(v for _, v in named) == pytest.approx(
        tr.window_s() - tr.busy_s(), rel=1e-9)
    assert {n for n, _ in named} <= set(spans) | {"other"}


def test_no_collectives_on_one_chip(tr):
    assert not tr.has_collectives()
    assert tr.collective_exposed_s() == 0.0


def test_every_device_metric_reads_the_recorded_period(tr):
    """The readers find their kernels in a real trace, and a share of a
    roofline stays within 100%."""
    spec = common.cell_spec(CELL)
    records = [{"refresh": i == 0, "batch_at": 0.0, "put_tree": 0.0,
                "dispatch": 0.0, "wait": 0.0} for i in range(STEPS)]
    ctx = {"spec": spec, "records": records, "window_s": tr.window_s(),
           "tokens": STEPS * spec["traffic"]["seq_len"]
           * spec["traffic"]["global_batch"], "k": STEPS, "chips": 1,
           "peaks": common.peaks("TPU v5 lite"), "trace": tr}
    for m in spec["per_layer"]:
        if m["source"] != "device_trace":
            continue
        value = common.metric_reader(m["name"]).read(ctx)
        assert value is not None and 0.0 < value <= 100.0, m["name"]

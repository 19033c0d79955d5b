"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced run.  In it each
chip is a plane named ``/device:TPU:<i>`` whose line ``XLA Ops`` holds
every operation that ran on the chip, with its start and duration on the
profiler's clock, named by their HLO instruction; the
host plane ``/host:CPU`` holds the benchmark's own spans
(``bench.window`` around the measured window, ``bench.batch_at``,
``bench.put_tree``, ``bench.dispatch`` and ``bench.wait`` around each
step's phases) on the same clock.

A Pallas kernel's instruction is named after the code around its call
(``closed_call.178``, ``jvp__.2``), not after the kernel.  So
``kernel_names`` reads, from the compiled step's HLO text, the name of the
Mosaic module inside each ``tpu_custom_call`` (the kernel function's name,
``_fwd_kernel``), and the reduction names each such op by it.  Other ops are
named without their HLO ``.<n>`` uniquifier.

All times here are seconds.  Per-chip quantities are averaged over the
chips of the run.
"""
from __future__ import annotations

import base64
import glob
import json
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# an HLO instruction's name ends in a uniquifying ".<n>", and a copy made
# by the compiler in ".<n>.remat<...>" and the like
HLO_SUFFIX = re.compile(r"\.\d+(\..*)?$")
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(merged):
    return sum(e - s for s, e in merged)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a, b):
    """Total length of the overlap of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _own_times(ops):
    """[(name, seconds)]: each op's duration less that of the ops nested in
    it.  On one line of a TPU trace ops nest or follow one another; an op
    that only overlaps another is not counted as inside it."""
    out = []
    stack = []  # indices into out, with their end times
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= e - s
        out.append([name, e - s])
        stack.append((len(out) - 1, e))
    return out


def is_collective(name: str) -> bool:
    return any(w in name for w in COLLECTIVE_WORDS)


class Trace:
    """``ops``: per chip, a list of (name, start, end); ``spans``: host
    spans (name, start, end); ``window``: (start, end)."""

    def __init__(self, ops, spans, window):
        self.ops = [[(n, s, e) for n, s, e in dev
                     if e > window[0] and s < window[1]] for dev in ops]
        self.spans = spans
        self.window = window

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _busy(self, dev):
        return union(_clip([(s, e) for _, s, e in dev], *self.window))

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        return sum(_length(self._busy(d)) for d in self.ops) / len(self.ops)

    def op_s(self, names) -> float:
        """Summed device time of the ops named exactly as in ``names``,
        averaged over the chips."""
        names = set(names)
        return sum(e - s for d in self.ops for n, s, e in d
                   if n in names) / len(self.ops)

    def top_ops(self, n):
        """The ``n`` op names with the most device time of their own,
        averaged over the chips.  An op that holds others (a ``while`` over
        the layers, a ``conditional``) counts only the time in which none
        of them runs."""
        tot = defaultdict(float)
        for d in self.ops:
            for name, own in _own_times(d):
                tot[name] += own / len(self.ops)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, dev):
        busy = self._busy(dev)
        gaps, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        return gaps

    def idle_by_span(self, span_names, n):
        """Idle device time named by the host span it fell in (the
        innermost of ``span_names`` covering it; ``other`` when none),
        summed over the window and averaged over the chips."""
        by = {name: union([(s, e) for nm, s, e in self.spans if nm == name])
              for name in span_names}
        tot = defaultdict(float)
        for d in self.ops:
            gaps = union(self.idle_gaps(d))
            covered = 0.0
            for name in span_names:
                ov = _overlap(gaps, by[name])
                tot[name] += ov / len(self.ops)
                covered += ov
            tot["other"] += (_length(gaps) - covered) / len(self.ops)
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                if v > 0][:n]

    def collective_exposed_s(self) -> float:
        """Collective time during which no other op ran on that chip,
        averaged over the chips."""
        tot = 0.0
        for d in self.ops:
            coll = union(_clip([(s, e) for n, s, e in d if is_collective(n)],
                               *self.window))
            comp = union(_clip([(s, e) for n, s, e in d
                                if not is_collective(n)], *self.window))
            tot += _length(coll) - _overlap(coll, comp)
        return tot / len(self.ops)

    def has_collectives(self) -> bool:
        return any(is_collective(n) for d in self.ops for n, _, _ in d)


def to_record(tr: Trace) -> dict:
    """A trace as plain data (times in seconds from the window's start),
    for keeping a recorded trace with the self-tests."""
    t0 = tr.window[0]
    return {"window_s": tr.window_s(),
            "ops": [[[n, s - t0, e - t0] for n, s, e in d] for d in tr.ops],
            "spans": [[n, s - t0, e - t0] for n, s, e in tr.spans
                      if e > tr.window[0] and s < tr.window[1]]}


def from_record(rec: dict) -> Trace:
    return Trace([[tuple(o) for o in d] for d in rec["ops"]],
                 [tuple(x) for x in rec["spans"]], (0.0, rec["window_s"]))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{paths}")
    return paths[0]


def _mosaic_name(body: bytes) -> str:
    """The ``sym_name`` of a serialized Mosaic module: Pallas names the
    module after its kernel function."""
    from jaxlib.mlir import ir
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(body)
        return ir.StringAttr(module.operation.attributes["sym_name"]).value


def kernel_names(hlo_text: str) -> dict:
    """{HLO instruction name: kernel name} for every Pallas TPU kernel in a
    compiled program's HLO text (``compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        if TPU_CUSTOM_CALL not in line:
            continue
        instr = line.split("=", 1)[0].split()[-1].lstrip("%")
        cfg = line[line.index("backend_config=") + len("backend_config="):]
        cfg, _ = json.JSONDecoder().raw_decode(cfg)
        body = base64.b64decode(cfg["custom_call_config"]["body"])
        out[instr] = _mosaic_name(body)
    return out


def op_name(ev, kernels=None) -> str:
    """An op's name: a Pallas kernel's own name where ``kernels`` (from
    ``kernel_names``) knows its instruction, else the instruction's name
    without its HLO uniquifier.  A TPU trace names each op by the text of
    its HLO instruction, ``%fusion.426 = s32[...] fusion(...)``."""
    name = ev.name.split(" = ", 1)[0].strip().lstrip("%")
    if kernels and name in kernels:
        return kernels[name]
    return HLO_SUFFIX.sub("", name)


def from_xplane(path: str, n_devices: int, kernels=None) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(dev.group(1))] = [
                        (op_name(ev, kernels), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    chips = [ops.get(i, []) for i in range(n_devices)]
    if not any(chips):
        raise RuntimeError(f"no '{OPS_LINE}' events on any TPU plane of "
                           f"{path}")
    return Trace(chips, spans, windows[0])


def load(log_dir: str, n_devices: int, kernels=None) -> Trace:
    return from_xplane(find_xplane(log_dir), n_devices, kernels)


def describe(path: str, top: int = 60) -> str:
    """A hand-readable summary of a trace's planes, lines and most costly
    event names (for naming kernels in the metric readers)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            tot = defaultdict(lambda: [0, 0.0])
            for ev in evs:
                tot[ev.name][0] += 1
                tot[ev.name][1] += ev.duration_ns * 1e-9
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:2]:
                out.append(f"    event {ev.name!r} stats " + "; ".join(
                    f"{k}={str(v)[:160]!r}" for k, v in ev.stats))
            for name, (c, s) in sorted(tot.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {s:12.6f} s {c:7d}x {name}")
    return "\n".join(out)

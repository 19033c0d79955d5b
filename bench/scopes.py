"""Attribution of a traced train step's device time to the program's layers.

The program puts each layer of its train step in a ``jax.named_scope``
(``SCOPES``), and XLA keeps the scope in the ``op_name`` metadata of every
instruction traced under it: the fusions, the backward pass
(``transpose(jvp(model.layers))``) and the copies recomputed under remat.
``op_scopes`` reads that metadata from the compiled step's HLO text, as
``trace.kernel_names`` reads the Pallas kernels' names, and gives each
instruction its *scope path*: the program's scopes in the order they appear
in its ``op_name``, joined by ``/``
(``sophia.estimate/model.layers/model.block/model.attn``).  An instruction
the compiler made with no metadata takes a path from its fused body, its
operands or its caller (``op_scopes``); one under none of the scopes gets
``""``.

``from_xplane`` reduces a profile as ``trace.from_xplane`` does, and keeps
beside each op its scope path (``ScopedTrace.scopes``, parallel to
``ops``) and beside the benchmark's ``bench.`` host spans the program's own
(``data.``).  Scope time is own time, as ``Trace.top_ops`` counts it, so the
``while`` over the layers and the refresh's ``conditional`` are not counted
twice.  A program without the scopes gives every op ``""``.

The reductions here are not yet read by ``bench/run.py``; PERF.md §7 says
which harness files would call them.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from bench import trace

SCOPES = ("model.embed", "model.layers", "model.block", "model.attn",
          "model.mlp", "loss.lm", "train.grad_clip", "optimizer.ravel",
          "optimizer.update", "sophia.estimate")
PROGRAM_SPAN_PREFIX = "data."
STEP_SPANS = ("bench.dispatch", "bench.wait")
DATA_SPAN = "data.batch_at"
NONE = "(none)"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# an instruction's opcode and operands: ``opcode(%a, %b)`` (a layout's
# ``T(8,128)`` is followed by a digit, never by ``%`` or ``)``)
_OPERANDS = re.compile(r"\s[a-z][\w\-]*\(((?:%[\w.\-]+(?:,\s*)?)*)\)")
# the computations an instruction runs: a while's body and condition, a
# conditional's branches, a call's or a fusion's computation
_CALLED = re.compile(r"(?:body|condition|calls|to_apply|branch_computations|"
                     r"true_computation|false_computation)="
                     r"\{?((?:%[\w.\-]+(?:,\s*)?)+)\}?")
_NAME = re.compile(r"%([\w.\-]+)")
_WORD = re.compile(r"[\w.]+")


def scope_path(op_name: str) -> str:
    """The program scopes named in an ``op_name``, in order, once each."""
    out = []
    for w in _WORD.findall(op_name):
        if w in SCOPES and w not in out:
            out.append(w)
    return "/".join(out)


def op_scopes(hlo_text: str) -> dict:
    """{HLO instruction name: scope path} for every instruction of a
    compiled program's HLO text (``compiled.as_text()``).

    An instruction with ``op_name`` metadata has the scopes it names.  The
    compiler makes some with no metadata (the copies of a loop's state,
    the ``dynamic-update-slice`` chains it turns a concatenate into): such
    a fusion takes the path most of its fused instructions have, another
    instruction that of its first operand with a scope, and failing both,
    that of the instruction that calls its computation."""
    order, own, comp_of, operands, called = [], {}, {}, {}, {}
    members = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
            continue
        name = m.group(1)
        order.append(name)
        comp_of[name] = comp
        members[comp].append(name)
        op = _OP_NAME.search(line)
        own[name] = scope_path(op.group(1)) if op else None
        ops = _OPERANDS.search(line[m.end():])
        operands[name] = _NAME.findall(ops.group(1)) if ops else []
        called[name] = [c for group in _CALLED.findall(line)
                        for c in _NAME.findall(group)]
    callers = {}
    for name in order:
        for c in called[name]:
            callers.setdefault(c, name)

    # what an instruction's own metadata, its fused body or its operands
    # say, in text order (an operand is printed before its users)
    path = {}
    for name in order:
        p = own[name]
        if p is None:
            inner = Counter(own[x] for c in called[name]
                            for x in members[c] if own[x])
            p = inner.most_common(1)[0][0] if inner else None
        if p is None:
            p = next((path[x] for x in operands[name] if path.get(x)), None)
        path[name] = p
    # the rest from their callers, callers first (a computation is printed
    # before the instructions that call it)
    for name in reversed(order):
        if path[name] is None:
            caller = callers.get(comp_of[name])
            path[name] = path.get(caller) or "" if caller else ""
    return path


def instruction(ev) -> str:
    """The HLO instruction an ``XLA Ops`` event ran, with its uniquifier."""
    return ev.name.split(" = ", 1)[0].strip().lstrip("%")


class ScopedTrace(trace.Trace):
    """A ``trace.Trace`` whose ``scopes`` give, per chip and parallel to
    ``ops``, each op's scope path."""

    def __init__(self, ops, spans, window, scopes):
        keep = [[i for i, (_, s, e) in enumerate(d)
                 if e > window[0] and s < window[1]] for d in ops]
        super().__init__([[d[i] for i in k] for d, k in zip(ops, keep)],
                         spans, window)
        self.scopes = [[sc[i] for i in k] for sc, k in zip(scopes, keep)]

    def _own_by_scope(self):
        """{scope path: own device seconds}, averaged over the chips."""
        tot = defaultdict(float)
        for d, sc in zip(self.ops, self.scopes):
            for path, own in trace._own_times(
                    [(p, s, e) for (_, s, e), p in zip(d, sc)]):
                tot[path] += own / len(self.ops)
        return tot

    def scope_s(self, keep) -> float:
        """Own device seconds of the ops whose scope path ``keep(path)``
        accepts, averaged over the chips."""
        return sum(v for k, v in self._own_by_scope().items() if keep(k))

    def top_scopes(self, n):
        """The ``n`` scope paths with the most own device time; ``""``
        shows as ``(none)``."""
        tot = self._own_by_scope()
        return [[k or NONE, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_ops_in(self, keep, n):
        """The ``n`` op names with the most own device time among the ops
        whose scope path ``keep(path)`` accepts."""
        tot = defaultdict(float)
        for d, sc in zip(self.ops, self.scopes):
            for (name, path), own in trace._own_times(
                    [((nm, p), s, e) for (nm, s, e), p in zip(d, sc)]):
                if keep(path):
                    tot[name] += own / len(self.ops)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_in_step(self, n, step_spans=STEP_SPANS):
        """Device idle time inside the host's ``step_spans`` (the step's
        dispatch and wait), named by the scope path of the op that ends each
        gap (the innermost op starting there; ``(none)`` if no op follows in
        the window), the ``n`` largest."""
        steps = trace.union([(s, e) for nm, s, e in self.spans
                             if nm in step_spans])
        tot = defaultdict(float)
        for d, sc in zip(self.ops, self.scopes):
            order = sorted(range(len(d)), key=lambda i: (d[i][1], d[i][2]))
            starts = [d[i][1] for i in order]
            for gs, ge in self.idle_gaps(d):
                inside = trace._overlap([[gs, ge]], steps)
                if inside <= 0:
                    continue
                j = bisect.bisect_left(starts, ge)
                name = (sc[order[j]] or NONE) if j < len(order) else NONE
                tot[name] += inside / len(self.ops)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_in_span_s(self, name) -> float:
        """Device idle seconds inside the host spans called ``name``,
        averaged over the chips."""
        spans = trace.union([(s, e) for nm, s, e in self.spans
                             if nm == name])
        return sum(trace._overlap(trace.union(self.idle_gaps(d)), spans)
                   for d in self.ops) / len(self.ops)

    def steps(self, name="bench.dispatch") -> int:
        """Steps of the window: the host spans called ``name`` that start
        in it."""
        return sum(1 for nm, s, _ in self.spans
                   if nm == name and self.window[0] <= s < self.window[1])

    def breakdown(self):
        """What ``bench/run.py`` would add to a traced run's breakdown."""
        return {"scopes": self.top_scopes(10),
                "idle_in_step": self.idle_in_step(5)}


def _has(path, scope):
    return scope in path.split("/")


def estimate_device_pct(tr: ScopedTrace) -> float:
    """100 x own device time under ``sophia.estimate`` / the rest of the
    busy time: the Hessian estimator's device cost over the steps'."""
    est = tr.scope_s(lambda p: _has(p, "sophia.estimate"))
    return 100.0 * est / (tr.busy_s() - est)


def optimizer_ms_per_step(tr: ScopedTrace, steps: int) -> float:
    """Own device milliseconds a step under ``train.grad_clip`` and
    ``optimizer.*``, outside the estimator's pass."""
    def keep(p):
        parts = p.split("/")
        return "sophia.estimate" not in parts and any(
            x == "train.grad_clip" or x.startswith("optimizer.")
            for x in parts)
    return 1e3 * tr.scope_s(keep) / steps


def layer_scan_device_pct(tr: ScopedTrace) -> float:
    """100 x own device time of the layer scan itself (under
    ``model.layers`` and not in a block: slicing the stacked weights and
    saved residuals, the loop's copies), the estimator's pass included,
    over the busy time.  The writes of a layer's residuals into the stacks
    count here only where the compiler leaves them out of the block's own
    fusions."""
    return 100.0 * tr.scope_s(lambda p: _has(p, "model.layers")
                              and not _has(p, "model.block")) / tr.busy_s()


def data_idle_ms_per_step(tr: ScopedTrace, steps: int) -> float:
    """Device idle milliseconds a step inside the program's
    ``data.batch_at`` spans."""
    return 1e3 * tr.idle_in_span_s(DATA_SPAN) / steps


def from_xplane(path: str, n_devices: int, kernels=None,
                scopes=None) -> ScopedTrace:
    """``trace.from_xplane`` keeping each op's scope path (from
    ``op_scopes``; ``""`` where ``scopes`` does not know the instruction)
    and the program's host spans beside the benchmark's."""
    from jax.profiler import ProfileData
    scopes = scopes or {}
    data = ProfileData.from_file(path)
    ops, paths, spans = {}, {}, []
    for plane in data.planes:
        dev = trace.DEVICE_PLANE.match(plane.name)
        if dev:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    evs = list(line.events)
                    i = int(dev.group(1))
                    ops[i] = [(trace.op_name(ev, kernels), ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                              for ev in evs]
                    paths[i] = [scopes.get(instruction(ev), "")
                                for ev in evs]
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((trace.SPAN_PREFIX,
                                           PROGRAM_SPAN_PREFIX)):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    windows = [(s, e) for n, s, e in spans if n == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {trace.WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    return ScopedTrace([ops.get(i, []) for i in range(n_devices)], spans,
                       windows[0],
                       [paths.get(i, []) for i in range(n_devices)])


def to_record(tr: ScopedTrace) -> dict:
    """``trace.to_record`` with the scope paths beside the ops; the record
    stays readable by ``trace.from_record``."""
    rec = trace.to_record(tr)
    rec["scopes"] = tr.scopes
    return rec


def from_record(rec: dict) -> ScopedTrace:
    """A record of ``to_record``, or of ``trace.to_record`` (every op then
    under no scope)."""
    ops = [[tuple(o) for o in d] for d in rec["ops"]]
    scopes = rec.get("scopes") or [[""] * len(d) for d in ops]
    return ScopedTrace(ops, [tuple(x) for x in rec["spans"]],
                       (0.0, rec["window_s"]), scopes)

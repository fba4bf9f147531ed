"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

A trace holds device planes (``/device:TPU:<n>``) whose ``XLA Ops`` line
lists every operation that ran on that chip, and host planes whose
lines carry the benchmark's own spans (``bench.window``,
``bench.train_step``: written by ``jax.profiler.TraceAnnotation`` in
``bench.lib.spans``).  Both are on
the profiler's clock, in nanoseconds.

Everything here works on plain lists of ``(start, end, name)`` tuples,
so it is checked against a small recorded trace
(``bench/tests/data``) and against hand-made intervals alike.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

Interval = tuple[float, float, str]          # start ns, end ns, name

SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"[.\-_]\d+$")


@dataclass
class Trace:
    devices: dict[str, list[Interval]] = field(default_factory=dict)
    spans: list[Interval] = field(default_factory=list)

    def leaves(self, device: str) -> list[Interval]:
        """The device's ops that hold no other op: a ``while`` that runs
        the layer scan is listed around the ops of its body, and counts
        for busy time but not as an op of its own."""
        ops = self.devices[device]
        out = []
        for i, (a, b, n) in enumerate(ops):
            nxt = ops[i + 1] if i + 1 < len(ops) else None
            if nxt is not None and nxt[0] < b and nxt[1] <= b and (
                    nxt[0], nxt[1]) != (a, b):
                continue
            out.append((a, b, n))
        return out


def op_name(text: str) -> str:
    """A TPU trace names each op by its HLO text (``%fusion.3 = bf16[..]
    fusion(...)``); the op's name is the part before `` = ``."""
    if text.startswith("%"):
        return text[1:].split(" ", 1)[0]
    return text


def load(path: str) -> Trace:
    """Device operations and the benchmark's spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                ops += [(e.start_ns, e.start_ns + e.duration_ns,
                         op_name(e.name)) for e in line.events]
            if ops:
                tr.devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    tr.spans.sort()
    for ops in tr.devices.values():
        ops.sort(key=lambda o: (o[0], -o[1]))
    return tr


def family(op_name: str) -> str:
    """An op's name without the numeric suffix XLA gives each instance
    (``mx_gemm_pallas.78`` -> ``mx_gemm_pallas``)."""
    prev = None
    while prev != op_name:
        prev, op_name = op_name, _SUFFIX.sub("", op_name)
    return op_name


def spans_named(tr: Trace, name: str) -> list[Interval]:
    return [s for s in tr.spans if s[2] == name]


def window(tr: Trace) -> tuple[float, float]:
    """The first ``bench.window`` span."""
    w = spans_named(tr, SPAN_PREFIX + "window")
    if not w:
        raise ValueError("trace holds no bench.window span")
    return w[0][0], w[0][1]


def clip(ivs, lo: float, hi: float) -> list[Interval]:
    return [(max(a, lo), min(b, hi), n) for a, b, n in ivs
            if b > lo and a < hi]


def union(ivs) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering ``ivs``."""
    out: list[list[float]] = []
    for a, b, *_ in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(ivs, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by ``ivs`` (ns)."""
    return sum(b - a for a, b in union(clip(ivs, lo, hi)))


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some operation ran, averaged over the
    chips."""
    if not tr.devices:
        return 0.0
    return sum(covered(ops, lo, hi)
               for ops in tr.devices.values()) / len(tr.devices)


def op_ns(tr: Trace, lo: float, hi: float, match) -> float:
    """Device time in [lo, hi] of the ops (``Trace.leaves``) whose
    family ``match`` accepts, averaged over the chips."""
    if not tr.devices:
        return 0.0
    total = 0.0
    for dev in tr.devices:
        total += sum(b - a for a, b, n in clip(tr.leaves(dev), lo, hi)
                     if match(family(n)))
    return total / len(tr.devices)


def top_ops(tr: Trace, lo: float, hi: float, n: int = 10):
    """The ``n`` op names that took most device time in [lo, hi]
    (seconds, summed over the chips and averaged over them)."""
    by: dict[str, float] = {}
    for dev in tr.devices:
        for a, b, name in clip(tr.leaves(dev), lo, hi):
            by[name] = by.get(name, 0.0) + (b - a)
    k = max(len(tr.devices), 1)
    return sorted(((name, t / k / 1e9) for name, t in by.items()),
                  key=lambda x: -x[1])[:n]


def idle_gaps(tr: Trace, lo: float, hi: float, n: int = 10):
    """The ``n`` longest stretches of [lo, hi] in which the first chip
    ran nothing, each named by the innermost benchmark span around its
    middle (seconds)."""
    if not tr.devices:
        return []
    dev = sorted(tr.devices)[0]
    gaps, t = [], lo
    for a, b in union(clip(tr.devices[dev], lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        around = [s for s in tr.spans if s[0] <= mid <= s[1]]
        inner = min(around, key=lambda s: s[1] - s[0])[2] if around else "none"
        named.append((inner, (b - a) / 1e9))
    return sorted(named, key=lambda x: -x[1])[:n]


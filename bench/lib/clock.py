"""The offset between the host's clock and a chip's in a profiler trace.

A trace puts host events (the benchmark's spans, the program's launches)
and each chip's ops on one time line, but the chip's times come from the
chip's own clock.  Each run of a compiled program shows on both sides:
on the host its launch (``PJRT_LoadedExecutable_Execute``) and the
host's sight of its end (``CompleteCallbacks``, which carries the run's
``run_id`` and correlation id ``_c``), on the chip an ``XLA Modules``
event with the same ``run_id`` and ``_c``.  A run starts on the chip
after its launch and ends before the host sees it end, so with
host time = chip time + offset, each run bounds the offset:

    launch - chip start  <=  offset  <=  complete - chip end

Launches carry no run id; on one chip they pair with the runs in order.
"""

from __future__ import annotations

from . import trace as tr

LAUNCH = "PJRT_LoadedExecutable_Execute"
COMPLETE = "CompleteCallbacks"


def runs(path: str) -> dict[str, list[tuple[float, float, float, float]]]:
    """Each chip's runs in a trace file: (launch, complete, chip start,
    chip end) in ns, or none where launches and runs do not pair."""
    from jax.profiler import ProfileData

    launches, completes, chips = [], {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/host:"):
                    if e.name == LAUNCH:
                        launches.append(e.start_ns)
                    elif e.name == COMPLETE:
                        st = dict(e.stats)
                        completes[st["run_id"], st["_c"]] = e.start_ns
                elif line.name == "XLA Modules":
                    st = dict(e.stats)
                    chips.setdefault(plane.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         (st["run_id"], st["_c"])))
    launches.sort()
    out = {}
    for dev, mods in chips.items():
        mods.sort()
        if len(mods) != len(launches) or any(m[2] not in completes
                                             for m in mods):
            out[dev] = []
            continue
        out[dev] = [(launch, completes[key], a, b)
                    for launch, (a, b, key) in zip(launches, mods)]
    return out


def offset_ns(path: str) -> tuple[float, float] | None:
    """(least, greatest) host-minus-chip offset that every run of every
    chip in the trace allows, in ns; None where the runs do not pair or
    allow no offset."""
    rs = [r for dev_runs in runs(path).values() for r in dev_runs]
    if not rs:
        return None
    lo = max(launch - a for launch, _, a, _ in rs)
    hi = min(done - b for _, done, _, b in rs)
    return (lo, hi) if lo <= hi else None


def idle_gaps(trace: tr.Trace, lo: float, hi: float, offset: float,
              n: int = 10):
    """``trace.idle_gaps`` of the host-clock window [lo, hi], with the
    window and the host spans that name the gaps moved onto the chip's
    clock by ``offset`` (ns)."""
    moved = tr.Trace(devices=trace.devices,
                     spans=[(a - offset, b - offset, name)
                            for a, b, name in trace.spans])
    return tr.idle_gaps(moved, lo - offset, hi - offset, n)

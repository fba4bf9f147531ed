"""Program scopes of an MoE training step, read from the device trace:
``lib.scopes`` with the MoE layer's own scopes beside the step's
(``moe.router``, ``moe.dispatch``, ``moe.experts``; an op belongs to the
innermost scope on its path, so these take their ops out of ``mlp``).

The cell hands its readers a ``Split`` built from the HLO of the step it
compiled at set-up; the split itself is made from the trace the first
time a reader asks, after the window.
"""

from __future__ import annotations

from collections.abc import Mapping

from . import scopes

SCOPES = scopes.SCOPES + ("moe.router", "moe.dispatch", "moe.experts")


def hlo_scopes(text: str) -> dict:
    """``scopes.hlo_scopes`` over ``SCOPES``."""
    saved = scopes.SCOPES
    scopes.SCOPES = SCOPES
    try:
        return scopes.hlo_scopes(text)
    finally:
        scopes.SCOPES = saved


class Split(Mapping):
    """Device ms per step of each of ``SCOPES`` in a traced run's window
    (empty where no op resolves to a scope), as ``scopes.step_ms``
    returns it, computed on first use with the same two notes."""

    def __init__(self, run, names: dict):
        self.run, self.names, self._ms = run, names, None

    def _split(self) -> dict:
        if self._ms is None:
            self._ms = self._compute()
        return self._ms

    def _compute(self) -> dict:
        run = self.run
        if run.trace is None or not run.trace.devices:
            return {}
        lo, hi = run.window_ns
        ns = scopes.scope_ns(run.trace, lo, hi, self.names)
        total = sum(ns.values())
        if total <= 0 or not any(s for s in ns):
            return {}
        steps = run.extra["steps"]
        split = {s: ns.get(s, 0.0) / 1e6 / steps for s in SCOPES}
        run.notes.append(
            "device ms per step by program scope: "
            + ", ".join(f"{s} {v:.3f}" for s, v in split.items())
            + f"; in no scope: {100.0 * ns.get(None, 0.0) / total:.3f} % of "
            "the window's device time")
        n = scopes._window_compiles(run)
        if n is not None:
            run.notes.append(f"compiles in the window: {n}")
        return split

    def __getitem__(self, scope):
        return self._split()[scope]

    def __iter__(self):
        return iter(self._split())

    def __len__(self):
        return len(self._split())

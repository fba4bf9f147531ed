"""The per-scope split of the device trace (``lib.scopes``) on a
hand-made trace and map, and the step it maps against the window's."""

import re
import time
import types

import jax
import pytest

import tiny
from bench.lib import harness, scopes, train_cell
from bench.lib import trace as tr

MS = 1e6          # ns


def _trace():
    t = tr.Trace()
    t.devices["/device:TPU:0"] = [
        (0, 10 * MS, "fusion.1"), (10 * MS, 40 * MS, "custom-call.2"),
        (40 * MS, 50 * MS, "copy.3"), (50 * MS, 80 * MS, "while.1"),
        (52 * MS, 60 * MS, "fusion.4"), (95 * MS, 120 * MS, "fusion.5")]
    t.spans = [(0, 100 * MS, "bench.window")]
    return t


NAMES = {"fusion.1": "head", "custom-call.2": "moss.scale",
         "fusion.4": "optimizer", "fusion.5": "head", "copy.3": None}


def test_split_clips_to_the_window_and_skips_containers():
    ns = scopes.scope_ns(_trace(), 0, 100 * MS, NAMES)
    # the while holds fusion.4 and is no op of its own; fusion.5 is
    # clipped at the window's end
    assert ns == {"head": 15 * MS, "moss.scale": 30 * MS,
                  "optimizer": 8 * MS, None: 10 * MS}
    split, unresolved = scopes.split_ms(_trace(), 0, 100 * MS, NAMES, 5)
    assert split["head"] == pytest.approx(3.0)
    assert split["moss.scale"] == pytest.approx(6.0)
    assert split["attn_core"] == 0.0
    assert unresolved == pytest.approx(10 / 63)


def test_a_program_without_scopes_reads_nothing():
    assert scopes.split_ms(_trace(), 0, 100 * MS,
                           dict.fromkeys(NAMES), 5) is None
    run = types.SimpleNamespace(chips=1, trace=tr.Trace(), extra={},
                                notes=[])
    assert scopes.read_ms(run, "head") is None
    assert run.notes == []


def test_compiled_step_is_the_windows_step():
    """Compiled from its arguments' shapes, the step is the program the
    cell's window runs: its instructions carry the trace's names."""
    cell, conf, job, limits = tiny.files("train-olmo7b-moss")
    run = harness.Run(cell=cell, conf=conf, traffic=job, limits=limits,
                      seed=2**31 + 5, seconds=0.0, tracing=False,
                      t_start=time.monotonic(), devices=jax.devices())
    c = train_cell.Cell(run, hooks={})
    c.setup()
    b = c.feed(c.key, c.next_step)
    want = c.step.lower(c.state, b).compile().as_text()
    got = scopes.compile_step(conf, job).as_text()
    assert _code(got) == _code(want)
    assert scopes.hlo_scopes(got) == scopes.hlo_scopes(want)


def _code(text):
    """An HLO module's text without the tables of source files and stack
    frames, which name the callers, and each op's metadata."""
    text = re.sub(r"\n\nFileNames\n.*?(?=\n\n[%E])", "", text, flags=re.S)
    return re.sub(r", metadata=\{[^}]*\}", "", text)


def test_traced_run_reads_time_per_scope_and_notes_it():
    """The readers on a run whose trace holds ops of the compiled step's
    ``head`` and ``moss.scale`` and one op of another program."""
    cell, conf, job, limits = tiny.files("train-olmo7b-moss")
    names = scopes.hlo_scopes(scopes.compile_step(conf, job).as_text())
    head, scale = (next(n for n, s in names.items() if s == want)
                   for want in ("head", "moss.scale"))
    run = harness.Run(cell=cell, conf=conf, traffic=job, limits=limits,
                      seed=1, seconds=1.0, tracing=True, t_start=0.0,
                      devices=jax.devices())
    run.trace = tr.Trace(devices={"/device:TPU:0": [
        (0, 4 * MS, head), (4 * MS, 6 * MS, scale),
        (6 * MS, 8 * MS, "fusion.of_another_program")]})
    run.window_ns = (0, 10 * MS)
    run.extra.update(steps=2, window_s=1.0)
    run.end_to_end["setup_s"] = 1.0
    assert scopes.read_ms(run, "head", "loss") == pytest.approx(2.0)
    assert scopes.read_ms(run, "moss.scale") == pytest.approx(1.0)
    assert scopes.read_ms(run, "attn_core") == 0.0
    split, compiles = run.notes
    assert split.startswith("device ms per step by program scope: embed 0.000")
    assert split.endswith("in no scope: 25.000 % of the window's device time")
    assert compiles == "compiles in the window: 0"

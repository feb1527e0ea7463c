"""The reduction from a profiler trace to busy time, top operations and
named idle gaps (``bench/devtrace.py``), on a trace recorded on one TPU
v5e chip by ``record_trace.py`` and on a hand-built one."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.append(str(BENCH))

RECORDED = BENCH / "tests" / "data" / "small_trace.xplane.pb"
MS = 1_000_000


def _ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def _fake(monkeypatch, planes):
    import jax.profiler
    data = NS(planes=[NS(name=n, lines=[NS(name=ln, events=evs)
                                        for ln, evs in lines])
                      for n, lines in planes])
    monkeypatch.setattr(jax.profiler, "ProfileData",
                        NS(from_file=lambda _: data))


def test_reduce_hand_built(monkeypatch):
    import devtrace
    host = [("python3", [_ev("window", 0, 100),
                         _ev("payload j00000", 10, 60)])]
    dev = [("XLA Ops", [_ev("%while.1 = (f32[8]) while(...)", 30, 15),
                        _ev("%fusion.2 = f32[8] fusion(...)", 32, 8),
                        _ev("%fusion.1 = f32[8] fusion(...)", 50, 10),
                        _ev("%copy = f32[8] copy(...)", 95, 10)]),
           ("XLA Modules", [_ev("jit_train_step(1)", 30, 15),
                            _ev("jit_train_step(1)", 50, 10)])]
    _fake(monkeypatch, [("/host:CPU", host), ("/device:TPU:0", dev)])
    r = devtrace.reduce("unused")
    assert r["window_s"] == pytest.approx(0.100)
    # ops cover 30-45, 50-60 and 95-100 inside the window
    assert r["busy_s"] == pytest.approx(0.030)
    # the loop's own time excludes its body's
    assert [[n, round(t * 1000)] for n, t in r["device_ops"]] == [
        ["%fusion.1", 10], ["%fusion.2", 8], ["%while.1", 7], ["%copy", 5]]
    # idle 0-30, 45-50 and 60-95, cut where the payload (10-70) starts
    # and ends; longest first
    assert [[lab, round(t * 1000)] for lab, t in r["idle_gaps"]] == [
        ["dispatch gap", 25], ["job set-up", 20], ["dispatch gap", 10],
        ["job wind-down", 10], ["between steps", 5]]


def test_payload_running_when_the_trace_stops(monkeypatch):
    """A payload that straddles the window's close has no span in the
    trace, only its start mark; its set-up is still the job's."""
    import devtrace
    host = [("python3", [_ev("window", 0, 100),
                         _ev("payload j00001 start", 60, 0)])]
    dev = [("XLA Ops", [_ev("%fusion.1 = f32[8] fusion(...)", 0, 40)]),
           ("XLA Modules", [_ev("jit_train_step(1)", 0, 40)])]
    _fake(monkeypatch, [("/host:CPU", host), ("/device:TPU:0", dev)])
    r = devtrace.reduce("unused")
    assert [[lab, round(t * 1000)] for lab, t in r["idle_gaps"]] == [
        ["job set-up", 40], ["dispatch gap", 20]]


def test_reduce_needs_a_window_and_device_ops(monkeypatch):
    import devtrace
    _fake(monkeypatch, [("/host:CPU", [("python3", [_ev("x", 0, 1)])])])
    with pytest.raises(RuntimeError):
        devtrace.reduce("unused")


def test_reduce_recorded_trace():
    import devtrace
    from record_trace import PAUSE
    r = devtrace.reduce(str(RECORDED))
    # two jobs: a dispatch pause, a set-up pause, three steps each with a
    # pause after it; one more dispatch pause at the end
    least = 3 * PAUSE["dispatch"] + 2 * (PAUSE["setup"] + 3 * PAUSE["between"])
    assert r["window_s"] >= least
    assert 0 < r["busy_s"] < r["window_s"] - least + 0.05
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    by_label = {}
    for label, s in r["idle_gaps"]:
        by_label.setdefault(label, []).append(s)
    assert set(by_label) <= {"dispatch gap", "job set-up", "between steps",
                             "job wind-down"}
    # three dispatch pauses and two set-up pauses, each a gap of its own
    assert len(by_label["dispatch gap"]) == 3
    assert min(by_label["dispatch gap"]) >= PAUSE["dispatch"] * 0.9
    assert len(by_label["job set-up"]) == 2
    assert min(by_label["job set-up"]) >= PAUSE["setup"] * 0.9
    assert max(by_label["between steps"]) >= PAUSE["between"] * 0.9
    # operations are named by their short HLO name
    assert all(" = " not in name for name, _ in r["device_ops"])

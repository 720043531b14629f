"""Rounds on the profiler's clock (sidecar/trace.py): the tracer keeps the
rounds closed while a JAX profiler session records, and anchors its
clock in the trace so the two timelines can be joined."""

import glob
import os
import statistics
import time

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from cilium_tpu.sidecar import trace
from cilium_tpu.sidecar.trace import CLOCK_ANCHOR, VerdictTracer

STAMPS = ("t_admit", "t_pop", "t_form", "t_submit", "t_complete",
          "t_drain", "t_send")


def _round(tr: VerdictTracer, path: str = "vec", n: int = 8):
    t = time.monotonic()
    rt = tr.begin_round(path, n, t - 0.002, t)
    rt.reasm_s = 1e-4
    for stamp in (rt.formed, rt.submitted, rt.completed, rt.drained):
        stamp()
    return rt


def _tracer() -> VerdictTracer:
    return VerdictTracer(sample_every=0, slow_ms=1e9, stage_metrics=False)


def _xplane(trace_dir: str) -> ProfileData:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return ProfileData.from_file(path)


def _events(pd, name: str) -> list:
    return [e for plane in pd.planes for line in plane.lines
            for e in line.events if e.name == name]


def test_profiler_off_records_nothing(monkeypatch):
    calls = []

    def off():
        calls.append(1)
        return False

    def no_anchor(*a, **kw):
        raise AssertionError("anchor emitted with the profiler off")

    monkeypatch.setattr(trace, "_profiling", off)
    monkeypatch.setattr(trace, "TraceAnnotation", no_anchor)
    tr = _tracer()
    for _ in range(5):
        tr.finish_round(_round(tr))
    assert tr.profiled_rounds() == []
    assert tr.rounds == 5
    # The whole cost with the profiler off: two checks a round.
    assert len(calls) == 10


def test_rounds_recorded_in_a_profiler_session(tmp_path):
    tr = _tracer()
    tr.finish_round(_round(tr))  # before the session: not kept
    jax.profiler.start_trace(str(tmp_path))
    try:
        rts = [_round(tr, n=k + 1) for k in range(3)]
        for rt in rts:
            tr.finish_round(rt)
    finally:
        jax.profiler.stop_trace()
    got = tr.profiled_rounds()
    assert [r["n"] for r in got] == [1, 2, 3]
    assert len({r["id"] for r in got}) == 3
    for r, rt in zip(got, rts):
        assert r["path"] == "vec"
        assert [r[k] for k in STAMPS] == [getattr(rt, k) for k in STAMPS]
        assert all(r[a] <= r[b] for a, b in zip(STAMPS, STAMPS[1:]))
        assert r["t_admit"] < r["t_pop"] < r["t_send"]
        assert r["reasm"] == pytest.approx(
            min(1e-4, r["t_form"] - r["t_pop"]))
        assert r["swap"] == 0.0 and r["cache"] == 0.0


def test_straddling_rounds_kept_ring_bounded_and_cleared(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(trace, "PROFILED_ROUNDS", 4)
    tr = _tracer()
    before = _round(tr, n=100)  # begun before the session, closed in it
    jax.profiler.start_trace(str(tmp_path / "a"))
    try:
        tr.finish_round(before)
        for k in range(2):
            tr.finish_round(_round(tr, n=k))
        after = _round(tr, n=99)  # begun in the session, closed after it
    finally:
        jax.profiler.stop_trace()
    tr.finish_round(after)
    tr.finish_round(_round(tr, n=98))  # wholly after: not kept
    assert [r["n"] for r in tr.profiled_rounds()] == [100, 0, 1, 99]
    # A new session starts a new record, and the ring keeps its newest.
    jax.profiler.start_trace(str(tmp_path / "b"))
    try:
        for k in range(6):
            tr.finish_round(_round(tr, n=k))
    finally:
        jax.profiler.stop_trace()
    assert [r["n"] for r in tr.profiled_rounds()] == [2, 3, 4, 5]


def test_anchors_map_program_clock_onto_trace(tmp_path):
    tr = _tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(20):
            tr.finish_round(_round(tr))
        known = time.monotonic_ns()
        with TraceAnnotation("test.known"):
            pass
    finally:
        jax.profiler.stop_trace()
    pd = _xplane(str(tmp_path))
    anchors = _events(pd, CLOCK_ANCHOR)
    assert len(anchors) == 20
    offs = [a.start_ns - dict(a.stats)["mono_ns"] for a in anchors]
    off = statistics.median(offs)
    (probe,) = _events(pd, "test.known")
    assert abs(probe.start_ns - off - known) < 1e6
    # The anchors' own stamps read back on the trace's clock, in order.
    monos = [dict(a.stats)["mono_ns"] for a in anchors]
    assert monos == sorted(monos)
    rounds = tr.profiled_rounds()
    assert rounds[0]["t_admit"] * 1e9 <= monos[0]
    assert monos[-1] <= rounds[-1]["t_send"] * 1e9 + 1e6

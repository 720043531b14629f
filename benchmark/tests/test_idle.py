"""The split of the device's idle time by what the sidecar was doing."""

import pytest

from benchmark import idle, run, xtrace
from benchmark.cell import Cell

# The program's clock reads M ns at the trace's zero.
M = 7_000_000_000_000


def _us(t: float) -> int:
    return int(round(t * 1e6))  # picoseconds


OPS = ((10, 20), (50, 55), (90, 95))


def _trace(anchors: bool = True, ahead_us: float = 0.0) -> str:
    """Device ops (one module each) launched by the host at their true
    starts; the device plane's clock ``ahead_us`` ahead of the host's."""
    ops = "".join(
        f"events {{ metadata_id: 1 offset_ps: {_us(s - ahead_us)} "
        f"duration_ps: {_us(e - s)} }}\n" for s, e in OPS)
    # (metadata, start us, length us)
    host = [(2, 25, 23), (3, 60, 20)] + [(4, s, 1e-3) for s, _ in OPS]
    marks = ""
    if anchors:
        # Three anchors, one read 400 ns late.
        for at_ns, late in ((1000, 0), (2000, 0), (3000, 400)):
            marks += (f"events {{ metadata_id: 1 offset_ps: {at_ns * 1000} "
                      f"duration_ps: 1000 stats {{ metadata_id: 1 "
                      f"int64_value: {M + at_ns - late} }} }}\n")
    evs = "".join(f"events {{ metadata_id: {m} offset_ps: {_us(s)} "
                  f"duration_ps: {_us(n)} }}\n" for m, s, n in host)
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{ops}  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
{ops}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{marks}{evs}  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "sidecar.clock" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "host_work" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "dispatch" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "{idle.LAUNCH}" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "mono_ns" }} }}
}}
"""


def _mono(us: float) -> float:
    return (us * 1e3 + M) / 1e9


def _round(rid, admit, pop, form, submit, complete, drain, send,
           reasm=0.0) -> dict:
    r = dict(zip(("t_admit", "t_pop", "t_form", "t_submit", "t_complete",
                  "t_drain", "t_send"),
                 map(_mono, (admit, pop, form, submit, complete, drain,
                             send))))
    return dict(r, id=rid, path="vec", n=8, swap=0.0, reasm=reasm,
                cache=0.0)


# Over a window of 0-100 us with the device busy in [10, 20), [50, 55)
# and [90, 95):
# - A is on the device in [12, 22) and in drain/send until 45;
# - C waits in [15, 21) under A's device stage (the device wins), forms
#   mostly in reassembly, and is on the device in [28, 30) inside A's
#   send (the device wins again);
# - B waits from 40, under A's send until 45 (host stages win), then
#   alone until the device goes idle at 55; it is on the device in
#   [63, 85); after 88 nothing is admitted.
ROUNDS = [
    _round(0, 0, 5, 8, 12, 22, 24, 45),
    _round(1, 15, 21, 26, 28, 30, 31, 32, reasm=4e-6),
    _round(2, 40, 60, 62, 63, 85, 87, 88),
]
WINDOW = (_mono(0), _mono(100))


def _pd(anchors: bool = True, ahead_us: float = 0.0):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(_trace(anchors, ahead_us))


@pytest.mark.parametrize("ahead_us", [0.0, 1.5])
def test_split_follows_precedence(ahead_us):
    pd = _pd(ahead_us=ahead_us)
    out = idle.attribute(pd, ROUNDS, WINDOW)
    assert out["anchors"] == 3
    assert out["offset_ns"] == pytest.approx(-M)
    assert 0 < out["offset_iqr_ns"] <= 400
    # Each module starts at its launch once the device plane is moved
    # later by how far it ran ahead.
    assert out["device_lead_ns"] == pytest.approx(ahead_us * 1e3)
    assert out["device_lead_iqr_ns"] == pytest.approx(0.0)
    # Idle 80 us of 100: [0, 10) queue 5 + host 5; [20, 50) device 4,
    # host 21, queue 5; [55, 90) queue 5, host 6, device 22, nothing
    # admitted 2; [95, 100) nothing admitted 5.
    assert out["shares"] == {
        "device_stage": pytest.approx(26.0),
        "host_stages": pytest.approx(32.0),
        "queue": pytest.approx(15.0),
        "no_work": pytest.approx(7.0),
    }
    want = xtrace.reduce(pd, 100e-6)["idle_share_pct"]
    assert want == pytest.approx(80.0)
    assert sum(out["shares"].values()) == pytest.approx(want)
    assert out["idle_share_pct"] == pytest.approx(want)
    assert out["idle_in_window_pct"] == pytest.approx(80.0)
    # Only [10, 20) of the 20 us busy lies inside a round's
    # t_form-t_complete (A's [8, 22)).
    assert out["busy_in_rounds_pct"] == pytest.approx(50.0)
    assert out["idle_gaps"] == [
        ["device/dispatch", pytest.approx(35e-6)],
        ["send/host_work", pytest.approx(30e-6)],
    ]


def test_shares_scaled_to_the_traced_idle_share():
    # Operations traced outside the window count in reduce's busy time;
    # the split keeps its proportions and sums to reduce's idle share.
    window = (_mono(0), _mono(80))
    out = idle.attribute(_pd(), ROUNDS, window)
    want = xtrace.reduce(_pd(), 80e-6)["idle_share_pct"]
    assert want == pytest.approx(75.0)
    assert out["idle_in_window_pct"] == pytest.approx(100 * 65 / 80)
    assert sum(out["shares"].values()) == pytest.approx(want)


def test_formation_named_by_its_largest_part():
    stages = [s for s, _, _ in idle.round_spans(ROUNDS[1])]
    assert stages == ["queue", "reasm", "device_submit", "device", "drain",
                      "send"]
    assert idle.round_spans(ROUNDS[0])[1][0] == "batch_form"


def test_nothing_to_join():
    assert idle.attribute(_pd(anchors=False), ROUNDS, WINDOW) is None
    assert idle.attribute(_pd(), [], WINDOW) is None


def test_cpu_run_keeps_rounds_and_anchors(tiny_root, monkeypatch):
    """A traced CPU run through the hooks: run.py's result line is what
    it is without them, and the service's rounds and the trace's anchors
    are there to join (no TPU plane, so nothing is split)."""
    seen: dict = {}
    idle.install(seen, monkeypatch.setattr)
    cell = Cell("r2d2-line.mixed-closed", root=tiny_root)
    out = run.run_cell(cell, 17, 2.0, True)
    assert out["correct"] is True
    assert "device_idle_share.closed" not in out["metrics"]
    assert seen["split"] is None
    rounds, offs = seen["rounds"], seen["anchors"]
    assert rounds and len(offs) >= len(rounds) // 2
    off = sorted(offs)[len(offs) // 2]
    t0, t1 = (t * 1e9 + off for t in (seen["t0"], seen["t1"]))
    assert t0 < t1
    # Every kept round closed after the session began, and some inside
    # the window.
    assert any(t0 <= r["t_send"] * 1e9 + off <= t1 for r in rounds)

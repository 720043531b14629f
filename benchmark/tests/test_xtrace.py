"""The reduction from a profiler trace to device busy time and idle share."""

import glob
import os

import pytest

from benchmark import xtrace

DATA = os.path.join(os.path.dirname(__file__), "data")

# Two overlapping ops and one apart on the device; one host event over
# the gap between them; a second device plane with no op line.
SYNTHETIC = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
}
planes {
  id: 3
  name: "/device:TPU:1"
  lines { id: 1 name: "Steps" timestamp_ns: 1000 }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "host_work" } }
  event_metadata { key: 2 value { id: 2 name: "dispatch" } }
}
"""


def test_union_merges_overlaps():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                              (5, 8)]


def test_synthetic_trace():
    from jax.profiler import ProfileData

    out = xtrace.reduce(ProfileData.from_text_proto(SYNTHETIC), 20e-6)
    # Busy: [1000, 4000) and [11000, 12000) ns, overlap counted once.
    assert out["busy_s"] == pytest.approx(4e-6)
    assert out["idle_share_pct"] == pytest.approx(80.0)
    assert out["devices"] == 1
    assert out["breakdown"]["device_ops"] == [
        ["fusion.1", pytest.approx(3e-6)], ["copy.2", pytest.approx(2e-6)]]
    assert out["breakdown"]["idle_gaps"] == [
        ["host_work", pytest.approx(7e-6)]]


def test_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData

    host_only = SYNTHETIC[SYNTHETIC.index("planes {\n  id: 2"):]
    assert xtrace.reduce(ProfileData.from_text_proto(host_only), 1.0) is None


def _brute_busy_ns(pd) -> float:
    """Busy time counted event edge by event edge, independently of
    ``xtrace.union``: sweep the sorted starts and ends, count the time
    in which at least one op is open."""
    edges = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    edges.append((e.start_ns, 1))
                    edges.append((e.start_ns + e.duration_ns, -1))
    edges.sort(key=lambda x: (x[0], -x[1]))
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite: ten 512x512 matmul steps with
    2 ms host sleeps between them, in a traced window of 32.9 ms."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    window_s = 0.032932457
    out = xtrace.reduce(pd, window_s)
    assert out is not None and out["devices"] == 1
    assert out["busy_s"] * 1e9 == pytest.approx(_brute_busy_ns(pd))
    assert 0 < out["busy_s"] < window_s
    assert out["idle_share_pct"] == pytest.approx(
        100 * (1 - out["busy_s"] / window_s))
    # Ten steps: nine gaps between them, each labelled by the host's
    # dispatch of the next step.
    gaps = out["breakdown"]["idle_gaps"]
    assert len(gaps) == 10 and all(g > 0.002 for _, g in gaps[:9])
    assert all(n.startswith("PjitFunction") for n, _ in gaps[:9])
    ops = dict(out["breakdown"]["device_ops"])
    assert any(n.endswith("%convolution_tanh_fusion") for n in ops)

"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time.

Busy time is the union of the intervals in which an operation ran on a
device: the events of the ``XLA Ops`` line of each ``/device:TPU:<n>``
plane (``XLA Modules`` where a plane has no op line).  Overlapping
events count once.  The idle share of a traced window is
``1 - busy / window``.

The breakdown names the device operations that took the most time and
the longest idle gaps, each gap labelled with the host event that
overlaps it most (from the host planes of the same trace, on the same
clock).
"""

from __future__ import annotations

import bisect
import glob
import os

OP_LINES = ("XLA Ops", "XLA Modules")


def load(trace_dir: str):
    """The newest trace that ``jax.profiler`` wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        return None
    return ProfileData.from_file(max(found, key=os.path.getmtime))


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge [start, end) intervals; the result is sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(hlo: str) -> str:
    """``%fusion.3`` of ``%fusion.3 = f32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0]


def device_events(pd) -> dict[str, list[tuple[str, float, float]]]:
    """Per device plane: (name, start_ns, end_ns) of each operation,
    named ``<module>/<instruction>`` where the plane has modules."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        starts = [m[0] for m in mods]
        for name in OP_LINES:
            if name not in lines:
                continue
            evs = []
            for e in lines[name].events:
                label = short_name(e.name)
                j = bisect.bisect_right(starts, e.start_ns) - 1
                if name != "XLA Modules" and j >= 0 \
                        and e.start_ns < mods[j][1]:
                    label = f"{mods[j][2]}/{label}"
                evs.append((label, e.start_ns, e.start_ns + e.duration_ns))
            if evs:
                out[plane.name] = evs
            break
    return out


def host_events(pd) -> list[tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.duration_ns > 0)
    return out


def reduce(pd, window_s: float, top: int = 10) -> dict | None:
    """busy_s (mean over the devices with operations), the idle share
    of ``window_s`` in percent, and the breakdown; None when no device
    operation was traced."""
    per_dev = device_events(pd)
    if not per_dev:
        return None
    busy, by_name, gaps = [], {}, []
    for evs in per_dev.values():
        merged = union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, e in evs:
            by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
        gaps.extend((merged[j][1], merged[j + 1][0])
                    for j in range(len(merged) - 1))
    busy_s = sum(busy) / len(busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = host_events(pd)
    idle = []
    for s, e in gaps[:top]:
        label, best = "no host event", 0.0
        for name, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                label, best = name, ov
        idle.append([label, (e - s) / 1e9])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
        "devices": len(per_dev),
        "breakdown": {"device_ops": [[n, s] for n, s in ops],
                      "idle_gaps": idle},
    }

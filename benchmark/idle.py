"""Split the device's idle time in a profiler trace by what the sidecar
was doing, from the rounds ``VerdictTracer.profiled_rounds()`` kept.

Each round closed while the trace recorded wrote a ``sidecar.clock``
annotation whose ``mono_ns`` stat is ``time.monotonic_ns()`` read just
before it began; the median of ``start_ns - mono_ns`` over them maps the
rounds' stamps onto the trace's clock.  Idle time (the complement of
the union of the device's operations, as in ``xtrace.reduce``) is then
split four ways, first match wins:

- ``device_stage``: some round is between ``t_submit`` and
  ``t_complete`` (issued, not yet read back);
- ``host_stages``: no round is on the device and some round is in batch
  formation (``t_pop``-``t_submit``) or in drain and send
  (``t_complete``-``t_send``);
- ``queue``: only admitted entries wait for a pop (``t_admit``-``t_pop``);
- ``no_work``: nothing admitted anywhere in the service.

The device planes' clock need not agree with the host planes', and the
error differs from one profiler session to the next: on a TPU v5 lite
with jax 0.9.0, device modules started a median 0.88-1.05 ms before the
host's ``PJRT_LoadedExecutable_Execute`` that launched them in some
traces and under 0.07 ms in others.  A module cannot start before its launch, so the
device's operations are moved later by the median distance from a
module's start to the nearest launch on the host, where that is above
zero.

The split is measured inside the traced window and given in percent of
it, scaled so that the four sum to ``xtrace.reduce``'s idle share (which
also counts operations traced just outside the window).

    python3 benchmark/idle.py --workload <cell> --seed <n> --seconds <s>

runs one cell with ``--trace 1`` through ``run.py`` unchanged, then
prints the split, the join's checks and the ten longest idle gaps of
the window labelled ``<stage>/<host event>`` on stderr, and the split as
the last line of stdout; ``--keep <dir>`` also keeps the trace and the
rounds.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import json
import os
import shutil
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import xtrace  # noqa: E402

CLOCK_ANCHOR = "sidecar.clock"
LAUNCH = "PJRT_LoadedExecutable_Execute"
SHARES = ("device_stage", "host_stages", "queue", "no_work")
HOST_STAGES = ("table_swap", "reasm", "cache", "batch_form",
               "device_submit", "drain", "send")


def anchors(pd) -> list[float]:
    """``start_ns - mono_ns`` of every clock anchor in the trace."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == CLOCK_ANCHOR:
                    out.append(e.start_ns - dict(e.stats)["mono_ns"])
    return out


def launch_leads(pd) -> list[float]:
    """Per device module: start of the nearest launch on the host minus
    the module's start (above zero where the device plane runs ahead)."""
    mods, launches = [], []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU:") \
                    and line.name == "XLA Modules":
                mods.extend(e.start_ns for e in line.events)
            elif plane.name.startswith("/host:"):
                launches.extend(e.start_ns for e in line.events
                                if e.name == LAUNCH)
    launches.sort()
    out = []
    for m in mods:
        j = bisect.bisect_left(launches, m)
        near = [launches[i] - m for i in (j - 1, j)
                if 0 <= i < len(launches)]
        if near:
            out.append(min(near, key=abs))
    return out


def round_spans(r: dict) -> list[tuple[str, float, float]]:
    """(stage, start_s, end_s) of one profiled round; batch formation is
    named by the largest of its parts."""
    form = max((("table_swap", r["swap"]), ("reasm", r["reasm"]),
                ("cache", r["cache"]),
                ("batch_form", r["t_form"] - r["t_pop"] - r["swap"]
                 - r["reasm"] - r["cache"])), key=lambda kv: kv[1])[0]
    return [("queue", r["t_admit"], r["t_pop"]),
            (form, r["t_pop"], r["t_form"]),
            ("device_submit", r["t_form"], r["t_submit"]),
            ("device", r["t_submit"], r["t_complete"]),
            ("drain", r["t_complete"], r["t_drain"]),
            ("send", r["t_drain"], r["t_send"])]


def meet(a: list, b: list) -> list[tuple[float, float]]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def _only(iv: list, cover: list, above: list) -> float:
    """Time of ``iv`` under ``cover`` and not under ``above``."""
    under = meet(iv, cover)
    return length(under) - length(meet(under, above))


def attribute(pd, rounds: list[dict], window: tuple[float, float],
              top: int = 10) -> dict | None:
    """The idle split of the traced ``window`` (its two edges on
    ``time.monotonic()``), the join's checks and the labelled gaps; None
    without anchors, rounds or device operations."""
    offs = anchors(pd)
    per_dev = xtrace.device_events(pd)
    if not offs or not rounds or not per_dev:
        return None
    off = statistics.median(offs)
    q = _quartiles(offs)
    leads = launch_leads(pd)
    lead = max(statistics.median(leads), 0.0) if leads else 0.0
    lq = _quartiles(leads) if leads else None
    per_dev = {p: [(s + lead, e + lead) for _, s, e in evs]
               for p, evs in per_dev.items()}

    def ns(t: float) -> float:
        return t * 1e9 + off

    w0, w1 = ns(window[0]), ns(window[1])
    spans: dict[str, list] = {}
    for r in rounds:
        for stage, s, e in round_spans(r):
            spans.setdefault(stage, []).append((ns(s), ns(e)))
    cover = {k: xtrace.union(v) for k, v in spans.items()}
    dev = cover.get("device", [])
    host = xtrace.union([iv for k in HOST_STAGES
                         for iv in cover.get(k, [])])
    engaged = xtrace.union(dev + host)
    in_flight = xtrace.union([(ns(r["t_form"]), ns(r["t_complete"]))
                              for r in rounds])
    split = dict.fromkeys(SHARES, 0.0)
    busy_all = busy_in = busy_rounds = 0.0
    gaps = []
    for evs in per_dev.values():
        busy = xtrace.union(evs)
        busy_all += length(busy)
        inside = meet(busy, [(w0, w1)])
        busy_in += length(inside)
        busy_rounds += length(meet(inside, in_flight))
        edges = [w0] + [x for iv in inside for x in iv] + [w1]
        idle = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k] < edges[k + 1]]
        d = length(meet(idle, dev))
        h = _only(idle, host, dev)
        qu = _only(idle, cover.get("queue", []), engaged)
        split["device_stage"] += d
        split["host_stages"] += h
        split["queue"] += qu
        split["no_work"] += length(idle) - d - h - qu
        gaps.extend((inside[k][1], inside[k + 1][0])
                    for k in range(len(inside) - 1))
    ndev = len(per_dev)
    window_ns = w1 - w0
    idle_pct = 100.0 * (1.0 - busy_all / ndev / window_ns)
    idle_in = sum(split.values())
    scale = idle_pct / idle_in if idle_in > 0 else 0.0
    gaps.sort(key=lambda g: g[0] - g[1])
    host_evs = xtrace.host_events(pd)
    labelled = [[f"{_gap_stage(g, cover, dev, engaged)}/"
                 f"{_gap_event(g, host_evs)}", (g[1] - g[0]) / 1e9]
                for g in gaps[:top]]
    return {
        "shares": {k: v * scale for k, v in split.items()},
        "idle_share_pct": idle_pct,
        "idle_in_window_pct": 100.0 * idle_in / ndev / window_ns,
        "anchors": len(offs),
        "offset_ns": off,
        "offset_iqr_ns": q[2] - q[0],
        "device_lead_ns": lead,
        "device_lead_iqr_ns": lq[2] - lq[0] if lq else None,
        "busy_in_rounds_pct": (100.0 * busy_rounds / busy_in
                               if busy_in > 0 else None),
        "idle_gaps": labelled,
    }


def _quartiles(xs: list) -> list:
    return statistics.quantiles(xs, n=4) if len(xs) > 1 else [xs[0]] * 3


def _gap_stage(g: tuple, cover: dict, dev: list, engaged: list) -> str:
    """The stage with the most of the gap, by the split's precedence."""
    best, most = "no_work", length([g]) - length(meet([g], xtrace.union(
        [iv for ivs in cover.values() for iv in ivs])))
    for stage, ivs in cover.items():
        above = [] if stage == "device" else (
            engaged if stage == "queue" else dev)
        t = _only([g], ivs, above)
        if t > most:
            best, most = stage, t
    return best


def _gap_event(g: tuple, host_evs: list) -> str:
    label, best = "no host event", 0.0
    for name, hs, he in host_evs:
        ov = min(g[1], he) - max(g[0], hs)
        if ov > best:
            label, best = name, ov
    return label


def install(seen: dict, patch=setattr) -> None:
    """Hooks on ``run.py``'s traced run that change nothing it does:
    they keep the service, the trace window's edges (``t0``, ``t1``),
    the profiled rounds and the split in ``seen``."""
    import jax

    from benchmark import run

    class Beat(run.Heartbeat):
        def __init__(self, svc):
            super().__init__(svc)
            seen["svc"] = svc

    start, stop, reduce = (jax.profiler.start_trace,
                           jax.profiler.stop_trace, xtrace.reduce)

    def start_trace(log_dir, *a, **kw):
        start(log_dir, *a, **kw)
        seen["t0"] = time.monotonic()
        seen["dir"] = log_dir

    def stop_trace():
        seen["t1"] = time.monotonic()
        stop()

    def reduce_and_split(pd, window_s, top=10):
        seen["rounds"] = seen["svc"].tracer.profiled_rounds()
        seen["anchors"] = anchors(pd)
        try:
            seen["split"] = attribute(pd, seen["rounds"],
                                      (seen["t0"], seen["t1"]), top)
            if seen.get("keep"):
                keep(seen)
        except Exception:  # noqa: BLE001 -- the run's own line still stands
            run.log(f"idle split failed:\n{traceback.format_exc()}")
        return reduce(pd, window_s, top)

    patch(run, "Heartbeat", Beat)
    patch(jax.profiler, "start_trace", start_trace)
    patch(jax.profiler, "stop_trace", stop_trace)
    patch(xtrace, "reduce", reduce_and_split)


def keep(seen: dict) -> None:
    """The trace (gzipped) and the rounds with the window's edges, under
    ``seen["keep"]``, to be split again without the chip."""
    (xplane,) = glob.glob(os.path.join(seen["dir"], "**", "*.xplane.pb"),
                          recursive=True)
    os.makedirs(seen["keep"], exist_ok=True)
    with open(xplane, "rb") as src, gzip.open(
            os.path.join(seen["keep"], "trace.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(seen["keep"], "rounds.json"), "w") as f:
        json.dump({"window": [seen["t0"], seen["t1"]],
                   "rounds": seen["rounds"]}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="",
                    help="directory for the trace and the rounds")
    args = ap.parse_args(argv)
    from benchmark import run

    run.place_cache()
    seen: dict = {"keep": args.keep}
    install(seen)
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if rc:
        return rc
    split = seen.get("split")
    if split is None:
        run.log(f"idle split: nothing to split ({len(seen.get('anchors', []))}"
                f" anchors, {len(seen.get('rounds', []))} profiled rounds)")
        return 1
    run.log(f"idle split: anchors {split['anchors']}, offset spread "
            f"(Q3-Q1) {split['offset_iqr_ns'] / 1e3:.3f} us; device busy "
            f"time inside some round's t_form-t_complete "
            f"{split['busy_in_rounds_pct']:.2f}%; device planes ahead of "
            f"the host's launches by {split['device_lead_ns'] / 1e3:.1f} us "
            f"(Q3-Q1 {(split['device_lead_iqr_ns'] or 0) / 1e3:.1f} us), "
            f"moved later by that")
    run.log("idle split (% of the window): " + ", ".join(
        f"{k} {v:.3f}" for k, v in split["shares"].items())
        + f"; sum {sum(split['shares'].values()):.3f}, idle share "
        f"{split['idle_share_pct']:.3f}, inside the window "
        f"{split['idle_in_window_pct']:.3f}")
    for label, secs in split["idle_gaps"]:
        run.log(f"idle gap {secs * 1e3:.3f} ms: {label}")
    print(json.dumps(split), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

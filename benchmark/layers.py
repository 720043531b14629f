"""Arithmetic the per-layer readers share.

A reader (``benchmark/metrics/<metric name>.py``) defines
``read(ctx) -> float | None``.  ``ctx`` holds:

- ``status``: the service's ``status()`` when the window opened and
  when it closed (the parent reads both in the service's process);
- ``trace``: ``xtrace.reduce`` of the profiler trace taken inside the
  window, or None;
- ``summary``: the load generator's numbers for the window.

A reader that finds nothing to read returns None, and the metric is
left out of the result line.
"""

from __future__ import annotations

# RoundTrace stages that are host work between queue pop and the wire.
HOST_STAGES = ("batch_form", "reasm", "device_submit", "drain", "send")


def _totals(status: dict) -> dict:
    """(path, stage) -> (rounds, total seconds) from status()'s means."""
    out = {}
    for path, stages in status["latency"]["stages"].items():
        for stage, v in stages.items():
            out[(path, stage)] = (v["rounds"],
                                  v["rounds"] * v["mean_us"] / 1e6)
    return out


def stage_ms(ctx: dict, stages) -> float | None:
    """Round-weighted mean, in ms, of the sum of ``stages`` over the
    rounds the window closed (all serving paths together)."""
    before, after = (_totals(s) for s in ctx["status"])
    rounds, total = 0, 0.0
    for (path, stage), (r1, t1) in after.items():
        r0, t0 = before.get((path, stage), (0, 0.0))
        if stage == "queue":
            rounds += r1 - r0
        if stage in stages:
            total += t1 - t0
    if rounds <= 0:
        return None
    return 1e3 * total / rounds


def per_round(ctx: dict, key: str) -> float | None:
    """Delta of a tracer counter per round closed in the window."""
    s0, s1 = (s["latency"] for s in ctx["status"])
    rounds = s1["rounds"] - s0["rounds"]
    return (s1[key] - s0[key]) / rounds if rounds > 0 else None


def idle_share(ctx: dict) -> float | None:
    tr = ctx.get("trace")
    return None if tr is None else tr["idle_share_pct"]


def summary(ctx: dict, key: str) -> float | None:
    return ctx["summary"].get(key)

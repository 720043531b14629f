"""The comparison that decides ``correct``.

Every push of the checked connections (drawn from the seed by the load
generator, warm-up, window and drain alike, since a connection's state
runs through all of them) is replayed through the plain reference in
order, and the served answer must equal the reference's exactly: result,
ops, and both injects for a batch entry; result, forwarded bytes and the
bytes handed out toward the client for an ``on_io`` call.

Limits (exact comparison): ``mismatches`` 0 and ``unanswered`` 0.  An
answer with a typed failure result (shed, unavailable, restarting,
fenced, unknown error) judges nothing: it is counted as ``failed`` and
not compared.

The control (``stale_verdicts``) breaks the configuration's guarantee
that every frame is judged on its own bytes: it is the reference put in
the program's place, answering each push on a connection with what the
connection's first push was answered, as a verdict cache that never
checks the frame would.  It goes through the same comparison and has to
come out not correct.
"""

from __future__ import annotations

from .reference import Reference, apply_ops

LIMITS = {"mismatches": 0, "unanswered": 0}
FAILED_RESULT_MIN = 7  # UNKNOWN_ERROR and the typed shed/restart results


def expected(traffic, policies: list[dict], served: dict) -> dict:
    """conn index -> the reference's answer to each push ``served`` holds
    for it, in order."""
    ref = Reference(policies, traffic.policy)
    out = {}
    for i in sorted(served):
        lane = bool(traffic.lane[i])
        wants = []
        for k in range(len(served[i])):
            reply, data = traffic.push(i, k)
            want = ref.feed(i, reply, data)
            if lane:
                want = (want[0], apply_ops(data, want[1]), want[3])
            wants.append(want)
        out[i] = wants
    return out


def stale_verdicts(want: dict) -> dict:
    """The control: every push on a connection answered as its first."""
    return {i: [w[0]] * len(w) for i, w in want.items() if w}


def compare(traffic, want: dict, served: dict) -> dict:
    """Counts of the comparison of ``served`` with ``want``, plus up to
    five examples of a mismatch."""
    out = {"checked": 0, "mismatches": 0, "unanswered": 0, "failed": 0,
           "conns": 0, "examples": []}
    for i in sorted(served):
        out["conns"] += 1
        for k, have in enumerate(served[i]):
            if have is None:
                out["unanswered"] += 1
                continue
            if have[0] >= FAILED_RESULT_MIN:
                out["failed"] += 1
                continue
            out["checked"] += 1
            if have != want[i][k]:
                out["mismatches"] += 1
                if len(out["examples"]) < 5:
                    out["examples"].append(
                        f"conn {i + 1} ({traffic.proto[i]}, "
                        f"{'on_io' if traffic.lane[i] else 'batch'}) push "
                        f"{k}: served {have!r} != reference {want[i][k]!r}")
    return out


def is_correct(counts: dict) -> bool:
    return counts["checked"] > 0 and all(
        counts[k] <= lim for k, lim in LIMITS.items())

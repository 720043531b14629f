"""Load generator: one shim process driving the sidecar over its socket.

Started by ``run.py`` with ``JAX_PLATFORMS=cpu``: it never opens the
chip, and it does not share the service's interpreter lock.  It pushes
the policy, binds every connection, sends the cell's traffic for
``warmup_s`` and then for the measured window, waits for the answers
that are still due, and writes what the check and the metrics need to
``--out``.  It tells its parent where it is with lines on stdout:
``BOUND <t>``, ``WINDOW <t>``, ``CLOSE <t>`` and ``DONE``; ``t`` is
``time.monotonic()``, the clock the parent reads too.

    python3 benchmark/gen.py --socket S --config C --traffic T \\
        --seed N --seconds 30 --width 256 --out answers.pkl
"""

from __future__ import annotations

import argparse
import ctypes
import gc
import json
import os
import pickle
import sys
import threading
import time
from collections import deque

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import deploy  # noqa: E402
from benchmark.check import FAILED_RESULT_MIN  # noqa: E402
from benchmark.mixgen import Traffic  # noqa: E402

GRACE_S = 60.0  # how long answers due at the close are waited for
SEQ_BASE = 1 << 40  # async round seqs live far above the client's own
# A pacing-loop turn this much longer than its 0.1 ms sleep is time in
# which the loop's thread did not run.
PACE_STALL_S = 0.002


def say(word: str, t: float | None = None) -> None:
    print(word if t is None else f"{word} {t!r}", flush=True)


def log(msg: str) -> None:
    print(f"gen: {msg}", file=sys.stderr, flush=True)


def tighten_timer_slack() -> None:
    """Ask for 1 us of timer slack (Linux; the 50 us default stretches a
    100 us pacing sleep to about 175 us)."""
    try:
        ctypes.CDLL("libc.so.6", use_errno=True).prctl(29, 1000, 0, 0, 0)
    except OSError:
        pass


class Recorder:
    """Every answer as it arrives: (seq, verdict batch, receipt time)."""

    def __init__(self):
        self.got: list = []
        self.on_answer = None

    def __call__(self, vb) -> None:
        t = time.monotonic()
        self.got.append((vb.seq, vb, t))

        if self.on_answer is not None:
            self.on_answer(vb.seq)


class Shim:
    """The shim side of one cell: its client, connections and sends."""

    def __init__(self, socket_path: str, cfg: dict, traffic: Traffic):
        from cilium_tpu.sidecar.client import SidecarClient

        self.traffic = traffic
        self.client = SidecarClient(socket_path, timeout=600.0)
        self.module = self.client.open_module([])
        if not self.module:
            raise RuntimeError("open_module failed")
        res = self.client.policy_update(self.module,
                                        deploy.network_policies(cfg))
        if res != 0:
            raise RuntimeError(f"policy_update: result {res}")
        self.shims = {}
        for i in range(traffic.n):
            res, shim = self.client.new_connection(
                self.module, str(traffic.proto[i]), int(traffic.cid[i]),
                True, 1, 2, "1.1.1.1:1", f"2.2.2.2:{traffic.port[i]}",
                traffic.policy[i])
            if res != 0:
                raise RuntimeError(f"new_connection {i + 1}: result {res}")
            if traffic.lane[i]:
                self.shims[i] = shim
        self.rec = Recorder()
        self.client.verdict_callback = self.rec
        self.seq = SEQ_BASE
        # seq -> (conn indices, push indices, positions, send time)
        self.sent: dict = {}

    def send(self, idx: np.ndarray, ks: np.ndarray,
             pos: np.ndarray | None = None) -> float:
        """Sends the pushes; returns the seconds spent in the client's
        send calls (the rest built the messages)."""
        spent = 0.0
        for kind, sel, args in self.traffic.messages(idx, ks):
            self.seq += 1
            t0 = time.monotonic()
            self.sent[self.seq] = (idx[sel], ks[sel],
                                   None if pos is None else pos[sel], t0)
            if kind == "matrix":
                ids, lens, rows = args
                self.client.send_matrix(self.seq, self.traffic.width, ids,
                                        lens, rows, complete=True)
            else:
                self.client.send_batch(self.seq, *args)
            spent += time.monotonic() - t0
        return spent

    def wait_answers(self, t_limit: float) -> None:
        while time.monotonic() < t_limit:
            if len(self.rec.got) >= len(self.sent):
                return
            time.sleep(0.01)

    def close(self) -> None:
        self.client.close()


# --- the loops ---------------------------------------------------------------

def run_lane(shim: Shim, t_close: float, out: list) -> None:
    """The on_io lane: one call at a time, round robin over its conns.
    Each push is followed by a reply-direction call with no bytes, which
    hands out what a deny injected toward the client."""
    tr = shim.traffic
    order = tr.lane_order
    cursor = dict.fromkeys(order.tolist(), 0)
    j = 0
    while time.monotonic() < t_close and len(order):
        i = int(order[j % len(order)])
        j += 1
        k = cursor[i]
        cursor[i] += 1
        reply, data = tr.push(i, k)
        s = shim.shims[i]
        res, fwd = s.on_io(reply, data)
        _, inj = s.on_io(True, b"")
        out.append((i, k, res, fwd, inj, time.monotonic()))


def run_closed(shim: Shim, params: dict, t_open: float,
               t_close: float) -> np.ndarray:
    """Closed loop: a conn sends its next push once its last is answered;
    at most ``outstanding`` pushes are unanswered.  Returns pushes sent
    per conn."""
    tr = shim.traffic
    cap = int(params["outstanding"])
    cursor = np.zeros(tr.n, np.int64)
    ready = deque(tr.order.tolist())
    state = {"out": 0}
    cond = threading.Condition()

    def on_answer(seq: int) -> None:
        idx = shim.sent[seq][0]
        with cond:
            ready.extend(idx.tolist())
            state["out"] -= len(idx)
            cond.notify()

    shim.rec.on_answer = on_answer
    lane_out: list = []
    lane = threading.Thread(target=run_lane, name="on-io-lane",
                            args=(shim, t_close, lane_out))
    lane.start()
    opened = False
    try:
        while True:
            now = time.monotonic()
            if not opened and now >= t_open:
                say("WINDOW", t_open)
                opened = True
            if now >= t_close:
                break
            with cond:
                if not ready or state["out"] >= cap:
                    cond.wait(0.005)
                    continue
                m = min(len(ready), cap - state["out"])
                idx = np.fromiter((ready.popleft() for _ in range(m)),
                                  np.int64, m)
                state["out"] += m
            ks = cursor[idx]
            cursor[idx] += 1
            shim.send(idx, ks)
    finally:
        lane.join()
    shim.lane_out = lane_out
    return cursor


def run_poisson(shim: Shim, params: dict, seed: int, rate: float,
                warm: float, seconds: float, announce: bool = True):
    """Open loop: arrivals at exponential gaps for ``warm`` and then
    ``seconds`` seconds, released in shim batches.  The offered rate
    ramps from a tenth of ``rate`` to ``rate`` over the first half of the
    warm-up and holds from there.  The schedule is drawn before its
    start is fixed, so drawing it makes no push late.  Returns
    (schedule, conn, push index, release time) per arrival, and the
    window's open and close."""
    tr = shim.traffic
    rng = np.random.default_rng([seed, 1])
    ramp = warm / 2
    span = warm + seconds
    # Unit-rate arrivals mapped through the inverse of the cumulative
    # offered load: rate * (0.1 t + 0.45 t^2 / ramp) up to the ramp's
    # end, then rate per second.
    u = np.cumsum(rng.exponential(1.0, int(rate * span * 1.1) + 1000))
    u /= rate
    at_ramp = 0.55 * ramp
    t = np.where(
        u < at_ramp,
        (np.sqrt(0.01 + 1.8 * u / max(ramp, 1e-9)) - 0.1) * ramp / 0.9,
        ramp + (u - at_ramp))
    t = t[t < span]
    m = len(t)
    order = tr.order
    batch = int(params["client_batch"])
    if batch > len(order):
        raise ValueError("client_batch exceeds the open-loop conns: one "
                         "message would carry two pushes of a conn")
    conn = order[np.arange(m) % len(order)]
    ks = np.arange(m) // len(order)
    release = np.full(m, np.nan)
    hold = float(params["client_hold_ms"]) / 1e3
    # Where the pacing loop's time went: (start, seconds, seconds in the
    # client's send calls, which block while the service does not read)
    # of each release, and (start, seconds) of each turn in which the
    # loop's thread did not run.
    shim.pace = {"sends": [], "stalls": []}
    tighten_timer_slack()
    t_start = time.monotonic()
    sched = t_start + t
    t_open = t_start + warm
    t_close = t_open + seconds
    i = 0
    opened = False
    last = time.monotonic()
    while i < m:
        now = time.monotonic()
        if now - last > PACE_STALL_S:
            shim.pace["stalls"].append((last, now - last))
        if announce and not opened and now >= t_open:
            say("WINDOW", t_open)
            opened = True
        j = int(np.searchsorted(sched, now, side="right"))
        if j > i and (j - i >= batch or now - sched[i] >= hold or j == m):
            while i < j:
                b = min(j, i + batch)
                pos = np.arange(i, b)
                t0 = time.monotonic()
                release[i:b] = t0
                spent = shim.send(conn[i:b], ks[i:b], pos)
                shim.pace["sends"].append(
                    (t0, time.monotonic() - t0, spent))
                i = b
            last = time.monotonic()
        else:
            last = time.monotonic()
            time.sleep(0.0001)
    while announce and time.monotonic() < t_close:
        time.sleep(0.001)
    return sched, conn, ks, release, t_open, t_close


# --- what the parent reads -----------------------------------------------------

def _answered(shim: Shim):
    """Per seq: receipt time and the conn indices whose answer is a typed
    failure (shed, unavailable, restarting, unknown error)."""
    out = {}
    for seq, vb, t in shim.rec.got:
        bad = vb.conn_ids[vb.results >= FAILED_RESULT_MIN]
        out[seq] = (t, set(int(c) - 1 for c in bad))
    return out


def closed_summary(shim: Shim, t_open: float, t_close: float) -> dict:
    tr = shim.traffic
    answered = _answered(shim)
    verdicts = attempted = failed = 0
    for seq, (idx, ks, _, t_send) in shim.sent.items():
        in_window = t_open <= t_send < t_close
        got = answered.get(seq)
        v = tr.verdicts(idx, ks)
        if got is None:
            failed += len(idx) if in_window else 0
            continue
        t_recv, bad = got
        ok = np.array([i not in bad for i in idx.tolist()], bool)
        if in_window:
            attempted += len(idx)
            failed += int((~ok).sum())
        if t_open <= t_recv < t_close:
            verdicts += int(v[ok].sum())
    for i, k, res, _, _, t in shim.lane_out:
        if t_open <= t < t_close:
            attempted += 1
            ok = res < FAILED_RESULT_MIN
            failed += not ok
            verdicts += int(tr.verdicts(np.array([i]), np.array([k]))[0]) * ok
    return {"verdicts": verdicts, "attempted": attempted, "failed": failed,
            "window_s": t_close - t_open,
            "lane_calls": len(shim.lane_out)}


def poisson_summary(shim: Shim, sched, release, t_open: float,
                    t_close: float, t_grace: float) -> dict:
    answered = _answered(shim)
    m = len(sched)
    t_recv = np.full(m, np.nan)
    bad = np.zeros(m, bool)
    conns = np.zeros(m, np.int64)
    for seq, (idx, _, pos, _) in shim.sent.items():
        conns[pos] = idx
        got = answered.get(seq)
        if got is None:
            continue
        t_recv[pos] = got[0]
        if got[1]:
            bad[pos] = np.isin(idx, list(got[1]))
    win = (sched >= t_open) & (sched < t_close)
    unanswered = np.isnan(t_recv)
    failed = win & (unanswered | bad)
    # A request never answered, or answered with a typed failure, misses
    # any latency limit: it counts with the whole wait it was given.
    lat = np.where(unanswered | bad, t_grace, t_recv) - sched
    lat_ms = lat[win] * 1e3
    late_ms = (release[win] - sched[win]) * 1e3
    n = int(win.sum())
    q = max(n // 4, 1)
    return {
        "attempted": n, "failed": int(failed.sum()),
        "goodput_per_s": (n - int(failed.sum())) / (t_close - t_open),
        "p50_ms": float(np.percentile(lat_ms, 50)) if n else None,
        "p99_ms": float(np.percentile(lat_ms, 99)) if n else None,
        "gen_late_p50_ms": float(np.percentile(late_ms, 50)) if n else None,
        "gen_late_p99_ms": float(np.percentile(late_ms, 99)) if n else None,
        "gen_late_max_ms": float(late_ms.max()) if n else None,
        "achieved_per_s": float(np.sum(win & (t_recv < t_close))
                                / (t_close - t_open)),
        "offered_per_s": n / (t_close - t_open),
        "first_quarter_ms": float(lat_ms[:q].mean()) if n else None,
        "last_quarter_ms": float(lat_ms[-q:].mean()) if n else None,
        "pace": pace_summary(shim, sched, release, t_open, t_close),
    }


def _per_second(t: np.ndarray, w: np.ndarray, t_open: float,
                n: int, how=np.add) -> np.ndarray:
    """``how``-reduce of weights ``w`` by whole second of the window."""
    sec = np.floor(t - t_open).astype(np.int64)
    keep = (sec >= 0) & (sec < n)
    out = np.zeros(n)
    how.at(out, sec[keep], w[keep])
    return out


def pace_summary(shim: Shim, sched, release, t_open: float,
                 t_close: float, worst: int = 3) -> dict:
    """Where the generator's lateness came from, over the window: time
    in releases (building messages and the client's send calls, which
    block while the service does not read its socket), time the pacing
    loop's thread did not run (this process), and gaps in the answers
    coming back; and, for the seconds with the latest pushes, each of
    these in that second."""
    n = max(int(np.ceil(t_close - t_open)), 1)
    sends = np.array(shim.pace["sends"], float).reshape(-1, 3)
    stalls = np.array(shim.pace["stalls"], float).reshape(-1, 2)
    recv = np.sort(np.array([t for _, _, t in shim.rec.got], float))
    recv = recv[(recv >= t_open) & (recv < t_close)]
    gaps = np.diff(recv)
    win = (sched >= t_open) & (sched < t_close)
    late = _per_second(sched[win], release[win] - sched[win], t_open, n,
                       np.maximum)
    send_s = _per_second(sends[:, 0], sends[:, 1], t_open, n)
    write_s = _per_second(sends[:, 0], sends[:, 2], t_open, n)
    stall_s = _per_second(stalls[:, 0], stalls[:, 1], t_open, n)
    answers = _per_second(recv, np.ones(len(recv)), t_open, n)
    rows = [[int(s), late[s] * 1e3, write_s[s] * 1e3, stall_s[s] * 1e3,
             int(answers[s])]
            for s in np.argsort(-late)[:worst]]
    return {
        "send_s": float(send_s.sum()),
        "write_s": float(write_s.sum()),
        "send_max_ms": float(sends[:, 2].max() * 1e3) if len(sends) else 0.0,
        "stall_s": float(stall_s.sum()),
        "stall_max_ms": (float(stalls[:, 1].max() * 1e3)
                         if len(stalls) else 0.0),
        "answer_gap_max_ms": float(gaps.max() * 1e3) if len(gaps) else None,
        "worst_seconds": rows,
    }


def pick_checked(tr: Traffic, pushes: np.ndarray, budget: int) -> list[int]:
    """Connections whose every push the reference checks: drawn in the
    seed's order, stratum by stratum (protocol, category, lane), until
    each stratum's share of ``budget`` pushes is used; at least one
    connection from each stratum that sent anything."""
    strata: dict = {}
    for i in tr.check_order.tolist():
        if pushes[i]:
            key = (str(tr.proto[i]), int(tr.category[i]), bool(tr.lane[i]))
            strata.setdefault(key, []).append(i)
    share = budget / max(len(strata), 1)
    out = []
    for conns in strata.values():
        used = 0
        for i in conns:
            if used and used + pushes[i] > share:
                break
            out.append(i)
            used += int(pushes[i])
    return sorted(out)


def served_answers(shim: Shim, checked: list[int],
                   pushes: np.ndarray) -> dict:
    """conn index -> the answer to each of its pushes (None: never came).
    A batch answer is (result, ops, inject toward the server, inject
    toward the client); an on_io answer is (result, forwarded bytes,
    bytes handed out toward the client)."""
    tr = shim.traffic
    want = np.zeros(tr.n + 1, bool)
    want[np.array(checked, np.int64) + 1] = True
    out = {i: [None] * int(pushes[i]) for i in checked}
    k_of = np.zeros(tr.n, np.int64)
    for seq, vb, _ in shim.rec.got:
        cids = vb.conn_ids.astype(np.int64)
        hit = np.flatnonzero(want[np.minimum(cids, tr.n)])
        if not len(hit):
            continue
        idx, ks = shim.sent[seq][0], shim.sent[seq][1]
        k_of[idx] = ks
        joined: dict = {}
        for e in hit.tolist():
            cid, res, ops, io, ir = vb.entry(e)
            io, ir = bytes(io), bytes(ir)
            prev = joined.get(cid)
            joined[cid] = ((res, ops, io, ir) if prev is None else
                           (res, prev[1] + ops, prev[2] + io, prev[3] + ir))
        for cid, ans in joined.items():
            out[cid - 1][int(k_of[cid - 1])] = ans
    for i, k, res, fwd, inj, _ in getattr(shim, "lane_out", ()):
        if i in out:
            out[i][k] = (res, fwd, inj)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--socket", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--width", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rates", default="",
                    help="open loop only: comma-separated offered rates, "
                         "one window each (the knee sweep)")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        cfg = json.load(f)
    with open(args.traffic) as f:
        params = json.load(f)
    tr = Traffic(params, cfg, args.seed, args.width)
    shim = Shim(args.socket, cfg, tr)
    # A cyclic collection over the answers kept so far is a pause of
    # tens of ms in the generator; nothing it keeps forms cycles.
    gc.collect()
    gc.freeze()
    gc.disable()
    say("BOUND", time.monotonic())
    warm = float(params["warmup_s"])
    result: dict = {}
    try:
        if args.rates:
            result["sweep"] = sweep(shim, params, args, warm)
        elif params["loop"] == "closed":
            t_open = time.monotonic() + warm
            t_close = t_open + args.seconds
            pushes = run_closed(shim, params, t_open, t_close)
            say("CLOSE", t_close)
            shim.wait_answers(t_close + GRACE_S)
            for i, k, *_ in shim.lane_out:
                pushes[i] = max(pushes[i], k + 1)
            result["summary"] = closed_summary(shim, t_open, t_close)
            result["checked"] = served_answers(
                shim, pick_checked(tr, pushes, params["check_pushes"]),
                pushes)
        else:
            sched, conn, ks, release, t_open, t_close = run_poisson(
                shim, params, args.seed, float(params["rate"]), warm,
                args.seconds)
            say("CLOSE", t_close)
            t_grace = t_close + GRACE_S
            shim.wait_answers(t_grace)
            result["summary"] = poisson_summary(
                shim, sched, release, t_open, t_close, t_grace)
            pushes = np.bincount(conn, minlength=tr.n)
            result["checked"] = served_answers(
                shim, pick_checked(tr, pushes, params["check_pushes"]),
                pushes)
        result["pushes_total"] = sum(len(v[0]) for v in shim.sent.values())
        result["answered_total"] = len(shim.rec.got)
        result["messages"] = len(shim.sent)
    finally:
        shim.close()
    with open(args.out, "wb") as f:
        pickle.dump(result, f)
    say("DONE")
    return 0


def sweep(shim: Shim, params: dict, args, warm: float) -> list:
    """One open-loop window per offered rate, after one warm-up at the
    first rate; each window's pushes are drained before the next."""
    rows = []
    rates = [float(r) for r in args.rates.split(",")]
    run_poisson(shim, params, args.seed, rates[0], warm, 0.0,
                announce=False)
    shim.wait_answers(time.monotonic() + GRACE_S)
    for rate in rates:
        shim.sent.clear()
        shim.rec.got.clear()
        sched, _, _, release, t_open, t_close = run_poisson(
            shim, params, args.seed, rate, 0.0, args.seconds,
            announce=False)
        shim.wait_answers(t_close + GRACE_S)
        row = poisson_summary(shim, sched, release, t_open, t_close,
                              t_close + GRACE_S)
        row["rate"] = rate
        log(json.dumps(row))
        rows.append(row)
    return rows


if __name__ == "__main__":
    sys.exit(main())

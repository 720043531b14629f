#!/usr/bin/env python3
"""Serve verdicts on the chip through the sidecar's normal path.

One process drives the verdict service the way a deployment does: a
``VerdictService`` at the default ``DaemonConfig`` owns the device, a
``SidecarClient`` pushes the policy over the wire, opens the
connections and pushes frames through the shim calls (batched
``send_matrix``/``send_batch`` rounds, plus a lane of per-connection
``ShimConnection.on_io`` calls).  Every verdict, op and inject is
checked against the in-process proxylib oracle fed the same bytes.

The deployment is BASELINE config 5 (mixed 10k-rule policy,
pcap-replay stress) restricted to the protocols the sidecar serves:
250 HTTP policies x 20 rules (12 literal, 6 DFA-tier, 2 NFA-tier) and
50 DNS policies x (16 exact + 4 pattern) rules, from bench.py's stress
set, plus the MixBench r2d2 policy.  Traffic, made from ``--seed``:
8,192 connections and at least 200,000 frames, 80% complete, 10%
partial, 5% pipelined and 5% reply-direction.

``--chips 4`` runs only the multi-chip path: the same deployment and
traffic served with ``mesh="on"`` on a 2 flows x 2 rules mesh, every
verdict compared with the unsharded single-device service on the same
batches, and the mesh ladder held at ``full``.

Any failure raises: the script then exits non-zero and prints no
result line.  Without a TPU it exits 2 before serving anything.  The
last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

# Per-round answer wait; a cold chip compiles inside the first rounds'
# prewarm, never inside a round, so this only bounds a wedged service.
ROUND_TIMEOUT_S = 300.0
ORACLE_CONN_BASE = 1 << 40  # oracle conn ids never collide with served ones


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclass(frozen=True)
class Scale:
    conns: int = 8192
    frames: int = 200_000
    http_policies: int = 250
    dns_policies: int = 50
    shim_conns: int = 96  # conns driven one call at a time through on_io


def log(msg: str) -> None:
    print(msg, flush=True)


# --- deployment -----------------------------------------------------------

def deployment(scale: Scale) -> list:
    """The policy set: bench.py's stress HTTP and DNS rules plus the
    MixBench r2d2 policy, as proxylib NetworkPolicy objects (what the
    agent pushes over the wire)."""
    import bench
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )
    from cilium_tpu.sidecar.mixbench import mix_policy

    n_lit = (
        bench.STRESS_HTTP_RULES - bench.STRESS_HTTP_REGEX_RULES
        - bench.STRESS_HTTP_NFA_RULES
    )
    policies = []
    for p in range(scale.http_policies):
        paths = (
            [f"/svc{p:03d}/r{j:02d}/.*" for j in range(n_lit)]
            + [bench._stress_regex_path(j)
               for j in range(bench.STRESS_HTTP_REGEX_RULES)]
            + [bench._stress_nfa_path(j)
               for j in range(bench.STRESS_HTTP_NFA_RULES)]
        )
        policies.append(NetworkPolicy(
            name=f"http-{p:03d}", policy=1000 + p,
            ingress_per_port_policies=[PortNetworkPolicy(port=80, rules=[
                PortNetworkPolicyRule(http_rules=[
                    {"method": "GET", "path": path} for path in paths
                ]),
            ])],
        ))
    for p in range(scale.dns_policies):
        rules = (
            [{"matchName": bench._stress_dns_name(p, j)}
             for j in range(bench.STRESS_DNS_EXACT_RULES)]
            + [{"matchPattern": bench._stress_dns_pattern(j)}
               for j in range(bench.STRESS_DNS_PATTERN_RULES)]
        )
        policies.append(NetworkPolicy(
            name=f"dns-{p:03d}", policy=2000 + p,
            ingress_per_port_policies=[PortNetworkPolicy(port=53, rules=[
                PortNetworkPolicyRule(l7_proto="dns", l7_rules=rules),
            ])],
        ))
    policies.append(mix_policy())
    return policies


# --- traffic --------------------------------------------------------------

CATEGORIES = ("complete", "partial", "pipelined", "reply")
VARIANTS = 8  # request frames per connection the rounds draw from


def _http_frames(rng, p: int, n_pol: int) -> list[bytes]:
    import bench

    out = []
    for _ in range(VARIANTS):
        roll = rng.random()
        j = int(rng.integers(0, 12))
        k = int(rng.integers(0, 1 << 20))
        if roll < 0.40:  # literal tier, allowed
            path = f"/svc{p:03d}/r{j:02d}/o{k}"
        elif roll < 0.55:  # DFA tier, allowed
            path = f"/g{j % bench.STRESS_HTTP_REGEX_RULES:02d}/x{k:x}/item/{k}"
        elif roll < 0.65:  # NFA tier, allowed
            ab = "".join("ab"[b] for b in rng.integers(0, 2, 12))
            path = f"/n{j % bench.STRESS_HTTP_NFA_RULES:02d}/{ab}a{ab[:7]}/x"
        elif roll < 0.80:  # another policy's literal: denied
            path = f"/svc{(p + 1) % max(n_pol, 2):03d}/r{j:02d}/o{k}"
        elif roll < 0.90:  # DFA near miss (upper case): denied
            path = f"/g00/X{k:X}/item/{k}"
        else:
            path = f"/private/{k}"
        out.append(
            f"GET {path} HTTP/1.1\r\nHost: svc.local\r\n"
            f"User-Agent: smoke\r\n\r\n".encode()
        )
    return out


def _dns_frames(rng, p: int, n_pol: int) -> list[bytes]:
    import bench
    from cilium_tpu.proxylib.parsers.dns import encode_dns_query

    out = []
    for _ in range(VARIANTS):
        roll = rng.random()
        j = int(rng.integers(0, bench.STRESS_DNS_EXACT_RULES))
        if roll < 0.45:
            name = bench._stress_dns_name(p, j)
        elif roll < 0.65:
            jp = j % bench.STRESS_DNS_PATTERN_RULES
            name = f"h{int(rng.integers(0, 999))}.w{jp:02d}.svc.local"
        elif roll < 0.85:
            name = bench._stress_dns_name((p + 1) % max(n_pol, 2), j)
        else:
            name = f"x{int(rng.integers(0, 999))}.example.com"
        out.append(encode_dns_query(name, qid=int(rng.integers(0, 1 << 16))))
    return out


def _r2d2_frames(rng) -> list[bytes]:
    out = []
    for _ in range(VARIANTS):
        roll = rng.random()
        k = int(rng.integers(0, 997))
        if roll < 0.4:
            out.append(f"READ /public/f{k}.txt\r\n".encode())
        elif roll < 0.55:
            out.append(b"HALT\r\n")
        else:
            out.append(f"READ /private/f{k}\r\n".encode())
    return out


REPLY_BYTES = {
    "http": b"HTTP/1.1 200 OK\r\ncontent-length: 2\r\n\r\nok",
    "r2d2": b"OK\r\n",
}


@dataclass
class Conn:
    cid: int
    proto: str
    policy: str
    port: int
    category: str
    frames: list
    reply: bytes
    shim: bool = False


def make_traffic(seed: int, scale: Scale) -> tuple[list[Conn], int]:
    """Connections with their frame corpora, and the round count that
    reaches ``scale.frames``.  Conn counts per category are chosen so
    FRAMES split 80/10/5/5: a partial conn completes one frame every
    second round, a pipelined conn two frames per round."""
    from cilium_tpu.proxylib.parsers.dns import encode_dns_query

    rng = np.random.default_rng(seed)
    n = scale.conns
    # Frames per conn per round: complete 1, partial 1/2, pipelined 2,
    # reply 1; k conns' worth of frames splits 0.80/0.10/0.05/0.05 with
    # 0.80k + 0.20k + 0.025k + 0.05k = 1.075k conns.
    k = n / 1.075
    counts = {
        "partial": int(round(0.20 * k)),
        "pipelined": int(round(0.025 * k)),
        "reply": int(round(0.05 * k)),
    }
    counts["complete"] = n - sum(counts.values())
    cats = np.repeat(
        np.arange(len(CATEGORIES)),
        [counts[c] for c in CATEGORIES],
    )
    rng.shuffle(cats)
    protos = np.array(["http"] * (n // 2) + ["dns"] * (n // 4)
                      + ["r2d2"] * (n - n // 2 - n // 4))
    rng.shuffle(protos)
    conns = []
    per_proto = {"http": 0, "dns": 0, "r2d2": 0}
    for i in range(n):
        proto = str(protos[i])
        idx = per_proto[proto]
        per_proto[proto] += 1
        if proto == "http":
            p = idx % scale.http_policies
            frames = _http_frames(rng, p, scale.http_policies)
            policy, port = f"http-{p:03d}", 80
        elif proto == "dns":
            p = idx % scale.dns_policies
            frames = _dns_frames(rng, p, scale.dns_policies)
            policy, port = f"dns-{p:03d}", 53
        else:
            frames = _r2d2_frames(rng)
            policy, port = "mixbench", 80
        reply = REPLY_BYTES.get(proto) or encode_dns_query(
            "reply.svc.local", qid=int(rng.integers(0, 1 << 16))
        )
        conns.append(Conn(i + 1, proto, policy, port,
                          CATEGORIES[int(cats[i])], frames, reply))
    # The on_io lane: complete-frame conns of every protocol, an equal
    # share each.
    lane = {p: 0 for p in per_proto}
    for c in conns:
        if c.category == "complete" and lane[c.proto] < scale.shim_conns // 3:
            c.shim = True
            lane[c.proto] += 1
    per_round = (
        counts["complete"] + counts["partial"] / 2
        + 2 * counts["pipelined"] + counts["reply"]
    )
    rounds = int(np.ceil(scale.frames / per_round))
    rounds += rounds % 2  # partial conns finish their frame on odd rounds
    return conns, rounds


def round_entries(conns: list[Conn], r: int, picks) -> list[tuple]:
    """(conn, reply, payload, frames) for every conn in round ``r``."""
    out = []
    for c in conns:
        f = c.frames
        pk = picks[c.cid - 1]
        if c.category == "complete":
            out.append((c, False, f[pk[r]], 1))
        elif c.category == "partial":
            fr = f[pk[r // 2]]
            half = len(fr) // 2
            if r % 2 == 0:
                out.append((c, False, fr[:half], 0))
            else:
                out.append((c, False, fr[half:], 1))
        elif c.category == "pipelined":
            out.append((c, False, f[pk[2 * r]] + f[pk[2 * r + 1]], 2))
        else:
            out.append((c, True, c.reply, 1))
    return out


# --- oracle ---------------------------------------------------------------

class Oracle:
    """The in-process proxylib parsers, fed the same bytes per conn.

    Each (conn, direction) keeps the bytes a datapath retains between
    calls, as the shim does: the parser always sees every unconsumed
    byte, and a PASS/DROP past the end consumes later input."""

    def __init__(self, policies, conns: list[Conn]):
        from cilium_tpu.proxylib import instance as pl

        self.pl = pl
        self.mod = pl.open_module([], True)
        pl.find_instance(self.mod).policy_update(policies)
        self.conns = {}
        self.dirs: dict = {}
        for c in conns:
            res, oc = pl.on_new_connection(
                self.mod, c.proto, ORACLE_CONN_BASE + c.cid, True, 1, 2,
                "1.1.1.1:1", f"2.2.2.2:{c.port}", c.policy,
            )
            check(int(res) == 0, f"oracle conn {c.cid}: result {res}")
            self.conns[c.cid] = oc

    def feed(self, cid: int, reply: bool, data: bytes) -> tuple:
        from cilium_tpu.proxylib.types import DROP, PASS

        oc = self.conns[cid]
        d = self.dirs.setdefault((cid, reply), [bytearray(), 0, 0])
        buf, skip_pass, skip_drop = d
        rest = data
        take = min(skip_pass or skip_drop, len(rest))
        if skip_pass:
            d[1] -= take
        elif skip_drop:
            d[2] -= take
        buf += rest[take:]
        ops: list = []
        res = oc.on_data(reply, False, [bytes(buf)], ops)
        for op, n in ops:
            if op in (PASS, DROP):
                used = min(n, len(buf))
                del buf[:used]
                d[1 if op == PASS else 2] += n - used
        return (
            int(res), [(int(o), int(n)) for o, n in ops],
            bytes(oc.orig_buf.take()), bytes(oc.reply_buf.take()),
        )

    def close(self) -> None:
        self.pl.close_module(self.mod)


# --- serving --------------------------------------------------------------

def _collect(vb) -> dict:
    """cid -> (result, ops, inject_orig, inject_reply); continuation
    entries of one conn are joined in order."""
    out: dict = {}
    for i in range(vb.count):
        cid, res, ops, io, ir = vb.entry(i)
        prev = out.get(cid)
        if prev is None:
            out[cid] = (res, list(ops), bytes(io), bytes(ir))
        else:
            out[cid] = (res, prev[1] + list(ops), prev[2] + io, prev[3] + ir)
    return out


def _apply_ops(data: bytes, ops) -> bytes:
    """Bytes a shim forwards for one complete-frame push under ``ops``."""
    from cilium_tpu.proxylib.types import DROP, PASS

    out, pos = bytearray(), 0
    for op, n in ops:
        if op == PASS:
            out += data[pos:pos + n]
            pos += n
        elif op == DROP:
            pos += n
    return bytes(out)


class Served:
    """One service + client serving the deployment."""

    def __init__(self, workdir: str, name: str, policies, conns, config):
        from cilium_tpu.proxylib.types import FilterResult
        from cilium_tpu.sidecar.client import SidecarClient
        from cilium_tpu.sidecar.service import VerdictService

        self.service = VerdictService(
            os.path.join(workdir, f"{name}.sock"), config
        ).start()
        self.client = SidecarClient(self.service.socket_path,
                                    timeout=ROUND_TIMEOUT_S)
        self.answers: dict = {}
        import threading

        self._cv = threading.Condition()

        def on_verdict(vb):
            with self._cv:
                self.answers[vb.seq] = vb
                self._cv.notify_all()

        self.client.verdict_callback = on_verdict
        self.module = self.client.open_module([])
        check(self.module != 0, "open_module failed")
        t0 = time.perf_counter()
        res = self.client.policy_update(self.module, policies)
        check(res == int(FilterResult.OK), f"policy_update: {res}")
        self.policy_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.shims = {}
        for c in conns:
            res, shim = self.client.new_connection(
                self.module, c.proto, c.cid, True, 1, 2, "1.1.1.1:1",
                f"2.2.2.2:{c.port}", c.policy,
            )
            check(res == int(FilterResult.OK),
                  f"new_connection {c.cid} ({c.proto}): {res}")
            self.shims[c.cid] = shim
        self.bind_s = time.perf_counter() - t0
        # Round seqs live far above the client's own per-call seqs
        # (on_io draws those from its counter).
        self.seq = 1 << 40

    def wait(self, seqs) -> dict:
        deadline = time.monotonic() + ROUND_TIMEOUT_S
        with self._cv:
            while not all(s in self.answers for s in seqs):
                left = deadline - time.monotonic()
                check(left > 0, f"no verdicts for seqs {seqs}")
                self._cv.wait(left)
            return {s: self.answers.pop(s) for s in seqs}

    def serve_round(self, entries, width: int, window: int) -> dict:
        """Push one round as each endpoint's shim would: per policy, one
        complete-flag matrix of its whole r2d2/DNS frames and one data
        batch of everything else.  Closed loop: at most ``window``
        entries are unanswered at a time, as shims that wait for their
        verdicts keep them.  Returns cid -> answer."""
        groups: dict = {}
        for e in entries:
            c, reply, data, _ = e
            if c.shim:
                continue
            whole = (c.category == "complete"
                     and c.proto in ("r2d2", "dns") and len(data) <= width)
            groups.setdefault((c.policy, whole), []).append(e)
        got: dict = {}
        inflight: deque = deque()
        outstanding = 0
        for (_, whole), grp in groups.items():
            while inflight and outstanding + len(grp) > window:
                seq, n = inflight.popleft()
                got.update(_collect(self.wait([seq])[seq]))
                outstanding -= n
            self.seq += 1
            ids = np.array([e[0].cid for e in grp], np.uint64)
            lens = np.array([len(e[2]) for e in grp], np.uint32)
            if whole:
                rows = np.zeros((len(grp), width), np.uint8)
                for i, e in enumerate(grp):
                    rows[i, :len(e[2])] = np.frombuffer(e[2], np.uint8)
                self.client.send_matrix(self.seq, width, ids, lens,
                                        rows.tobytes(), complete=True)
            else:
                self.client.send_batch(
                    self.seq, ids,
                    np.array([1 if e[1] else 0 for e in grp], np.uint8),
                    lens, b"".join(e[2] for e in grp),
                )
            inflight.append((self.seq, len(grp)))
            outstanding += len(grp)
        for vb in self.wait([seq for seq, _ in inflight]).values():
            got.update(_collect(vb))
        return got

    def status(self) -> dict:
        return self.client.status()

    def close(self) -> None:
        try:
            self.client.close()
        finally:
            self.service.stop()


class CompileWatch:
    """Every backend compile in this process, from JAX's own monitoring
    events: the serving window must contain none (prewarm compiles every
    shape before traffic is admitted)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        import threading

        if event == self.EVENT:
            self.events.append(
                (time.monotonic(), secs, threading.current_thread().name)
            )

    def since(self, t0: float) -> list:
        return [e for e in self.events if e[0] >= t0]


def serve(served: Served, conns, rounds: int, seed: int,
          oracle: Oracle | None, config, watch: CompileWatch,
          record: list | None = None, expect: list | None = None) -> dict:
    """Drive every round through ``served`` and hold each answer to the
    oracle, or — with ``expect`` — to another service's recorded
    answers.  Returns frame counts and the compiles that ran while
    serving; appends per-round answers to ``record`` when given."""
    t_window = time.monotonic()
    rng = np.random.default_rng(seed + 1)
    picks = rng.integers(0, VARIANTS, (len(conns), 2 * rounds + 2))
    frames = dict.fromkeys(CATEGORIES, 0)
    counters = {"verdicts": 0, "shim_calls": 0}
    t0 = time.perf_counter()
    try:
        _serve_rounds(served, conns, rounds, oracle, config.batch_width,
                      config.batch_flows, record, expect, picks, frames,
                      counters, t0)
    except BaseException:
        _dump_failure(served, watch, t_window)
        raise
    return {
        "rounds": rounds,
        "frames": sum(frames.values()),
        "frames_by_category": frames,
        "answers_checked": counters["verdicts"],
        "shim_calls": counters["shim_calls"],
        "serve_s": time.perf_counter() - t0,
        "window_compiles": watch.since(t_window),
    }


def _dump_failure(served: Served, watch: CompileWatch, t_window: float):
    """What a failed run needs to be read: compiles since traffic began,
    the containment counters and the compile ledger."""
    for t, secs, thread in watch.since(t_window):
        log(f"FAILED RUN compile at +{t - t_window:.3f}s: {secs:.3f}s "
            f"on {thread}")
    st = served.service.status()
    log(f"FAILED RUN containment: {json.dumps(st['containment'])}")
    for ev in served.service.ledger.events(n=10_000):
        log(f"FAILED RUN ledger: {json.dumps(ev, default=str)[:400]}")
    log(f"FAILED RUN stages: {json.dumps(st['latency']['stages'])}")


def _serve_rounds(served, conns, rounds, oracle, width, window, record,
                  expect, picks, frames, counters, t0) -> None:
    for r in range(rounds):
        entries = round_entries(conns, r, picks)
        got = served.serve_round(entries, width, window)
        answers = {}
        for c, reply, data, nf in entries:
            frames[c.category] += nf
            if expect is not None:
                want = expect[r][c.cid]
            else:
                want = oracle.feed(c.cid, reply, data)
                if c.shim:  # what the shim forwards under the oracle's ops
                    want = (want[0], _apply_ops(data, want[1]), want[3])
            if c.shim:
                shim = served.shims[c.cid]
                res, out = shim.on_io(reply, data)
                # A deny's reply-direction inject leaves on the next
                # reply-direction call.
                inj = shim.on_io(True, b"")[1] if want[2] else b""
                counters["shim_calls"] += 1 + bool(want[2])
                have = (res, out, inj)
            else:
                have = got.get(c.cid)
            check(have == want,
                  f"round {r} conn {c.cid} ({c.proto}/{c.category}"
                  f"{'/on_io' if c.shim else ''}): served {have} != "
                  f"{'reference' if expect is not None else 'oracle'} "
                  f"{want}")
            answers[c.cid] = have
            counters["verdicts"] += 1
        if record is not None:
            record.append(answers)
        if r == 0 or (r + 1) % 7 == 0 or r + 1 == rounds:
            log(f"  round {r + 1}/{rounds} done at "
                f"{time.perf_counter() - t0:.1f}s")


# --- checks and report ----------------------------------------------------

def check_device_path(st: dict, counts: dict, label: str) -> None:
    """No rung that hides the device may have been used, and nothing
    compiled while traffic was served."""
    cont = st["containment"]
    for key in ("fallback_entries", "shed_entries", "error_entries",
                "batch_crashes"):
        check(cont[key] == 0, f"{label}: containment {key}={cont[key]}")
    check(not cont["quarantined"],
          f"{label}: device quarantined ({cont.get('reason')})")
    check(cont["quarantine_events"] == 0,
          f"{label}: {cont['quarantine_events']} quarantine event(s)")
    check(st["vec_batches"] > 0, f"{label}: no vec (device-path) rounds")
    led = st["ledger"]
    check(led["dispatch_path_compiles"] == 0,
          f"{label}: {led['dispatch_path_compiles']} compile(s) on the "
          f"dispatch path")
    check(not counts["window_compiles"],
          f"{label}: {len(counts['window_compiles'])} compile(s) while "
          f"serving traffic")


def report(served: Served, label: str, counts: dict) -> dict:
    st = served.status()
    led = served.client.ledger(n=10_000)
    for ev in led["compiles"]:
        log(f"[{label}] compile: cause={ev.get('cause')} "
            f"family={ev.get('family')} kind={ev.get('kind')} "
            f"role={ev.get('role')} wall_s={ev.get('seconds')} "
            f"on_dispatch_path={ev.get('on_dispatch_path')}")
    log(f"[{label}] ledger: {json.dumps(led['ledger'])}")
    for path, stages in st["latency"]["stages"].items():
        parts = " ".join(
            f"{k}={v['mean_us']}us/{v['rounds']}" for k, v in stages.items()
        )
        log(f"[{label}] round stages ({path}): {parts}")
    log(f"[{label}] setup: policy_update={served.policy_s:.3f}s "
        f"bind+prewarm={served.bind_s:.3f}s")
    log(f"[{label}] frames={counts['frames']} "
        f"by_category={json.dumps(counts['frames_by_category'])} "
        f"answers_checked={counts['answers_checked']} "
        f"shim_calls={counts['shim_calls']} rounds={counts['rounds']} "
        f"serve_s={counts['serve_s']:.3f}")
    log(f"[{label}] service: requests={st['requests']} "
        f"denied={st['denied']} vec_batches={st['vec_batches']} "
        f"vec_entries={st['vec_entries']} engines={st['engines']} "
        f"connections={st['connections']}")
    for _t, secs, thread in counts["window_compiles"]:
        log(f"[{label}] compile while serving: {secs:.3f}s on {thread}")
    log(f"[{label}] containment: {json.dumps(st['containment'])}")
    if st.get("mesh") is not None:
        log(f"[{label}] mesh: {json.dumps(st['mesh'])}")
    return st


def run_one_chip(seed: int, scale: Scale, workdir: str,
                 watch: CompileWatch) -> None:
    from cilium_tpu.proxylib import instance as pl
    from cilium_tpu.utils.option import DaemonConfig

    config = DaemonConfig()
    check(config.verdict_device == "default", "verdict_device must be default")
    policies = deployment(scale)
    conns, rounds = make_traffic(seed, scale)
    log(f"deployment: {len(policies)} policies, {len(conns)} conns, "
        f"{rounds} rounds")
    served = Served(workdir, "one", policies, conns, config)
    oracle = Oracle(policies, conns)
    try:
        counts = serve(served, conns, rounds, seed, oracle,
                       config, watch)
        st = report(served, "1chip", counts)
        check(counts["frames"] >= scale.frames,
              f"only {counts['frames']} frames served")
        check_device_path(st, counts, "1chip")
        http = [e for e in served.service._engines.values()
                if getattr(e, "proto", "") == "http"]
        judged = sum(e.device_judged for e in http)
        log(f"[1chip] http engines={len(http)} device_judged={judged}")
        check(judged > 0, "no HTTP frame was judged on the device")
    finally:
        oracle.close()
        served.close()
        pl.reset_module_registry()


def run_mesh(seed: int, scale: Scale, workdir: str,
             watch: CompileWatch) -> None:
    """The multi-chip path and what it is compared with: the 2x2 mesh
    service, held to the oracle, then the single-device service on the
    same batches, held to the mesh service's answers — one process."""
    from cilium_tpu.proxylib import instance as pl
    from cilium_tpu.utils.option import DaemonConfig

    policies = deployment(scale)
    conns, rounds = make_traffic(seed, scale)
    records: dict = {}
    for label, cfg in (
        ("mesh", DaemonConfig(mesh="on", mesh_flow_shards=2,
                              mesh_rule_shards=2)),
        ("single", DaemonConfig(mesh="off")),
    ):
        served = Served(workdir, label, policies, conns, cfg)
        oracle = Oracle(policies, conns) if label == "mesh" else None
        rec: list = []
        try:
            counts = serve(served, conns, rounds, seed, oracle,
                           cfg, watch, record=rec,
                           expect=records.get("mesh"))
            st = report(served, label, counts)
            check_device_path(st, counts, label)
            if label == "mesh":
                mesh = st["mesh"]
                check(mesh is not None, "mesh serving did not resolve")
                check(mesh["rung"] == "full",
                      f"mesh ladder left full: {mesh['rung']}")
                check(mesh["reshapes"] == 0 and not mesh["lost_devices"],
                      f"mesh reshaped: {json.dumps(mesh)}")
                check(mesh["serving_devices"] == 4,
                      f"mesh serves {mesh['serving_devices']} devices")
                check(not served.service.mesh_demotions,
                      f"mesh demoted: {served.service.mesh_demotions}")
            else:
                check(st["mesh"] is None or not st["mesh"]["active"],
                      "the single-device service built a mesh")
        finally:
            if oracle is not None:
                oracle.close()
            served.close()
            pl.reset_module_registry()
        records[label] = rec
    log(f"[mesh] {sum(len(r) for r in records['mesh'])} answers of the "
        f"single-device service equal the mesh service's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # The package first: a copy of this script alone fails here, before
    # anything touches JAX.
    from cilium_tpu.utils.jaxcache import configure_compile_cache

    import jax

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    log(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
        f" entries at start)")
    hits = {"requests": 0, "hits": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            hits["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    watch = CompileWatch()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke") as workdir:
        if args.chips == 4:
            run_mesh(args.seed, Scale(), workdir, watch)
        else:
            run_one_chip(args.seed, Scale(), workdir, watch)
    secs = [e[1] for e in watch.events]
    log(f"backend compiles: {len(secs)}, {sum(secs):.3f}s in all, "
        f"longest {max(secs, default=0.0):.3f}s")
    log(f"compile cache: {hits['hits']} hit(s) of {hits['requests']} "
        f"compile(s); total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

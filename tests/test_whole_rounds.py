"""Whole-round matrix path: parity across completion modes and routes.

A round whose data items are all complete-flag matrix batches of the
configured width is judged as ONE round (``_run_mat_group``): one table
gather, one device issue, one answer frame per client.  A pipelined
service (batch deadline, completion pipeline) and a greedy service
(inline completion) both take it; a third service with the whole-round
path declined serves the same rounds by the per-item route.  All three
must answer bit-identically to the in-process oracle — ops, injects and
flow records — with every seq answered exactly once, and the whole-round
counter must engage only on rounds the whole path can serve.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cilium_tpu.proxylib import FilterResult
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.sidecar import SidecarClient, VerdictService, wire
from cilium_tpu.utils.option import DaemonConfig

from test_sidecar import CORPUS, oracle_ops, r2d2_policy

POLICY_A = r2d2_policy()  # READ /public/.* and HALT from remotes 1, 3
POLICY_B = r2d2_policy("pol-b")
POLICY_B.ingress_per_port_policies[0].rules[0].l7_rules = [{"cmd": "RESET"}]
POLICIES = [POLICY_A, POLICY_B]
PARTIAL = b"READ /pub"

ROUTES = {
    "pipelined": dict(batch_timeout_ms=2.0),
    "greedy": dict(batch_timeout_ms=0.0),
    # Pipelined with the whole-round path declined: the per-item route.
    "per_item": dict(batch_timeout_ms=2.0),
}
N_CLIENTS = 3


def _expected() -> dict:
    """Oracle answers, computed before any service shares the process's
    proxylib registry: (policy, remote, frame) -> (ops, reply inject)
    for one whole frame on a fresh conn, and (policy, remote, PARTIAL +
    frame) for the frame that completes a retained partial."""
    inst.reset_module_registry()
    exp = {}
    for pol in POLICIES:
        for remote in (1, 9):
            for frame in CORPUS:
                exp[pol.name, remote, frame] = oracle_ops(
                    pol, [frame], remote_id=remote
                )[0]
                exp[pol.name, remote, PARTIAL + frame] = oracle_ops(
                    pol, [PARTIAL, frame], remote_id=remote
                )[1]
    inst.reset_module_registry()
    return exp


class _Route:
    """One service, its clients, and each client's service-side handler
    (the round items' ``client``)."""

    def __init__(self, tmp, name: str, base: int, **cfg_kw):
        cfg = DaemonConfig(batch_flows=256, **cfg_kw)
        self.svc = VerdictService(str(tmp / f"{name}.sock"), cfg).start()
        if name == "per_item":
            self.svc._run_mat_group = lambda items, t_pop: False
        self.base = base  # conn ids are process-global in proxylib
        self.width = cfg.batch_width
        self.clients, self.handlers, self.got = [], [], []
        for _ in range(N_CLIENTS):
            c = SidecarClient(self.svc.socket_path, timeout=60.0)
            got: dict[int, list] = {}
            c.verdict_callback = (
                lambda vb, got=got: got.setdefault(vb.seq, []).append(vb)
            )
            self.module = c.open_module([])
            assert c.policy_update(self.module, POLICIES) == int(
                FilterResult.OK
            )
            self.clients.append(c)
            self.handlers.append(self.svc._clients[-1])
            self.got.append(got)

    def bind(self, client: int, cid: int, policy: str, remote: int) -> None:
        res, _ = self.clients[client].new_connection(
            self.module, "r2d2", self.base + cid, True, remote, 2,
            "1.1.1.1:1", "2.2.2.2:80", policy,
        )
        assert res == int(FilterResult.OK)

    def item(self, client: int, seq: int, cids, frames) -> tuple:
        """A round item: the wire matrix batch as the reader decodes it."""
        rows = np.zeros((len(cids), self.width), np.uint8)
        for j, f in enumerate(frames):
            rows[j, : len(f)] = np.frombuffer(f, np.uint8)
        mb = wire.unpack_data_matrix(wire.pack_data_matrix(
            seq, self.width, np.asarray(cids, np.uint64) + self.base,
            np.asarray([len(f) for f in frames], np.uint32),
            rows.tobytes(), wire.MAT_FLAG_COMPLETE,
        ))
        return ("mat", self.handlers[client], mb)

    def answers(self, seqs_by_client: dict, timeout: float = 20.0) -> dict:
        """seq -> [(conn, result, ops, reply inject)] once every seq has
        its answer, each seq answered exactly once."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(s in self.got[c] for c, seqs in seqs_by_client.items()
                   for s in seqs):
                break
            time.sleep(0.01)
        self.svc.dispatcher.flush(10.0)
        time.sleep(0.05)  # a late second answer would land by now
        out = {}
        for c, seqs in seqs_by_client.items():
            assert self.clients[c].double_replies == 0
            assert self.clients[c].misrouted_verdicts == 0
            for s in seqs:
                vbs = self.got[c].get(s, [])
                assert len(vbs) == 1, f"seq {s}: {len(vbs)} answers"
                out[s] = [
                    (cid - self.base, res,
                     [(int(o), int(n)) for o, n in ops], ir)
                    for cid, res, ops, _io, ir in (
                        vbs[0].entry(j) for j in range(vbs[0].count)
                    )
                ]
        return out

    def records(self, since: int) -> list:
        recs = self.svc.flowlog.query(n=1 << 20, since=since)
        return sorted(
            (r["conn_id"] - self.base, r["verdict"], r["rule_id"],
             r["match_kind"])
            for r in recs
        )

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.svc.stop()


@pytest.fixture(scope="module")
def routes(tmp_path_factory):
    exp = _expected()
    tmp = tmp_path_factory.mktemp("whole")
    out = {}
    try:
        for k, (name, kw) in enumerate(ROUTES.items()):
            out[name] = _Route(tmp, name, (k + 1) * 100_000, **kw)
        yield exp, out
    finally:
        for r in out.values():
            r.close()
        inst.reset_module_registry()


def _frame(i: int) -> bytes:
    return CORPUS[i % len(CORPUS)]


def _round(case: str, r: _Route) -> tuple[list, dict, dict, bool]:
    """Bind the case's conns on route ``r`` and build its round: (items,
    seqs by client, expected answer key per (seq, entry), whole).  The
    items are built after every bind: a bind that builds an engine can
    take seconds on a loaded host, and an item's queue age (shed past
    ``shed_queue_age_ms``) counts from its build."""
    off = {"whole": 0, "mixed_engines": 1000, "dirty_conn": 2000}[case]
    specs, seqs, keys = [], {}, {}

    def built(whole: bool) -> tuple[list, dict, dict, bool]:
        return [r.item(*sp) for sp in specs], seqs, keys, whole

    def add(client, seq, conns):
        # conns: [(cid, policy, remote, frame, key frame)]
        for cid, pol, remote, _f, _kf in conns:
            r.bind(client, off + cid, pol, remote)
        specs.append((client, off + seq, [off + c[0] for c in conns],
                      [c[3] for c in conns]))
        seqs.setdefault(client, []).append(off + seq)
        for j, (_cid, pol, remote, _f, kf) in enumerate(conns):
            keys[off + seq, j] = (pol, remote, kf)

    a, b = POLICY_A.name, POLICY_B.name
    if case == "whole":
        # Three clients, five wire batches interleaved (each multi-batch
        # client's spans non-contiguous), remotes 1 and 9 (denied by
        # remote: ERROR injects), every corpus frame.
        k = 0
        for seq, client in enumerate((0, 1, 2, 0, 1)):
            conns = []
            for _ in range(4):
                remote = 9 if k % 5 == 4 else 1
                conns.append((k, a, remote, _frame(k), _frame(k)))
                k += 1
            add(client, seq, conns)
        return built(True)
    if case == "mixed_engines":
        add(0, 0, [(c, a, 1, _frame(c), _frame(c)) for c in range(4)])
        add(1, 1, [(c, b, 1, _frame(c), _frame(c)) for c in range(4, 8)])
        add(2, 2, [(c, a, 1, _frame(c), _frame(c)) for c in range(8, 11)])
        return built(False)
    # dirty_conn: conn 0 holds a retained partial frame (served
    # entrywise first), so its next frame completes it.
    r.bind(0, off, a, 1)
    res, _ = r.clients[0]._on_data_rpc(r.base + off, False, False,
                                       PARTIAL)
    assert res == int(FilterResult.OK)
    specs.append((0, off, [off], [b"HALT\r\n"]))
    seqs[0] = [off]
    keys[off, 0] = (a, 1, PARTIAL + b"HALT\r\n")
    add(1, 1, [(c, a, 1, _frame(c), _frame(c)) for c in range(1, 5)])
    add(0, 2, [(c, a, 9, _frame(c), _frame(c)) for c in range(5, 8)])
    return built(False)


def _shed_case(tmp_path):
    """A pipelined whole round stalled in its device issue: the stall
    watchdog deposes the round and sheds its seqs typed; the round's
    late completion record is suppressed; every seq answered once."""
    inst.reset_module_registry()
    r = _Route(tmp_path, "shed", 900_000, batch_timeout_ms=300.0,
               device_call_timeout_s=0.5, device_reprobe_interval_s=30.0)
    gate = threading.Event()
    plan = list(enumerate((0, 1, 2, 0)))
    try:
        for k, client in plan:
            r.bind(client, 2 * k, POLICY_A.name, 1)
            r.bind(client, 2 * k + 1, POLICY_A.name, 1)
        # One served push first: the engine is built and warm before the
        # device call is made to hang.
        r.clients[0].send_matrix(
            49, r.width, np.asarray([r.base], np.uint64),
            np.asarray([len(_frame(0))], np.uint32),
            _frame(0).ljust(r.width, b"\0"), complete=True,
        )
        r.answers({0: [49]})
        orig = r.svc._model_call_attr

        def stalled(*a, **kw):
            gate.wait(20.0)
            return orig(*a, **kw)

        r.svc._model_call_attr = stalled
        whole0, vec0 = r.svc.whole_rounds, r.svc.vec_batches
        seqs = {}
        for k, client in plan:
            cids = [2 * k, 2 * k + 1]
            r.clients[client].send_matrix(
                50 + k, r.width,
                np.asarray(cids, np.uint64) + r.base,
                np.asarray([len(_frame(c)) for c in cids], np.uint32),
                b"".join(_frame(c).ljust(r.width, b"\0") for c in cids),
                complete=True,
            )
            seqs.setdefault(client, []).append(50 + k)
        deadline = time.monotonic() + 10.0
        while not r.svc.dispatcher.stall_deposals:
            assert time.monotonic() < deadline, "round never deposed"
            time.sleep(0.01)
        gate.set()  # the deposed round completes and queues its record
        time.sleep(0.3)
        got = r.answers(seqs)
        assert r.svc.whole_rounds > whole0
        # The deposed round's completion record was dropped, not sent
        # and stood down; later rounds ran quarantined (host fallback).
        assert r.svc.vec_batches == vec0
        shed = [s for s, ents in got.items()
                if all(e[1] == int(FilterResult.SHED) for e in ents)]
        assert shed, "no seq was shed by the watchdog"
        return got
    finally:
        gate.set()
        r.close()
        inst.reset_module_registry()


@pytest.mark.parametrize(
    "case", ["whole", "mixed_engines", "dirty_conn", "shed"]
)
def test_matrix_round_parity(case, routes, tmp_path):
    """Each route serves the same round; answers match the oracle and
    each other, flow records match across routes, every seq is answered
    once, and only the whole case moves the whole-round counter."""
    if case == "shed":
        _shed_case(tmp_path)
        return
    exp, by_route = routes
    results = {}
    for name, r in by_route.items():
        items, seqs, keys, whole = _round(case, r)
        since = r.svc.flowlog.stats()["next_seq"] - 1
        w0 = (r.svc.whole_rounds, r.svc.whole_entries)
        r.svc._process(items)
        got = r.answers(seqs)
        for (seq, j), (pol, remote, kf) in keys.items():
            cid, res, ops, inj = got[seq][j]
            assert res == int(FilterResult.OK)
            eops, einj = exp[pol, remote, kf]
            assert ops == [(int(o), int(n)) for o, n in eops], (name, seq, j)
            assert inj == einj, (name, seq, j)
        n = sum(it[2].count for it in items)
        engaged = (r.svc.whole_rounds - w0[0], r.svc.whole_entries - w0[1])
        assert engaged == ((1, n) if whole and name != "per_item"
                           else (0, 0)), name
        st = r.svc.status()["vec"]
        assert st == {"whole_rounds": r.svc.whole_rounds,
                      "whole_entries": r.svc.whole_entries}
        results[name] = (got, r.records(since))
    first = results["pipelined"]
    for name, res in results.items():
        assert res[0] == first[0], f"{name} answers differ"
        assert res[1] == first[1], f"{name} flow records differ"

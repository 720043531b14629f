"""One way to launch a verdict model: every device launch of the sidecar
goes through the service's shape-keyed, ledgered jit launcher.

A service with an r2d2 engine and a DNS engine serves, after its binds,
a whole matrix round, a per-item vec round, a columnar round and a split
frame that the engines' scalar pump judges.  None of them may compile:
prewarm warmed every executable they launch, and the pump launches the
very executables the round paths do, through the launcher the service
injects into each engine (``judge_dispatch``).
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

from cilium_tpu.proxylib import (
    FilterResult,
    NetworkPolicy,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
)
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.parsers.dns import encode_dns_query
from cilium_tpu.runtime.batch import R2d2BatchEngine
from cilium_tpu.sidecar import SidecarClient, VerdictService, wire
from cilium_tpu.utils.option import DaemonConfig

from test_sidecar import oracle_ops, r2d2_policy

BASE = 63000  # conn ids are process-global in proxylib
R2D2 = [BASE + i for i in range(12)]
DNS = [BASE + 100 + i for i in range(8)]
ALLOW = b"READ /public/a.txt\r\n"
DENY = b"WRITE /private/b.txt\r\n"
Q_OK = encode_dns_query("www.example.com")
Q_DENY = encode_dns_query("evil.test")


def _dns_policy() -> NetworkPolicy:
    return NetworkPolicy(
        name="dns-launch",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=53,
                rules=[PortNetworkPolicyRule(
                    l7_proto="dns",
                    l7_rules=[{"matchName": "www.example.com"}],
                )],
            )
        ],
    )


class _Served:
    """Service + client with answers collected by seq."""

    def __init__(self, path: str):
        self.svc = VerdictService(path, DaemonConfig(
            batch_flows=64, batch_width=64, batch_timeout_ms=2.0,
        )).start()
        self.client = SidecarClient(path, timeout=60.0)
        self.got: dict = {}
        self.evt = threading.Event()

        def cb(vb):
            self.got[vb.seq] = [vb.entry(i) for i in range(vb.count)]
            self.evt.set()

        self.client.verdict_callback = cb
        self.seq = 0
        mod = self.client.open_module([])
        assert self.client.policy_update(
            mod, [r2d2_policy(), _dns_policy()]
        ) == int(FilterResult.OK)
        for cid in R2D2:
            self._bind(mod, "r2d2", cid, "2.2.2.2:80", "sidecar-pol")
        for cid in DNS:
            self._bind(mod, "dns", cid, "2.2.2.2:53", "dns-launch")

    def _bind(self, mod, proto, cid, dst, policy):
        res, _ = self.client.new_connection(
            mod, proto, cid, True, 1, 2, "1.1.1.1:1", dst, policy,
        )
        assert res == int(FilterResult.OK)

    def _wait(self, seq: int) -> list:
        deadline = time.monotonic() + 60
        while seq not in self.got and time.monotonic() < deadline:
            self.evt.wait(0.5)
            self.evt.clear()
        assert seq in self.got, f"round {seq} unanswered"
        return self.got[seq]

    def matrix(self, cids, frames) -> list:
        self.seq += 1
        w = self.svc.config.batch_width
        self.client.send_matrix(
            self.seq, w, np.asarray(cids, np.uint64),
            np.asarray([len(f) for f in frames], np.uint32),
            b"".join(f.ljust(w, b"\0") for f in frames), complete=True,
        )
        return self._wait(self.seq)

    def batch(self, entries) -> list:
        """entries: [(conn id, flags, bytes)] in one DATA batch."""
        self.seq += 1
        self.client.send_batch(
            self.seq,
            np.asarray([e[0] for e in entries], np.uint64),
            np.asarray([e[1] for e in entries], np.uint8),
            np.asarray([len(e[2]) for e in entries], np.uint32),
            b"".join(e[2] for e in entries),
        )
        return self._wait(self.seq)

    def close(self) -> None:
        self.client.close()
        self.svc.stop()


def _ops(entries) -> list:
    return [[(int(o), int(n)) for o, n in e[2]] for e in entries]


def test_every_round_path_launches_through_the_service(tmp_path,
                                                       monkeypatch):
    """Whole matrix, per-item vec, columnar and pump rounds over r2d2
    and DNS engines compile nothing after prewarm, and the pump's
    device step is the service's injected launcher."""
    inst.reset_module_registry()
    pumped: list = []
    judge = VerdictService._engine_judge_dispatch

    def spy(self, eng, data, lengths, remotes):
        pumped.append((type(eng).__name__, data.shape))
        return judge(self, eng, data, lengths, remotes)

    monkeypatch.setattr(VerdictService, "_engine_judge_dispatch", spy)
    s = _Served(str(tmp_path / "launch.sock"))
    try:
        engines = [e for e in s.svc._engines.values()
                   if isinstance(e, R2d2BatchEngine)]
        assert sorted(type(e).__name__ for e in engines) == [
            "DnsBatchEngine", "R2d2BatchEngine"]
        assert all(e.judge_dispatch is not None for e in engines)
        compiles = s.svc.ledger.status()["compiles"]
        assert compiles > 0 and not pumped

        # Whole matrix round: r2d2 frames, one per conn.
        whole0 = s.svc.whole_rounds
        ans = s.matrix(R2D2[:2], [ALLOW, DENY])
        assert s.svc.whole_rounds == whole0 + 1
        assert [e[1] for e in ans] == [int(FilterResult.OK)] * 2

        # Per-item vec rounds: whole frames on fresh conns, a batch per
        # engine.
        vec0 = s.svc.vec_batches
        s.batch([(R2D2[2], 0, ALLOW)])
        s.batch([(DNS[0], 0, Q_OK), (DNS[1], 0, Q_DENY)])
        assert s.svc.vec_batches == vec0 + 2

        # Columnar round: split frames, then their completions.
        cols0 = s.svc.status()["reasm"]["rounds"]
        halves = [(R2D2[3 + k], ALLOW[:7], ALLOW[7:]) for k in range(4)]
        halves += [(DNS[2 + k], Q_OK[:9], Q_OK[9:]) for k in range(4)]
        s.batch([(cid, 0, a) for cid, a, _ in halves])
        done = s.batch([(cid, 0, b) for cid, _, b in halves])
        assert s.svc.status()["reasm"]["rounds"] > cols0
        assert _ops(done[:4]) == [
            oracle_ops(r2d2_policy(), [ALLOW[:7], ALLOW[7:]])[1][0]
        ] * 4

        # Pump: the completing half arrives with end-of-stream, which
        # only the engine's scalar feed/pump path serves.
        assert not pumped
        s.batch([(R2D2[8], 0, DENY[:5])])
        end = s.batch([(R2D2[8], wire.FLAG_END_STREAM, DENY[5:])])
        assert end[0][1] == int(FilterResult.OK)
        assert pumped and pumped[0][0] == "R2d2BatchEngine"
        assert pumped[0][1] == (s.svc._min_bucket,
                                s.svc.config.batch_width)

        s.svc.dispatcher.flush(10.0)
        assert s.svc.ledger.status()["compiles"] == compiles, (
            s.svc.ledger.events(n=64)[-8:]
        )
    finally:
        s.close()
        inst.reset_module_registry()


def test_daemon_config_has_no_dispatch_mode():
    """jit is the only launch: no mode field, and a configuration that
    still pins the removed mode runs the defaults."""
    from benchmark.run import daemon_config

    fields = dataclasses.fields(DaemonConfig)
    assert "dispatch_mode" not in {f.name for f in fields}
    got, want = daemon_config({"dispatch_mode": "jit"}), DaemonConfig()
    for f in fields:
        if f.name != "opts":  # an OptionMap compares by identity
            assert getattr(got, f.name) == getattr(want, f.name), f.name

"""DNS engine rung end to end (ISSUE 13): the first non-CRLF columnar
lane.  Applies the test_reasm.py parity template to the DNS framing —
every-byte-offset splits across the length prefix and mid-QNAME,
mid-frame faults, overflow/dead-flow latch — asserting bit-identity of
verdicts, rule attribution, and flowlog records vs the scalar/oracle
rung; plus the per-framing verdict-cache tier, the flow-cache LRU
eviction satellite, and the mesh build-while-demoted rebind heal."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cilium_tpu.proxylib import (
    FilterResult,
    NetworkPolicy,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
)
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.parsers.dns import DNS_QNAME_OFF, encode_dns_query
from cilium_tpu.runtime.dnsengine import DnsBatchEngine
from cilium_tpu.sidecar import reasm, wire
from cilium_tpu.sidecar.client import SidecarClient
from cilium_tpu.sidecar.reasm import FRAMINGS, Reassembler
from cilium_tpu.sidecar.service import VerdictService
from cilium_tpu.utils.option import DaemonConfig

DNS_FRAMING = FRAMINGS["dns"]

F_OK = encode_dns_query("www.example.com")
F_WILD = encode_dns_query("api.svc.cluster.local")
F_DENY = encode_dns_query("evil.test")


def _policy(rules=None, name="dns-t"):
    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=53,
                rules=[
                    PortNetworkPolicyRule(
                        l7_proto="dns",
                        l7_rules=rules or [
                            {"matchName": "www.example.com"},
                            {"matchPattern": "*.svc.cluster.local"},
                        ],
                    )
                ],
            )
        ],
    )


# --- framing primitives ----------------------------------------------------

def test_dns_framing_scan_and_alignment():
    f1, f2 = F_OK, F_WILD
    entry0 = f1 + f2 + f1[:1]  # two frames + partial prefix
    entry1 = f2[:-4]  # header complete, frame truncated
    stream = np.frombuffer(entry0 + entry1, np.uint8)
    offs = np.array([0, len(entry0)], np.int64)
    ends = np.array([len(entry0), len(entry0) + len(entry1)], np.int64)
    fe, fs, fl = DNS_FRAMING.scan(stream, offs, ends)
    assert fe.tolist() == [0, 0]
    assert fs.tolist() == [0, len(f1)]
    assert fl.tolist() == [len(f1), len(f2)]
    blob = np.frombuffer(f1 + f2 + f1[:5], np.uint8)
    starts = np.array([0, len(f1), len(f1) + len(f2)], np.int64)
    lens = np.array([len(f1), len(f2), 5], np.int64)
    assert DNS_FRAMING.segments_aligned(blob, starts, lens).tolist() \
        == [True, True, False]
    # multi-frame aligned segment
    blob2 = np.frombuffer(f1 + f2, np.uint8)
    assert DNS_FRAMING.segments_aligned(
        blob2, np.array([0]), np.array([len(f1) + len(f2)])
    ).tolist() == [True]
    assert DNS_FRAMING.payload_aligned(f1 + f2)
    assert not DNS_FRAMING.payload_aligned(f1 + f2[:-1])
    assert DNS_FRAMING.payload_single_frame(f1)
    assert not DNS_FRAMING.payload_single_frame(f1 + f2)
    assert DNS_FRAMING.segments_single_frame(
        blob2, np.array([0, len(f1)], np.int64),
        np.array([len(f1), len(f2)], np.int64),
    ).all()


# --- engine-level columnar parity (the test_reasm template) ---------------

def _scalar_round(eng, cid, chunk, allow_of):
    frames = eng.feed_extract(cid, chunk, remote_id=1)
    fl = eng.flows.get(cid)
    if fl is not None and fl.overflowed and not frames:
        more = False
    else:
        more = bool(frames) or bool(fl is not None and fl.buffer)
    judged = [(m, ln, allow_of(m), -1) for m, ln in frames]
    return eng.settle_entry(cid, judged, more)


def test_columnar_parity_every_byte_offset():
    """DNS frames split at EVERY byte offset (through the length
    prefix, the header, and mid-QNAME), pipelined frames, a zero-body
    frame, cap overflow mid-frame and the dead-flow latch: the
    columnar round under the dns framing must be op-for-op and
    inject-for-inject identical to the scalar DnsBatchEngine rung."""
    frame = F_WILD
    zero = (0).to_bytes(2, "big")  # 2-byte frame, zero-length message
    cap = 96

    def allow_of(msg: bytes) -> bool:
        return b"svc" in msg

    for split in range(1, len(frame)):
        chunks_by_round = [
            [frame[:split]],
            [frame[split:] + F_OK + zero],  # completes + pipelined pair
            [b"x" * (cap + 10)],  # overflow mid-frame
            [b"more"],  # dead-flow entry
        ]
        eng = DnsBatchEngine(None, max_buffer=cap)
        R = Reassembler(cap_per_conn=cap)
        cid = np.array([7], np.int64)
        for chunks in chunks_by_round:
            blob = np.frombuffer(b"".join(chunks), np.uint8)
            lens = np.array([len(c) for c in chunks], np.int64)
            starts = np.concatenate(([0], np.cumsum(lens)))[:-1]
            rnd = R.ingest(cid, starts, lens, blob, framing=DNS_FRAMING)
            msgs = [
                rnd.stream[s : s + ln].tobytes()
                for s, ln in zip(rnd.f_start, rnd.f_len)
            ]
            allow = np.array([allow_of(m) for m in msgs], bool)
            oc, ops, inj_len, inj_blob, _nd = R.assemble(rnd, allow)
            col_ops, col_inj = R.entry_ops(
                rnd, oc, ops, inj_len, inj_blob, 0
            )
            sc_ops, sc_inj = _scalar_round(eng, 7, chunks[0], allow_of)
            sc_ops = [(int(o), int(n)) for o, n in sc_ops]
            assert col_ops == sc_ops, (split, chunks, col_ops, sc_ops)
            assert col_inj == sc_inj == b"", (split, chunks)
            fl = eng.flows.get(7)
            res, dead = R.arena.release(7)
            assert res == bytes(fl.buffer if fl else b"")
            assert dead == bool(fl and fl.overflowed)
            slots = R.arena.ensure_slots(cid)
            if res:
                R.arena.store(slots, np.frombuffer(res, np.uint8),
                              np.array([0]), np.array([len(res)]))
            if dead:
                R.arena.s_dead[slots] = 1
        assert R.rounds_by_framing["dns"] == len(chunks_by_round)


# --- service-level paired runs --------------------------------------------

class _Svc:
    """One service+client pair driven round-by-round (the test_reasm
    harness, DNS edition)."""

    def __init__(self, path: str, reasm_on: bool, **cfg_kw):
        defaults = dict(
            batch_flows=256, batch_timeout_ms=0.25, batch_width=64,
            reasm=reasm_on, reasm_min_entries=1,
            device_reprobe_interval_s=1e9,
        )
        defaults.update(cfg_kw)
        cfg = DaemonConfig(**defaults)
        self.svc = VerdictService(path, cfg).start()
        self.cl = SidecarClient(path, timeout=120.0)
        self.mod = self.cl.open_module([])
        assert self.cl.policy_update(
            self.mod, [_policy()]
        ) == int(FilterResult.OK)
        self.got: dict = {}
        self.evt = threading.Event()

        def cb(vb):
            self.got[vb.seq] = [vb.entry(i) for i in range(vb.count)]
            self.evt.set()

        self.cl.verdict_callback = cb
        self.seq = 0

    def conns(self, n: int) -> None:
        for cid in range(1, n + 1):
            res, _ = self.cl.new_connection(
                self.mod, "dns", cid, True, 1, 2,
                "1.1.1.1:1", "2.2.2.2:53", "dns-t",
            )
            assert res == int(FilterResult.OK)

    def send_round(self, entries) -> list:
        self.seq += 1
        cids = np.array([e[0] for e in entries], np.uint64)
        fl = np.array([e[1] for e in entries], np.uint8)
        lens = np.array([len(e[2]) for e in entries], np.uint32)
        self.cl.send_batch(
            self.seq, cids, fl, lens, b"".join(e[2] for e in entries)
        )
        deadline = time.monotonic() + 90
        while self.seq not in self.got and time.monotonic() < deadline:
            self.evt.wait(0.5)
            self.evt.clear()
        assert self.seq in self.got, f"round {self.seq} unanswered"
        return self.got[self.seq]

    def records(self) -> dict:
        def snap():
            out = self.svc.observe_dump({"n": 1 << 20})["records"]
            per: dict = {}
            for r in sorted(out, key=lambda r: r["seq"]):
                per.setdefault(r["conn_id"], []).append(
                    (r["verdict"], r["rule_id"], r["match_kind"],
                     r.get("epoch"))
                )
            return per

        prev = snap()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            time.sleep(0.05)
            cur = snap()
            if cur == prev:
                return cur
            prev = cur
        return prev

    def close(self) -> None:
        self.cl.close()
        self.svc.stop()


def _one_run(path: str, reasm_on: bool, scenario, **cfg_kw):
    inst.reset_module_registry()
    svc = _Svc(path, reasm_on, **cfg_kw)
    try:
        outs = scenario(svc)
        recs = svc.records()
        st = svc.svc.status()["reasm"]
        return outs, recs, st
    finally:
        svc.close()
        inst.reset_module_registry()


def _paired(tmp_path, scenario, **cfg_kw):
    out_a, rec_a, st = _one_run(
        str(tmp_path / "dns_on.sock"), True, scenario, **cfg_kw
    )
    out_b, rec_b, _off = _one_run(
        str(tmp_path / "dns_off.sock"), False, scenario, **cfg_kw
    )
    assert len(out_a) == len(out_b)
    for i, (ra, rb) in enumerate(zip(out_a, out_b)):
        assert ra == rb, f"verdict mismatch in round {i}:\n{ra}\n{rb}"
    assert rec_a == rec_b, "flow-record attribution diverged"
    assert st is not None and st["rounds_by_framing"].get("dns", 0) > 0, \
        f"dns columnar lane never engaged: {st}"
    return st


def test_service_parity_dns_framing(tmp_path):
    """Length-prefix splits at per-conn byte offsets (through the
    prefix and mid-QNAME), pipelined + invalid frames, reply bytes,
    duplicate conns, and a swap-epoch flip landing mid-reassembly —
    columnar and scalar DNS services byte-identical, attribution
    included."""
    frame = F_WILD
    n = 12

    def scenario(svc: _Svc):
        svc.conns(n + 2)
        outs = []
        pre, suf = [], []
        for k in range(1, n + 1):
            off = k % (len(frame) - 1) + 1
            pre.append((k, 0, frame[:off]))
            suf.append((k, 0, frame[off:]))
        outs.append(svc.send_round(pre))
        outs.append(svc.send_round(suf))
        bad = bytearray(encode_dns_query("bad.svc.cluster.local"))
        bad[DNS_QNAME_OFF] = 0xC0
        mixed = []
        for k in range(1, n + 1):
            if k % 4 == 0:
                mixed.append((k, 0, bytes(bad)))  # invalid QNAME: deny
            elif k % 4 == 1:
                mixed.append((k, 0, F_OK + F_DENY + F_WILD))
            elif k % 4 == 2:
                mixed.append((k, wire.FLAG_REPLY, F_OK))
            else:
                mixed.append((k, 0, F_DENY))
        mixed.append((n + 1, 0, frame[:9]))
        mixed.append((n + 1, 0, frame[9:]))  # duplicate conn: scalar
        mixed.append((n + 2, 0, F_OK))
        outs.append(svc.send_round(mixed))
        # swap-epoch flip mid-reassembly: half frames in flight, a
        # policy update that flips the verdicts, then the second
        # halves (judged on the NEW epoch in both lanes)
        outs.append(svc.send_round(
            [(k, 0, frame[:10]) for k in range(1, n + 1)]
        ))
        assert svc.cl.policy_update(
            svc.mod, [_policy(rules=[{"matchName": "nothing.else"}])],
        ) == int(FilterResult.OK)
        outs.append(svc.send_round(
            [(k, 0, frame[10:]) for k in range(1, n + 1)]
        ))
        return outs

    _paired(tmp_path, scenario)


def test_service_parity_dns_cap_overflow(tmp_path):
    """Retained-bytes cap tripping mid-DNS-frame: typed DROP+ERROR,
    dead-flow latch after — identical across lanes."""

    def scenario(svc: _Svc):
        svc.conns(5)
        outs = []
        outs.append(svc.send_round(
            [(k, 0, b"\x00\xff" + b"A" * 28) for k in range(1, 5)]
        ))
        outs.append(svc.send_round(  # 30 + 30 > 48: overflow
            [(k, 0, b"B" * 30) for k in range(1, 5)]
        ))
        outs.append(svc.send_round(  # dead flows error typed
            [(k, 0, F_OK) for k in range(1, 5)]
        ))
        outs.append(svc.send_round([(5, 0, F_OK)]))
        return outs

    _paired(tmp_path, scenario, max_flow_buffer=48)


# --- verdict cache: the per-framing alignment tier ------------------------

def test_dns_rides_verdict_cache(tmp_path):
    """A byte-free DNS rule arms the PR 12 cache and whole-frame-
    aligned payloads short-circuit (columnar Phase-A / whole-item
    tiers) with the ORIGINAL rule row attributed — while a partial
    frame stays on the device path.  Output parity vs a cache-off
    control over identical traffic."""
    byte_free = [{"matchName": "www.example.com"}]

    def run(flow_cache: bool):
        inst.reset_module_registry()
        svc = _Svc(
            str(tmp_path / f"dnsc_{int(flow_cache)}.sock"), True,
            flow_cache=flow_cache,
        )
        # Re-push a policy whose FIRST row is byte-free for remote 1.
        assert svc.cl.policy_update(svc.mod, [_policy(
            rules=[{}, {"matchName": "www.example.com"}],
        )]) == int(FilterResult.OK)
        try:
            svc.conns(8)
            outs = []
            for r in range(6):
                entries = []
                for k in range(1, 9):
                    if k % 4 == 0:  # split frames: never cacheable
                        half = len(F_WILD) // 2
                        entries.append(
                            (k, 0,
                             F_WILD[:half] if r % 2 == 0 else F_WILD[half:])
                        )
                    elif k % 4 == 1:  # two whole frames, aligned
                        entries.append((k, 0, F_OK + F_DENY))
                    else:
                        entries.append((k, 0, F_OK))
                outs.append(svc.send_round(entries))
            recs = svc.records()
            st = svc.svc.status()
            return outs, recs, st
        finally:
            svc.close()
            inst.reset_module_registry()

    outs_on, recs_on, st_on = run(True)
    outs_off, _recs_off, _st_off = run(False)

    def norm(outs):
        """The cache coalesces per-frame ops into stream-level PASS
        (the documented flow_cache contract: byte-EQUIVALENT forwarded
        output, not op-identical) — compare per-entry pass/drop byte
        totals and injects."""
        from cilium_tpu.proxylib.types import DROP, PASS

        normed = []
        for rnd in outs:
            normed.append([
                (cid, res,
                 sum(n for op, n in ops if op == int(PASS)),
                 sum(n for op, n in ops if op == int(DROP)),
                 io, ir)
                for cid, res, ops, io, ir in rnd
            ])
        return normed

    assert norm(outs_on) == norm(outs_off), \
        "cached output diverged from control at the byte level"
    fc = st_on["flow_cache"]
    assert fc["armed"] > 0, fc
    assert fc["hits"] > 0, fc
    # Cached records attribute the claimed (byte-free) rule row 0 on
    # the `cached` path label.
    cached_rows = [
        t for seqs in recs_on.values() for t in seqs if t[2] == "literal"
    ]
    assert cached_rows, recs_on
    assert _st_off["flow_cache"] is None


def test_flow_cache_lru_eviction(tmp_path):
    """Satellite 3d: at the flow_cache_entries cap the least-recently-
    HIT armed row is evicted (verdict_cache_evictions_total) and the
    new flow arms — not silently left unarmed."""
    inst.reset_module_registry()
    svc = _Svc(
        str(tmp_path / "dns_lru.sock"), True,
        flow_cache=True, flow_cache_entries=2,
    )
    assert svc.cl.policy_update(
        svc.mod, [_policy(rules=[{}])]
    ) == int(FilterResult.OK)
    try:
        s = svc.svc
        svc.conns(2)  # conns 1, 2 arm (cap reached)
        assert s._cache_armed == 2
        # Hit conn 2 (recency), leave conn 1 cold.
        svc.send_round([(2, 0, F_OK), (2, 0, F_OK)])
        # Registering conn 3 must evict the LRU row (conn 1).
        res, _ = svc.cl.new_connection(
            svc.mod, "dns", 3, True, 1, 2, "1.1.1.1:1",
            "2.2.2.2:53", "dns-t",
        )
        assert res == int(FilterResult.OK)
        st = s.status()["flow_cache"]
        assert st["armed"] == 2 and st["evictions"] == 1, st
        assert s._tab_cache[1] == 0  # the cold row was the victim
        assert s._tab_cache[2] == 1 and s._tab_cache[3] == 1
        assert st["cap"] == 2
    finally:
        svc.close()
        inst.reset_module_registry()


# --- mesh: build-while-demoted heals via queued rebinds (ROADMAP 1c) ------

def test_mesh_rebinds_engine_built_while_demoted(tmp_path):
    """Regression for ROADMAP 1c: an engine BUILT during a mesh
    demotion (single-chip, no retained wrapper) must serve sharded
    after the heal — the re-promotion queues an off-path rebuild for
    it instead of waiting for the next epoch swap."""
    from cilium_tpu.parallel.rulesharding import ShardedVerdictModel

    inst.reset_module_registry()
    svc = cl = None
    try:
        cfg = DaemonConfig(
            batch_flows=64, batch_timeout_ms=0.0,
            mesh="on", mesh_rule_shards=2,
            mesh_reprobe_interval_s=0.05,
            device_reprobe_interval_s=1e9,
        )
        svc = VerdictService(str(tmp_path / "dns_mesh.sock"), cfg).start()
        cl = SidecarClient(svc.socket_path, timeout=120.0)
        mod = cl.open_module([])
        assert cl.policy_update(mod, [_policy()]) == int(FilterResult.OK)
        res, shim = cl.new_connection(
            mod, "dns", 1, True, 1, 2, "1.1.1.1:1", "2.2.2.2:53",
            "dns-t",
        )
        assert res == int(FilterResult.OK)
        r, out = shim.on_io(False, F_OK)
        assert r == int(FilterResult.OK) and out == F_OK
        eng0 = next(iter(svc._engines.values()))
        assert isinstance(eng0.model, ShardedVerdictModel)

        # Demote the mesh rung via a lost-device fault injection.
        orig = svc._jit_for

        def lost_device(cache, model, trace_fn, arg_fn=None):
            if isinstance(model, ShardedVerdictModel):
                def boom(*_a, **_k):
                    raise RuntimeError("PJRT_Error: device lost")

                return boom
            return orig(cache, model, trace_fn, arg_fn)

        svc._jit_for = lost_device
        r, out = shim.on_io(False, F_WILD)
        assert r == int(FilterResult.OK) and out == F_WILD
        assert svc.status()["mesh"]["demoted"] == "device-call"

        # Build a NEW engine while demoted (a different policy name →
        # a key the swap-era engine table has never seen): it compiles
        # single-chip.
        assert cl.policy_update(
            mod, [_policy(), _policy(name="dns-late")],
        ) == int(FilterResult.OK)
        res, shim2 = cl.new_connection(
            mod, "dns", 2, True, 1, 2, "1.1.1.2:2", "2.2.2.2:53",
            "dns-late",
        )
        assert res == int(FilterResult.OK)
        r, out = shim2.on_io(False, F_OK)
        assert r == int(FilterResult.OK) and out == F_OK
        late_key = next(
            k for k in svc._engines if k[1] == "dns-late"
        )
        late = svc._engines[late_key]
        assert not isinstance(late.model, ShardedVerdictModel)

        # Heal: remove the fault, let the paced re-probe re-promote
        # AND flip the demotion-era engine through the queued rebind.
        svc._jit_for = orig
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            r, out = shim.on_io(False, F_OK)
            assert r == int(FilterResult.OK)
            cur = svc._engines.get(late_key)
            if (
                svc.status()["mesh"]["active"]
                and cur is not None
                and isinstance(cur.model, ShardedVerdictModel)
            ):
                break
            time.sleep(0.05)
        st = svc.status()["mesh"]
        assert st["active"] is True, st
        assert st["rebind_rebuilds"] >= 1, st
        cur = svc._engines[late_key]
        assert isinstance(cur.model, ShardedVerdictModel), (
            "build-while-demoted engine still single-chip after heal"
        )
        # ... and it actually serves, bit-identically.
        r, out = shim2.on_io(False, F_WILD)
        assert r == int(FilterResult.OK) and out == F_WILD
        r, out = shim2.on_io(False, F_DENY)
        assert r == int(FilterResult.OK) and out == b""
    finally:
        if cl is not None:
            cl.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()

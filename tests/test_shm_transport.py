"""Shared-memory transport tests (sidecar/shm.py, sidecar/transport.py).

The contract under test (ISSUE 8): the shm fast path is bit-identical
to the socket path (verdicts, op sequences, flowlog attribution), and
every ring fault degrades TYPED to the socket rung — torn slot, stale
generation, service restart — with zero silent loss even at 2×
capacity with ring-fault injection.
"""

from __future__ import annotations

import json
import struct
import threading
import time

import numpy as np
import pytest

from cilium_tpu.proxylib import FilterResult
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.sidecar import (
    SidecarClient,
    VerdictService,
    wire,
)
from cilium_tpu.sidecar.shm import (
    SLOT_HEADER_BYTES,
    GenerationMismatch,
    ShmRing,
    TornSlot,
)
from cilium_tpu.sidecar.transport import (
    REASON_TORN_SLOT,
    TRANSPORT_SHM,
    TRANSPORT_SOCKET,
    ShmSession,
)
from cilium_tpu.utils.option import DaemonConfig

from test_sidecar import CORPUS, assert_parity, oracle_ops, r2d2_policy
from test_sidecar_faults import _open_conn, _shim_run, _wait


def _service(tmp_path, name, **cfg_kw):
    inst.reset_module_registry()
    defaults = dict(
        batch_timeout_ms=2.0,
        batch_flows=256,
    )
    defaults.update(cfg_kw)
    cfg = DaemonConfig(**defaults)
    return VerdictService(str(tmp_path / f"{name}.sock"), cfg).start()


SHM_KW = dict(
    transport=TRANSPORT_SHM,
    shm_data_slots=16,
    shm_slot_bytes=1 << 16,
    shm_verdict_slots=16,
    shm_verdict_slot_bytes=1 << 16,
)


# --- ring unit behavior ----------------------------------------------------

def test_ring_roundtrip_and_full_refusal():
    ring = ShmRing.create("test", 3, slots=4, slot_bytes=256)
    try:
        assert ring.try_push(5, b"abc", 0)
        assert ring.try_push(6, b"defg", 0)
        # 4-slot ring with zero credit: two more fit, the fifth refuses
        assert ring.try_push(5, b"x", 0)
        assert ring.try_push(5, b"y", 0)
        assert not ring.try_push(5, b"z", 0), "full ring must refuse"
        got = [ring.read(i)[:2] for i in range(3)]
        assert got == [(5, b"abc"), (6, b"defg"), (5, b"x")]
        assert ring.try_push(5, b"z", 1), "credit frees the slot"
        assert ring.read(4)[:2] == (5, b"z")  # wrapped into slot 0
        assert not ring.fits(257)
        assert not ring.try_push(5, b"q" * 500, 5), "oversize refuses"
    finally:
        ring.close()
        ring.unlink()


def test_ring_torn_slot_and_stale_generation():
    ring = ShmRing.create("test", 9, slots=4, slot_bytes=256)
    try:
        assert ring.try_push(5, b"abc", 0)
        # Tear the slot: zero the commit word (producer died mid-write).
        struct.pack_into("<Q", ring.seg.buf, 64, 0)
        with pytest.raises(TornSlot):
            ring.read(0)
        # Attach validates generation against the segment header.
        with pytest.raises(GenerationMismatch):
            ShmRing.attach(ring.seg.name, 10)
        peer = ShmRing.attach(ring.seg.name, 9)
        peer.close()
    finally:
        ring.close()
        ring.unlink()


def test_wire_shm_roundtrips():
    g, t, vh = 7, 123456789, 42
    assert wire.unpack_shm_doorbell(
        wire.pack_shm_doorbell(g, t, vh)
    ) == (g, t, vh)
    assert wire.unpack_shm_credit(
        wire.pack_shm_credit(g, 1, t, vh)
    ) == (g, 1, t, vh)
    assert wire.unpack_shm_detach(wire.pack_shm_detach(g)) == (g, 0)
    assert wire.unpack_shm_detach(
        wire.pack_shm_detach(g, wire.DETACH_FLAG_NO_ACK)
    ) == (g, wire.DETACH_FLAG_NO_ACK)


# --- bit-identical parity across transports --------------------------------

def _flow_records(svc):
    """Flowlog extract for parity: the attribution-relevant columns as
    a sorted multiset.  Seqs/timestamps are transport noise, and
    CROSS-round emission order is thread-interleave noise (vec rounds
    record on the send thread, entrywise rounds on the dispatcher) —
    the contract is that every flow gets the same verdict with the
    same rule attribution on both transports."""
    recs = svc.flowlog.query(n=10_000)
    return sorted(
        (r["conn_id"], r["verdict"], r["rule_id"], r["match_kind"])
        for r in recs
    )


PARITY_MSGS = CORPUS + [
    b"READ /pub",                 # partial frame...
    b"lic/tail.txt\r\n",          # ...completed next entry
    b"READ /public/a.txt\r\nHALT\r\n",  # pipelined pair
]


def _settle_flows(svc, timeout_s: float = 5.0) -> None:
    """Record emission may lag the RPC reply (vec-round records are
    appended on the send thread after the verdict frame is written):
    wait until the record count is quiescent before comparing."""
    deadline = time.monotonic() + timeout_s
    last, stable = -1, 0
    while time.monotonic() < deadline and stable < 3:
        n = svc.flowlog.stats().get("records", 0)
        stable = stable + 1 if n == last else 0
        last = n
        time.sleep(0.05)


def _run_transport(tmp_path, name, **client_kw):
    svc = _service(tmp_path, name)
    client = SidecarClient(svc.socket_path, timeout=30.0, **client_kw)
    try:
        _, shim = _open_conn(client, 4100)
        got = _shim_run(client, shim, PARITY_MSGS)
        _settle_flows(svc)
        flows = _flow_records(svc)
        return got, flows, client.transport_status(), svc.status()
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_shm_socket_parity_verdicts_and_flowlog(tmp_path):
    """The acceptance gate: identical traffic through both transports
    produces bit-identical op sequences, injects, AND flow-record
    attribution — and the shm run really rode the ring."""
    got_sock, flows_sock, _, _ = _run_transport(tmp_path, "par_sock")
    got_shm, flows_shm, tstat, sstat = _run_transport(
        tmp_path, "par_shm", **SHM_KW
    )
    assert_parity(got_shm, got_sock)
    # Both also match the in-process oracle (the definition of exact).
    assert_parity(got_sock, oracle_ops(r2d2_policy(), PARITY_MSGS))
    assert flows_shm == flows_sock
    assert tstat["mode"] == TRANSPORT_SHM
    assert tstat["session"]["data_frames"] == len(PARITY_MSGS)
    assert tstat["session"]["verdict_frames"] > 0, (
        "verdicts must ride the verdict ring, not the socket"
    )
    assert tstat["fallbacks"] == {}
    sess = sstat["transport"]["sessions"][0]
    assert sess["mode"] == TRANSPORT_SHM
    assert sstat["transport"]["shm_entries"] == len(PARITY_MSGS)
    # Ring-stage observability: shm rounds carve STAGE_RING out of the
    # queue wait in the latency decomposition.
    stages = sstat["latency"]["stages"]
    assert any("ring" in per_path for per_path in stages.values())


def test_oversize_batch_falls_back_per_batch(tmp_path):
    """A frame larger than a slot rides the socket (typed, counted) —
    the session itself stays on the shm rung."""
    svc = _service(tmp_path, "oversize")
    client = SidecarClient(
        svc.socket_path, timeout=30.0, transport=TRANSPORT_SHM,
        shm_data_slots=4, shm_slot_bytes=SLOT_HEADER_BYTES + 64,
    )
    try:
        _, shim = _open_conn(client, 4200)
        big = b"READ /public/" + b"a" * 200 + b"\r\n"
        exp = oracle_ops(r2d2_policy(), [big])
        got = _shim_run(client, shim, [big])
        assert_parity(got, exp)
        assert client.transport_mode == TRANSPORT_SHM
        assert client.transport_fallbacks.get("oversize", 0) >= 1
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- fault injection -------------------------------------------------------

def test_torn_slot_quarantines_and_demotes_typed(tmp_path):
    """Shim dies mid-write (simulated: a claimed-but-uncommitted slot
    behind an inflated doorbell): the service quarantines the ring and
    demotes the session; the never-admitted frame is answered with a
    client-synthesized typed SHED — zero silent loss — and the session
    keeps serving over the socket."""
    svc = _service(tmp_path, "torn")
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    try:
        _, shim = _open_conn(client, 4300)
        _shim_run(client, shim, [b"HALT\r\n"])  # shm path warm
        sess = client._shm
        assert sess is not None and sess.active

        got: dict[int, wire.VerdictBatch] = {}
        client.verdict_callback = lambda vb: got.setdefault(vb.seq, vb)

        with client._wlock:
            pos = sess.data.tail
            payload = wire.pack_data_batch(
                991, [shim.conn_id], [0], [6], b"HALT\r\n"
            )
            assert sess.data.try_push(
                wire.MSG_DATA_BATCH, payload, sess.credit_head
            )
            sess.inflight[991] = (
                pos, np.array([shim.conn_id], np.uint64)
            )
            # Tear the slot the doorbell is about to claim.
            off = 64 + (pos % sess.data.slots) * sess.data.slot_bytes
            struct.pack_into("<Q", sess.data.seg.buf, off, 0)
            client._doorbell_send(sess, sess.data.tail)

        _wait(
            lambda: client.transport_mode == TRANSPORT_SOCKET,
            10.0, "session demotion to socket",
        )
        _wait(lambda: 991 in got, 5.0, "typed SHED for the torn frame")
        vb = got[991]
        assert list(vb.results) == [int(FilterResult.SHED)]
        assert client.transport_fallbacks.get(REASON_TORN_SLOT, 0) == 1
        st = svc.status()
        sess_st = st["transport"]["sessions"][0]
        assert sess_st["mode"] == TRANSPORT_SOCKET
        assert sess_st["quarantine_reason"] == REASON_TORN_SLOT

        # Fallback serves, same bit-exact verdicts, on the SAME shim.
        client.verdict_callback = None
        got2 = _shim_run(client, shim, CORPUS)
        assert_parity(got2, oracle_ops(r2d2_policy(), CORPUS))
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_partial_drain_before_torn_slot_is_submitted(tmp_path):
    """Frames drained BEFORE the torn slot in the same doorbell are
    admitted work: they must be submitted (real verdicts over the
    socket after quarantine), while the torn frame and beyond get the
    client's synthesized SHED.  Discarding the partial drain would
    strand its callers below the credit's data_head watermark — silent
    loss by timeout."""
    svc = _service(tmp_path, "partial_torn")
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    try:
        _, shim = _open_conn(client, 4350)
        _shim_run(client, shim, [b"HALT\r\n"])  # shm path warm
        sess = client._shm

        got: dict[int, wire.VerdictBatch] = {}
        client.verdict_callback = lambda vb: got.setdefault(vb.seq, vb)

        with client._wlock:
            msg = b"HALT\r\n"
            # Good frame at pos, torn frame at pos+1, ONE doorbell.
            for seq in (990, 991):
                pos = sess.data.tail
                payload = wire.pack_data_batch(
                    seq, [shim.conn_id], [0], [len(msg)], msg
                )
                assert sess.data.try_push(
                    wire.MSG_DATA_BATCH, payload, sess.credit_head
                )
                sess.inflight[seq] = (
                    pos, np.array([shim.conn_id], np.uint64)
                )
                if seq == 991:
                    off = (
                        64 + (pos % sess.data.slots) * sess.data.slot_bytes
                    )
                    struct.pack_into("<Q", sess.data.seg.buf, off, 0)
            client._doorbell_send(sess, sess.data.tail)

        _wait(lambda: 990 in got and 991 in got, 10.0,
              "both frames answered")
        assert list(got[990].results) == [int(FilterResult.OK)], (
            "the pre-torn frame must get its REAL verdict"
        )
        assert list(got[991].results) == [int(FilterResult.SHED)]
        assert client.transport_mode == TRANSPORT_SOCKET
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_client_fault_demotion_notifies_service(tmp_path):
    """A CLIENT-detected ring fault (torn verdict slot) must latch the
    SERVICE off the rings too — otherwise the service keeps writing
    verdicts into a ring nobody drains and admitted in-flight RPCs
    time out instead of getting their promised socket verdicts."""
    svc = _service(tmp_path, "clientfault")
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    try:
        _, shim = _open_conn(client, 4360)
        _shim_run(client, shim, [b"HALT\r\n"])
        assert client.transport_mode == TRANSPORT_SHM
        client._demote_shm(REASON_TORN_SLOT)
        assert client.transport_mode == TRANSPORT_SOCKET
        _wait(
            lambda: svc.status()["transport"]["sessions"][0]["mode"]
            == TRANSPORT_SOCKET,
            5.0, "service latched off the rings",
        )
        # Verdicts keep flowing — over the socket, bit-identical.
        got = _shim_run(client, shim, CORPUS)
        assert_parity(got, oracle_ops(r2d2_policy(), CORPUS))
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_stale_generation_attach_rejected_fallback_serves(tmp_path):
    """Service restart with a stale segment: an attach whose negotiated
    generation mismatches the segment header is rejected TYPED, and the
    session serves on the socket rung."""
    svc = _service(tmp_path, "stalegen")
    client = SidecarClient(svc.socket_path, timeout=30.0)
    sess = ShmSession.create(5, 4, 4096, 4, 4096)
    try:
        req = sess.attach_request()
        req["generation"] = 6  # stale: segment headers say 5
        got = client._control_rpc(
            lambda: (wire.MSG_SHM_ATTACH, json.dumps(req).encode()),
            wire.MSG_SHM_ATTACH_REPLY,
            retry=False,
        )
        rep = json.loads(got.decode())
        assert rep["status"] != int(FilterResult.OK)
        assert "generation" in rep["error"]
        assert svc.transport_rejects.get("generation_mismatch", 0) == 1
        # Fallback serves: the same session keeps verdicting.
        _, shim = _open_conn(client, 4400)
        got2 = _shim_run(client, shim, CORPUS)
        assert_parity(got2, oracle_ops(r2d2_policy(), CORPUS))
        assert client.transport_mode == TRANSPORT_SOCKET
    finally:
        sess.destroy()
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_shm_disabled_by_config_rejects_typed(tmp_path):
    svc = _service(tmp_path, "disabled", shm_transport=False)
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    try:
        assert client.transport_mode == TRANSPORT_SOCKET
        assert client.transport_fallbacks.get("attach_rejected", 0) == 1
        assert svc.transport_rejects.get("disabled", 0) == 1
        _, shim = _open_conn(client, 4500)
        got = _shim_run(client, shim, [b"HALT\r\n"])
        assert_parity(got, oracle_ops(r2d2_policy(), [b"HALT\r\n"]))
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_reconnect_renegotiates_fresh_rings(tmp_path):
    """auto_reconnect replays the session AND re-negotiates fresh rings
    (bumped generation, new segments) — a restarted service never
    attaches a stale segment."""
    svc = _service(tmp_path, "renegotiate")
    path = svc.socket_path
    client = SidecarClient(
        path, timeout=8.0, auto_reconnect=True, **SHM_KW
    )
    try:
        _, shim = _open_conn(client, 4600)
        assert client.transport_mode == TRANSPORT_SHM
        gen1 = client._shm.generation
        name1 = client._shm.data.seg.name
        _shim_run(client, shim, [b"HALT\r\n"])

        svc.stop()
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert res == int(FilterResult.SERVICE_UNAVAILABLE)

        inst.reset_module_registry()
        svc2 = VerdictService(path, DaemonConfig(
            batch_timeout_ms=2.0, batch_flows=256,
        )).start()
        try:
            _wait(
                lambda: client.connected
                and client.reconnects >= 1
                and client.transport_mode == TRANSPORT_SHM,
                10.0, "reconnect with fresh shm rings",
            )
            assert client._shm.generation > gen1
            assert client._shm.data.seg.name != name1

            def verdict_ok():
                res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
                return res == int(FilterResult.OK) and out
            _wait(verdict_ok, 10.0, "verdicts over the fresh rings")
            got = _shim_run(client, shim, CORPUS)
            assert_parity(got, oracle_ops(r2d2_policy(), CORPUS))
            assert client._shm.counters.data_frames > 0
        finally:
            svc2.stop()
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_overload_2x_capacity_zero_silent_loss_with_ring_fault(tmp_path):
    """The acceptance gate: a burst past 2× the admission cap over the
    shm transport, with a ring fault injected mid-burst.  EVERY seq is
    answered — real verdict, service-side typed SHED, or the client's
    demotion-synthesized SHED.  Zero silent loss, zero double replies."""
    svc = _service(
        tmp_path, "overload_shm",
        shed_queue_entries=8,
        shed_queue_age_ms=0.0,
        batch_timeout_ms=20.0,  # slow cadence: the queue really builds
    )
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    try:
        _, shim = _open_conn(client, 4700)
        _shim_run(client, shim, [b"HALT\r\n"])  # engine + shm warm

        answered: dict[int, int] = {}
        double = []
        done = threading.Event()
        N = 64  # 8× the 8-entry cap; 16 data slots → ring full too

        def cb(vb):
            if vb.seq in answered:
                double.append(vb.seq)
            answered[vb.seq] = int(vb.results[0]) if vb.count else -1
            if len(answered) >= N:
                done.set()

        client.verdict_callback = cb
        msg = b"READ /public/a.txt\r\n"

        def inject_fault() -> bool:
            """Tear the NEXT slot the producer claims and doorbell it
            (only once there is ring space — a full ring would route
            the frame to the socket and inject nothing)."""
            sess = client._shm
            if sess is None or not sess.active:
                return False
            with client._wlock:
                pos = sess.data.tail
                payload = wire.pack_data_batch(
                    3000, [shim.conn_id], [0], [len(msg)], msg
                )
                if not sess.data.try_push(
                    wire.MSG_DATA_BATCH, payload, sess.credit_head
                ):
                    return False  # ring full right now; retry
                sess.inflight[3000] = (
                    pos, np.array([shim.conn_id], np.uint64)
                )
                off = (
                    64 + (pos % sess.data.slots) * sess.data.slot_bytes
                )
                struct.pack_into("<Q", sess.data.seg.buf, off, 0)
                client._doorbell_send(sess, sess.data.tail)
            return True

        injected = False
        for k in range(N):
            client.send_batch(2000 + k, [shim.conn_id], [0], [len(msg)], msg)
            if not injected and k >= N // 2:
                injected = inject_fault()
        if not injected:
            # The burst kept the ring saturated: inject as it drains.
            _wait(inject_fault, 10.0, "ring space for fault injection")

        assert done.wait(30.0), (
            f"silent loss: {N - len(answered)} of {N} entries never "
            f"answered (got {len(answered)})"
        )
        assert not double, f"double replies for seqs {sorted(set(double))}"
        results = set(answered.values())
        assert results <= {
            int(FilterResult.OK),
            int(FilterResult.SHED),
        }, results
        # The fault really demoted the session (and the burst continued
        # on the socket rung afterwards).
        assert client.transport_mode == TRANSPORT_SOCKET
        assert client.transport_fallbacks.get(REASON_TORN_SLOT, 0) == 1
        # The torn frame itself was answered typed too.
        _wait(lambda: 3000 in answered, 5.0, "torn frame typed answer")
        assert answered[3000] == int(FilterResult.SHED)
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_graceful_detach_returns_to_socket(tmp_path):
    svc = _service(tmp_path, "detach")
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    try:
        _, shim = _open_conn(client, 4800)
        _shim_run(client, shim, [b"HALT\r\n"])
        assert client.transport_mode == TRANSPORT_SHM
        client.detach_shm()
        assert client.transport_mode == TRANSPORT_SOCKET
        _wait(
            lambda: svc.status()["transport"]["sessions"][0]["mode"]
            == TRANSPORT_SOCKET,
            5.0, "service side detach",
        )
        got = _shim_run(client, shim, CORPUS)
        assert_parity(got, oracle_ops(r2d2_policy(), CORPUS))
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_credit_piggybacked_verdict_polling(tmp_path):
    """ISSUE-10 satellite (ROADMAP item 3 remainder): verdicts already
    COMMITTED to the verdict ring are consumable without the credit
    socket frame — the next data push piggybacks a mirror drain, and
    poll_shm_verdicts() exposes the same sweep explicitly.  Proven by
    DROPPING the service's credit frames entirely: verdicts still
    arrive, through the mirror, with zero spinning (every drain rides
    an event the client performed anyway)."""
    svc = _service(tmp_path, "piggy")
    client = SidecarClient(svc.socket_path, timeout=30.0, **SHM_KW)
    got: dict = {}
    evt = threading.Event()
    try:
        assert client.transport_mode == TRANSPORT_SHM
        handler = svc._clients[0]
        assert handler.shm is not None

        def cb(vb):
            got[vb.seq] = vb
            evt.set()

        client.verdict_callback = cb
        # Kill the credit channel: verdict frames land in the ring but
        # the socket never tells the client.
        handler._send_credit_locked = lambda flags=0: None
        ids = np.array([990001], np.uint64)
        fl = np.zeros(1, np.uint8)
        lens = np.array([3], np.uint32)
        client.send_batch(1, ids, fl, lens, b"x\r\n")
        time.sleep(1.0)
        assert 1 not in got, "no credit frame should mean no delivery"
        # A second push piggybacks the drain — no explicit poll, no
        # credit frame, the verdict for seq 1 arrives anyway.
        deadline = time.monotonic() + 10
        seq = 2
        while 1 not in got and time.monotonic() < deadline and seq < 8:
            client.send_batch(seq, ids, fl, lens, b"x\r\n")
            seq += 1
            evt.wait(0.5)
            evt.clear()
        assert 1 in got, "push-time piggyback drain never delivered"
        assert got[1].entry(0)[1] == int(FilterResult.UNKNOWN_CONNECTION)
        # Explicit mirror polling drains the rest (bounded loop on the
        # WALL CLOCK, not on the mirror — R2.2 stays clean).
        deadline = time.monotonic() + 10
        while len(got) < seq - 1 and time.monotonic() < deadline:
            client.poll_shm_verdicts()
            time.sleep(0.05)
        assert len(got) == seq - 1, (sorted(got), seq)
        sess = client.transport_status()["session"]
        assert sess["mirror_drains"] > 0
        assert sess["mirror_frames"] == seq - 1, (
            "every verdict must have been consumed via the mirror"
        )
        assert client.transport_mode == TRANSPORT_SHM
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst.reset_module_registry()

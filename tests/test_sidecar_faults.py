"""Fault-containment tests for the sidecar verdict hot path.

The contract under test (ISSUE 2): bounded-latency degradation, never
availability loss.  A hung device call must quarantine the device while
verdicts continue through the bit-identical host/oracle fallback; a
crashed batch must produce typed per-entry errors; a burst past
capacity must shed with typed SHED verdicts; a dead service must fail
closed and reconnect — and across ALL of it, zero silently dropped or
hung ``on_io`` calls.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cilium_tpu.proxylib import FilterResult
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.sidecar import (
    BatchDispatcher,
    SidecarClient,
    SidecarUnavailable,
    VerdictService,
)
from cilium_tpu.utils.option import DaemonConfig

from test_sidecar import CORPUS, assert_parity, oracle_ops, r2d2_policy


# Every device launch of the service goes through its jit launcher
# (``VerdictService._jit_for``): the round paths, the engines' pump, the
# gather path and the quarantine re-probe alike.  A pipelined
# (two-frame) entry rides the engine slow path, whose launch runs on the
# dispatcher thread — the spot where a hung/crashed device manifests.
PIPELINED = b"READ /public/a.txt\r\nHALT\r\n"


class FaultLauncher:
    """Injectable faults on the service's device launches: ``stall``
    blocks every launch until cleared (a hung TPU / compile storm);
    ``crash`` raises (a poisoned engine); ``calls`` counts launches."""

    MAX_STALL_S = 30.0  # leak guard: a stuck thread frees itself in CI

    def __init__(self):
        self.stall = threading.Event()
        self.crash = threading.Event()
        self.calls = 0

    def wrap(self, fn):
        def launch(*args):
            self.calls += 1
            waited = 0.0
            while self.stall.is_set() and waited < self.MAX_STALL_S:
                time.sleep(0.01)
                waited += 0.01
            if self.crash.is_set():
                raise RuntimeError("injected device crash")
            return fn(*args)

        return launch


@pytest.fixture
def fault_device(monkeypatch):
    """Every executable the service's launcher hands out is gated by
    one FaultLauncher; the fixture hands the test that gate."""
    gate = FaultLauncher()
    jit_for = VerdictService._jit_for
    monkeypatch.setattr(
        VerdictService, "_jit_for",
        lambda self, *a, **kw: gate.wrap(jit_for(self, *a, **kw)),
    )
    yield gate
    # Never leave a thread parked on the gate (conftest leak guard).
    gate.stall.clear()
    gate.crash.clear()


def _service(tmp_path, name, **cfg_kw):
    inst.reset_module_registry()
    defaults = dict(
        batch_timeout_ms=2.0,
        batch_flows=256,
    )
    defaults.update(cfg_kw)
    cfg = DaemonConfig(**defaults)
    return VerdictService(str(tmp_path / f"{name}.sock"), cfg).start()


def _open_conn(client, conn_id, policies=None):
    mod = client.open_module([])
    assert client.policy_update(mod, policies or [r2d2_policy()]) == int(
        FilterResult.OK
    )
    res, shim = client.new_connection(
        mod, "r2d2", conn_id, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
        "sidecar-pol",
    )
    assert res == int(FilterResult.OK)
    return mod, shim


def _shim_run(client, shim, msgs):
    out = []
    for m in msgs:
        result, entries = client._on_data_rpc(shim.conn_id, False, False, m)
        ops, inj = [], b""
        for _, r, eops, _io, ir in entries:
            assert r == int(FilterResult.OK)
            ops.extend(eops)
            inj += ir
        out.append((ops, inj))
    return out


def _wait(predicate, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# --- hung device: quarantine + bit-identical fallback + heal ---------------

def test_hung_model_quarantine_fallback_and_heal(tmp_path, fault_device):
    """The acceptance scenario: with the model stalled, the service
    keeps rendering verdicts through the host fallback (bit-identical
    to the oracle on the same inputs), the stuck round is shed TYPED
    (no silent hang), and the engine un-quarantines after the stall
    clears."""
    svc = _service(
        tmp_path, "hung",
        device_call_timeout_s=0.4,
        device_reprobe_interval_s=0.05,
        shed_queue_age_ms=0.0,  # keep queued entries alive across the stall
    )
    client = SidecarClient(svc.socket_path, timeout=20.0)
    try:
        _, shim_a = _open_conn(client, 7001)
        res, shim_b = client.new_connection(
            1, "r2d2", 7002, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "sidecar-pol",
        )
        assert res == int(FilterResult.OK)
        assert fault_device.calls, "service launched no model"

        # Baseline: device path, parity with the oracle.
        assert_parity(
            _shim_run(client, shim_a, CORPUS), oracle_ops(r2d2_policy(), CORPUS)
        )

        # Stall the device.  The in-flight round is deposed by the
        # watchdog and answered with a typed SHED — never a hang.
        fault_device.stall.set()
        stalled_result = {}

        def stalled_request():
            t0 = time.monotonic()
            result, _ = client._on_data_rpc(
                shim_a.conn_id, False, False, PIPELINED
            )
            stalled_result["result"] = result
            stalled_result["elapsed"] = time.monotonic() - t0

        t = threading.Thread(target=stalled_request)
        t.start()
        _wait(lambda: svc.guard.quarantined, 5.0, "quarantine")
        t.join(timeout=10.0)
        assert not t.is_alive(), "stalled on_io call hung"
        assert stalled_result["result"] == int(FilterResult.SHED)
        assert stalled_result["elapsed"] < 5.0
        assert svc.dispatcher.stall_deposals >= 1

        # While quarantined: verdicts continue via the host fallback,
        # bit-identical to the oracle on the same inputs, and p99 stays
        # bounded (each call is a host parse, no device wait).
        t0 = time.monotonic()
        got = _shim_run(client, shim_b, CORPUS)
        per_call = (time.monotonic() - t0) / len(CORPUS)
        assert_parity(got, oracle_ops(r2d2_policy(), CORPUS))
        assert per_call < 1.0, f"fallback verdicts too slow: {per_call}s"
        st = svc.status()
        assert st["containment"]["quarantined"] is True
        assert st["containment"]["fallback_entries"] > 0
        assert st["containment"]["stalls"] >= 1

        # Stall clears -> traffic-driven re-probe heals automatically.
        fault_device.stall.clear()
        def poke_and_check():
            _shim_run(client, shim_b, [b"HALT\r\n"])
            return not svc.guard.quarantined
        _wait(poke_and_check, 15.0, "un-quarantine after stall cleared")

        # Healed: parity still holds and the device path resumes (the
        # demoted conn rebinds its engine; new traffic hits the model).
        calls_before = fault_device.calls
        assert_parity(
            _shim_run(client, shim_b, CORPUS), oracle_ops(r2d2_policy(), CORPUS)
        )
        _shim_run(client, shim_b, [PIPELINED])  # engine slow-path round
        _wait(
            lambda: fault_device.calls > calls_before, 5.0,
            "device path resumed after heal",
        )
        assert svc.status()["containment"]["quarantined"] is False
    finally:
        fault_device.stall.clear()
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- crashed batch: typed per-entry errors, poisoned-engine quarantine -----

def test_batch_crash_typed_errors_then_quarantine(tmp_path, fault_device):
    svc = _service(
        tmp_path, "crash",
        device_call_timeout_s=5.0,
        device_reprobe_interval_s=0.05,
        device_fail_threshold=3,
    )
    client = SidecarClient(svc.socket_path, timeout=10.0)
    try:
        _, shim = _open_conn(client, 7101)
        assert_parity(
            _shim_run(client, shim, CORPUS[:2]),
            oracle_ops(r2d2_policy(), CORPUS[:2]),
        )

        fault_device.crash.set()
        # Every crashed round answers EVERY entry with a typed error —
        # promptly, with no client hang.
        for _ in range(3):
            t0 = time.monotonic()
            result, entries = client._on_data_rpc(
                shim.conn_id, False, False, PIPELINED
            )
            assert result == int(FilterResult.UNKNOWN_ERROR)
            assert len(entries) == 1
            assert time.monotonic() - t0 < 5.0
        assert svc.batch_crashes >= 3

        # Three consecutive crashes = poisoned engine -> quarantined ->
        # verdicts come back OK through the host fallback, bit-identical.
        _wait(lambda: svc.guard.quarantined, 5.0, "poisoned-engine quarantine")
        got = _shim_run(client, shim, CORPUS)
        assert_parity(got, oracle_ops(r2d2_policy(), CORPUS))

        # Fix the model -> automatic re-probe heals.
        fault_device.crash.clear()
        def poke():
            _shim_run(client, shim, [b"HALT\r\n"])
            return not svc.guard.quarantined
        _wait(poke, 15.0, "heal after crash cleared")
        assert_parity(
            _shim_run(client, shim, CORPUS[:3]),
            oracle_ops(r2d2_policy(), CORPUS[:3]),
        )
    finally:
        fault_device.crash.clear()
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- overload: bounded queue, typed sheds, zero silent loss ----------------

def test_overload_shed_bounded_zero_silent_loss(tmp_path, fault_device):
    svc = _service(
        tmp_path, "overload",
        device_call_timeout_s=10.0,  # no deposal: pure queue pressure
        shed_queue_entries=8,
        shed_queue_age_ms=0.0,
    )
    client = SidecarClient(svc.socket_path, timeout=20.0)
    try:
        _, shim = _open_conn(client, 7201)
        _shim_run(client, shim, [b"HALT\r\n"])  # engine warm

        answered: dict[int, int] = {}
        done = threading.Event()
        N = 60

        def cb(vb):
            answered[vb.seq] = int(vb.results[0]) if vb.count else -1
            if len(answered) == N:
                done.set()

        client.verdict_callback = cb
        # Stall the worker (a pipelined round pins it inside the model
        # call) so the queue builds past the 8-entry cap, then release.
        # Every entry must be answered: OK or typed SHED.
        fault_device.stall.set()
        occupier = threading.Thread(
            target=lambda: client._on_data_rpc(
                shim.conn_id, False, False, PIPELINED
            )
        )
        occupier.start()
        time.sleep(0.1)  # the round is now in-process and stuck
        msg = b"READ /public/a.txt\r\n"
        for k in range(N):
            client.send_batch(
                1000 + k, [shim.conn_id], [0], [len(msg)], msg
            )
        time.sleep(0.3)
        fault_device.stall.clear()
        occupier.join(10.0)
        assert not occupier.is_alive()
        assert done.wait(15.0), (
            f"silent loss: {N - len(answered)} of {N} entries never "
            f"answered (got {len(answered)})"
        )
        results = set(answered.values())
        assert results <= {int(FilterResult.OK), int(FilterResult.SHED)}, results
        st = svc.status()
        assert st["containment"]["shed_entries"] > 0, "queue cap never shed"
        assert st["dispatcher"]["shed_submits"] > 0
    finally:
        fault_device.stall.clear()
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_wire_deadline_sheds_typed(tmp_path, fault_device):
    """A per-entry deadline propagated from on_io over the wire: queue
    time past the budget sheds with a typed SHED verdict."""
    svc = _service(
        tmp_path, "deadline",
        device_call_timeout_s=10.0,
        shed_queue_age_ms=0.0,
    )
    client = SidecarClient(svc.socket_path, timeout=20.0)
    try:
        _, shim_a = _open_conn(client, 7301)
        res, shim_b = client.new_connection(
            1, "r2d2", 7302, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "sidecar-pol",
        )
        assert res == int(FilterResult.OK)
        _shim_run(client, shim_a, [b"HALT\r\n"])  # engine warm

        fault_device.stall.set()
        results = {}

        def slow_req():  # occupies the worker for the stall duration
            r, _ = client._on_data_rpc(
                shim_a.conn_id, False, False, PIPELINED
            )
            results["a"] = r

        ta = threading.Thread(target=slow_req)
        ta.start()
        time.sleep(0.1)  # the round is now in-process and stuck
        # 30ms budget, queued behind a ~0.5s stall -> shed typed.
        res_b, _ = None, None
        def dl_req():
            r, _ = shim_b.client._on_data_rpc(
                shim_b.conn_id, False, False, b"HALT\r\n", deadline_ms=30.0
            )
            results["b"] = r

        tb = threading.Thread(target=dl_req)
        tb.start()
        time.sleep(0.4)
        fault_device.stall.clear()
        ta.join(10.0)
        tb.join(10.0)
        assert not ta.is_alive() and not tb.is_alive()
        assert results["a"] == int(FilterResult.OK)  # stall < watchdog
        assert results["b"] == int(FilterResult.SHED)
    finally:
        fault_device.stall.clear()
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- client: typed unavailability + auto-reconnect -------------------------

def test_control_rpc_unavailable_is_typed_and_prompt(tmp_path):
    svc = _service(tmp_path, "unavail")
    client = SidecarClient(svc.socket_path, timeout=10.0)
    try:
        client.open_module([])
        svc.stop()
        t0 = time.monotonic()
        with pytest.raises(SidecarUnavailable):
            client.status()
        # typed and immediate — not a 10s RPC-timeout hang
        assert time.monotonic() - t0 < 3.0
        t0 = time.monotonic()
        with pytest.raises(SidecarUnavailable):
            client._on_data_rpc(1, False, False, b"HALT\r\n")
        assert time.monotonic() - t0 < 3.0
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_client_reconnect_after_service_restart(tmp_path):
    svc = _service(tmp_path, "restart")
    path = svc.socket_path
    client = SidecarClient(path, timeout=8.0, auto_reconnect=True)
    try:
        _, shim = _open_conn(client, 7401)
        exp = oracle_ops(r2d2_policy(), [b"READ /public/a.txt\r\n"])
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert res == int(FilterResult.OK)
        assert out == b"READ /public/a.txt\r\n"

        svc.stop()
        # Down: fail-closed typed verdicts, returned promptly, no raise.
        t0 = time.monotonic()
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert res == int(FilterResult.SERVICE_UNAVAILABLE)
        assert out == b""  # nothing passes unverdicted
        assert time.monotonic() - t0 < 3.0

        # Service returns (fresh process: fresh module registry) -> the
        # client reconnects and REPLAYS modules, policies, conns.
        inst.reset_module_registry()
        svc2 = VerdictService(path, DaemonConfig(
            batch_timeout_ms=2.0, batch_flows=256,
        )).start()
        try:
            _wait(
                lambda: client.connected and client.reconnects >= 1,
                10.0, "client reconnect",
            )
            # Verdicts flow again on the SAME shim object, same parity.
            def verdict_ok():
                res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
                return res == int(FilterResult.OK) and out
            _wait(verdict_ok, 10.0, "verdicts after reconnect")
            got = _shim_run(client, shim, CORPUS)
            assert_parity(got, oracle_ops(r2d2_policy(), CORPUS))
        finally:
            svc2.stop()
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_reconnect_single_loop_when_replay_socket_dies(tmp_path):
    """A replay socket dying MID-replay (service restarting again) must
    not spawn a second reconnect loop: _resume re-arms the disconnect
    latch before replaying, so the dying reader's _on_disconnect fires
    while the first loop is still active — without loop ownership, two
    loops race over self.sock, the session replays twice, and the
    loser's socket is orphaned with a live reader."""
    import os
    import socket as socket_mod

    svc = _service(tmp_path, "oneloop")
    path = svc.socket_path
    client = SidecarClient(path, timeout=8.0, auto_reconnect=True)

    def loops():
        return [
            t for t in threading.enumerate()
            if t.name == "sidecar-reconnect" and t.is_alive()
        ]

    try:
        _open_conn(client, 7501)
        svc.stop()
        _wait(lambda: not client.connected, 5.0, "client down")

        # Flaky phase: a raw acceptor that kills every connection
        # immediately — each cycle gets _resume far enough to start a
        # reader whose prompt death runs _on_disconnect with the latch
        # re-armed (the double-spawn window).
        flaky = socket_mod.socket(
            socket_mod.AF_UNIX, socket_mod.SOCK_STREAM
        )
        flaky.bind(path)
        flaky.listen(8)
        flaky.settimeout(8.0)
        try:
            for _ in range(4):
                conn, _ = flaky.accept()
                conn.close()
        finally:
            flaky.close()
            try:
                os.unlink(path)
            except OSError:
                pass
        assert len(loops()) <= 1, [t.name for t in loops()]

        # Healthy service returns: the one loop replays exactly once,
        # verdicts flow, and the loop winds down.
        inst.reset_module_registry()
        svc2 = VerdictService(path, DaemonConfig(
            batch_timeout_ms=2.0, batch_flows=256,
        )).start()
        try:
            _wait(
                lambda: client.connected and client.reconnects >= 1,
                10.0, "client reconnect",
            )
            assert client.reconnects == 1
            _wait(lambda: not loops(), 5.0, "reconnect loop exit")
        finally:
            svc2.stop()
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- flow buffer caps: typed protocol-error DROP + close -------------------

def test_flow_buffer_cap_request_direction(tmp_path):
    svc = _service(tmp_path, "bufcap", max_flow_buffer=4096)
    client = SidecarClient(svc.socket_path, timeout=10.0)
    try:
        _, shim = _open_conn(client, 7501)
        # A stream with no frame delimiter grows the engine flow buffer
        # until the cap trips: typed protocol-error, buffer dropped.
        res = int(FilterResult.OK)
        chunk = b"A" * 1000
        for _ in range(6):
            res, _out = shim.on_io(False, chunk)
            if res != int(FilterResult.OK):
                break
        assert res == int(FilterResult.PARSER_ERROR)
        assert len(shim.dirs[False].buffer) == 0, "retained bytes leaked"
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_flow_buffer_cap_reply_direction_oracle(tmp_path):
    svc = _service(tmp_path, "bufcap2", max_flow_buffer=4096)
    client = SidecarClient(svc.socket_path, timeout=10.0)
    try:
        _, shim = _open_conn(client, 7502)
        res = int(FilterResult.OK)
        chunk = b"B" * 1000
        for _ in range(6):
            res, _out = shim.on_io(True, chunk)
            if res != int(FilterResult.OK):
                break
        assert res == int(FilterResult.PARSER_ERROR)
        assert len(shim.dirs[True].buffer) == 0
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- dispatcher: flush without busy-wait, idempotent stop ------------------

def test_dispatcher_flush_condition_based():
    seen = []
    release = threading.Event()

    def proc(items):
        release.wait(5.0)
        seen.extend(items)

    d = BatchDispatcher(proc, max_batch=1000, timeout_ms=0.0).start()
    try:
        for i in range(10):
            d.submit(i)
        # flush must block while a round is in process()...
        assert d.flush(timeout=0.2) is False
        release.set()
        # ...and return promptly once the work drains (no poll loop).
        assert d.flush(timeout=5.0) is True
        assert len(seen) == 10
    finally:
        d.stop()


def test_dispatcher_stop_idempotent():
    d = BatchDispatcher(lambda items: None)
    d.stop()  # before start: no RuntimeError
    d.stop()
    d2 = BatchDispatcher(lambda items: None).start()
    d2.stop()
    d2.stop()  # double stop after start


def test_dispatcher_admission_cap_refuses():
    gate = threading.Event()

    def proc(items):
        gate.wait(5.0)

    d = BatchDispatcher(proc, max_batch=1, timeout_ms=0.0, max_pending=4).start()
    try:
        d.submit("head")  # popped by the worker, blocks in proc
        time.sleep(0.1)
        accepted = [d.submit(i) for i in range(8)]
        assert not all(accepted), "cap never refused"
        assert d.submit("ctl", weight=0, force=True) is True  # never shed
        assert d.shed_submits > 0
    finally:
        gate.set()
        d.stop()


# --- CLI surface -----------------------------------------------------------

def test_cli_sidecar_status(tmp_path, capsys):
    from cilium_tpu.cli import main as cli_main

    svc = _service(tmp_path, "cli")
    client = SidecarClient(svc.socket_path, timeout=10.0)
    try:
        _, shim = _open_conn(client, 7601)
        _shim_run(client, shim, [b"HALT\r\n"])
        rc = cli_main(["sidecar", "status", "--address", svc.socket_path])
        assert rc == 0
        out = capsys.readouterr().out
        assert "containment:" in out and "queue:" in out
        rc = cli_main(
            ["sidecar", "status", "--address", svc.socket_path, "--json"]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert '"containment"' in out
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- review regressions: deposal vs cut-through / send pipeline ------------

def test_cut_through_survives_mid_round_deposal(tmp_path):
    """The stall watchdog can depose (swap _in_process_lock, bump the
    generation) WHILE a cut-through round holds the lock.  The finally
    must release the lock it acquired — releasing the swapped-in fresh
    lock instead raises RuntimeError out of submit_data (killing the
    shim connection) and leaks the old lock held forever."""
    svc = VerdictService(
        str(tmp_path / "ct.sock"),
        DaemonConfig(batch_timeout_ms=0.0),
    )
    disp = svc.dispatcher
    old_lock = disp._in_process_lock

    def deposing_process(items):  # what the watchdog does mid-round
        disp._gen += 1
        disp._in_process_lock = threading.Lock()

    svc._process = deposing_process
    item = ("data", None, object())
    assert svc._try_cut_through(item) is True  # no RuntimeError escapes
    # The lock cut-through held was released (not leaked held)...
    assert old_lock.acquire(blocking=False)
    old_lock.release()
    # ...and the replacement generation's lock was never touched.
    assert disp._in_process_lock.acquire(blocking=False)
    disp._in_process_lock.release()


def test_send_loop_suppresses_only_shed_rounds(tmp_path):
    """Vec/ready groups a stuck round already queued to the send
    pipeline are emitted by the send thread, not the stuck worker —
    the send loop must adopt each record's ROUND id so exactly the
    shed round's sends are suppressed (its batch already got typed
    SHED verdicts), while a deposed worker's EARLIER completed rounds
    still in the pipeline are emitted — never silently lost."""
    svc = VerdictService(
        str(tmp_path / "sl.sock"),
        DaemonConfig(batch_timeout_ms=2.0),
    )

    import socket

    from cilium_tpu.sidecar.service import _ClientHandler

    a_sock, b_sock = socket.socketpair()

    class _Probe(_ClientHandler):
        def __init__(self):
            super().__init__(svc, a_sock)
            self.calls = []

        def send_verdicts(self, seq, entries, batch=None):
            self.calls.append(
                (seq, svc.dispatcher.thread_round_is_shed())
            )
            return super().send_verdicts(seq, entries, batch=batch)

    class _Batch:
        def __init__(self, seq):
            self.seq = seq
            self.answered = False

    probe = _Probe()
    batches = [_Batch(1), _Batch(2), _Batch(3)]
    t = threading.Thread(target=svc._send_loop, daemon=True)
    t.start()
    # Watchdog deposed the worker mid-round 7; rounds 6 (completed
    # earlier, records still queued) and 8 (replacement worker) were
    # never shed.
    svc.dispatcher._shed_rounds.add(7)
    svc._sends.put(([(6, ("ready", probe, batches[0], [], None))], None, 0))
    svc._sends.put(([(7, ("ready", probe, batches[1], [], None))], None, 0))
    svc._sends.put(([(8, ("ready", probe, batches[2], [], None))], None, 0))
    svc._sends.put(None)
    t.join(5)
    a_sock.close()
    b_sock.close()
    assert not t.is_alive()
    assert probe.calls == [(1, False), (2, True), (3, False)]
    # The shed round's batch stays unanswered (its typed SHED reply was
    # the answer); the emitted rounds' batches are marked answered so a
    # later deposal can never double-reply their seqs.
    assert [b.answered for b in batches] == [True, False, True]


def test_crash_containment_skips_answered_items(tmp_path):
    """A greedy multi-group round can serve one group's real verdicts
    inline, then crash in a later group: _on_batch_error must answer
    only the still-unanswered items — a second reply for a seq the
    shim already consumed would desync it."""
    svc = VerdictService(
        str(tmp_path / "cc.sock"),
        DaemonConfig(batch_timeout_ms=2.0),
    )

    class _Probe:
        def __init__(self):
            self.calls = []

        def send_verdicts(self, seq, entries, batch=None):
            self.calls.append((seq, [r for _, r, *_ in entries]))
            if batch is not None:
                batch.answered = True
            return True

    class _Batch:
        def __init__(self, seq):
            self.seq = seq
            self.count = 1
            self.conn_ids = np.array([5], "<u8")
            self.answered = False

    probe = _Probe()
    served, unserved = _Batch(1), _Batch(2)
    served.answered = True  # its real verdicts already went out
    svc._on_batch_error(
        [("data", probe, served), ("data", probe, unserved)],
        RuntimeError("boom"),
    )
    assert [seq for seq, _ in probe.calls] == [2]
    assert probe.calls[0][1] == [int(FilterResult.UNKNOWN_ERROR)]
    assert unserved.answered


def test_demoted_matrix_shares_answered_state():
    """A demoted mat item is served via its DataBatch conversion while
    the dispatcher's _current_batch (what a deposal/crash sweep
    iterates) still holds the ORIGINAL MatrixBatch — the two must
    share ONE answered flag, or the sweep sends a typed SHED/error for
    a seq the round already served (shim desync)."""
    from cilium_tpu.sidecar import wire
    from cilium_tpu.sidecar.service import _matrix_to_batch

    mb = wire.MatrixBatch(
        seq=9,
        width=16,
        conn_ids=np.array([1, 2], "<u8"),
        lengths=np.array([4, 4], "<u4"),
        rows=np.zeros((2, 16), np.uint8),
    )
    batch = _matrix_to_batch(mb)
    assert not mb.answered
    batch.answered = True  # real verdicts served via the conversion
    assert mb.answered  # the sweep must stand down


def test_send_marks_answered_under_write_lock(tmp_path):
    """The real-verdict send paths mark their wire batches answered
    under the client write lock BEFORE the write: a fail-closed
    replier racing an in-flight sendall for the same seq — the wedged
    send that trips the stall watchdog — finds the batch already
    answered and stands down.  Conversely, a frame whose batch a
    fail-closed reply already answered is dropped under the same lock,
    never written."""
    import socket

    from cilium_tpu.sidecar import wire
    from cilium_tpu.sidecar.service import _ClientHandler

    svc = VerdictService(
        str(tmp_path / "wl.sock"),
        DaemonConfig(batch_timeout_ms=2.0),
    )
    a_sock, b_sock = socket.socketpair()
    try:
        handler = _ClientHandler(svc, a_sock)

        class _Batch:
            answered = False

        fresh, shed = _Batch(), _Batch()
        shed.answered = True  # a SHED reply already answered this seq
        assert handler.send_frames(
            wire.MSG_VERDICT_BATCH, [b"fresh", b"stale"],
            batches=[fresh, shed],
        )
        assert fresh.answered
        # Only the fresh frame reached the wire.
        b_sock.settimeout(2.0)
        reader = wire.BufferedReader(b_sock)
        _, payload = reader.recv_msg()
        assert payload == b"fresh"
        assert not reader.pending
        # send() with ANY covered batch answered stands the whole
        # payload down (a packed multi-seq payload cannot be split) and
        # leaves the unanswered sibling unmarked — the deposal sweep
        # still owes it a typed reply; marking it here would make the
        # sweep skip it (silent loss).  The stand-down returns False
        # (this call answered nothing) so fail-closed repliers don't
        # count a shed/error for an entry that was actually served.
        fresh2 = _Batch()
        assert not handler.send(
            wire.MSG_VERDICT_BATCH, b"dup", batches=[fresh2, shed]
        )
        assert not fresh2.answered
        b_sock.setblocking(False)
        with pytest.raises(BlockingIOError):
            b_sock.recv(64)
        # A write to a dead peer must not raise out of the send path
        # (the handler tears its own socket down instead).
        b_sock.close()
        assert handler.send(
            wire.MSG_VERDICT_BATCH, b"gone", batches=[_Batch()]
        )
    finally:
        a_sock.close()
        try:
            b_sock.close()
        except OSError:
            pass


def test_cut_through_stall_on_idle_service_is_shed(tmp_path, fault_device):
    """Greedy mode, idle service: the round runs inline on the shim
    reader thread (cut-through), where a hung device call used to be
    invisible to the stall watchdog (_busy never set — no deposal, no
    quarantine, a wedged reader, and a client waiting forever).  The
    cut-through round must arm the watchdog: the stuck round is shed
    with typed SHED verdicts within the deadline and the device is
    quarantined."""
    svc = _service(
        tmp_path, "ctstall",
        batch_timeout_ms=0.0,
        device_call_timeout_s=0.5,
        device_reprobe_interval_s=30.0,  # no heal during the test
    )
    client = SidecarClient(svc.socket_path, timeout=10.0)
    try:
        _, shim = _open_conn(client, 7701)
        fault_device.stall.set()
        t0 = time.monotonic()
        result, entries = client._on_data_rpc(
            shim.conn_id, False, False, b"HALT\r\n"
        )
        elapsed = time.monotonic() - t0
        assert entries, "no reply for the stalled cut-through round"
        assert all(
            r == int(FilterResult.SHED) for _, r, *_ in entries
        ), entries
        assert elapsed < 5.0  # bounded by the watchdog, not the stall
        assert svc.guard.quarantined
        assert svc.dispatcher.stall_deposals >= 1
    finally:
        fault_device.stall.clear()
        client.close()
        # Wait for the unstuck reader thread to drain out of the
        # service (it prunes itself from _clients on exit): a daemon
        # thread dying inside an XLA call at interpreter teardown
        # aborts the process ("terminate called without an active
        # exception").
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with svc._lock:
                if not svc._clients:
                    break
            time.sleep(0.02)
        svc.stop()
        inst.reset_module_registry()


def test_guard_streak_is_consecutive_rounds():
    """Alternating crashed/clean rounds must never reach the
    fail_threshold: a crashed round's taint (it never records ok) is
    round-local and must not swallow the NEXT clean round's reset."""
    from cilium_tpu.sidecar import DeviceGuard

    g = DeviceGuard(fail_threshold=3)
    for _ in range(5):
        g.round_start()
        g.record_failure("crash")  # round crashed: no record_ok
        g.round_start()
        g.record_ok()  # genuinely clean round resets the streak
    assert not g.quarantined
    # Contained in-round failures still count as a streak: the round
    # completes (record_ok fires) but its taint holds the counter.
    g2 = DeviceGuard(fail_threshold=3)
    for _ in range(3):
        g2.round_start()
        g2.record_failure("contained")
        g2.record_ok()
    assert g2.quarantined


def test_zombie_round_guard_calls_are_suppressed(tmp_path):
    """A deposed (shed) round that unsticks must not touch the guard's
    streak bookkeeping: its late record_ok would reset a genuine crash
    streak the replacement worker is accumulating (or consume a live
    round's taint), and a crash on the way out must not taint the live
    rounds — deposal already booked the stall."""
    svc = VerdictService(
        str(tmp_path / "zg.sock"),
        DaemonConfig(batch_timeout_ms=2.0),
    )
    cur = threading.current_thread()
    try:
        svc.guard._crash_streak = 2
        svc.dispatcher._shed_rounds.add(99)
        cur._disp_round = 99  # this thread carries the shed round
        svc._process([])  # empty round: reaches the record_ok epilogue
        assert svc.guard._crash_streak == 2  # not reset by the zombie
        svc._on_batch_error([], RuntimeError("zombie crash"))
        assert svc.guard._crash_streak == 2  # not tainted either
        cur._disp_round = None  # a LIVE round's epilogue does reset
        svc._process([])
        assert svc.guard._crash_streak == 0
    finally:
        cur._disp_round = None


def test_engine_overflow_drops_only_overflowing_direction():
    """The retained-bytes cap must not clear the OPPOSITE direction's
    buffer: those bytes are still mirrored by the shim, and vanishing
    them with no covering op desyncs the mirror."""
    from cilium_tpu.proxylib.types import DROP, ERROR
    from cilium_tpu.runtime.l7engine import DeviceAssistedEngine

    class _MiniEngine(DeviceAssistedEngine):
        proto = "mini"

        def _make_parser(self, conn):
            return None

    eng = _MiniEngine(None, True, 80, None, max_buffer=64)
    eng.feed(1, b"x" * 40, reply=False)  # request-direction retained
    eng.feed(1, b"y" * 40, reply=True)  # 40 + 40 > 64: reply overflows
    st = eng.flows[1]
    assert st.overflowed
    # The DROP covers exactly the reply direction's cleared bytes...
    assert st.ops[True][0] == (DROP, 40)
    assert st.ops[True][1][0] == ERROR
    # ...and the request direction's retained bytes stay accounted.
    assert bytes(st.bufs[False]) == b"x" * 40
    assert not st.ops[False]


def test_worker_waits_out_inline_round():
    """A submit landing while a cut-through inline round is in flight
    must NOT be popped until that round closes: _pop_locked would
    overwrite the watchdog's round state (_round_start, round_seq,
    _current_batch) with the merely lock-blocked pop's, leaving the
    genuinely stuck inline item invisible to deposal."""
    processed = []
    disp = BatchDispatcher(
        lambda b: processed.append(list(b)), timeout_ms=0.0,
        name="t-inline-wait",
    ).start()
    armed = threading.Event()
    release = threading.Event()
    rid_box = {}

    def reader():  # a shim reader mid-cut-through, "hung" in the device
        lock = disp._in_process_lock
        with lock:
            rid_box["rid"] = disp.begin_inline_round(["inline-item"])
            armed.set()
            release.wait(10)
        disp.end_inline_round(rid_box["rid"])

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    assert armed.wait(5) and rid_box["rid"] is not None
    disp.submit("queued-behind")
    time.sleep(0.15)  # window for a (buggy) worker pop to clobber
    assert disp.round_seq == rid_box["rid"]
    assert disp._current_batch == ["inline-item"]
    assert processed == []
    release.set()
    t.join(5)
    assert disp.flush(5)
    assert processed == [["queued-behind"]]
    disp.stop()


def test_watchdog_sheds_stuck_inline_round_under_load():
    """The loaded variant of the cut-through stall: with traffic queued
    behind a stuck inline round, the watchdog must shed the INLINE
    round (the one actually holding the device), not the lock-blocked
    pop — and the queued work must then be served by the replacement
    generation."""
    shed, processed = [], []
    disp = BatchDispatcher(
        lambda b: processed.append(list(b)), timeout_ms=0.0,
        stall_timeout_s=0.3, on_stall=lambda b: shed.append(list(b)),
        name="t-ct-load",
    ).start()
    armed = threading.Event()
    release = threading.Event()
    rid_box = {}

    def reader():
        lock = disp._in_process_lock
        with lock:
            rid_box["rid"] = disp.begin_inline_round(["stuck-inline"])
            armed.set()
            release.wait(10)
        disp.end_inline_round(rid_box["rid"])

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    assert armed.wait(5) and rid_box["rid"] is not None
    disp.submit("queued-behind")
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and not shed:
        time.sleep(0.02)
    assert shed == [["stuck-inline"]]
    assert rid_box["rid"] in disp._shed_rounds
    assert disp.flush(5)
    assert processed == [["queued-behind"]]
    release.set()
    t.join(5)
    disp.stop()


def test_cut_through_releases_lock_before_round_close(tmp_path):
    """_try_cut_through must mirror _run's ordering — release the
    in-process lock BEFORE clearing _busy: the watchdog reads a free
    lock as 'process() returned, verdicts sent' and skips deposal, so
    the inverse ordering leaves a busy+locked window in which a round
    completing just past the deadline is deposed and double-replied."""
    svc = VerdictService(
        str(tmp_path / "ord.sock"),
        DaemonConfig(batch_timeout_ms=0.0),
    )
    disp = svc.dispatcher
    svc._process = lambda items: None
    seen = {}
    orig = disp.end_inline_round

    def probing_end(rid):
        lk = disp._in_process_lock
        free = lk.acquire(blocking=False)
        if free:
            lk.release()
        seen["lock_free_at_close"] = free
        orig(rid)

    disp.end_inline_round = probing_end
    assert svc._try_cut_through(("data", None, object())) is True
    assert seen["lock_free_at_close"] is True


def test_guard_deferred_failures_hold_streak_across_rounds():
    """A deferred completion crashing on the send loop lands OUTSIDE
    any dispatcher round — round_start must not erase that taint, and
    record_ok must consume it without resetting, so an engine whose
    every deferred round crashes still reaches fail_threshold."""
    from cilium_tpu.sidecar import DeviceGuard

    g = DeviceGuard(fail_threshold=3)
    for _ in range(3):
        g.round_start()
        g.record_ok()  # the round's sync part is clean
        # ...its deferred completion crashes later, in the gap.
        g.deferred_scope(g.record_failure, "pump-crash")
    assert g.quarantined
    # Round-local semantics are unchanged: alternating sync crash /
    # clean rounds still reset (the original review's contract).
    g2 = DeviceGuard(fail_threshold=3)
    for _ in range(5):
        g2.round_start()
        g2.record_failure("crash")
        g2.round_start()
        g2.record_ok()
    assert not g2.quarantined


# --- latency decomposition across the degradation ladder -------------------

def test_stage_histograms_follow_degradation_ladder(tmp_path, fault_device):
    """PR 4 acceptance: stage histograms and trace exemplars carry the
    correct serving-path label at every rung of the PR 2 ladder —
    vec (device vectorized) → oracle (entrywise slow path) →
    shed (typed SHED under a wire deadline) → host (quarantine
    fallback)."""
    from cilium_tpu.utils import metrics as m

    svc = _service(
        tmp_path, "ladder",
        device_call_timeout_s=10.0,  # no deposal: the stall is brief
        shed_queue_age_ms=0.0,
        trace_slow_ms=0.0,  # every answered batch leaves an exemplar
        trace_sample_every=0,
    )
    client = SidecarClient(svc.socket_path, timeout=60.0)
    paths = ("vec", "oracle", "host", "shed")

    def e2e_counts():
        return {p: m.VerdictE2ESeconds.get_count(p) for p in paths}

    def stage_counts(stage):
        return {p: m.VerdictStageSeconds.get_count(stage, p)
                for p in paths}

    try:
        _, shim = _open_conn(client, 9301)
        base = e2e_counts()
        base_q = stage_counts("queue")

        # Rung 1 — vec: a single complete frame rides the vectorized
        # device path.
        _shim_run(client, shim, [b"READ /public/ladder.txt\r\n"])
        _wait(lambda: e2e_counts()["vec"] > base["vec"], 10,
              "vec e2e histogram")
        _wait(lambda: stage_counts("device")["vec"] > 0, 10,
              "vec device stage")

        # Rung 2 — oracle: a pipelined (two-frame) entry takes the
        # entrywise slow path, no quarantine.
        _shim_run(client, shim, [PIPELINED])
        _wait(lambda: e2e_counts()["oracle"] > base["oracle"], 10,
              "oracle e2e histogram")

        # Rung 3 — shed: a deadline-stamped entry queued behind a
        # stalled round sheds typed, labeled shed.
        fault_device.stall.set()
        results = {}

        def slow_req():
            r, _ = client._on_data_rpc(
                shim.conn_id, False, False, PIPELINED
            )
            results["slow"] = r

        t = threading.Thread(target=slow_req)
        t.start()
        time.sleep(0.1)  # the stalled round is now in-process
        res, shim_b = client.new_connection(
            1, "r2d2", 9302, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "sidecar-pol",
        )
        assert res == int(FilterResult.OK)

        def dl_req():
            r, _ = client._on_data_rpc(
                shim_b.conn_id, False, False, b"HALT\r\n",
                deadline_ms=30.0,
            )
            results["dl"] = r

        tb = threading.Thread(target=dl_req)
        tb.start()
        time.sleep(0.4)

        fault_device.stall.clear()
        t.join(10.0)
        tb.join(10.0)
        assert not t.is_alive() and not tb.is_alive()
        assert results["dl"] == int(FilterResult.SHED)
        _wait(lambda: e2e_counts()["shed"] > base["shed"], 10,
              "shed e2e histogram")

        # Rung 4 — host: quarantine (as a real stall would) with the
        # model re-wedged so traffic-driven probes hang and the
        # quarantine HOLDS; the fallback serves bit-identically and
        # its rounds are labeled host.
        fault_device.stall.set()
        svc.guard.record_stall("ladder-stall")
        assert svc.guard.quarantined
        _shim_run(client, shim, [b"READ /public/fallback.txt\r\n"])
        _wait(lambda: e2e_counts()["host"] > base["host"], 10,
              "host e2e histogram")
        fault_device.stall.clear()

        # Every rung also observed its queue stage...
        after_q = stage_counts("queue")
        for p in paths:
            assert after_q[p] > base_q[p], f"no queue stage for {p}"
        # ...and left a correctly-labeled exemplar in the trace ring
        # (slow threshold 0: every answered batch; shed spans carry
        # their reason).
        spans = svc.tracer.spans(10_000)
        seen = {s["path"] for s in spans}
        assert seen >= set(paths), f"missing exemplar paths: {seen}"
        shed_spans = [s for s in spans if s["path"] == "shed"]
        assert shed_spans and shed_spans[0]["kind"] == "shed"
        assert shed_spans[0]["reason"] == "deadline"
        assert all(
            s["stages_us"].get("queue") is not None for s in spans
        )
        # Status surfaces the same decomposition per path.
        lat = svc.status()["latency"]
        assert set(lat["stages"]) >= set(paths)
        assert lat["slow_exemplars"] > 0
    finally:
        fault_device.stall.clear()
        client.close()
        svc.stop()
        inst.reset_module_registry()

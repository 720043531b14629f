"""Hitless sidecar restart (ISSUE 16): state handoff, shim-side
survival window, and crash recovery.

The contract under test:

- **Graceful handoff**: a successor on the same socket path pulls the
  predecessor's snapshot (sessions, conns, grants, residue, policy
  epoch, rule sources, quarantine latch, warm shapes) over the side
  channel, fences the predecessor, and serves warm — no cold
  recompile, restored restart generation, counters carried.
- **Generation fencing**: the fenced zombie answers every late write
  TYPED — policy updates and new conns FENCED, data frames SHED —
  never silently; stale and duplicate surrender claims are refused.
- **Shim survival window**: with ``restart_grace_s`` armed, shim-local
  grants outlive the socket for the grace budget (served + counted),
  non-granted frames come back typed RESTARTING, held async rounds
  resend under their ORIGINAL seq after the replay, and expiry sheds
  everything typed.
- **Cross-restart exactly-once**: every seq in flight at death is
  answered exactly once — by the old process, the new process, or a
  typed local shed; the client's double-reply tripwire stays at zero
  through kill -9.
"""

from __future__ import annotations

import os
import subprocess
import sys
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
from cilium_tpu.sidecar import SidecarClient, VerdictService
from cilium_tpu.sidecar import wire
from cilium_tpu.sidecar.guard import DeviceGuard
from cilium_tpu.sidecar.shm import sweep_stale_segments
from cilium_tpu.utils.option import DaemonConfig

OK = int(FilterResult.OK)
SHED = int(FilterResult.SHED)
FENCED = int(FilterResult.FENCED)
RESTARTING = int(FilterResult.RESTARTING)
UNAVAILABLE = int(FilterResult.SERVICE_UNAVAILABLE)


def _policy(name="restart-pol", gen=0):
    """Remote 1: byte-free row (invariant allow — grantable).
    Remote 2: byte-gated rows (never granted).  ``gen`` varies the
    byte-gated regex so policy churn rebuilds real tables."""
    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=[1], l7_proto="r2d2",
                        l7_rules=[{}],
                    ),
                    PortNetworkPolicyRule(
                        remote_policies=[2], l7_proto="r2d2",
                        l7_rules=[
                            {"cmd": "READ", "file": f"/public/g{gen}/.*"},
                            {"cmd": "HALT"},
                        ],
                    ),
                ],
            )
        ],
    )


def _cfg(**kw):
    defaults = dict(
        batch_timeout_ms=0.0, batch_flows=64, batch_width=64,
        flow_cache=True,
    )
    defaults.update(kw)
    return DaemonConfig(**defaults)


def _service(path, **cfg_kw):
    return VerdictService(path, _cfg(**cfg_kw)).start()


def _client(path, **kw):
    defaults = dict(
        timeout=60.0, flow_cache=True, auto_reconnect=True,
        restart_grace_s=30.0, restart_queue_frames=32,
    )
    defaults.update(kw)
    return SidecarClient(path, **defaults)


def _conn(client, mod, conn_id, remote=1, policy="restart-pol"):
    res, shim = client.new_connection(
        mod, "r2d2", conn_id, True, remote, 2, "1.1.1.1:1",
        "2.2.2.2:80", policy,
    )
    assert res == OK, res
    return shim


def _wait(pred, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _warm_grant(client, shim, tries=100):
    """Run granted-flow ops until the shim-local grant serves one."""
    for _ in range(tries):
        res, _ = shim.on_io(False, b"READ /anything\r\n")
        assert res == OK, res
        if client._grant_valid(shim.conn_id):
            return
        time.sleep(0.05)
    raise AssertionError("grant never armed shim-side")


GEN0_READ = b"READ /public/g0/a.txt\r\n"


# --- graceful handoff ------------------------------------------------------

def test_graceful_handoff_restores_state(tmp_path):
    """The acceptance scenario: successor pulls the snapshot, serves
    warm (restored sessions/conns/grants, adopted shape ledger, epoch
    continuity), the shims fail over and traffic never loses a
    frame."""
    inst.reset_module_registry()
    path = str(tmp_path / "handoff.sock")
    svc = _service(path)
    client = _client(path, identity="pod-handoff")
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        granted = _conn(client, mod, 1, remote=1)
        plain = _conn(client, mod, 2, remote=2)
        _warm_grant(client, granted)
        res, _ = plain.on_io(False, GEN0_READ)
        assert res == OK
        # Partial frame: residue the snapshot must carry.
        res, _ = plain.on_io(False, b"READ /public/g0/par")
        assert res == OK
        epoch_before = svc.policy_epoch
        assert epoch_before >= 1

        successor = VerdictService(path, _cfg()).start()
        st = successor.status()["restart"]
        assert st["generation"] == 2
        assert st["handoff_age_s"] is not None
        svc.stop()  # zombie teardown pops the shims onto the successor
        _wait(lambda: client._alive, 30.0, "client failover")

        # Replay revalidated the handed-off rows.  _alive flips at the
        # START of the replay (hello first, conn re-registration last,
        # behind the policy replay) — wait for the final counter, not
        # a snapshot racing the replay's tail.
        _wait(
            lambda: successor.status()["restart"]["conn_restores"] >= 2,
            15.0, "conn restores",
        )
        st = successor.status()["restart"]
        assert st["session_restores"] >= 1, st
        assert st["grant_restores"] >= 1, st
        # The plain conn's partial frame rode the snapshot and the
        # shim claimed RETAINED: the successor adopted it.
        assert st["residue_restores"] >= 1, st
        # No cold recompile: the predecessor's shape ledger was adopted.
        assert st["warm_shapes"] >= 1, st
        assert st["fenced"] is False
        # Epoch continuity: restored epoch, then the replay's
        # policy_update committed on top of it — never backwards.
        assert successor.policy_epoch >= epoch_before

        # Traffic serves on both flow classes; the residue conn's
        # stream completes from the retained partial frame — the
        # passed output is the WHOLE reassembled frame (the shim kept
        # its retained bytes because the successor adopted the
        # mirror).  Hitless, mid-frame, across the restart.
        _wait(lambda: client.reconnects >= 1, 15.0, "replay completion")
        assert plain.mirror_ok is True
        res, out = plain.on_io(False, b"tial.txt\r\n")
        assert res == OK
        assert out == b"READ /public/g0/partial.txt\r\n"
        res, _ = granted.on_io(False, b"READ /anything\r\n")
        assert res == OK
        assert client.double_replies == 0
        assert client.misrouted_verdicts == 0
    finally:
        client.close()
        svc.stop()
        successor = locals().get("successor")
        if successor is not None:
            successor.stop()
        inst.reset_module_registry()


def test_fenced_predecessor_rejects_late_writes_typed(tmp_path):
    """After surrender the predecessor is a zombie: policy updates and
    new conns come back FENCED, data frames SHED — typed, never
    silent — and surrender itself refuses stale/duplicate claims."""
    inst.reset_module_registry()
    path = str(tmp_path / "fence.sock")
    svc = _service(path)
    # No auto-reconnect: this client must STAY on the zombie.
    client = _client(path, auto_reconnect=False, restart_grace_s=0.0)
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        shim = _conn(client, mod, 1, remote=2)
        res, _ = shim.on_io(False, b"HALT\r\n")
        assert res == OK

        successor = VerdictService(path, _cfg()).start()
        assert svc.status()["restart"]["fenced"] is True

        # Late writes on the still-open zombie session: all typed.
        assert client.policy_update(mod, [_policy(gen=1)]) == FENCED
        res, conn2 = client.new_connection(
            mod, "r2d2", 99, True, 2, 2, "1.1.1.1:1", "2.2.2.2:80",
            "restart-pol",
        )
        assert res == FENCED and conn2 is None
        res, _ = shim.on_io(False, b"HALT\r\n")
        assert res == SHED
        st = svc.status()["restart"]
        assert st["fence_rejects"] >= 3, st

        # Duplicate surrender claim: refused typed, not re-fenced.
        snap, err = svc.handoff_surrender(99, 1.0)
        assert snap is None and "already fenced" in err
        assert svc.handoff_refused.get("already-fenced", 0) == 1
        # Stale claim against the live successor (generation 2): a
        # claimant at or below it is refused and the successor is NOT
        # fenced (PR 1 fencing semantics).
        snap, err = successor.handoff_surrender(2, 1.0)
        assert snap is None and "stale" in err
        assert successor.handoff_refused.get("stale-generation", 0) == 1
        assert successor.status()["restart"]["fenced"] is False
    finally:
        client.close()
        svc.stop()
        successor = locals().get("successor")
        if successor is not None:
            successor.stop()
        inst.reset_module_registry()


def test_snapshot_roundtrip_and_refusals(tmp_path):
    """snapshot_handoff -> restore_handoff round-trip carries every
    table; malformed / future-version / wrong-path snapshots are
    refused whole with typed counters (cold boot serves correctly)."""
    inst.reset_module_registry()
    path = str(tmp_path / "snap.sock")
    svc = _service(path)
    client = _client(path, identity="pod-snap")
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        granted = _conn(client, mod, 1, remote=1)
        plain = _conn(client, mod, 2, remote=2)
        _warm_grant(client, granted)
        res, _ = plain.on_io(False, b"READ /public/g0/par")  # residue
        assert res == OK

        snap = svc.snapshot_handoff()
        assert snap["version"] == wire.HANDOFF_VERSION
        assert snap["generation"] == 1
        assert snap["policy_epoch"] == svc.policy_epoch
        assert {c["conn_id"] for c in snap["conns"]} == {1, 2}
        assert [g["conn_id"] for g in snap["grants"]] == [1]
        assert [r["conn_id"] for r in snap["residue"]] == [2]
        assert any(r["policy"] == "restart-pol" for r in snap["rules"])
        assert snap["sessions"][0]["identity"] == "pod-snap"

        fresh = VerdictService(path, _cfg())  # never started: no bind
        assert fresh.restore_handoff(snap) is True
        assert fresh.restart_generation == 2
        assert fresh.policy_epoch == snap["policy_epoch"]
        assert set(fresh._handoff_conns) == {1, 2}
        assert set(fresh._handoff_grants) == {1}
        assert set(fresh._handoff_residue) == {2}

        refuser = VerdictService(path, _cfg())
        bad_version = dict(snap, version=wire.HANDOFF_VERSION + 1)
        assert refuser.restore_handoff(bad_version) is False
        bad_path = dict(snap, socket_path="/nope.sock")
        assert refuser.restore_handoff(bad_path) is False
        malformed = {k: v for k, v in snap.items() if k != "generation"}
        assert refuser.restore_handoff(malformed) is False
        assert refuser.handoff_refused == {
            "version": 1, "path-mismatch": 1, "malformed": 1,
        }
        assert refuser.restart_generation == 1  # untouched by refusals
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- shim survival window --------------------------------------------------

def test_survival_window_serves_granted_flows(tmp_path):
    """Service gone, nobody listening: granted flows keep serving from
    the shim-local table (counted), non-granted frames come back typed
    RESTARTING, and a successor closes the window via replay."""
    inst.reset_module_registry()
    path = str(tmp_path / "window.sock")
    svc = _service(path)
    client = _client(path)
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        granted = _conn(client, mod, 1, remote=1)
        plain = _conn(client, mod, 2, remote=2)
        _warm_grant(client, granted)

        svc.stop()
        _wait(lambda: not client._alive, 10.0, "disconnect latch")
        hits = []
        for _ in range(3):
            res, out = granted.on_io(False, b"READ /through\r\n")
            assert res == OK
            assert out.endswith(b"READ /through\r\n")
            hits.append(client.survival_hits)
        assert hits == sorted(hits) and hits[0] >= 1, hits
        res, _ = plain.on_io(False, b"HALT\r\n")
        assert res == RESTARTING
        st = client.transport_status()["restart"]
        assert st["window_open"] is True
        assert st["windows"] == 1
        assert st["survival_hits"] == hits[-1]

        successor = VerdictService(path, _cfg()).start()
        # The window closes when the REPLAY completes (reconnects
        # bumps last) — _alive flips at the start of an attempt, and
        # a transiently failed attempt retries with the window still
        # open.
        _wait(lambda: client.reconnects >= 1, 30.0, "replay completion")
        assert client.transport_status()["restart"]["window_open"] is False
        res, _ = plain.on_io(False, b"HALT\r\n")
        assert res == OK
        res, _ = granted.on_io(False, b"READ /after\r\n")
        assert res == OK
        assert client.double_replies == 0
    finally:
        client.close()
        svc.stop()
        successor = locals().get("successor")
        if successor is not None:
            successor.stop()
        inst.reset_module_registry()


def test_survival_window_expiry_sheds_typed(tmp_path):
    """Past restart_grace_s the window closes LAZILY on the next
    check: grants reset (fail closed), held async rounds shed typed
    RESTARTING — nothing serves on stale authority, nothing is
    silent."""
    inst.reset_module_registry()
    path = str(tmp_path / "expiry.sock")
    svc = _service(path)
    client = _client(path, restart_grace_s=0.4)
    answered: dict[int, list[int]] = {}
    lock = threading.Lock()

    def cb(vb):
        with lock:
            answered.setdefault(vb.seq, []).extend(
                int(r) for r in vb.results
            )

    client.verdict_callback = cb
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        granted = _conn(client, mod, 1, remote=1)
        plain = _conn(client, mod, 2, remote=2)
        _warm_grant(client, granted)

        svc.stop()
        _wait(lambda: not client._alive, 10.0, "disconnect latch")
        res, _ = granted.on_io(False, b"READ /in-window\r\n")
        assert res == OK  # window open: grant serves
        # Hold one async round through the window.
        msg = b"HALT\r\n"
        ids = np.full(1, plain.conn_id, np.uint64)
        client.send_batch(7_001, ids, [0], np.full(1, len(msg)), msg)
        assert client.transport_status()["restart"]["queued_frames"] == 1

        time.sleep(0.5)  # past the grace deadline
        # First check past the deadline closes the window: the grant
        # is revoked (typed unavailability, not stale service) and the
        # held round sheds typed RESTARTING.
        res, _ = granted.on_io(False, b"READ /expired\r\n")
        assert res == UNAVAILABLE
        _wait(lambda: 7_001 in answered, 5.0, "held round shed typed")
        assert answered[7_001] == [RESTARTING]
        st = client.transport_status()["restart"]
        assert st["window_open"] is False
        assert st["queued_frames"] == 0
        assert st["shed_frames"] >= 1
        assert client.double_replies == 0
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_restart_queue_flush_exactly_once(tmp_path):
    """Async rounds held through the window resend under their
    ORIGINAL seqs after the replay and are answered exactly once;
    overflow past restart_queue_frames sheds typed RESTARTING
    immediately (bounded, never silent)."""
    inst.reset_module_registry()
    path = str(tmp_path / "rq.sock")
    svc = _service(path)
    client = _client(path, restart_queue_frames=4)
    answered: dict[int, list[int]] = {}
    lock = threading.Lock()

    def cb(vb):
        with lock:
            answered.setdefault(vb.seq, []).extend(
                int(r) for r in vb.results
            )

    client.verdict_callback = cb
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        plain = _conn(client, mod, 2, remote=2)
        res, _ = plain.on_io(False, b"HALT\r\n")
        assert res == OK

        svc.stop()
        _wait(lambda: not client._alive, 10.0, "disconnect latch")
        msg = b"HALT\r\n"
        ids = np.full(1, plain.conn_id, np.uint64)
        lens = np.full(1, len(msg))
        for seq in (9_001, 9_002, 9_003, 9_004):  # held (queue of 4)
            client.send_batch(seq, ids, [0], lens, msg)
        client.send_batch(9_005, ids, [0], lens, msg)  # overflow
        _wait(lambda: 9_005 in answered, 5.0, "overflow shed typed")
        assert answered[9_005] == [RESTARTING]
        assert client.transport_status()["restart"]["queued_frames"] == 4
        held = {9_001, 9_002, 9_003, 9_004}
        with lock:
            assert not (held & set(answered)), "held rounds answered early"

        successor = VerdictService(path, _cfg()).start()
        _wait(lambda: client._alive, 30.0, "reconnect")
        _wait(lambda: held <= set(answered), 10.0,
              "held rounds answered after replay")
        with lock:
            for seq in held:
                assert answered[seq] == [OK], (seq, answered[seq])
        assert client.double_replies == 0
        assert client.transport_status()["restart"]["queued_frames"] == 0
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        successor = locals().get("successor")
        if successor is not None:
            successor.stop()
        inst.reset_module_registry()


# --- crash (kill -9) recovery ----------------------------------------------

_CHILD_SERVICE = """
import sys, time
from cilium_tpu.sidecar import VerdictService
from cilium_tpu.utils.option import DaemonConfig

cfg = DaemonConfig(batch_timeout_ms=0.0, batch_flows=64, batch_width=64,
                   flow_cache=True)
VerdictService(sys.argv[1], cfg).start()
print("ready", flush=True)
time.sleep(600)
"""


def _spawn_service(path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD_SERVICE, path],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True,
    )
    line = proc.stdout.readline()
    if "ready" not in line:
        proc.kill()
        raise AssertionError(f"child service never came up: {line!r}")
    return proc


def test_kill9_crash_recovery_exactly_once(tmp_path):
    """kill -9 mid-doorbell-drain: a burst of async rounds is in
    flight when the service dies without a syscall of warning.  Every
    seq is answered exactly once (old process / typed local shed), the
    survival window carries granted flows through the blackout, and a
    cold successor on the same path recovers full service — zero
    double replies, zero misroutes."""
    inst.reset_module_registry()
    path = str(tmp_path / "kill9.sock")
    proc = _spawn_service(path)
    client = _client(path, identity="pod-kill9")
    answered: dict[int, list[int]] = {}
    lock = threading.Lock()

    def cb(vb):
        with lock:
            answered.setdefault(vb.seq, []).extend(
                int(r) for r in vb.results
            )

    client.verdict_callback = cb
    successor = None
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        granted = _conn(client, mod, 1, remote=1)
        plain = _conn(client, mod, 2, remote=2)
        _warm_grant(client, granted)
        res, _ = plain.on_io(False, b"HALT\r\n")
        assert res == OK

        # Burst in flight at the kill: the drain these rounds were
        # queued behind dies with the process.
        msg = b"HALT\r\n"
        ids = np.full(1, plain.conn_id, np.uint64)
        lens = np.full(1, len(msg))
        burst = list(range(5_000, 5_032))
        for seq in burst:
            client.send_batch(seq, ids, [0], lens, msg)
        proc.kill()  # SIGKILL: no flush, no goodbye
        proc.wait(10)

        _wait(lambda: not client._alive, 10.0, "crash detected")
        # Every in-flight seq answered exactly once: served by the old
        # process before death, or swept typed at disconnect, or held
        # for the replay — audited below once the successor answers.
        # Meanwhile: the survival window serves granted flows.
        h0 = client.survival_hits
        res, _ = granted.on_io(False, b"READ /blackout\r\n")
        assert res == OK
        assert client.survival_hits > h0

        # Cold successor (the socket path is a dead remnant — the
        # handoff dial fails and cold boot takes over).
        successor = _service(path)
        assert successor.status()["restart"]["generation"] == 1
        # reconnects bumps only when the whole replay (hello, policy,
        # conns, queue flush) has landed — _alive flips earlier and
        # sync rounds still answer typed RESTARTING until then.
        _wait(lambda: client.reconnects >= 1, 30.0, "recovery replay")
        _wait(lambda: set(burst) <= set(answered), 15.0,
              "every burst seq answered")
        with lock:
            for seq in burst:
                assert len(answered[seq]) == 1, (seq, answered[seq])
                assert answered[seq][0] in (OK, SHED, RESTARTING), (
                    seq, answered[seq]
                )
        res, _ = plain.on_io(False, b"HALT\r\n")
        assert res == OK
        res, _ = granted.on_io(False, b"READ /after\r\n")
        assert res == OK
        assert client.double_replies == 0
        assert client.misrouted_verdicts == 0
    finally:
        client.verdict_callback = None
        client.close()
        if proc.poll() is None:
            proc.kill()
        if successor is not None:
            successor.stop()
        inst.reset_module_registry()


# --- ugly timing -----------------------------------------------------------

def test_snapshot_races_policy_swap_single_epoch(tmp_path):
    """A snapshot taken while a policy swap commits lands on exactly
    one of the two epochs — never a torn mix (the successor re-derives
    grants from the snapshot's epoch, so a half-committed view would
    poison every revalidation)."""
    inst.reset_module_registry()
    path = str(tmp_path / "swap.sock")
    svc = _service(path)
    client = _client(path)
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == OK
        shim = _conn(client, mod, 2, remote=2)
        res, _ = shim.on_io(False, b"HALT\r\n")
        assert res == OK
        for gen in range(1, 5):
            before = svc.policy_epoch
            done = threading.Event()
            status = {}

            def swap(g=gen):
                status["res"] = client.policy_update(mod, [_policy(gen=g)])
                done.set()

            t = threading.Thread(target=swap, daemon=True)
            t.start()
            epochs = set()
            while not done.is_set():
                snap = svc.snapshot_handoff()
                epochs.add(snap["policy_epoch"])
            t.join(10)
            assert status["res"] == OK
            after = svc.policy_epoch
            assert after == before + 1
            assert epochs <= {before, after}, (before, after, epochs)
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_restart_races_quarantine_heal_probe(tmp_path):
    """Restart racing the heal probe: the predecessor dies with a
    quarantine open and a probe in flight.  The successor inherits the
    OPEN latch (a proxy restart heals no device) with counters intact,
    and its re-armed pacer probes immediately — the heal completes in
    the successor exactly as it would have in the predecessor."""
    g1 = DeviceGuard(timeout_s=5.0, reprobe_interval_s=60.0)
    g1.quarantine("injected-stall")
    # The predecessor's pacer just fired (probe in flight at death):
    # without the restore re-arm, the successor would wait out the full
    # interval before its first probe.
    g1.maybe_reprobe(lambda: (_ for _ in ()).throw(RuntimeError("dead")))
    snap = g1.snapshot_state()

    g2 = DeviceGuard(timeout_s=5.0, reprobe_interval_s=60.0)
    g2.restore_state(snap)
    assert g2.quarantined is True
    assert g2.reason == "injected-stall"
    assert g2.quarantine_events == g1.quarantine_events
    assert g2.probes == g1.probes
    assert g2._last_probe == 0.0  # pacer re-armed: probe fires NOW

    probed = threading.Event()

    def probe():
        probed.set()

    g2.maybe_reprobe(probe)
    _wait(probed.is_set, 5.0, "immediate successor probe")
    _wait(lambda: not g2.quarantined, 5.0, "heal in the successor")

    # Malformed snapshots restore nothing (cold guard = fail-open
    # toward the device, which re-trips on the first real stall).
    g3 = DeviceGuard()
    g3.restore_state({"quarantined": "yes-but-not-a-bool-context"})
    g3.restore_state({})
    assert g3.quarantined is False


def test_startup_stale_segment_sweep(tmp_path):
    """A kill -9'd predecessor's shm orphans (dead owner pid, lease
    expired) are reclaimed at the next service boot; live-owner and
    in-lease segments are never touched."""
    shm_dir = tmp_path / "shm"
    shm_dir.mkdir()
    dead = subprocess.Popen(["true"])
    dead.wait()
    old = time.time() - 120.0

    stale = shm_dir / f"ctpu-data-{dead.pid}-deadbeef"
    stale.write_bytes(b"x")
    os.utime(stale, (old, old))
    fresh_dead = shm_dir / f"ctpu-data-{dead.pid}-cafecafe"
    fresh_dead.write_bytes(b"x")  # dead owner but inside the lease
    live = shm_dir / f"ctpu-verdict-{os.getpid()}-beefbeef"
    live.write_bytes(b"x")
    os.utime(live, (old, old))
    unrelated = shm_dir / "not-ctpu"
    unrelated.write_bytes(b"x")

    removed = sweep_stale_segments(30.0, shm_dir=str(shm_dir))
    assert removed == 1
    assert not stale.exists()
    assert fresh_dead.exists()
    assert live.exists()
    assert unrelated.exists()
    # Second sweep: nothing left to reclaim.
    assert sweep_stale_segments(30.0, shm_dir=str(shm_dir)) == 0


# --- chaos soak ------------------------------------------------------------

def _soak(tmp_path, n_clients, cycles, cold_gap_s=0.15):
    """Restart chaos soak: ``n_clients`` sessions hammer granted and
    non-granted flows while the service restarts ``cycles`` times —
    alternating graceful handoff (successor pulls the snapshot first)
    and cold-gap crash shape (stop, dead air, cold boot) — under
    policy churn.  Invariants audited at every step and at the end:
    typed results only, zero double replies, zero misroutes, survival
    hits strictly positive, and a balanced exactly-once surface."""
    inst.reset_module_registry()
    path = str(tmp_path / "soak.sock")
    svc = _service(path)
    typed = {OK, SHED, RESTARTING, UNAVAILABLE}
    clients, granted, plain = [], [], []
    try:
        for i in range(n_clients):
            c = _client(path, identity=f"pod-soak-{i}")
            clients.append(c)
            mod = c.open_module([])
            assert c.policy_update(mod, [_policy()]) == OK
            c._soak_mod = mod
            # Conn ids are service-global: each session claims its own
            # range or a later registration would overwrite an earlier
            # session's row.
            granted.append(_conn(c, mod, 10 * i + 1, remote=1))
            plain.append(_conn(c, mod, 10 * i + 2, remote=2))
        for g, p in zip(granted, plain):
            _warm_grant(g.client, g)
            res, _ = p.on_io(False, b"HALT\r\n")
            assert res == OK

        stop = threading.Event()
        errs: list = []

        def hammer(shim, msg):
            try:
                while not stop.is_set():
                    res, _ = shim.on_io(False, msg)
                    assert res in typed, res
                    time.sleep(0.001)
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [
            threading.Thread(
                target=hammer, args=(s, b"READ /soak\r\n"), daemon=True
            )
            for s in granted
        ] + [
            threading.Thread(
                target=hammer, args=(s, b"HALT\r\n"), daemon=True
            )
            for s in plain
        ]
        for t in threads:
            t.start()

        for cycle in range(cycles):
            time.sleep(0.3)
            rc0 = [c.reconnects for c in clients]
            graceful = cycle % 2 == 0
            if graceful:
                successor = VerdictService(path, _cfg()).start()
                svc.stop()
            else:
                svc.stop()
                time.sleep(cold_gap_s)
                successor = VerdictService(path, _cfg()).start()
            svc = successor
            for c, r0 in zip(clients, rc0):
                # reconnects bumps at replay COMPLETION: the policy
                # churn below must not race a half-done replay.
                _wait(lambda c=c, r0=r0: c.reconnects > r0, 30.0,
                      f"cycle {cycle}: client failover")
            # Policy churn between restarts: the byte-gated row
            # changes, the byte-free (granted) row stays.
            for c in clients:
                assert c.policy_update(
                    c._soak_mod, [_policy(gen=cycle + 1)]
                ) == OK
            assert not errs, errs

        stop.set()
        for t in threads:
            t.join(10)
        assert not errs, errs
        for c in clients:
            assert c.double_replies == 0
            assert c.misrouted_verdicts == 0
        assert sum(c.survival_hits for c in clients) > 0
        for g, p in zip(granted, plain):
            res, _ = g.on_io(False, b"READ /post-soak\r\n")
            assert res == OK
            res, _ = p.on_io(False, b"HALT\r\n")
            assert res == OK
        time.sleep(0.3)
        for row in svc.status()["sessions"]["live"]:
            assert row["submitted"] == row["answered"], row
    finally:
        stop_evt = locals().get("stop")
        if stop_evt is not None:
            stop_evt.set()
        for c in clients:
            c.close()
        svc.stop()
        inst.reset_module_registry()


def test_restart_chaos_soak_fast(tmp_path):
    _soak(tmp_path, n_clients=2, cycles=3)


@pytest.mark.slow
def test_restart_chaos_soak_slow(tmp_path):
    """Node-scale churn shape: 4 sessions, more cycles, longer dead
    air — the tier-2 version of the same invariants."""
    _soak(tmp_path, n_clients=4, cycles=8, cold_gap_s=0.3)

"""Non-stop policy churn: versioned epochs, async compile-then-swap,
and the control-plane churn soak (PR 9 tentpole).

Contracts pinned here:

- **Swap atomicity / fail-closed.**  A policy update builds its entire
  new state (host map + device engines) OFF the dispatch path and
  publishes by one pointer flip; parse, host-compile, device-build,
  and parity failures are all typed NACKs with the OLD epoch still
  serving bit-identically (`policy_swap_failures_total{reason}`).
- **Versioned epochs.**  The ack carries the committed epoch; flowlog
  records carry the epoch their verdict was decided against, with the
  kinds legend captured from the SAME engine — a freed/reused engine
  slot can never re-attribute a late record (service.py slot-reuse
  satellite).
- **Churn soak.**  Continuous policy updates + endpoint churn +
  identity allocate/release across an injected kvstore failover,
  against live traffic: zero silent loss (every on_io answered), zero
  cross-epoch attribution, bounded swap stall visible as the
  table_swap stage.  Fast tier-1 variant + slow-marked 60s soak.
"""

from __future__ import annotations

import json
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
from cilium_tpu.utils.option import DaemonConfig


def _policy(name: str, rules: list[dict], remotes=(1, 3)) -> NetworkPolicy:
    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=list(remotes),
                        l7_proto="r2d2",
                        l7_rules=rules,
                    )
                ],
            )
        ],
    )


# Two alternating policy generations with DIFFERENT kinds at the same
# rule index, so a rule id resolved against the wrong epoch's table is
# detectable by its match_kind alone.
POLICY_A = [{"cmd": "READ", "file": "/public/.*"}, {"cmd": "HALT"}]
POLICY_B = [{"cmd": "HALT"}, {"cmd": "WRITE", "file": "/tmp/.*"},
            {"cmd": "RESET"}]
# Byte-FREE first row (a blank matcher admits everything): identities
# it admits get an invariant-allow verdict-cache claim at rule 0 —
# the flow-cache soak alternates this with POLICY_B so every flip
# drives arm -> wholesale invalidation -> no-claim re-check.
POLICY_CACHEABLE = [{}, {"cmd": "HALT"}]


def _start(tmp_path, greedy=True, name="churn", **cfg_kw):
    inst.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=0.0 if greedy else 2.0,
        batch_flows=256,
        **cfg_kw,
    )
    svc = VerdictService(str(tmp_path / f"{name}.sock"), cfg).start()
    client = SidecarClient(svc.socket_path, timeout=60.0)
    mod = client.open_module([])
    assert mod != 0
    return svc, client, mod


def _conn(client, mod, conn_id, policy="pol", remote=1):
    res, shim = client.new_connection(
        mod, "r2d2", conn_id, True, remote, 2,
        f"1.1.1.{conn_id % 250 + 1}:{1000 + conn_id % 60000}",
        "2.2.2.2:80", policy,
    )
    assert res == int(FilterResult.OK)
    return shim


def _verdict(shim, frame: bytes):
    """(allowed, output) for one complete request frame."""
    res, out = shim.on_io(False, frame)
    assert res == int(FilterResult.OK), f"on_io result {res}"
    return out == frame, out


# --- swap atomicity & fail-closed -----------------------------------------


def test_swap_ack_carries_epoch_and_status(tmp_path):
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        e1 = client.last_policy_epoch
        assert e1 == svc.policy_epoch >= 1
        assert client.policy_update(mod, [_policy("pol", POLICY_B)]) == int(
            FilterResult.OK
        )
        assert client.last_policy_epoch == e1 + 1
        pol = client.status()["policy"]
        assert pol["epoch"] == e1 + 1
        assert pol["swaps"] == 2
        assert pol["swap_failures"] == {}
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_compile_failure_keeps_old_policy_bit_identical(tmp_path):
    """Satellite: partial-failure atomicity.  A policy update whose
    compile fails at ANY stage (parse / host compile / device build /
    parity) leaves the instance un-mutated: the exact frames keep
    producing the exact pre-update bytes."""
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        e1 = client.last_policy_epoch
        shim = _conn(client, mod, 1)
        frames = [b"READ /public/a\r\n", b"READ /secret\r\n", b"HALT\r\n"]
        before = [_verdict(shim, f) for f in frames]
        assert [a for a, _ in before] == [True, False, True]

        # Host-compile failure: invalid r2d2 rule key.
        bad = _policy("pol", [{"bogus": "x"}])
        from dataclasses import asdict

        status, epoch = svc.policy_update(
            mod, json.dumps([asdict(bad)]).encode()
        )
        assert status == int(FilterResult.POLICY_DROP)
        assert epoch == e1  # old epoch still committed

        # Parse failure: not even JSON.
        status, epoch = svc.policy_update(mod, b"\xff not json")
        assert status == int(FilterResult.POLICY_DROP)
        assert epoch == e1

        # Device-build failure injected at the model builder: the
        # builder thread fails the swap typed; nothing half-applied.
        import cilium_tpu.models.r2d2 as r2d2mod

        orig = r2d2mod.build_r2d2_model

        def boom(*a, **k):
            raise RuntimeError("injected device-build crash")

        r2d2mod.build_r2d2_model = boom
        try:
            # Must be a CHANGED policy: unchanged ones are reused
            # without a rebuild.
            assert client.policy_update(
                mod, [_policy("pol", POLICY_B)]
            ) == int(FilterResult.POLICY_DROP)
        finally:
            r2d2mod.build_r2d2_model = orig
        assert svc.policy_epoch == e1
        fails = svc.status()["policy"]["swap_failures"]
        assert fails.get("host-compile", 0) >= 1
        assert fails.get("parse", 0) >= 1
        assert fails.get("device-build", 0) >= 1

        # Bit-identity: the old table serves exactly as before, on a
        # fresh conn AND the existing one.
        assert [_verdict(shim, f) for f in frames] == before
        shim2 = _conn(client, mod, 2)
        assert [_verdict(shim2, f) for f in frames] == before
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_epoch_parity_probe_rejects_miscompiled_table(tmp_path):
    """A device table that disagrees with the host oracle is caught by
    the per-epoch parity probe BEFORE the swap commits."""
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        e1 = client.last_policy_epoch
        _conn(client, mod, 1)
        import cilium_tpu.models.r2d2 as r2d2mod

        orig = r2d2mod.build_r2d2_model

        def wrong_model(policy, ingress, port):
            # Allow-all wildcard rows — a miscompile that no verdict
            # shape check would notice.
            return r2d2mod.build_r2d2_model_from_rows(
                [(frozenset(), "", "")], bucket=True
            )

        r2d2mod.build_r2d2_model = wrong_model
        try:
            assert client.policy_update(
                mod, [_policy("pol", POLICY_B)]
            ) == int(FilterResult.POLICY_DROP)
        finally:
            r2d2mod.build_r2d2_model = orig
        assert svc.policy_epoch == e1
        assert svc.status()["policy"]["swap_failures"].get("parity", 0) >= 1
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_swap_takes_effect_and_preserves_partial_frames(tmp_path):
    """The committed epoch serves the NEW policy, and a conn's
    engine-retained partial frame survives the swap (no byte lost or
    replayed across the flip)."""
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        shim = _conn(client, mod, 1)
        allowed, _ = _verdict(shim, b"READ /public/a\r\n")
        assert allowed
        # Half a frame buffered in the engine...
        res, out = shim.on_io(False, b"WRITE /tmp")
        assert res == int(FilterResult.OK) and out == b""
        # ...swap to a policy that allows WRITE /tmp/*...
        assert client.policy_update(mod, [_policy("pol", POLICY_B)]) == int(
            FilterResult.OK
        )
        # ...and complete the frame: the retained prefix must have
        # crossed the swap (the new table allows the whole frame).
        res, out = shim.on_io(False, b"/x\r\n")
        assert res == int(FilterResult.OK)
        assert out == b"WRITE /tmp/x\r\n", out
        # New policy active: READ is no longer allowed.
        allowed, _ = _verdict(shim, b"READ /public/a\r\n")
        assert not allowed
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_swap_defers_rebind_while_oracle_residue_undrained(tmp_path):
    """A swap committing while a quarantine-demoted conn holds
    undrained oracle-mirror bytes must NOT bind the new engine over
    them (engine entries never consume sc.bufs): the oracle keeps
    serving, the residue drains, and the heal path binds afterward —
    no byte lost across quarantine × swap."""
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        shim = _conn(client, mod, 1)
        assert _verdict(shim, b"READ /public/a\r\n")[0]
        # Quarantine, then feed HALF a frame: the conn demotes to the
        # oracle and the prefix lands in its oracle mirror.
        svc.guard.record_stall("churn-test")
        assert svc.guard.quarantined
        res, out = shim.on_io(False, b"WRITE /tmp")
        assert res == int(FilterResult.OK) and out == b""
        with svc._lock:
            sc = svc._conns[1]
        assert sc.engine is None and sc.bufs[False]
        # Swap under the demotion: the commit must leave the conn on
        # the oracle (residue undrained), re-marked for heal rebind.
        assert client.policy_update(mod, [_policy("pol", POLICY_B)]) == int(
            FilterResult.OK
        )
        assert sc.engine is None, "engine bound over oracle residue"
        assert sc.demoted_mod is not None
        # Complete the frame while still quarantined: the oracle
        # serves it against the NEW policy with the prefix intact.
        res, out = shim.on_io(False, b"/x\r\n")
        assert res == int(FilterResult.OK)
        assert out == b"WRITE /tmp/x\r\n", out
        # Heal; the next clean entry rebinds (builder/inline) and the
        # conn resumes the device path on the new epoch.
        svc.guard._heal()
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            allowed, _ = _verdict(shim, b"WRITE /tmp/y\r\n")
            assert allowed
            if sc.engine is not None:
                break
            time.sleep(0.02)
        assert sc.engine is not None
        assert sc.engine.epoch == svc.policy_epoch
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- epoch attribution -----------------------------------------------------


def test_slot_reuse_never_reattributes_late_records(tmp_path):
    """Satellite: engine slot reuse vs late attribution.  A flow
    record emitted AFTER churn freed and reused the judging engine's
    table slot must resolve rule ids against the CAPTURED engine
    (its epoch, its kinds legend) — never the slot's new occupant."""
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        _conn(client, mod, 1)
        with svc._lock:
            engine_a = next(
                v for k, v in svc._engines.items() if k[0] == mod
            )
        kinds_a = engine_a.model.match_kinds
        epoch_a = engine_a.epoch
        # Churn: the swap frees engine A's slot; the new engine reuses
        # it (same free-list slot).
        assert client.policy_update(mod, [_policy("pol", POLICY_B)]) == int(
            FilterResult.OK
        )
        with svc._lock:
            engine_b = next(
                v for k, v in svc._engines.items() if k[0] == mod
            )
        assert engine_b is not engine_a
        assert engine_b.model.match_kinds != kinds_a
        # The late record: a vec round judged by engine A drains AFTER
        # the swap (the completion pipeline shape).  Emission must use
        # A's legend + epoch.
        svc._record_vec_round(
            engine_a,
            np.array([1], np.int64),
            np.array([True]),
            np.array([0], np.int32),
        )
        rec = svc.flowlog.query(n=1)[0]
        assert rec["epoch"] == epoch_a
        assert rec["match_kind"] == kinds_a[0]
        assert rec["rule_id"] == 0
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_table_swap_stage_books_blocked_rounds(tmp_path):
    """A round whose snapshot acquisition blocks behind the swap's
    pointer flip books the overlap as the table_swap stage — the churn
    stall is visible in the decomposition, not smeared into
    batch_form."""
    svc, client, mod = _start(tmp_path)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) == int(
            FilterResult.OK
        )
        shim = _conn(client, mod, 1)
        _verdict(shim, b"READ /public/a\r\n")  # engines warm

        hold = threading.Event()
        held = threading.Event()

        def swapper():
            # The commit shape: hold _lock, publish, record the window.
            with svc._lock:
                t0 = time.monotonic()
                held.set()
                hold.wait(2.0)
                svc._swap_window = (t0, time.monotonic())

        t = threading.Thread(target=swapper, daemon=True)
        t.start()
        assert held.wait(2.0)
        releaser = threading.Timer(0.05, hold.set)
        releaser.start()
        # The round's snapshot acquisition blocks behind the flip and
        # books the overlap (deterministic: we ARE the blocked round,
        # stamped exactly like _process stamps it).
        class _Item:
            conn_ids = np.array([1], np.int64)

        t_pop = time.monotonic()
        snap = svc._tab_snapshot([("data", None, _Item())])
        t.join(5.0)
        releaser.cancel()
        assert snap.swap_s > 0.02, snap.swap_s
        rt = svc.tracer.begin_round(
            "vec", 1, t_pop, t_pop, swap_s=snap.swap_s
        )
        rt.formed()  # form spans the blocked snapshot, like _process
        svc.tracer.finish_round(rt, [(1, 1, 0.0, 1)])
        stages = svc.tracer.status()["stages"]
        swap_means = [
            s["table_swap"]["mean_us"]
            for s in stages.values() if "table_swap" in s
        ]
        assert swap_means and max(swap_means) > 0, stages
        # End-to-end: traffic keeps flowing after the flip.
        allowed, _ = _verdict(shim, b"READ /public/b\r\n")
        assert allowed
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- the churn soak --------------------------------------------------------


def _expected_kinds(rules: list[dict]) -> tuple:
    """The flattened match-kind legend build_r2d2_model produces for a
    single-rule-block policy (declaration order)."""
    kinds = []
    for r in rules:
        kinds.append("regex" if r.get("file") else "literal")
    return tuple(kinds)


def _churn_soak(tmp_path, duration_s: float, updates_per_s: float,
                n_conns: int = 8, policy_pair=None, n_sessions: int = 1,
                session_conns: int = 8, **cfg_kw):
    """The acceptance scenario: continuous policy updates + endpoint
    regeneration + identity allocate/release across an injected
    kvstore failover, against live mixed traffic.  ``policy_pair``
    overrides the two alternating rule generations (the flow-cache
    soak alternates a byte-free table — armed cache — with a
    byte-constrained one, so every flip exercises arm → invalidate →
    re-check).  ``n_sessions`` > 1 drives the soak through the fan-in
    seam: that many extra concurrent shim sessions (own SidecarClient,
    own module, ``session_conns`` conns each, identity-named) serve
    live traffic while the churn thread flips EVERY module's table
    each cycle — epoch flips, cache grants and revokes all land under
    multi-session fan-in, and the per-session exactly-once counters
    are asserted balanced at the end."""
    from cilium_tpu.kvstore import ChaosProxy, KvstoreFollower, KvstoreServer, NetBackend
    from cilium_tpu.kvstore.allocator import Allocator

    pol_even, pol_odd = policy_pair or (POLICY_A, POLICY_B)
    svc, client, mod = _start(
        tmp_path, name=f"soak{duration_s:g}", **cfg_kw
    )
    primary = KvstoreServer()
    chaos = ChaosProxy(primary.address)
    follower = KvstoreFollower(
        chaos.address, repl_timeout=1.0, failover_grace=0.1
    )
    assert follower.synced.wait(5.0)
    kv = NetBackend(f"{chaos.address},{follower.address}", timeout=15.0)
    alloc = Allocator(kv, "cilium/state/identities/v1", "soak-node")
    stop = threading.Event()
    errors: list[str] = []
    epoch_rules: dict[int, tuple] = {}
    io_count = [0]
    id_by_key: dict[str, int] = {}
    extra_sessions: list[tuple] = []  # (client, mod, shims) per session

    try:
        assert client.policy_update(mod, [_policy("pol", pol_even)]) == int(
            FilterResult.OK
        )
        epoch_rules[client.last_policy_epoch] = _expected_kinds(pol_even)
        epoch_rule_dicts = {client.last_policy_epoch: pol_even}

        shims = {i: _conn(client, mod, i) for i in range(1, n_conns + 1)}
        frames = [b"READ /public/a\r\n", b"READ /secret\r\n", b"HALT\r\n",
                  b"WRITE /tmp/x\r\n", b"RESET\r\n"]
        # Warm BOTH alternating generations' engine compiles before the
        # timed window (engines rebuild per flip only for BOUND conns,
        # so this must come after the conns): the first cold build of a
        # new automaton shape costs seconds on the CPU backend, and a
        # soak whose entire window is one cold compile churns nothing.
        # Prewarm compiles each generation's direct and gather
        # executables at its build — the shape-keyed executable cache
        # then serves every later same-shape flip with zero traces.
        # The client-side verdict cache is held OFF for these warm
        # frames only, so both generations' conns are bound before the
        # window.
        cache_was = client.flow_cache
        client.flow_cache = False
        for warm_rules in (pol_odd, pol_even):
            assert client.policy_update(
                mod, [_policy("pol", warm_rules)]
            ) == int(FilterResult.OK)
            epoch_rules[client.last_policy_epoch] = (
                _expected_kinds(warm_rules)
            )
            epoch_rule_dicts[client.last_policy_epoch] = warm_rules
            for f in frames:
                assert shims[1].on_io(False, f)[0] == int(
                    FilterResult.OK
                )
        client.flow_cache = cache_was
        next_cid = [n_conns + 1]

        # Fan-in sessions: each an independent shim process stand-in
        # (own socket, own module, own conns in a disjoint cid range).
        # Their modules are pre-warmed with both generations so the
        # churn window flips tables, not cold compiles (the
        # shape-bucketed executable cache makes the extra modules'
        # builds reuse the primary's compiled executables).
        for k in range(1, n_sessions):
            ec = SidecarClient(
                svc.socket_path, timeout=60.0,
                identity=f"soak-pod-{k}",
            )
            emod = ec.open_module([])
            for warm_rules in (pol_even, pol_odd, pol_even):
                assert ec.policy_update(
                    emod, [_policy("pol", warm_rules)]
                ) == int(FilterResult.OK)
                epoch_rules[ec.last_policy_epoch] = (
                    _expected_kinds(warm_rules)
                )
                epoch_rule_dicts[ec.last_policy_epoch] = warm_rules
            eshims = {
                100_000 * k + i: _conn(ec, emod, 100_000 * k + i)
                for i in range(1, session_conns + 1)
            }
            extra_sessions.append((ec, emod, eshims))

        # One warm pass through every fan-in session too (their shapes
        # alias the primary's shape-keyed executables, so this mostly
        # proves reuse), then snapshot the ledger.  Everything the
        # timed window does from here on is warm churn, and the
        # device-economics contract for warm churn is total: ZERO new
        # compile events, none of them on the dispatch path.
        for _ec, _emod, eshims in extra_sessions:
            wsh = next(iter(eshims.values()))
            for f in frames:
                assert wsh.on_io(False, f)[0] == int(FilterResult.OK)
        led0 = svc.ledger.status()

        def session_traffic(eshims):
            i = 0
            while not stop.is_set():
                time.sleep(0.0005)
                for cid, shim in list(eshims.items()):
                    try:
                        res, _ = shim.on_io(
                            False, frames[i % len(frames)]
                        )
                    except Exception as exc:  # noqa: BLE001
                        errors.append(f"fanin on_io raised: {exc!r}")
                        return
                    if res != int(FilterResult.OK):
                        errors.append(
                            f"fanin on_io result {res} (conn {cid})"
                        )
                        return
                    io_count[0] += 1
                    i += 1

        def traffic():
            i = 0
            while not stop.is_set():
                # Pace each sweep: with the verdict cache armed the
                # shim answers locally and this loop would become a
                # pure-CPU GIL spin that starves the builder thread's
                # off-path compiles (observed: one 0.2ms flip serialized
                # behind ~6s of starved XLA build).  Real datapaths are
                # I/O-paced; a sub-ms yield keeps the soak honest
                # without changing its load shape.
                time.sleep(0.0005)
                for cid, shim in list(shims.items()):
                    try:
                        res, _ = shim.on_io(False, frames[i % len(frames)])
                    except Exception as exc:  # noqa: BLE001
                        errors.append(f"on_io raised: {exc!r}")
                        return
                    if res != int(FilterResult.OK):
                        if (
                            res == int(FilterResult.UNKNOWN_CONNECTION)
                            and (shim.closed or cid not in shims)
                        ):
                            # Endpoint retired by the churn thread
                            # mid-request: a TYPED result, not silent
                            # loss — exactly the regeneration race the
                            # soak exists to exercise.
                            continue
                        errors.append(f"on_io result {res} (conn {cid})")
                        return
                    io_count[0] += 1
                    i += 1

        def churn():
            gen = 0
            while not stop.is_set():
                gen += 1
                rules = pol_odd if gen % 2 else pol_even
                st = client.policy_update(mod, [_policy("pol", rules)])
                if st == int(FilterResult.OK):
                    epoch_rules[client.last_policy_epoch] = (
                        _expected_kinds(rules)
                    )
                    epoch_rule_dicts[client.last_policy_epoch] = rules
                else:
                    errors.append(f"policy_update status {st}")
                    return
                # Fan-in: flip every extra session's table too (each
                # commit is its own epoch; grants/revokes fan out to
                # every opted-in session BEFORE the flip).
                for ec, emod, _eshims in extra_sessions:
                    est = ec.policy_update(emod, [_policy("pol", rules)])
                    if est != int(FilterResult.OK):
                        errors.append(f"fanin policy_update {est}")
                        return
                    epoch_rules[ec.last_policy_epoch] = (
                        _expected_kinds(rules)
                    )
                    epoch_rule_dicts[ec.last_policy_epoch] = rules
                # Endpoint regeneration: retire one conn, open another.
                retire = min(shims)
                shims.pop(retire).close()
                cid = next_cid[0]
                next_cid[0] += 1
                try:
                    shims[cid] = _conn(client, mod, cid)
                except Exception as exc:  # noqa: BLE001
                    errors.append(f"regen failed: {exc!r}")
                    return
                time.sleep(1.0 / updates_per_s)

        def identities():
            n = 0
            while not stop.is_set():
                key = f"k8s:app=soak-{n % 32}"
                try:
                    id_, _ = alloc.allocate(key)
                    prev = id_by_key.setdefault(key, id_)
                    if prev != id_:
                        errors.append(
                            f"identity moved: {key} {prev} -> {id_}"
                        )
                        return
                    alloc.release(key)
                except Exception:  # noqa: BLE001 — degraded mode rides
                    # through the failover window; cached identities
                    # keep serving (retain_cached), kvstore I/O retries.
                    cached = alloc.retain_cached(key)
                    if cached is not None:
                        alloc.release(key)
                n += 1
                time.sleep(0.002)

        threads = [
            threading.Thread(target=traffic, daemon=True),
            threading.Thread(target=churn, daemon=True),
            threading.Thread(target=identities, daemon=True),
        ] + [
            threading.Thread(
                target=session_traffic, args=(eshims,), daemon=True
            )
            for _ec, _emod, eshims in extra_sessions
        ]
        for t in threads:
            t.start()
        # Mid-soak kvstore failover under full churn.
        time.sleep(duration_s * 0.4)
        chaos.partition(reset_existing=True)
        time.sleep(duration_s * 0.3)
        chaos.heal()
        time.sleep(duration_s * 0.3)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:5]
        assert io_count[0] > 0
        # Zero silent loss at the service: everything admitted was
        # answered (on_io is a synchronous RPC — asserted above), and
        # nothing was shed or crashed.
        st = svc.status()
        assert st["containment"]["shed_entries"] == 0, st["containment"]
        assert st["containment"]["batch_crashes"] == 0
        pol = st["policy"]
        assert pol["swaps"] >= 2
        assert pol["epoch"] == max(epoch_rules)
        # Bounded swap stall: the flip is a pointer swap + conn rebind,
        # never a compile (compiles ride the builder thread).
        assert pol["last_swap_ms"] < 250.0, pol
        # Device-economics ledger (PR 20): the timed window was pure
        # WARM churn — both alternating generations' shapes prewarmed
        # (direct and gather executables) before the snapshot — so the
        # compile census must not have moved AT ALL across the whole
        # window (flips, regen, failover included).  This is the
        # asserted form of "warm churn performs ZERO compiles", and a
        # fortiori zero churn-cause and zero dispatch-path compiles.
        led1 = st["ledger"]
        window_events = svc.ledger.events(n=10_000, since=led0["seq"])
        assert not window_events, (
            f"warm churn performed a compile: {window_events}"
        )
        assert led1["churn_compiles"] == led0["churn_compiles"], (
            led0, led1,
        )
        assert (
            led1["dispatch_path_compiles"]
            == led0["dispatch_path_compiles"]
        ), (led0, led1)
        # The pre-window record stream tells the cold-start story in
        # cause terms: the first engine build is cold (its own warm
        # launches precede it as prewarm), and every event names a
        # known cause (churn causes here come from the
        # warm-both-generations flips above, BEFORE the snapshot).
        all_events = svc.ledger.events(n=10_000)
        builds = [e for e in all_events if e["kind"] == "engine-build"]
        assert builds and builds[0]["cause"] == "cold", builds[:1]
        assert {e["cause"] for e in all_events} <= {
            "cold", "prewarm", "churn-new-shape", "churn-vocab",
        }, sorted({e["cause"] for e in all_events})
        # Formation provenance rode the soak's rounds: at least one
        # trigger accumulated rounds, with sane occupancy bounds.
        form = led1["formation"]
        assert sum(acc["rounds"] for acc in form.values()) > 0, form
        for trig, acc in form.items():
            assert 0.0 <= acc["occ_mean"] <= 1.0, (trig, acc)
        # Zero cross-epoch attribution: every record's rule id resolves
        # in the epoch it carries, with that epoch's kind at that row.
        recs = svc.flowlog.query(n=100000)
        checked = 0
        for rec in recs:
            if rec.get("rule_id", -1) < 0:
                continue
            ep = rec.get("epoch", -1)
            assert ep in epoch_rules, (
                f"record carries unknown epoch {ep}: {rec}"
            )
            kinds = epoch_rules[ep]
            assert rec["rule_id"] < len(kinds), (
                f"rule {rec['rule_id']} out of range for epoch {ep} "
                f"({len(kinds)} rules): {rec}"
            )
            assert rec["match_kind"] == kinds[rec["rule_id"]], (
                f"cross-epoch attribution: {rec} vs epoch {ep} "
                f"kinds {kinds}"
            )
            checked += 1
        assert checked > 0
        # Verdict-cache parity gate (PR 12): with the cache armed,
        # every cached record's (verdict, rule id, epoch) is
        # re-validated against a COLD recompute of that epoch's table —
        # the invariance claim itself plus a per-frame host walk over
        # the traffic corpus.  Stale epochs are structurally impossible
        # (asserted: no cached record under a byte-constrained epoch).
        if cfg_kw.get("flow_cache"):
            from cilium_tpu.models.r2d2 import collect_policy_rows
            from cilium_tpu.policy.invariance import (
                invariant_verdict,
                reduce_r2d2_rows,
            )
            from cilium_tpu.proxylib.parsers.r2d2 import R2d2RequestData
            from cilium_tpu.proxylib.policy import compile_policy

            fc = st["flow_cache"]
            total_hits = client.cache_hits + fc["hits"]
            assert total_hits > 0, (client.cache_hits, fc)
            assert fc["invalidations"] > 0, fc  # flips retired rows
            cached_recs = [r for r in recs if r.get("path") == "cached"]
            for rec in cached_recs:
                ep = rec["epoch"]
                assert ep in epoch_rule_dicts, rec
                pol_obj = compile_policy(
                    _policy("pol", epoch_rule_dicts[ep])
                )
                rows = collect_policy_rows(pol_obj, True, 80)
                assert isinstance(rows, list), rows
                inv = invariant_verdict(reduce_r2d2_rows(rows), 1)
                # The cache only arms invariant-ALLOW claims, and the
                # record must name the claim's exact first-match row.
                assert inv is not None and inv[0] is True, (
                    f"cached record under a non-invariant epoch: {rec}"
                )
                assert rec["verdict"] == "Forwarded", rec
                assert rec["rule_id"] == inv[1], (rec, inv)
                # Per-frame cold recompute over the corpus: every
                # frame's host walk agrees with the cached claim.
                for f in frames:
                    parts = f[:-2].decode().split(" ")
                    cmd = parts[0]
                    file_ = parts[1] if len(parts) > 1 else ""
                    host = pol_obj.matches_at(
                        True, 80, 1, R2d2RequestData(cmd, file_)
                    )
                    assert host == (True, inv[1]), (f, host, inv, rec)
        # Fan-in exactly-once surface: every session's submitted ==
        # answered (on_io is synchronous, so all sessions are quiesced
        # once the threads joined), zero cross-session misrouting, one
        # live row per session.
        if extra_sessions:
            rows = st["sessions"]["live"]
            assert len(rows) == 1 + len(extra_sessions), rows
            for row in rows:
                assert row["submitted"] == row["answered"], row
                assert row["state"] == "active", row
            idents = {r["identity"] for r in rows}
            for k in range(1, n_sessions):
                assert f"soak-pod-{k}" in idents, rows
            for ec, _emod, _eshims in extra_sessions:
                assert ec.misrouted_verdicts == 0
            assert client.misrouted_verdicts == 0
        # Identity churn stayed sane across the failover.
        assert follower.promoted.is_set()
        assert len(set(id_by_key.values())) == len(id_by_key), (
            "duplicate identity ids"
        )
    finally:
        stop.set()
        for ec, _emod, _eshims in extra_sessions:
            try:
                ec.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        client.close()
        svc.stop()
        kv.close()
        follower.close()
        chaos.close()
        primary.close()
        inst.reset_module_registry()


def test_churn_soak_fast(tmp_path):
    """Tier-1 churn soak: seconds-scale, full scenario."""
    _churn_soak(tmp_path, duration_s=6.0, updates_per_s=4.0)


def test_churn_soak_fast_flow_cache(tmp_path):
    """The churn soak with the verdict cache ARMED: one alternating
    generation is a byte-free table (every conn's claim arms at bind),
    the other is byte-constrained (no claim) — so every flip drives
    arm → wholesale epoch invalidation → re-check.  On top of the
    standard zero-loss / cross-epoch-attribution gates, every cached
    record is re-validated against a cold recompute of its epoch's
    table (the cached == recomputed parity gate)."""
    _churn_soak(
        tmp_path, duration_s=5.0, updates_per_s=4.0,
        policy_pair=(POLICY_CACHEABLE, POLICY_B),
        flow_cache=True,
    )


def test_churn_soak_fast_fanin(tmp_path):
    """Tier-1 fan-in churn soak (the PR 9 leftover's fast shape, now
    multi-session): 4 concurrent shim sessions — each its own client,
    module and conns — serve live traffic while the churn thread flips
    EVERY session's table each cycle and the verdict cache is armed,
    so epoch flips, grants and revokes all land under fan-in.  On top
    of the standard gates: per-session submitted == answered, zero
    cross-session misrouting, one status row per session."""
    _churn_soak(
        tmp_path, duration_s=6.0, updates_per_s=2.0,
        n_sessions=4, session_conns=6,
        policy_pair=(POLICY_CACHEABLE, POLICY_B),
        flow_cache=True,
    )


@pytest.mark.slow
def test_churn_soak_fanin_thousands(tmp_path):
    """Node-scale churn chaos soak (slow tier): thousands of endpoints
    across 4 concurrent fan-in sessions under continuous policy flips,
    identity churn and a kvstore failover — the ROADMAP item 5 scale
    point (the fast twin above pins the same shape in tier-1)."""
    _churn_soak(
        tmp_path, duration_s=45.0, updates_per_s=2.0,
        n_conns=512, n_sessions=4, session_conns=512,
        policy_pair=(POLICY_CACHEABLE, POLICY_B),
        flow_cache=True,
    )


def test_churn_soak_fast_mesh(tmp_path):
    """The same churn soak with a SHARDED rule table (2 rule shards on
    the CPU mesh): every epoch's builder rebuilds all shards before
    the flip, records stay cross-epoch-attribution-clean, zero silent
    loss — non-stop churn holds on the multi-chip path too."""
    _churn_soak(tmp_path, duration_s=4.0, updates_per_s=4.0,
                mesh="on", mesh_rule_shards=2)


# --- epoch hot-swap × mesh -------------------------------------------------


def test_mesh_swap_rebuilds_all_shards_before_flip(tmp_path):
    """Sharded epoch swap: the builder rebuilds EVERY shard (stacked
    tables + single-chip fallback) off-path, then commits with the one
    pointer flip — the new epoch serves sharded, bit-identically with
    the new policy, and the mesh stays active throughout."""
    from cilium_tpu.parallel.rulesharding import ShardedVerdictModel

    svc, client, mod = _start(tmp_path, name="mesh-swap", mesh="on",
                              mesh_rule_shards=2)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) \
            == int(FilterResult.OK)
        shim = _conn(client, mod, 1)
        assert _verdict(shim, b"READ /public/a\r\n")[0]
        assert not _verdict(shim, b"WRITE /tmp/x\r\n")[0]
        eng0 = next(iter(svc._engines.values()))
        assert isinstance(eng0.model, ShardedVerdictModel)
        assert eng0.model.n_shards == 2
        epoch0 = svc.policy_epoch
        assert client.policy_update(mod, [_policy("pol", POLICY_B)]) \
            == int(FilterResult.OK)
        assert svc.policy_epoch == epoch0 + 1
        eng1 = next(iter(svc._engines.values()))
        assert eng1 is not eng0
        assert isinstance(eng1.model, ShardedVerdictModel)
        assert eng1.model.n_shards == 2
        # POLICY_B semantics on the new sharded epoch.
        assert not _verdict(shim, b"READ /public/a\r\n")[0]
        assert _verdict(shim, b"WRITE /tmp/x\r\n")[0]
        assert _verdict(shim, b"RESET\r\n")[0]
        st = svc.status()
        assert st["mesh"]["active"]
        assert st["policy"]["swaps"] >= 1
        assert st["policy"]["swap_failures"] == {}
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_mesh_mid_build_shard_failure_fails_closed(tmp_path):
    """A staged device build that dies on shard k (k=1 of 2) is a
    typed policy_swap_failures_total{device-build} NACK: the old
    SHARDED epoch keeps serving bit-identically — a torn half-sharded
    table can never be observed."""
    from cilium_tpu.parallel import rulesharding
    from cilium_tpu.parallel.rulesharding import ShardedVerdictModel

    svc, client, mod = _start(tmp_path, name="mesh-fail", mesh="on",
                              mesh_rule_shards=2)
    try:
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) \
            == int(FilterResult.OK)
        shim = _conn(client, mod, 1)
        before = [
            _verdict(shim, f)[0]
            for f in (b"READ /public/a\r\n", b"WRITE /tmp/x\r\n",
                      b"HALT\r\n")
        ]
        assert before == [True, False, True]
        epoch0 = svc.policy_epoch
        calls = [0]
        orig = rulesharding.compile_patterns

        def shard_k_dies(patterns):
            calls[0] += 1
            if calls[0] >= 2:  # shard k=1 of the staged 2-shard build
                raise RuntimeError("injected shard-build failure")
            return orig(patterns)

        rulesharding.compile_patterns = shard_k_dies
        try:
            assert client.policy_update(
                mod, [_policy("pol", POLICY_B)]
            ) == int(FilterResult.POLICY_DROP)
        finally:
            rulesharding.compile_patterns = orig
        assert calls[0] >= 2  # the failure really hit mid-build
        assert svc.policy_epoch == epoch0
        fails = svc.status()["policy"]["swap_failures"]
        assert fails.get("device-build", 0) >= 1
        # The old sharded epoch serves bit-identically, still meshed.
        after = [
            _verdict(shim, f)[0]
            for f in (b"READ /public/a\r\n", b"WRITE /tmp/x\r\n",
                      b"HALT\r\n")
        ]
        assert after == before
        eng = next(iter(svc._engines.values()))
        assert isinstance(eng.model, ShardedVerdictModel)
        assert svc.status()["mesh"]["active"]
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


@pytest.mark.slow
def test_churn_soak_long(tmp_path):
    """60s chaos soak (slow-marked): thousands of verdicts, dozens of
    epochs, endpoint churn, identity storm, kvstore failover."""
    _churn_soak(tmp_path, duration_s=60.0, updates_per_s=8.0,
                n_conns=16)

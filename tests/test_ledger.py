"""Device-economics ledger (PR 20): formation-trigger provenance, the
per-round-not-per-entry stamp cost, the wire/CLI/status round-trip, and
the evict-then-reuse compile classification.

The compile half of the ledger is soaked in test_policy_churn.py (warm
churn performs ZERO compiles, asserted as a window delta) and
test_multichip_serving.py (mesh-reshape/repromotion causes).  This file
pins the rest of the contract:

  - every batch-formation trigger the dispatcher can issue
    (size-full / flush / deadline / idle-greedy / cut-through) brands
    the popping thread with exactly ONE provenance stamp per round,
    regardless of how many entries the round carries;
  - the service folds that stamp into the ledger once per ROUND;
  - MSG_LEDGER / MSG_LEDGER_REPLY, ``SidecarClient.ledger()``,
    ``cilium sidecar ledger`` and ``status()["ledger"]`` all surface
    the same census;
  - re-tracing a shape the cache EVICTED records ``churn-new-shape``,
    never ``cold`` (the evict-then-reuse cost is churn, not a cold
    start).
"""

import json
import threading
import time

import pytest

from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.types import FilterResult
from cilium_tpu.sidecar.dispatch import BatchDispatcher

from test_policy_churn import POLICY_A, POLICY_B, _conn, _policy, _start


# --- trigger branding (dispatcher unit level) ------------------------------


class _PopRecorder:
    """Worker-side capture of the per-round provenance stamp: one
    record per process() call, straight off the popping thread."""

    def __init__(self):
        self.rounds = []
        self.gate = threading.Event()
        self.gate.set()

    def __call__(self, batch):
        self.gate.wait(5.0)
        t = threading.current_thread()
        self.rounds.append(
            (list(batch), dict(t._disp_pop), t._disp_round)
        )


def test_dispatcher_brands_idle_greedy_and_size_full():
    """Greedy dispatcher: the first lone item pops as idle-greedy;
    work that accumulates to max_batch while the worker is busy pops
    as size-full — and a multi-entry pop carries exactly ONE stamp."""
    rec = _PopRecorder()
    d = BatchDispatcher(rec, max_batch=4, timeout_ms=0.0,
                        name="ledger-greedy").start()
    try:
        rec.gate.clear()
        assert d.submit("a", nbytes=10)
        deadline = time.monotonic() + 5
        while not rec.rounds and time.monotonic() < deadline:
            time.sleep(0.005)
        # Worker is now parked inside process("a"); fill past max.
        for i in range(4):
            assert d.submit(f"b{i}", nbytes=5)
        rec.gate.set()
        assert d.flush(timeout=5.0)
        assert len(rec.rounds) == 2, rec.rounds
        (b0, pop0, rid0), (b1, pop1, rid1) = rec.rounds
        assert b0 == ["a"]
        assert pop0["trigger"] == "idle-greedy"
        assert pop0["bytes"] == 10
        assert b1 == ["b0", "b1", "b2", "b3"]
        assert pop1["trigger"] == "size-full"
        assert pop1["depth"] == 4
        assert pop1["bytes"] == 20
        assert pop1["age_s"] >= 0.0
        # One stamp per ROUND: the 4-entry pop produced one record
        # with one provenance dict, and round ids are distinct.
        assert rid0 != rid1
    finally:
        d.stop()


def test_dispatcher_brands_deadline_and_flush():
    """Pipelined dispatcher: an unfilled batch pops at the deadline —
    its age-at-pop is at least the configured wait; work still queued
    when stop() lands drains as a flush pop."""
    rec = _PopRecorder()
    d = BatchDispatcher(rec, max_batch=1024, timeout_ms=30.0,
                        name="ledger-deadline").start()
    try:
        assert d.submit("slow", nbytes=7)
        deadline = time.monotonic() + 5
        while not rec.rounds and time.monotonic() < deadline:
            time.sleep(0.005)
        assert rec.rounds and rec.rounds[0][1]["trigger"] == "deadline"
        assert rec.rounds[0][1]["age_s"] >= 0.025
    finally:
        d.stop()
    # Flush: a deadline far in the future cannot fire, so the only way
    # the queued pair pops is the stop() drain.
    rec2 = _PopRecorder()
    d2 = BatchDispatcher(rec2, max_batch=1024, timeout_ms=60_000.0,
                         name="ledger-flush").start()
    try:
        assert d2.submit("x1")
        assert d2.submit("x2")
        d2.stop()
        assert rec2.rounds, "flush drain never popped"
        assert rec2.rounds[0][0] == ["x1", "x2"]
        assert rec2.rounds[0][1]["trigger"] == "flush"
    finally:
        d2.stop()


def test_dispatcher_brands_cut_through_inline():
    """begin_inline_round brands the CALLING thread as a cut-through
    round (depth/age zero by construction, bytes = the inline item's
    payload) and end_inline_round releases the round state."""
    d = BatchDispatcher(lambda b: None, max_batch=8, timeout_ms=0.0,
                        name="ledger-inline")
    rid = d.begin_inline_round(["inline"], nbytes=33)
    assert rid is not None
    t = threading.current_thread()
    try:
        assert t._disp_round == rid
        assert t._disp_pop == {
            "trigger": "cut-through", "depth": 0, "age_s": 0.0,
            "bytes": 33,
        }
    finally:
        d.end_inline_round(rid)
        d.stop()
    # A second inline round is refused while one is busy.
    rid2 = d.begin_inline_round(["x"])
    assert rid2 is not None
    assert d.begin_inline_round(["y"]) is None
    d.end_inline_round(rid2)


# --- service-level formation stamps ----------------------------------------


def test_service_stamps_formation_once_per_round(tmp_path):
    """A greedy service's inline round is stamped cut-through exactly
    once per ROUND: a payload carrying three whole frames lands as one
    round, one item, all three frames' bytes — never three stamps."""
    svc = client = None
    try:
        svc, client, mod = _start(tmp_path, name="ledger-form")
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) \
            == int(FilterResult.OK)
        shim = _conn(client, mod, 1)
        payload = b"READ /public/a\r\nREAD /public/b\r\nREAD /public/c\r\n"
        assert shim.on_io(False, payload)[0] == int(FilterResult.OK)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            form = svc.ledger.formation()
            if form.get("cut-through", {}).get("rounds"):
                break
            time.sleep(0.01)
        ct = svc.ledger.formation()["cut-through"]
        assert ct["rounds"] == 1, ct
        assert ct["items"] == 1, ct  # one batch entry, three frames
        assert ct["bytes"] == len(payload), ct
        assert 0.0 < ct["occ_mean"] <= 1.0
        rounds0 = ct["rounds"]
        # Each further dispatch adds exactly one stamped round.
        for fr in (b"READ /public/d\r\n", b"READ /public/e\r\n"):
            assert shim.on_io(False, fr)[0] == int(FilterResult.OK)
        # The stamp rides the round's close, which may still be running
        # on the service's side when the answer has already arrived.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ct = svc.ledger.formation()["cut-through"]
            if ct["rounds"] >= rounds0 + 2:
                break
            time.sleep(0.01)
        assert ct["rounds"] == rounds0 + 2, ct
        # The ledger status tallies every stamped round.
        assert svc.ledger.status()["rounds"] >= rounds0 + 2
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


# --- wire / CLI / status round-trip ----------------------------------------


def test_ledger_wire_cli_status_roundtrip(tmp_path, capsys):
    """MSG_LEDGER round-trip: SidecarClient.ledger() returns the same
    census the service holds, --since/--cause filter server-side, the
    CLI renders both JSON and human output, the status surface carries
    the ledger section, and malformed ledger requests never kill the
    control connection."""
    from cilium_tpu.cli import main as cli_main
    from cilium_tpu.sidecar import wire as sw

    svc = client = None
    try:
        svc, client, mod = _start(tmp_path, name="ledger-wire")
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) \
            == int(FilterResult.OK)
        shim = _conn(client, mod, 1)
        assert shim.on_io(False, b"READ /public/a\r\n")[0] == int(
            FilterResult.OK
        )
        # One churn flip so the census carries a churn cause too.
        assert client.policy_update(mod, [_policy("pol", POLICY_B)]) \
            == int(FilterResult.OK)
        assert shim.on_io(False, b"READ /public/a\r\n")[0] == int(
            FilterResult.OK
        )

        out = client.ledger(n=100)
        truth = svc.ledger.dump(n=100)
        assert out["ledger"]["compiles"] == truth["ledger"]["compiles"]
        assert out["ledger"]["by_cause"] == truth["ledger"]["by_cause"]
        assert [e["seq"] for e in out["compiles"]] == [
            e["seq"] for e in truth["compiles"]
        ]
        assert out["formation"].keys() == truth["formation"].keys()
        events = out["compiles"]
        builds = [e for e in events if e["kind"] == "engine-build"]
        assert builds and builds[0]["cause"] == "cold"
        assert any(e["cause"] == "churn-vocab" for e in events)
        # since: strictly-after filter; cause: exact-match filter.
        seq0 = events[0]["seq"]
        after = client.ledger(n=100, since=seq0)["compiles"]
        assert after and all(e["seq"] > seq0 for e in after)
        vocab = client.ledger(n=100, cause="churn-vocab")["compiles"]
        assert vocab and all(
            e["cause"] == "churn-vocab" for e in vocab
        )

        # status() carries the same counters plus formation.
        st = client.status()["ledger"]
        assert st["compiles"] == truth["ledger"]["compiles"]
        assert st["churn_compiles"] >= 1
        assert "formation" in st and "dispatch_path_compiles" in st
        assert st["executables_resident"] >= 1

        # CLI: JSON mode parses to the same payload shape.
        rc = cli_main(["sidecar", "ledger", "--address",
                       svc.socket_path, "--json"])
        assert rc == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed.keys() == {"compiles", "formation", "ledger"}
        assert parsed["ledger"]["compiles"] == truth["ledger"]["compiles"]
        # CLI: human mode names the census and each event's cause.
        rc = cli_main(["sidecar", "ledger", "--address",
                       svc.socket_path])
        assert rc == 0
        human = capsys.readouterr().out
        assert "compile(s)" in human and "cold" in human
        assert "formation [" in human
        rc = cli_main(["sidecar", "ledger", "--address",
                       svc.socket_path, "--cause", "churn-vocab"])
        assert rc == 0
        assert "churn-vocab" in capsys.readouterr().out
        # CLI: the status printer shows the ledger section.
        rc = cli_main(["sidecar", "status", "--address",
                       svc.socket_path])
        assert rc == 0
        assert "ledger:" in capsys.readouterr().out

        # Malformed ledger payloads (valid JSON, wrong shape) degrade
        # to the defaults and the connection keeps serving.
        for bad in (b"[1]", b'{"n": null}', b'{"since": "x"}'):
            got = client._control_rpc(
                lambda b=bad: (sw.MSG_LEDGER, b), sw.MSG_LEDGER_REPLY
            )
            assert "ledger" in json.loads(got.decode())
        assert client.status()["connections"] >= 1  # still alive
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


# --- evict-then-reuse classification ---------------------------------------


def test_evict_then_reuse_records_churn_new_shape(tmp_path):
    """Re-tracing a shape the executable cache EVICTED is churn cost,
    not a cold start: with the shape cache clamped to one entry,
    alternating two table shapes forces evict-then-reuse every flip —
    the FIRST trace of each shape is its build's prewarm, every
    re-trace (the flip's prewarm re-warming the evicted shape) records
    churn-new-shape, and the resident gauge never exceeds the clamp."""
    svc = client = None
    try:
        svc, client, mod = _start(tmp_path, name="ledger-evict")
        assert client.policy_update(mod, [_policy("pol", POLICY_A)]) \
            == int(FilterResult.OK)
        shim = _conn(client, mod, 1)
        assert shim.on_io(False, b"READ /public/a\r\n")[0] == int(
            FilterResult.OK
        )
        svc.SHAPE_CACHE_MAX = 1  # every new shape now evicts the last
        for pol in (POLICY_B, POLICY_A, POLICY_B):
            assert client.policy_update(mod, [_policy("pol", pol)]) \
                == int(FilterResult.OK)
            assert shim.on_io(False, b"READ /public/a\r\n")[0] == int(
                FilterResult.OK
            )
        gather = [e for e in svc.ledger.events(n=100)
                  if e["kind"] == "jit" and e.get("role") == "gather"]
        assert len(gather) == 4, gather
        # A and B first traces, then A and B re-traces are
        # churn-new-shape: the ledger remembers the eviction.
        assert [e["cause"] for e in gather] == [
            "prewarm", "prewarm", "churn-new-shape", "churn-new-shape",
        ], gather
        shapes = [e["shape"] for e in gather]
        assert shapes[0] == shapes[2] and shapes[1] == shapes[3]
        assert shapes[0] != shapes[1]
        assert svc.ledger.status()["executables_resident"] <= 2
        assert svc.ledger.status()["by_cause"]["churn-new-shape"] >= 2
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()

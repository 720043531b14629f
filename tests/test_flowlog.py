"""Flow-level verdict observability (PR 5).

Covers the flowlog ring (bounds, filters, follow cursor, metrics,
option-gated monitor events), device-vs-host rule-attribution
bit-identity under a literal+regex+nfa stress mix, the end-to-end
observe surface (`cilium observe` / MSG_OBSERVE) in both completion
modes, the vec→host fault ladder, datapath/prefilter records, and the
flowdebug gate on the newly-routed per-flow debug sites.
"""

from __future__ import annotations

import json
import logging
import time

import numpy as np
import pytest

from cilium_tpu.flowlog import (
    CODE_DENIED,
    CODE_FORWARDED,
    CODE_SHED,
    FlowLog,
)
from cilium_tpu.monitor import Monitor
from cilium_tpu.utils import metrics as m
from cilium_tpu.utils.option import (
    OPTION_POLICY_VERDICT_NOTIFY,
    DaemonConfig,
    OptionMap,
)


def _mk_policy(name="obs-pol"):
    from cilium_tpu.proxylib.npds import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )

    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=[1, 3],
                        l7_proto="r2d2",
                        l7_rules=[
                            {"cmd": "READ", "file": "/public/.*"},
                            {"cmd": "HALT"},
                        ],
                    ),
                    PortNetworkPolicyRule(
                        l7_proto="r2d2",
                        l7_rules=[{"cmd": "WRITE", "file": "/tmp/x"}],
                    ),
                ],
            )
        ],
    )


# --- ring unit tests -------------------------------------------------------

def test_flowlog_ring_bounds_query_and_stats():
    fl = FlowLog(capacity=10)
    for k in range(8):
        fl.add_round(
            "vec",
            np.asarray([k, k + 100], np.int64),
            np.asarray([CODE_FORWARDED, CODE_DENIED], np.int8),
            np.asarray([2, -1], np.int32),
            kinds=("literal", "regex", "nfa"),
        )
    st = fl.stats()
    assert st["records"] <= 10
    assert st["records_total"] == 16 and st["rounds_total"] == 8
    # Newest first without a cursor.
    recs = fl.query(n=4)
    assert [r["seq"] for r in recs] == sorted(
        (r["seq"] for r in recs), reverse=True
    )
    # Filters compose.
    denied = fl.query(n=100, verdict="Denied")
    assert denied and all(r["verdict"] == "Denied" for r in denied)
    assert all(r["rule_id"] == -1 and r["match_kind"] == "" for r in denied)
    allowed = fl.query(n=100, verdict="Forwarded")
    assert allowed and all(
        r["rule_id"] == 2 and r["match_kind"] == "nfa" for r in allowed
    )
    by_rule = fl.query(n=100, rule=2)
    assert by_rule and all(r["verdict"] == "Forwarded" for r in by_rule)
    by_conn = fl.query(n=100, conn=107)
    assert len(by_conn) == 1 and by_conn[0]["conn_id"] == 107
    # Unknown verdict names (raw-JSON wire filter) match NOTHING —
    # returning unfiltered records would read as "everything matched".
    assert fl.query(n=100, verdict="denied") == []
    assert fl.query(n=100, verdict="bogus") == []


def test_flowlog_follow_cursor_ascending_exactly_once():
    fl = FlowLog(capacity=100)
    fl.add_round("vec", np.asarray([1], np.int64),
                 np.asarray([CODE_FORWARDED], np.int8))
    cursor = fl.stats()["next_seq"] - 1
    fl.add_round("vec", np.asarray([2, 3], np.int64),
                 np.asarray([CODE_FORWARDED, CODE_DENIED], np.int8))
    fl.add_round("oracle", np.asarray([4], np.int64),
                 np.asarray([CODE_SHED], np.int8))
    out = fl.query(n=100, since=cursor)
    seqs = [r["seq"] for r in out]
    assert seqs == sorted(seqs) and len(out) == 3
    assert all(s > cursor for s in seqs)
    # Advancing the cursor past everything yields nothing.
    assert fl.query(n=100, since=max(seqs)) == []


def test_flowlog_conn_meta_survives_close():
    fl = FlowLog(capacity=100)
    fl.register_conn(7, "pol", True, 1, 2, "a:1", "b:2", "r2d2", 80)
    fl.add_round("vec", np.asarray([7], np.int64),
                 np.asarray([CODE_FORWARDED], np.int8))
    fl.forget_conn(7)
    rec = fl.query(n=1)[0]
    assert rec["policy"] == "pol" and rec["src_identity"] == 1
    assert rec["dport"] == 80


def test_flow_verdicts_metric_aggregated_per_round():
    base_fwd = m.FlowVerdictsTotal.get("Forwarded", "vec", "literal")
    base_deny = m.FlowVerdictsTotal.get("Denied", "vec", "")
    fl = FlowLog(capacity=100)
    fl.add_round(
        "vec",
        np.arange(6, dtype=np.int64),
        np.asarray([0, 0, 0, 1, 1, 0], np.int8),
        np.asarray([0, 0, 1, -1, -1, 0], np.int32),
        kinds=("literal", "regex"),
    )
    assert m.FlowVerdictsTotal.get("Forwarded", "vec", "literal") == base_fwd + 3
    assert m.FlowVerdictsTotal.get("Denied", "vec", "") == base_deny + 2
    assert m.FlowVerdictsTotal.get("Forwarded", "vec", "regex") >= 1


# --- satellite: OPTION_POLICY_VERDICT_NOTIFY gates monitor events ----------

def test_policy_verdict_notify_option_toggle():
    """The previously-dead OPTION_POLICY_VERDICT_NOTIFY now gates the
    flow log's POLICY-VERDICT monitor events (same triage shape as PR
    4's dead-metric tests): off → silent, on → events with rule
    attribution, off again → silent."""
    from cilium_tpu.monitor.monitor import MSG_TYPE_POLICY_VERDICT

    opts = OptionMap()
    events = []
    mon = Monitor()
    mon.add_listener(events.append, queued=False)
    fl = FlowLog(capacity=100, opts=opts, monitor=mon)
    fl.register_conn(5, "pol", True, 1, 2, "a:1", "b:2", "r2d2", 80)

    def round_():
        fl.add_round(
            "vec", np.asarray([5, 5], np.int64),
            np.asarray([CODE_FORWARDED, CODE_DENIED], np.int8),
            np.asarray([1, -1], np.int32), kinds=("literal", "regex"),
        )

    round_()
    assert events == []  # default off: the gate holds

    assert opts.set(OPTION_POLICY_VERDICT_NOTIFY, True)
    round_()
    # BOTH directions are POLICY-VERDICT events (deny too — the
    # reference's send_policy_verdict_notify covers both; an extra
    # MSG_TYPE_DROP here would double-count the feeding layer's own
    # drop sample).
    assert all(e.type == MSG_TYPE_POLICY_VERDICT for e in events)
    assert {e.payload["allowed"] for e in events} == {True, False}
    allow_ev = next(e for e in events if e.payload["allowed"])
    assert allow_ev.payload["rule_id"] == 1
    assert allow_ev.payload["match_kind"] == "regex"
    assert allow_ev.payload["policy"] == "pol"

    events.clear()
    assert opts.set(OPTION_POLICY_VERDICT_NOTIFY, False)
    round_()
    assert events == []


# --- device-vs-host rule attribution bit-identity --------------------------

def test_r2d2_attr_parity_with_host_oracle():
    from cilium_tpu.models.r2d2 import build_r2d2_model
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.proxylib.parsers.r2d2 import R2d2RequestData

    inst.reset_module_registry()
    mod = inst.open_module([], True)
    ins = inst.find_instance(mod)
    ins.policy_update([_mk_policy()])
    pi = ins.policy_map()["obs-pol"]
    model = build_r2d2_model(pi, True, 80)
    assert model.match_kinds == ("regex", "literal", "regex")

    msgs = [
        (b"READ /public/a.txt\r\n", 1),
        (b"HALT\r\n", 3),
        (b"WRITE /tmp/x\r\n", 9),
        (b"READ /secret\r\n", 1),
        (b"WRITE /tmp/y\r\n", 1),
        (b"HALT\r\n", 9),  # remote 9 not in [1,3] for rule 0/1
        (b"READ /public/b\r\n", 3),
    ]
    F, L = len(msgs), 64
    data = np.zeros((F, L), np.uint8)
    lens = np.zeros(F, np.int32)
    remotes = np.zeros(F, np.int32)
    for i, (msg, rid) in enumerate(msgs):
        data[i, : len(msg)] = np.frombuffer(msg, np.uint8)
        lens[i] = len(msg)
        remotes[i] = rid
    _, _, allow, rule = model.verdicts_attr(data, lens, remotes)
    allow, rule = np.asarray(allow), np.asarray(rule)
    for i, (msg, rid) in enumerate(msgs):
        parts = msg[:-2].decode().split(" ")
        l7 = R2d2RequestData(parts[0], parts[1] if len(parts) > 1 else "")
        hok, hrule = pi.matches_at(True, 80, rid, l7)
        assert bool(allow[i]) == hok, msg
        assert int(rule[i]) == hrule, (msg, int(rule[i]), hrule)
    inst.reset_module_registry()


def test_http_attr_parity_stress_mix():
    """Literal + regex(DFA) + nfa rules with remote restrictions and a
    wildcard-port set behind the exact-port set: the device argmax and
    the host matches_at walk must name the same row for every request
    in the corpus — the bit-identity contract of rule attribution."""
    from cilium_tpu.models.http import build_http_model_for_port
    from cilium_tpu.ops.nfa import DeviceNfa
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.proxylib.npds import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )
    from cilium_tpu.proxylib.parsers.http import parse_head

    nfa_path = "/n/(a|b)*a" + "(a|b)" * 7 + "/x"
    pol = NetworkPolicy(
        name="http-pol",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=[1],
                        http_rules=[
                            {"method": "GET", "path": "/lit/.*"},
                            {"method": "GET|HEAD", "path": ""},
                        ],
                    ),
                    PortNetworkPolicyRule(
                        http_rules=[
                            {"method": "POST",
                             "path": "/g/[a-z0-9]+/item/.*"},
                            {"method": "PUT", "path": nfa_path},
                        ],
                    ),
                ],
            ),
            PortNetworkPolicy(
                port=0,  # wildcard set: rows offset past the exact set
                rules=[
                    PortNetworkPolicyRule(
                        http_rules=[{"method": "DELETE", "path": "/wc/.*"}],
                    ),
                ],
            ),
        ],
    )
    inst.reset_module_registry()
    mod = inst.open_module([], True)
    ins = inst.find_instance(mod)
    ins.policy_update([pol])
    pi = ins.policy_map()["http-pol"]
    model = build_http_model_for_port(pi, True, 80)
    # The mix exercises all three compiled tiers.
    kinds = set(model.match_kinds)
    assert {"literal", "regex"} <= kinds or {"literal", "nfa"} <= kinds

    corpus = [
        # (head, remote) — allowed and denied, across tiers + cascade
        (b"GET /lit/a HTTP/1.1\r\n\r\n", 1),        # rule 0 (literal)
        (b"GET /lit/a HTTP/1.1\r\n\r\n", 9),        # remote 9: falls to..?
        (b"HEAD /any HTTP/1.1\r\n\r\n", 1),          # rule 1 (alt literal)
        (b"POST /g/abc/item/1 HTTP/1.1\r\n\r\n", 9),  # rule 2 (regex)
        (b"PUT /n/ababaabababab/x HTTP/1.1\r\n\r\n", 2),  # nfa rule
        (b"PUT /n/bbbb/x HTTP/1.1\r\n\r\n", 2),      # nfa non-match
        (b"DELETE /wc/z HTTP/1.1\r\n\r\n", 4),       # wildcard-port rule
        (b"PATCH /lit/a HTTP/1.1\r\n\r\n", 1),       # deny
        (b"GET /other HTTP/1.1\r\n\r\n", 1),         # rule 1 (method any-path)
    ]
    width = 128
    F = len(corpus)
    data = np.zeros((F, width), np.uint8)
    lens = np.zeros(F, np.int32)
    remotes = np.zeros(F, np.int32)
    for i, (head, rid) in enumerate(corpus):
        data[i, : len(head)] = np.frombuffer(head, np.uint8)
        lens[i] = len(head)
        remotes[i] = rid
    _, _, allow, rule = model.verdicts_attr(data, lens, remotes)
    allow, rule = np.asarray(allow), np.asarray(rule)
    hits = 0
    for i, (head, rid) in enumerate(corpus):
        head_data = parse_head(head[: head.find(b"\r\n\r\n") + 4])
        hok, hrule = pi.matches_at(True, 80, rid, head_data)
        assert bool(allow[i]) == hok, head
        assert int(rule[i]) == hrule, (head, int(rule[i]), hrule)
        hits += hok
    assert 0 < hits < F  # corpus covers both outcomes
    inst.reset_module_registry()


# --- end-to-end: observe over the sidecar seam -----------------------------

def _start_service(tmp_path, greedy: bool, **cfg_kw):
    from cilium_tpu.proxylib import FilterResult
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.sidecar import SidecarClient, VerdictService

    inst.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=0.0 if greedy else 2.0,
        batch_flows=256,
        **cfg_kw,
    )
    svc = VerdictService(
        str(tmp_path / f"obs-{greedy}.sock"), cfg
    ).start()
    client = SidecarClient(svc.socket_path, timeout=60.0)
    mod = client.open_module([])
    assert client.policy_update(mod, [_mk_policy("sidecar-pol")]) == int(
        FilterResult.OK
    )
    res, shim = client.new_connection(
        mod, "r2d2", 4242, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
        "sidecar-pol",
    )
    assert res == int(FilterResult.OK)
    return svc, client, shim


def _wait_records(client, want: int, timeout=10.0, **filters):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = client.observe(n=100, **filters)
        if len(out["records"]) >= want:
            return out
        time.sleep(0.02)
    return client.observe(n=100, **filters)


@pytest.mark.parametrize("greedy", [False, True])
def test_observe_e2e_allowed_and_denied_both_modes(tmp_path, greedy):
    """Acceptance: `cilium observe` returns the record for a dropped
    AND an allowed flow in both completion modes, with the device-path
    rule attribution matching the host oracle."""
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.proxylib.parsers.r2d2 import R2d2RequestData

    svc, client, shim = _start_service(tmp_path, greedy)
    try:
        shim.on_io(False, b"READ /public/a.txt\r\n")
        shim.on_io(False, b"READ /secret\r\n")
        out = _wait_records(client, 2)
        recs = out["records"]
        allowed = [r for r in recs if r["verdict"] == "Forwarded"]
        denied = [r for r in recs if r["verdict"] == "Denied"]
        assert allowed and denied
        a, d = allowed[0], denied[0]
        assert a["path"] == "vec" and d["path"] == "vec"
        assert a["conn_id"] == 4242 and a["policy"] == "sidecar-pol"
        assert a["match_kind"] == "regex"
        # Device attribution == host oracle walk.
        ins = inst.find_instance(1)
        hpi = ins.policy_map()["sidecar-pol"]
        hok, hrule = hpi.matches_at(
            True, 80, 1, R2d2RequestData("READ", "/public/a.txt")
        )
        assert hok and a["rule_id"] == hrule == 0
        assert d["rule_id"] == -1
        # Server-side filters.
        filt = client.observe(n=10, verdict="Denied")
        assert filt["records"] and all(
            r["verdict"] == "Denied" for r in filt["records"]
        )
        filt = client.observe(n=10, rule=0)
        assert filt["records"] and all(
            r["rule_id"] == 0 for r in filt["records"]
        )
        # Malformed observe payloads never kill the read loop.
        from cilium_tpu.sidecar import wire as sw

        for bad in (b"[1]", b'{"n": "x"}', b"\xff\xfe"):
            got = client._control_rpc(
                lambda b=bad: (sw.MSG_OBSERVE, b), sw.MSG_OBSERVE_REPLY
            )
            assert "records" in json.loads(got.decode())
        assert client.status()["flowlog"]["records_total"] >= 2
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_observe_fault_ladder_rule_identity(tmp_path):
    """Acceptance: across the fault ladder (vec → host fallback), every
    record's rule_id matches the host oracle's walk — the attribution
    survives quarantine because the host path IS the same flattened
    row order."""
    from cilium_tpu.proxylib import instance as inst

    # No re-probe while the test runs: the quarantine holds for both
    # host-path frames (a prewarmed probe heals within milliseconds).
    svc, client, shim = _start_service(
        tmp_path, greedy=False, device_reprobe_interval_s=1e9
    )
    try:
        shim.on_io(False, b"HALT\r\n")
        out = _wait_records(client, 1, path="vec")
        vec = [r for r in out["records"] if r["verdict"] == "Forwarded"]
        assert vec and vec[0]["rule_id"] == 1
        assert vec[0]["match_kind"] == "literal"

        # Quarantine the device: the next rounds render via the host
        # fallback (oracle demotion), path label "host".
        svc.guard.record_stall("test-ladder")
        assert svc.guard.quarantined
        shim.on_io(False, b"HALT\r\n")
        shim.on_io(False, b"READ /secret\r\n")
        # The two frames land in separate rounds: wait for BOTH host
        # records before asserting on them.
        out = _wait_records(client, 2, path="host")
        host = out["records"]
        h_allow = [r for r in host if r["verdict"] == "Forwarded"]
        h_deny = [r for r in host if r["verdict"] == "Denied"]
        assert h_allow and h_allow[0]["rule_id"] == 1  # same deciding row
        assert h_deny and h_deny[0]["rule_id"] == -1
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_flow_observe_disabled_no_records(tmp_path):
    svc, client, shim = _start_service(
        tmp_path, greedy=False, flow_observe=False
    )
    from cilium_tpu.proxylib import instance as inst

    try:
        assert svc.flowlog is None
        shim.on_io(False, b"HALT\r\n")
        time.sleep(0.2)
        out = client.observe(n=10)
        assert out["records"] == [] and out["stats"].get("disabled")
        assert client.status()["flowlog"] is None
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_cli_observe(tmp_path, capsys):
    from cilium_tpu.cli import main as cli_main
    from cilium_tpu.proxylib import instance as inst

    svc, client, shim = _start_service(tmp_path, greedy=False)
    try:
        shim.on_io(False, b"READ /public/cli.txt\r\n")
        shim.on_io(False, b"READ /nope\r\n")
        _wait_records(client, 2)
        rc = cli_main(["observe", "--address", svc.socket_path])
        assert rc == 0
        out = capsys.readouterr().out
        assert "FORWARDED" in out and "DENIED" in out
        assert "rule=0 (regex)" in out and "[vec]" in out
        rc = cli_main(
            ["observe", "--address", svc.socket_path, "--json",
             "--verdict", "Denied"]
        )
        assert rc == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["records"] and all(
            r["verdict"] == "Denied" for r in parsed["records"]
        )
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


# --- R5 coverage: the MSG_OBSERVE pair is wired on both seam ends ----------

def test_msg_observe_pair_covered_both_ends():
    """The satellite contract behind lint R5: both seam ends reference
    the new MSG_OBSERVE/MSG_OBSERVE_REPLY constants (the tree gate in
    test_static_analysis enforces it structurally; this pins the
    intent against renames)."""
    import cilium_tpu.sidecar.client as client_mod
    import cilium_tpu.sidecar.service as service_mod
    import inspect

    for mod in (client_mod, service_mod):
        src = inspect.getsource(mod)
        assert "MSG_OBSERVE" in src and "MSG_OBSERVE_REPLY" in src


# --- monitor formatting (satellite: round-trip with attribution) -----------

def test_monitor_format_rule_attribution_round_trip():
    from cilium_tpu.monitor import format_event
    from cilium_tpu.monitor.monitor import (
        MSG_TYPE_DROP,
        MSG_TYPE_POLICY_VERDICT,
        MSG_TYPE_TRACE,
        MonitorEvent,
    )

    allow = MonitorEvent(
        MSG_TYPE_POLICY_VERDICT,
        {"src_identity": 1, "dst_identity": 2, "dport": 80, "proto": 6,
         "allowed": True, "rule_id": 3, "match_kind": "literal",
         "policy": "web"},
        timestamp=0.0,
    )
    line = format_event(allow)
    assert "POLICY-VERDICT: ALLOW identity 1 -> 2 dport 80/tcp" in line
    assert "rule=3 (literal)" in line and "policy=web" in line

    deny = MonitorEvent(
        MSG_TYPE_POLICY_VERDICT,
        {"src_identity": 1, "dst_identity": 2, "dport": 80, "proto": 6,
         "allowed": False, "rule_id": -1, "match_kind": "",
         "policy": "web"},
        timestamp=0.0,
    )
    assert "POLICY-VERDICT: DENY identity 1 -> 2" in format_event(deny)

    drop = MonitorEvent(
        MSG_TYPE_DROP,
        {"src_identity": 5, "dst_identity": 6, "dport": 443, "proto": 6,
         "allowed": False, "rule_id": -1, "match_kind": "",
         "policy": "web"},
        timestamp=0.0,
    )
    dline = format_event(drop)
    assert "DROP: identity 5 -> 6 dport 443/tcp" in dline
    assert "rule=" not in dline  # denied: no deciding rule to name
    assert "policy=web" in dline

    # Events WITHOUT attribution fields keep the legacy rendering.
    legacy = format_event(
        MonitorEvent(
            MSG_TYPE_DROP,
            {"src_identity": 1, "dst_identity": 2, "dport": 80,
             "proto": 6},
            timestamp=0.0,
        )
    )
    assert legacy.endswith("dport 80/tcp")

    # Round-trip through the event dict codec (the monitor socket path).
    back = MonitorEvent.from_dict(
        json.loads(json.dumps(allow.to_dict()))
    )
    assert format_event(back)[9:] == line[9:]  # timestamps differ fmt

    # SLOW-VERDICT trace lines still format (regression guard).
    tline = format_event(
        MonitorEvent(
            MSG_TYPE_TRACE,
            {"slow_verdict": {"path": "vec", "seq": 1, "conn_id": 2,
                              "entries": 3, "e2e_us": 1500.0,
                              "stages_us": {"queue": 1200.0}}},
            timestamp=0.0,
        )
    )
    assert "SLOW-VERDICT" in tline


# --- datapath layers -------------------------------------------------------

def test_datapath_account_verdicts_flow_records_and_option_gate():
    from cilium_tpu.datapath.notify import account_verdicts
    from cilium_tpu.maps.metricsmap import MetricsMap
    from cilium_tpu.monitor.monitor import MSG_TYPE_POLICY_VERDICT

    opts = OptionMap()
    events = []
    mon = Monitor()
    mon.add_listener(events.append, queued=False)
    fl = FlowLog(capacity=100)
    out = {
        "verdict": np.asarray([0, 1, 0, 2]),  # FORWARD/DROP/FORWARD/TO_PROXY
        "dst_identity": np.asarray([10, 11, 12, 13]),
        "new_dport": np.asarray([80, 443, 80, 80]),
        "established": np.asarray([True, False, False, False]),
        "proxy_port": np.asarray([0, 0, 0, 15001]),
    }
    counts = account_verdicts(
        out, MetricsMap(), monitor=mon,
        proto=np.asarray([6, 6, 6, 6]),
        src_identity=np.asarray([1, 2, 3, 4]),
        flowlog=fl, opts=opts,
    )
    assert counts == {"forwarded": 2, "dropped": 1, "proxied": 1}
    # Option off: only the drop sample reached the monitor.
    assert all(e.type != MSG_TYPE_POLICY_VERDICT for e in events)
    recs = fl.query(n=10)
    assert len(recs) == 4
    denied = [r for r in recs if r["verdict"] == "Denied"]
    assert len(denied) == 1 and denied[0]["drop_reason"] == 133
    assert denied[0]["ct_state"] == "new"
    est = [r for r in recs if r.get("ct_state") == "established"]
    assert len(est) == 1 and est[0]["verdict"] == "Forwarded"
    assert all(r["path"] == "datapath" and r["match_kind"] == "l4"
               for r in recs)

    # Option on: allowed verdicts now notify too.
    opts.set(OPTION_POLICY_VERDICT_NOTIFY, True)
    events.clear()
    account_verdicts(
        out, MetricsMap(), monitor=mon,
        proto=np.asarray([6, 6, 6, 6]),
        src_identity=np.asarray([1, 2, 3, 4]),
        opts=opts,
    )
    assert sum(e.type == MSG_TYPE_POLICY_VERDICT for e in events) == 3


def test_prefilter_filter_batch_records_xdp_drops():
    import ipaddress

    from cilium_tpu.datapath.prefilter import PreFilter

    pf = PreFilter()
    pf.insert(1, ["198.51.100.0/24"])
    bad = int(ipaddress.ip_address("198.51.100.7"))
    good = int(ipaddress.ip_address("192.0.2.1"))
    saddr = np.asarray([good, bad, good], np.int64).astype(np.int32)
    fl = FlowLog(capacity=100)
    keep = pf.filter_batch(saddr, flowlog=fl)
    assert list(keep) == [True, False, True]
    recs = fl.query(n=10)
    assert len(recs) == 1
    assert recs[0]["path"] == "xdp" and recs[0]["verdict"] == "Denied"
    assert recs[0]["match_kind"] == "l3"
    assert recs[0]["reason"] == "prefilter"


# --- flowdebug gate on the newly-routed sites ------------------------------

def test_flowdebug_gate_new_sites_silent_when_disabled(caplog):
    """Satellite contract: the per-flow debug logging in the runtime
    engines and the datapath accounting pays one boolean when disabled
    — enabled()=False emits NOTHING on the flow loggers."""
    from cilium_tpu.datapath.notify import account_verdicts
    from cilium_tpu.maps.metricsmap import MetricsMap
    from cilium_tpu.runtime.batch import R2d2BatchEngine
    from cilium_tpu.utils import flowdebug

    flowdebug.disable()
    eng = R2d2BatchEngine(model=__import__(
        "cilium_tpu.models.base", fromlist=["ConstVerdict"]
    ).ConstVerdict(True), width=64)
    out = {
        "verdict": np.asarray([1]),
        "dst_identity": np.asarray([1]),
        "new_dport": np.asarray([80]),
    }
    mon = Monitor()
    with caplog.at_level(
        logging.DEBUG, logger="cilium_tpu.runtime.flow"
    ), caplog.at_level(
        logging.DEBUG, logger="cilium_tpu.datapath.flow"
    ):
        eng.feed(1, b"HALT\r\n", remote_id=1)
        eng.pump()
        account_verdicts(out, MetricsMap(), monitor=mon,
                         proto=np.asarray([6]),
                         src_identity=np.asarray([9]))
        assert [r for r in caplog.records if r.name.endswith(".flow")] == []

        # Enabled: the same operations DO emit on the flow loggers.
        flowdebug.enable()
        try:
            eng.feed(1, b"HALT\r\n", remote_id=1)
            eng.pump()
            account_verdicts(out, MetricsMap(), monitor=mon,
                             proto=np.asarray([6]),
                             src_identity=np.asarray([9]))
        finally:
            flowdebug.disable()
        msgs = [
            r.getMessage() for r in caplog.records
            if r.name.endswith(".flow")
        ]
        assert any("r2d2" in mg and "PASS" in mg for mg in msgs)
        assert any("datapath drop" in mg for mg in msgs)

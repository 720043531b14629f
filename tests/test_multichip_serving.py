"""Multi-chip sharded verdict serving on the LIVE dispatch path.

The service builds mesh-resident models (parallel/rulesharding.py
ShardedVerdictModel: rule rows split-balanced across RULE_AXIS, flow
batches sharded across FLOW_AXIS) and serves every lane — vec, fast
entry, columnar reassembly — through the sharded steps.  Contracts
pinned here, on the conftest 8-device CPU mesh:

- **Bit-identity.**  A mesh service answers byte-identically to the
  single-chip service for the same traffic, including denials with
  injected error replies, and its flow records carry the SAME global
  rule ids and match kinds the host oracle walk names (shard-local
  argmax + cross-shard min-index reduction).
- **Columnar lane.**  The reassembler's bucket issue routes through
  the sharded step with no new jit shapes (fixed power-of-two buckets
  divide the flow axis by construction).
- **Fail-closed degradation.**  A lost/erroring mesh device demotes
  the service to the single-chip fallback executable — typed
  (mesh_demotions_total{reason}), counted, status-surfaced — with
  zero silent loss and bit-identical verdicts after the flip.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from cilium_tpu.parallel.rulesharding import ShardedVerdictModel
from cilium_tpu.proxylib import (
    FilterResult,
    NetworkPolicy,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
)
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.sidecar import SidecarClient, VerdictService
from cilium_tpu.utils.option import DaemonConfig

POLICY_RULES = [
    {"cmd": "READ", "file": "/public/.*"},
    {"cmd": "HALT"},
    {"cmd": "WRITE", "file": "^/tmp/"},
    {"file": "\\.txt$"},
]


def _policy(name="mesh-pol"):
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
                        l7_rules=POLICY_RULES[:2],
                    ),
                    PortNetworkPolicyRule(
                        l7_proto="r2d2", l7_rules=POLICY_RULES[2:]
                    ),
                ],
            )
        ],
    )


# (frame, remote) -> allowed under the policy above.
TRAFFIC = [
    (b"READ /public/a.txt\r\n", 1, True),
    (b"READ /secret\r\n", 1, False),
    (b"HALT\r\n", 3, True),
    (b"HALT\r\n", 9, False),     # remote 9 not in [1, 3]
    (b"WRITE /tmp/x\r\n", 9, True),
    (b"WRITE /etc/x\r\n", 1, False),
    (b"READ notes.txt\r\n", 5, True),
]


def _start(tmp_path, name, **cfg_kw):
    defaults = dict(
        batch_flows=64,
        mesh="on", mesh_rule_shards=2,
        device_reprobe_interval_s=1e9,
    )
    defaults.update(cfg_kw)
    cfg = DaemonConfig(**defaults)
    svc = VerdictService(str(tmp_path / f"{name}.sock"), cfg).start()
    client = SidecarClient(svc.socket_path, timeout=120.0)
    mod = client.open_module([])
    assert mod != 0
    assert client.policy_update(mod, [_policy()]) == int(FilterResult.OK)
    return svc, client, mod


def _conn(client, mod, conn_id, remote):
    res, shim = client.new_connection(
        mod, "r2d2", conn_id, True, remote, 2,
        f"1.1.1.{conn_id}:{1000 + conn_id}", "2.2.2.2:80", "mesh-pol",
    )
    assert res == int(FilterResult.OK)
    return shim


def _drive(client, mod, base_cid=1):
    """Serve TRAFFIC one conn per (frame, remote); returns outputs."""
    outs = []
    for i, (frame, remote, _want) in enumerate(TRAFFIC):
        shim = _conn(client, mod, base_cid + i, remote)
        res, out = shim.on_io(False, frame)
        assert res == int(FilterResult.OK)
        outs.append(out)
        shim.close()
    return outs


def test_mesh_service_serves_sharded_bit_identical(tmp_path):
    """Greedy mesh service: engine model IS the sharded wrapper, the
    status surface names the layout, and every verdict matches both
    the policy truth and a single-chip control service byte-for-byte."""
    inst.reset_module_registry()
    svc = client = ctrl = cctl = None
    try:
        svc, client, mod = _start(tmp_path, "mesh",
                                  batch_timeout_ms=0.0)
        mesh_outs = _drive(client, mod)
        eng = next(iter(svc._engines.values()))
        assert isinstance(eng.model, ShardedVerdictModel)
        assert eng.model.n_shards == 2
        st = svc.status()["mesh"]
        assert st == {
            "devices": 8, "flow_shards": 4, "rule_shards": 2,
            "active": True, "demoted": None, "demotions": {},
            "repromotions": 0, "rebind_rebuilds": 0,
            # Width-ladder surface (PR 17): full rung, nothing lost.
            "rung": "full", "serving_devices": 8, "lost_devices": [],
            "reshapes": 0, "reshape_failures": {},
            "capacity_frac": 1.0, "reshape_window_ms": 0.0,
        }
        # Single-chip control, same traffic.
        inst.reset_module_registry()
        ctrl, cctl, cmod = _start(tmp_path, "ctrl",
                                  batch_timeout_ms=0.0, mesh="off")
        ctrl_outs = _drive(cctl, cmod)
        assert ctrl.status()["mesh"] is None
        assert mesh_outs == ctrl_outs
        for out, (frame, _r, want) in zip(mesh_outs, TRAFFIC):
            assert (out == frame) == want, (frame, out)
    finally:
        for c in (client, cctl):
            if c is not None:
                c.close()
        for s in (svc, ctrl):
            if s is not None:
                s.stop()
        inst.reset_module_registry()


def test_mesh_columnar_lane_parity(tmp_path):
    """Pipelined mesh service: split frames + multi-entry rounds ride
    the columnar reassembly lane, whose bucket issue dispatches the
    SHARDED step (fixed power-of-two buckets shard the batch axis with
    no new jit shapes).  Verdicts match the policy truth and the lane
    actually ran (rounds > 0 — a silent scalar fallback cannot pass)."""
    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(
            tmp_path, "mesh-col", batch_timeout_ms=2.0,
            batch_width=64, reasm_min_entries=1,
        )
        shims = {
            cid: _conn(client, mod, cid, 1) for cid in (1, 2, 3, 4)
        }
        got: dict = {}
        evt = threading.Event()

        def cb(vb):
            got[vb.seq] = [vb.entry(i) for i in range(vb.count)]
            evt.set()

        client.verdict_callback = cb

        def send(seq, entries):
            cids = np.array([e[0] for e in entries], np.uint64)
            fl = np.array([e[1] for e in entries], np.uint8)
            lens = np.array([len(e[2]) for e in entries], np.uint32)
            client.send_batch(
                seq, cids, fl, lens, b"".join(e[2] for e in entries)
            )

        def wait_for(seq):
            deadline = time.monotonic() + 90
            while seq not in got and time.monotonic() < deadline:
                evt.wait(0.5)
                evt.clear()
            assert seq in got, sorted(got)

        # Round 1: four split-frame heads (buffered, no verdict yet).
        # Answered before round 2 is sent — two batches racing into
        # ONE dispatcher round would make every conn a duplicate,
        # which (correctly) routes the round scalar.
        send(1, [(1, 0, b"READ /pub"), (2, 0, b"READ /sec"),
                 (3, 0, b"HALT"), (4, 0, b"WRITE /tm")])
        wait_for(1)
        # Round 2: the tails complete all four frames.
        send(2, [(1, 0, b"lic/a.txt\r\n"), (2, 0, b"ret\r\n"),
                 (3, 0, b"\r\n"), (4, 0, b"p/x\r\n")])
        wait_for(2)
        # Tail round: PASS/DROP per conn in the oracle's op shapes.
        by_cid = {e[0]: e for e in got[2]}
        from cilium_tpu.proxylib.types import DROP, PASS

        def first_op(cid):
            return by_cid[cid][2][0][0]

        assert first_op(1) == int(PASS)
        assert first_op(2) == int(DROP)
        assert first_op(3) == int(PASS)
        assert first_op(4) == int(PASS)
        st = svc.status()
        assert st["reasm"] is not None and st["reasm"]["rounds"] > 0, (
            st["reasm"]
        )
        assert st["mesh"]["active"]
        eng = next(iter(svc._engines.values()))
        assert isinstance(eng.model, ShardedVerdictModel)
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


def test_mesh_flowlog_attribution_matches_host_walk(tmp_path):
    """Flow records from the mesh path carry GLOBAL rule ids: each
    allowed record's (rule_id, match_kind) equals the host oracle
    walk's first match over the same frame."""
    from cilium_tpu.proxylib.parsers.r2d2 import R2d2RequestData

    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(tmp_path, "mesh-attr",
                                  batch_timeout_ms=0.0)
        _drive(client, mod)
        ins = inst.find_instance(mod)
        pi = ins.policy_map()["mesh-pol"]
        eng = next(iter(svc._engines.values()))
        kinds = eng.model.match_kinds
        # Record emission is asynchronous to the verdict reply; poll
        # until the allowed rows all landed (bounded).
        want_allowed = sum(1 for _f, _r, w in TRAFFIC if w)
        deadline = time.monotonic() + 10
        recs = []
        while time.monotonic() < deadline:
            recs = [
                r for r in svc.flowlog.query(n=10000)
                if r.get("rule_id", -1) >= 0
            ]
            if len(recs) >= want_allowed:
                break
            time.sleep(0.05)
        assert len(recs) >= want_allowed, recs
        frames = {
            i + 1: (f, r) for i, (f, r, _w) in enumerate(TRAFFIC)
        }
        checked = 0
        for rec in recs:
            frame, remote = frames[rec["conn_id"]]
            parts = frame[:-2].decode().split(" ")
            l7 = R2d2RequestData(
                parts[0], parts[1] if len(parts) > 1 else ""
            )
            hok, hrule = pi.matches_at(True, 80, remote, l7)
            assert hok
            assert rec["rule_id"] == hrule, (frame, rec, hrule)
            assert rec["match_kind"] == kinds[hrule], (frame, rec)
            checked += 1
        assert checked >= want_allowed
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


def test_http_sidecar_lane_serves_sharded_and_demotes(tmp_path):
    """The l7 (HTTP) judge routes through the service's dispatch —
    shared jit caches AND the mesh rung: a raising sharded dispatch
    demotes typed and the round is answered from the single-chip
    fallback, not host-judged forever through crash containment."""
    inst.reset_module_registry()
    svc = client = None
    try:
        pol = NetworkPolicy(
            name="http-mesh", policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(port=80, rules=[
                    PortNetworkPolicyRule(http_rules=[
                        {"method": "GET", "path": "/public/.*"},
                        {"method": "POST", "path": "/api/.*"},
                    ])
                ])
            ],
        )
        cfg = DaemonConfig(
            batch_flows=64, batch_timeout_ms=0.0,
            mesh="on", mesh_rule_shards=2,
            device_reprobe_interval_s=1e9,
        )
        svc = VerdictService(
            str(tmp_path / "http-mesh.sock"), cfg
        ).start()
        client = SidecarClient(svc.socket_path, timeout=120.0)
        mod = client.open_module([])
        assert client.policy_update(mod, [pol]) == int(FilterResult.OK)
        res, shim = client.new_connection(
            mod, "http", 9, True, 1, 2, "1.1.1.9:1009", "2.2.2.2:80",
            "http-mesh",
        )
        assert res == int(FilterResult.OK)
        ok_req = b"GET /public/a HTTP/1.1\r\n\r\n"
        res, out = shim.on_io(False, ok_req)
        assert res == int(FilterResult.OK) and out == ok_req
        eng = next(
            e for k, e in svc._engines.items() if k[4] == "http"
        )
        assert isinstance(eng.model, ShardedVerdictModel)

        orig = svc._jit_for

        def lost_device(cache, model, trace_fn, arg_fn=None):
            if isinstance(model, ShardedVerdictModel):
                def boom(*_a, **_k):
                    raise RuntimeError("PJRT_Error: device lost")

                return boom
            return orig(cache, model, trace_fn, arg_fn)

        svc._jit_for = lost_device
        res, out = shim.on_io(False, b"POST /api/x HTTP/1.1\r\n\r\n")
        assert res == int(FilterResult.OK)
        assert out == b"POST /api/x HTTP/1.1\r\n\r\n"
        st = svc.status()
        assert st["mesh"]["demoted"] == "device-call"
        assert not isinstance(eng.model, ShardedVerdictModel)
        res, out = shim.on_io(False, b"DELETE /x HTTP/1.1\r\n\r\n")
        assert out != b"DELETE /x HTTP/1.1\r\n\r\n"  # still denying
        assert st["containment"]["batch_crashes"] == 0
        assert svc.fallback_entries == 0  # never host-judged rounds
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


def test_daemon_engine_factory_builds_sharded_and_demotes():
    """The daemon-side factory path: build_model_for_filter with a
    mesh returns the sharded wrappers (http + kafka), the runtime
    engines serve them bit-identically, and the engine-level judge
    rung demotes a dead sharded model to its fallback typed instead
    of crashing the step."""
    import jax

    from cilium_tpu.labels import Labels
    from cilium_tpu.models.builder import build_model_for_filter
    from cilium_tpu.parallel.mesh import RULE_AXIS, serving_mesh
    from cilium_tpu.parallel.rulesharding import ShardedKafkaModel
    from cilium_tpu.policy.api import (
        EndpointSelector,
        L7Rules,
        PortRuleHTTP,
        PortRuleKafka,
    )
    from cilium_tpu.policy.l4 import (
        L4Filter,
        L7DataMap,
        PARSER_TYPE_HTTP,
        PARSER_TYPE_KAFKA,
    )
    from cilium_tpu.proxylib.types import DROP, PASS
    from cilium_tpu.runtime.engines import (
        HttpBatchEngine,
        KafkaBatchEngine,
        _daemon_mesh,
    )

    mesh = serving_mesh("on", rule_shards=2, devices=jax.devices())
    assert mesh is not None and mesh.shape[RULE_AXIS] == 2

    # _daemon_mesh resolves from config once and caches on the daemon.
    class _Daemon:
        config = DaemonConfig(mesh="on", mesh_rule_shards=2)

    d = _Daemon()
    got = _daemon_mesh(d)
    assert got is not None and got.shape[RULE_AXIS] == 2
    assert d.verdict_mesh is got
    assert _daemon_mesh(d) is got  # cached

    identity_cache = {7: Labels.from_model(["k8s:app=web"])}
    sel = EndpointSelector.from_dict({"k8s:app": "web"})
    dm = L7DataMap()
    dm[sel] = L7Rules(http=[PortRuleHTTP(method="GET", path="/ok/.*")])
    f = L4Filter(port=80, protocol="TCP", l7_parser=PARSER_TYPE_HTTP,
                 l7_rules_per_ep=dm)
    model = build_model_for_filter(f, identity_cache, mesh=mesh)
    assert isinstance(model, ShardedVerdictModel)
    eng = HttpBatchEngine(model)
    req = b"GET /ok/x HTTP/1.1\r\n\r\n"
    eng.feed(1, req, remote_id=7)
    eng.feed(2, req, remote_id=99)
    eng.pump()
    assert eng.take_ops(1)[0] == [(PASS, len(req))]
    assert eng.take_ops(2)[0][0][0] == int(DROP)

    # Engine-level mesh rung: a dead sharded model demotes in-step.
    class _DeadSharded:
        def __init__(self, fallback):
            self.fallback = fallback

        def __call__(self, *_a, **_k):
            raise RuntimeError("PJRT_Error: device lost")

        def verdicts_attr(self, *_a, **_k):
            raise RuntimeError("PJRT_Error: device lost")

    eng.model = _DeadSharded(model.fallback)
    eng.feed(3, req, remote_id=7)
    eng.pump()
    assert eng.take_ops(3)[0] == [(PASS, len(req))]
    assert eng.model is model.fallback  # demoted, typed, serving

    # Kafka wrapper through the same factory.
    kr = PortRuleKafka(topic="orders", role="produce")
    kr.sanitize()
    dmk = L7DataMap()
    dmk[sel] = L7Rules(kafka=[kr])
    fk = L4Filter(port=9092, protocol="TCP",
                  l7_parser=PARSER_TYPE_KAFKA, l7_rules_per_ep=dmk)
    kmodel = build_model_for_filter(fk, identity_cache, mesh=mesh)
    assert isinstance(kmodel, ShardedKafkaModel)
    from test_kafka import produce_request

    keng = KafkaBatchEngine(kmodel)
    ok = produce_request(["orders"])
    bad = produce_request(["secret"])
    keng.feed(1, ok, remote_id=7)
    keng.feed(2, bad, remote_id=7)
    keng.pump()
    assert keng.take_ops(1)[0] == [(PASS, len(ok))]
    assert keng.take_ops(2)[0][0][0] == int(DROP)


def test_device_loss_demotes_typed_zero_silent_loss(tmp_path):
    """Fault injection at the executable layer (how a lost mesh device
    actually surfaces: the compiled sharded dispatch raises): the
    in-flight round is answered from the single-chip fallback in the
    SAME round, the demotion is typed and status-surfaced, subsequent
    traffic serves bit-identically, and nothing is shed, crashed, or
    left unanswered."""
    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(tmp_path, "mesh-loss",
                                  batch_timeout_ms=0.0)
        shim = _conn(client, mod, 50, 1)
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert out == b"READ /public/a.txt\r\n"

        orig = svc._jit_for

        def lost_device(cache, model, trace_fn, arg_fn=None):
            if isinstance(model, ShardedVerdictModel):
                def boom(*_a, **_k):
                    raise RuntimeError("PJRT_Error: device lost")

                return boom
            return orig(cache, model, trace_fn, arg_fn)

        svc._jit_for = lost_device
        # The round that hits the dead mesh is still answered — with
        # the CORRECT verdict, from the fallback executable.
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        st = svc.status()
        assert st["mesh"]["demoted"] == "device-call"
        assert st["mesh"]["demotions"] == {"device-call": 1}
        assert st["mesh"]["active"] is False
        # Engines flipped to the single-chip executable.
        eng = next(iter(svc._engines.values()))
        assert not isinstance(eng.model, ShardedVerdictModel)
        # Still serving, still bit-identical, nothing lost.
        for frame, remote, want in TRAFFIC:
            s2 = _conn(client, mod, 60 + remote, remote)
            res, out = s2.on_io(False, frame)
            assert res == int(FilterResult.OK)
            assert (out == frame) == want, (frame, out)
            s2.close()
        st = svc.status()
        assert st["containment"]["shed_entries"] == 0
        assert st["containment"]["batch_crashes"] == 0
        assert st["containment"]["error_entries"] == 0
        # Sticky: one demotion, not one per round.
        assert st["mesh"]["demotions"] == {"device-call": 1}
        # New engine builds while demoted are single-chip.
        assert svc._serving_mesh() is None
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


def test_mesh_repromotes_after_heal_bit_identical(tmp_path):
    """Guarded re-promotion (ROADMAP 1b): after a demotion, the timed
    off-path re-probe rebuilds a sharded executable, parity-probes it
    against the single-chip fallback, and flips the retained sharded
    wrappers back — typed (mesh_repromotions_total), traffic-driven
    pacing, and the healed mesh serves bit-identically."""
    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(
            tmp_path, "mesh-heal", batch_timeout_ms=0.0,
            mesh_reprobe_interval_s=0.05,
        )
        shim = _conn(client, mod, 50, 1)
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert out == b"READ /public/a.txt\r\n"

        orig = svc._jit_for

        def lost_device(cache, model, trace_fn, arg_fn=None):
            if isinstance(model, ShardedVerdictModel):
                def boom(*_a, **_k):
                    raise RuntimeError("PJRT_Error: device lost")

                return boom
            return orig(cache, model, trace_fn, arg_fn)

        svc._jit_for = lost_device
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        assert svc.status()["mesh"]["demoted"] == "device-call"
        # Device heals: the fault injection is removed.  The next
        # paced re-probe (traffic-driven, like the quarantine heal)
        # must rebuild + parity-probe off-path and flip back.
        svc._jit_for = orig
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            res, out = shim.on_io(False, b"HALT\r\n")
            assert res == int(FilterResult.OK) and out == b"HALT\r\n"
            if svc.status()["mesh"]["active"]:
                break
            time.sleep(0.05)
        st = svc.status()
        assert st["mesh"]["active"] is True, st["mesh"]
        assert st["mesh"]["demoted"] is None
        assert st["mesh"]["repromotions"] == 1
        # Engines flipped BACK to the sharded wrappers.
        eng = next(iter(svc._engines.values()))
        assert isinstance(eng.model, ShardedVerdictModel)
        # New builds shard again.
        assert svc._serving_mesh() is not None
        # Bit-identical service on the re-promoted mesh, nothing lost.
        for i, (frame, remote, want) in enumerate(TRAFFIC):
            s2 = _conn(client, mod, 70 + i, remote)
            res, out = s2.on_io(False, frame)
            assert res == int(FilterResult.OK)
            assert (out == frame) == want, (frame, out)
            s2.close()
        st = svc.status()
        assert st["containment"]["shed_entries"] == 0
        assert st["containment"]["batch_crashes"] == 0
        assert st["containment"]["error_entries"] == 0
        # A second loss after the heal demotes AGAIN, typed — the
        # rung stays re-entrant, never a crashed round.
        svc._jit_for = lost_device
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        assert svc.status()["mesh"]["demotions"]["device-call"] == 2
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


# --- width ladder (PR 17): shard-loss reshape --------------------------------

def _arm_named_loss(svc, dev_id):
    """One-shot sharded-dispatch fault NAMING a device (the ladder's
    attribution source) plus a probe seam marking that device dead.
    Self-disarming: the reshaped wrappers (still ShardedVerdictModel)
    must serve cleanly after the fault, so the injector restores the
    real _jit_for the moment it fires."""
    orig = svc.__class__._jit_for.__get__(svc)

    def lost_device(cache, model, trace_fn, arg_fn=None):
        if isinstance(model, ShardedVerdictModel):
            def boom(*_a, **_k):
                svc._jit_for = orig
                raise RuntimeError(
                    f"PJRT_Error: transfer to device {dev_id} failed"
                )

            return boom
        return orig(cache, model, trace_fn, arg_fn)

    svc._jit_for = lost_device
    svc._device_probe_fn = lambda dev, _d=dev_id: dev.id != _d


def _await_rung(svc, rung, client=None, mod=None, timeout=60.0,
                drive=False):
    """Wait for the builder thread's ladder walk to land on ``rung``;
    optionally keep traffic flowing (the paced re-probe is
    traffic-driven)."""
    deadline = time.monotonic() + timeout
    i = 0
    while time.monotonic() < deadline:
        st = svc.status()["mesh"]
        if st["rung"] == rung:
            return st
        if drive:
            s = _conn(client, mod, 9000 + (i % 50), 3)
            res, out = s.on_io(False, b"HALT\r\n")
            assert res == int(FilterResult.OK) and out == b"HALT\r\n"
            s.close()
            i += 1
        time.sleep(0.05)
    raise AssertionError(
        f"rung {rung!r} never reached: {svc.status()['mesh']}"
    )


def test_mesh_reshape_serves_degraded_then_repromotes(tmp_path):
    """The tentpole walk, fast-entry lane: an attributed device loss
    demotes typed, the IMMEDIATE off-path reshape flips every engine
    onto a survivor mesh (fallback covers only the rebuild window),
    the reshaped rung serves bit-identically at a published capacity
    fraction that scales admission, and the paced re-probe walks back
    UP to full width when the device heals — all counted, zero loss."""
    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(
            tmp_path, "mesh-reshape", batch_timeout_ms=0.0,
            mesh_reprobe_interval_s=0.05,
        )
        shim = _conn(client, mod, 50, 1)
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert out == b"READ /public/a.txt\r\n"
        full_share = svc._drr_share()

        _arm_named_loss(svc, 3)
        # The faulting round is still answered from the fallback twin
        # in the SAME round (PR 11 contract: no round waits on the
        # rebuild).
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        assert svc.status()["mesh"]["demotions"] == {"device-call": 1}

        st = _await_rung(svc, "reshaped")
        assert st["active"] is True and st["demoted"] is None
        assert st["lost_devices"] == [3]
        assert st["reshapes"] == 1
        assert 1 <= st["serving_devices"] < 8
        assert 0.0 < st["capacity_frac"] < 1.0
        assert st["reshape_window_ms"] > 0.0
        # Engines flipped onto the SURVIVOR mesh (sharded again, and
        # the dead device is not in the serving layout).
        eng = next(iter(svc._engines.values()))
        assert isinstance(eng.model, ShardedVerdictModel)
        serving_ids = {d.id for d in svc._mesh_serving.devices.flat}
        assert 3 not in serving_ids
        assert len(serving_ids) == st["serving_devices"]
        # Capacity-aware admission: queue cap and DRR credit windows
        # shrink to the degraded fraction.
        assert svc.dispatcher.max_pending < svc.config.shed_queue_entries
        assert svc._drr_share() <= full_share
        # Guard health table attributes the chip, typed by reason.
        table = svc.guard.device_table()
        assert table["3"]["state"] == "lost"
        assert table["3"]["faults"].get("device-call", 0) >= 1
        # Bit-identical service on the reshaped rung, nothing lost.
        for i, (frame, remote, want) in enumerate(TRAFFIC):
            s2 = _conn(client, mod, 100 + i, remote)
            res, out = s2.on_io(False, frame)
            assert res == int(FilterResult.OK)
            assert (out == frame) == want, (frame, out)
            s2.close()
        # New engine builds while reshaped shard onto the survivors.
        assert svc._serving_mesh() is svc._mesh_serving
        # Device-economics ledger (PR 20): the reshape fan-out's
        # survivor-mesh rebuilds booked under the mesh-reshape cause —
        # off-path engine builds, each stamped with the mesh layout it
        # was built against.
        reshape_evs = svc.ledger.events(n=1000, cause="mesh-reshape")
        assert reshape_evs, svc.ledger.events(n=1000)
        for ev in reshape_evs:
            assert ev["kind"] == "engine-build", ev
            assert not ev["on_dispatch_path"], ev
            assert ev["mesh"], ev

        # Heal: the paced re-probe walks back up to full width.
        svc._device_probe_fn = lambda dev: True
        st = _await_rung(svc, "full", client, mod, drive=True)
        assert st["repromotions"] == 1
        assert st["lost_devices"] == []
        assert st["capacity_frac"] == 1.0
        assert st["serving_devices"] == 8
        assert svc.dispatcher.max_pending == svc.config.shed_queue_entries
        table = svc.guard.device_table()
        assert table["3"]["state"] == "ok"
        assert table["3"]["heals"] >= 1
        # The walk back up booked its full-width rebuilds under the
        # repromotion cause — distinct in the census from both the
        # demotion-era reshape and any cold start, so the ledger alone
        # answers "what did that incident cost on-device?".
        repro_evs = svc.ledger.events(n=1000, cause="repromotion")
        assert repro_evs, svc.ledger.events(n=1000)
        for ev in repro_evs:
            assert ev["kind"] == "engine-build", ev
            assert not ev["on_dispatch_path"], ev
        by_cause = svc.ledger.status()["by_cause"]
        assert by_cause.get("mesh-reshape", 0) >= 1, by_cause
        assert by_cause.get("repromotion", 0) >= 1, by_cause
        # Full-width mesh serves bit-identically again.
        eng = next(iter(svc._engines.values()))
        assert isinstance(eng.model, ShardedVerdictModel)
        for i, (frame, remote, want) in enumerate(TRAFFIC):
            s2 = _conn(client, mod, 200 + i, remote)
            res, out = s2.on_io(False, frame)
            assert res == int(FilterResult.OK)
            assert (out == frame) == want, (frame, out)
            s2.close()
        st = svc.status()
        assert st["containment"]["shed_entries"] == 0
        assert st["containment"]["batch_crashes"] == 0
        assert st["containment"]["error_entries"] == 0
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


def test_capacity_scaling_never_raises_a_small_cap(tmp_path):
    """The session_share_min floor under the capacity coupling guards
    deep degradation from starving admission — it must never RAISE an
    operator's small shed_queue_entries above its configured value
    (regression: mesh resolution at frac=1.0 once floored an 8-entry
    cap up to 64, so the overload test's queue never shed)."""
    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(
            tmp_path, "small-cap", shed_queue_entries=8,
        )
        shim = _conn(client, mod, 1, 3)
        res, out = shim.on_io(False, b"HALT\r\n")  # resolves the mesh
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        assert svc.status()["mesh"]["rung"] == "full"
        assert svc.dispatcher.max_pending == 8
        # Degraded: the scaled cap floors at min(entries, share_min)
        # — bounded by the configured cap on every rung.
        _arm_named_loss(svc, 3)
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        _await_rung(svc, "reshaped", client, mod, drive=True)
        assert 1 <= svc.dispatcher.max_pending <= 8
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


# Dispatch lanes the device-loss injection must cover (satellite 3):
# vec (pipelined single complete frames), fast-entry (greedy inline),
# columnar (_process_columnar: split frames through the reassembler),
# slow-async (engine slow path, reassembler off).  The HTTP-judge lane
# has its own test below (different protocol plumbing).
LANE_CONFIGS = {
    "vec": (dict(batch_timeout_ms=2.0), False),
    "fast-entry": (dict(batch_timeout_ms=0.0), False),
    "columnar": (
        dict(batch_timeout_ms=2.0, reasm_min_entries=1), True,
    ),
    "slow-async": (dict(batch_timeout_ms=2.0, reasm=False), True),
}


# The reassembler lanes carry two full chaos+control service pairs
# each (~10s apiece on the CPU smoke); keep tier-1 on the two cheap
# lanes and run the split-frame lanes in the slow suite.
@pytest.mark.parametrize(
    "lane",
    [
        pytest.param("columnar", marks=pytest.mark.slow),
        "fast-entry",
        pytest.param("slow-async", marks=pytest.mark.slow),
        "vec",
    ],
)
def test_mesh_reshape_per_lane_bit_identical(tmp_path, lane):
    """Every dispatch lane drives fault -> reshape -> bit-identical
    continued service -> re-promotion.  Outputs are compared against a
    single-chip control service fed the identical byte sequence — the
    ladder must be invisible in the reply stream."""
    cfg_kw, split = LANE_CONFIGS[lane]

    def run(name, mesh_mode, fault):
        inst.reset_module_registry()
        svc = client = None
        try:
            svc, client, mod = _start(
                tmp_path, name, mesh=mesh_mode,
                mesh_reprobe_interval_s=0.05, **cfg_kw,
            )
            outs = []

            def burst(base):
                for i, (frame, remote, _w) in enumerate(TRAFFIC):
                    shim = _conn(client, mod, base + i, remote)
                    if split and len(frame) > 6:
                        r1, o1 = shim.on_io(False, frame[:6])
                        assert r1 == int(FilterResult.OK)
                        r2, o2 = shim.on_io(False, frame[6:])
                        assert r2 == int(FilterResult.OK)
                        outs.append((o1, o2))
                    else:
                        r1, o1 = shim.on_io(False, frame)
                        assert r1 == int(FilterResult.OK)
                        outs.append(o1)
                    shim.close()

            burst(100)
            if fault:
                _arm_named_loss(svc, 5)
            burst(200)  # fault fires mid-burst; answered via fallback
            if fault:
                _await_rung(svc, "reshaped")
                st = svc.status()["mesh"]
                assert st["lost_devices"] == [5]
                assert st["reshapes"] == 1
            burst(300)  # reshaped rung (or full, for the control)
            if fault:
                svc._device_probe_fn = lambda dev: True
                _await_rung(svc, "full", client, mod, drive=True)
                assert svc.status()["mesh"]["repromotions"] == 1
            burst(400)  # re-promoted full width
            st = svc.status()
            if fault:
                assert st["containment"]["batch_crashes"] == 0
                assert st["containment"]["error_entries"] == 0
                assert st["containment"]["shed_entries"] == 0
            return outs
        finally:
            if client is not None:
                client.close()
            if svc is not None:
                svc.stop()
            inst.reset_module_registry()

    chaos = run(f"lane-{lane}", "on", fault=True)
    control = run(f"lane-{lane}-ctrl", "off", fault=False)
    assert chaos == control


def test_http_judge_lane_reshapes_and_repromotes(tmp_path):
    """The HTTP-judge lane walks the full ladder too: a named device
    loss mid-request demotes typed, the off-path reshape restores a
    SHARDED judge over the survivors, and the heal promotes back to
    full width — verdicts correct at every rung."""
    inst.reset_module_registry()
    svc = client = None
    try:
        pol = NetworkPolicy(
            name="http-mesh", policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(port=80, rules=[
                    PortNetworkPolicyRule(http_rules=[
                        {"method": "GET", "path": "/public/.*"},
                        {"method": "POST", "path": "/api/.*"},
                    ])
                ])
            ],
        )
        cfg = DaemonConfig(
            batch_flows=64, batch_timeout_ms=0.0,
            mesh="on", mesh_rule_shards=2,
            device_reprobe_interval_s=1e9,
            mesh_reprobe_interval_s=0.05,
        )
        svc = VerdictService(
            str(tmp_path / "http-mesh-l.sock"), cfg
        ).start()
        client = SidecarClient(svc.socket_path, timeout=120.0)
        mod = client.open_module([])
        assert client.policy_update(mod, [pol]) == int(FilterResult.OK)

        def req(cid, frame):
            res, shim = client.new_connection(
                mod, "http", cid, True, 1, 2,
                f"1.1.1.{cid}:{1000 + cid}", "2.2.2.2:80", "http-mesh",
            )
            assert res == int(FilterResult.OK)
            res, out = shim.on_io(False, frame)
            assert res == int(FilterResult.OK)
            shim.close()
            return out

        ok_req = b"GET /public/a HTTP/1.1\r\n\r\n"
        bad_req = b"DELETE /x HTTP/1.1\r\n\r\n"
        assert req(9, ok_req) == ok_req

        _arm_named_loss(svc, 2)
        # Faulting round still answered (fallback twin, same round).
        assert req(10, ok_req) == ok_req
        assert svc.status()["mesh"]["demotions"] == {"device-call": 1}
        st = _await_rung(svc, "reshaped")
        assert st["lost_devices"] == [2]
        eng = next(
            e for k, e in svc._engines.items() if k[4] == "http"
        )
        assert isinstance(eng.model, ShardedVerdictModel)
        assert req(11, ok_req) == ok_req
        assert req(12, bad_req) != bad_req  # still denying, reshaped

        svc._device_probe_fn = lambda dev: True
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if svc.status()["mesh"]["rung"] == "full":
                break
            assert req(13, ok_req) == ok_req
            time.sleep(0.05)
        st = svc.status()["mesh"]
        assert st["rung"] == "full" and st["repromotions"] == 1
        assert req(14, ok_req) == ok_req
        assert req(15, bad_req) != bad_req
        assert svc.status()["containment"]["batch_crashes"] == 0
        assert svc.fallback_entries == 0  # never host-judged rounds
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


# --- chaos soak: repeated device loss under churn ------------------------

def _churn_policy(j):
    """Policy-churn payload under its OWN name — forces builder-thread
    rebuild load without changing the truth table traffic asserts."""
    return NetworkPolicy(
        name="churn-pol", policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(port=80, rules=[
                PortNetworkPolicyRule(
                    l7_proto="r2d2",
                    l7_rules=POLICY_RULES[: 1 + (j % len(POLICY_RULES))],
                )
            ])
        ],
    )


def _chaos_soak(tmp_path, name, cycles, n_threads):
    """Kill a different shard device each cycle, mid-burst, under
    policy churn; every frame must still be answered exactly once with
    the policy-truth verdict, and the ladder must end back at full."""
    from test_sidecar import CORPUS, assert_parity, oracle_ops, \
        r2d2_policy
    from test_sidecar_faults import _open_conn, _shim_run

    inst.reset_module_registry()
    svc = None
    clients = []
    try:
        svc, client, _mod = _start(
            tmp_path, name, batch_timeout_ms=2.0,
            mesh_reprobe_interval_s=0.05,
        )
        clients.append(client)
        stop = threading.Event()
        errors = []
        counts = [0] * n_threads

        # Sessions, modules, policies, and conns are set up
        # SEQUENTIALLY (the contract under test is verdict serving
        # during device loss, not control-plane races); the threads
        # then drive persistent conns concurrently, each asserting
        # bit-identical ops vs its HOST-ORACLE walk every pass.
        def _slice(tid):
            return CORPUS + [
                f"READ /public/pod{tid}.txt\r\n".encode(),
                b"HALT\r\n",
            ]

        shims, oracles = [], []
        for tid in range(n_threads):
            c = SidecarClient(svc.socket_path, timeout=120.0,
                              identity=f"pod-{tid}")
            clients.append(c)
            _m, shim = _open_conn(c, 5000 + tid)
            shims.append(shim)
            oracles.append(oracle_ops(r2d2_policy(), _slice(tid)))
        churn_c = SidecarClient(svc.socket_path, timeout=120.0,
                                identity="pod-churn")
        clients.append(churn_c)
        churn_m = churn_c.open_module([])

        def traffic(tid):
            try:
                while not stop.is_set():
                    out = _shim_run(clients[tid + 1], shims[tid],
                                    _slice(tid))
                    assert_parity(out, oracles[tid])
                    counts[tid] += 1
            except Exception as exc:  # noqa: BLE001 - soak collector
                errors.append((tid, "exc", repr(exc)))

        def churn():
            try:
                j = 0
                while not stop.is_set():
                    # Full policy set each push (policy_update
                    # REPLACES the instance's map, like an xDS
                    # snapshot): churn-pol varies, the serving
                    # policies ride along unchanged.
                    res = churn_c.policy_update(
                        churn_m,
                        [r2d2_policy(), _policy(), _churn_policy(j)],
                    )
                    if res != int(FilterResult.OK):
                        errors.append(("churn", j, res))
                        return
                    j += 1
                    time.sleep(0.01)
            except Exception as exc:  # noqa: BLE001 - soak collector
                errors.append(("churn", "exc", repr(exc)))

        threads = [
            threading.Thread(target=traffic, args=(t,), daemon=True)
            for t in range(n_threads)
        ]
        threads.append(threading.Thread(target=churn, daemon=True))
        for t in threads:
            t.start()
        try:
            for cyc in range(cycles):
                # Let the full-width mesh serve a burst first.
                base = list(counts)
                deadline = time.monotonic() + 60.0
                while (time.monotonic() < deadline and not errors
                       and any(c - b < 1
                               for c, b in zip(counts, base))):
                    time.sleep(0.02)
                assert not errors, errors
                dev = 1 + (cyc % 7)
                _arm_named_loss(svc, dev)
                st = _await_rung(svc, "reshaped", timeout=60.0)
                assert st["lost_devices"] == [dev], st
                assert not errors, errors
                # Heal: traffic threads drive the paced re-probe.
                svc._device_probe_fn = lambda d: True
                st = _await_rung(svc, "full", timeout=60.0)
                assert not errors, errors
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60.0)
        assert not errors, errors
        assert all(c > 0 for c in counts), counts
        st = svc.status()
        assert st["mesh"]["rung"] == "full"
        assert st["mesh"]["reshapes"] == cycles
        assert st["mesh"]["repromotions"] == cycles
        assert st["containment"]["batch_crashes"] == 0
        assert st["containment"]["error_entries"] == 0
        assert st["containment"]["shed_entries"] == 0
        # Exactly-once across every fault cycle: no session lost a
        # round to the ladder (zero silent loss, zero double replies).
        rows = {
            r["identity"]: r for r in st["sessions"]["live"]
        }
        for tid in range(n_threads):
            row = rows[f"pod-{tid}"]
            assert row["submitted"] == row["answered"], row
            assert row["shed"] == {}, row
    finally:
        for c in clients:
            c.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


def test_mesh_device_loss_chaos_soak_fast(tmp_path):
    """Tier-1 chaos soak: two fault->reshape->heal->full cycles under
    concurrent traffic and policy churn, zero silent loss, zero double
    replies, every verdict policy-true."""
    _chaos_soak(tmp_path, "soak-fast", cycles=1, n_threads=2)


@pytest.mark.slow
def test_mesh_device_loss_chaos_soak_long(tmp_path):
    """Longer soak (BENCH_FULL tier): five cycles, four traffic
    threads — walks the ladder through most of the device set."""
    _chaos_soak(tmp_path, "soak-long", cycles=5, n_threads=4)


# --- flight recorder: the incident timeline through the device walk -------

def _await_bundles(rec, n, timeout=30.0):
    """Postmortem enrichment rides its own daemon thread (the
    fail-closed edge fires under service locks); wait for the
    written-bundle counter to land."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if rec.bundles_written >= n:
            return
        time.sleep(0.01)
    raise AssertionError(f"{n} bundle(s) never written: {rec.status()}")


def test_flight_recorder_records_device_loss_cascade(tmp_path):
    """The device-loss walk — descent, heal, re-promotion, second
    descent — lands in the flight recorder as an ORDERED declared-edge
    sequence: every recorded transition is an edge its protocols.py
    table declares, the fail-closed flags match the declared
    FAIL_CLOSED surface exactly, each descent yields exactly ONE
    postmortem bundle whose LAST event is the triggering edge, and the
    heal re-arms the latch so the second descent gets its own bundle."""
    from cilium_tpu.analysis import protocols as proto

    inst.reset_module_registry()
    svc = client = None
    try:
        svc, client, mod = _start(
            tmp_path, "recorder-walk", batch_timeout_ms=0.0,
            mesh_reprobe_interval_s=0.05,
            timeline_bundle_dir=str(tmp_path / "bundles"),
        )
        rec = svc.recorder
        shim = _conn(client, mod, 60, 1)
        res, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert res == int(FilterResult.OK)
        seq0 = rec.status()["seq"]
        assert rec.status()["armed"] is True

        # -- first descent -------------------------------------------
        _arm_named_loss(svc, 3)
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        _await_rung(svc, "reshaped")
        _await_bundles(rec, 1)

        evs = rec.events(n=512, since=seq0)
        assert evs, "descent recorded nothing"
        # Edge-for-edge validation against the DECLARED tables: every
        # recorded typestate transition is one of its table's edges,
        # and its fail-closed flag matches the declared surface.
        for ev in evs:
            if ev["table"] in ("mark", "overload"):
                continue
            table = proto._PROTOCOLS_BY_NAME[ev["table"]]
            edge = tuple(ev["edge"])
            assert edge in table.edges, ev
            want_fc = (ev["table"],) + edge in proto.FAIL_CLOSED_EDGES
            assert bool(ev.get("fail_closed")) == want_fc, ev
        # The cascade's shape, in ring order: the faulting round's
        # off-path demotion, then the guard attributing the chip, then
        # the builder's reshape onto the survivors.
        ladder = [tuple(e["edge"]) for e in evs
                  if e["table"] == "mesh_ladder"]
        assert ladder == [("full", "fallback"), ("fallback", "reshaped")]
        # The guard attributes the chip once typed ("device-call");
        # the paced re-probe may re-attribute it ("probe-failed",
        # the declared lost->lost self-edge) before the reshape lands.
        dev_evs = [e for e in evs if e["table"] == "mesh_device"]
        assert [tuple(e["edge"]) for e in dev_evs][0] == ("ok", "lost")
        assert all(tuple(e["edge"]) == ("lost", "lost")
                   for e in dev_evs[1:])
        # Correlation ids rode the thread-local annotation in.
        assert dev_evs[0]["device"] == "3"
        assert dev_evs[0]["reason"] == "device-call"
        assert all(e["device"] == "3" and e["reason"] == "probe-failed"
                   for e in dev_evs[1:])
        demote = next(e for e in evs if e["table"] == "mesh_ladder"
                      and e["edge"] == ["full", "fallback"])
        assert demote["reason"] == "device-call"
        reshape = next(e for e in evs
                       if e["edge"] == ["fallback", "reshaped"])
        assert reshape["reason"] == "reshape"
        assert demote["seq"] < dev_evs[0]["seq"] < reshape["seq"]

        # Exactly ONE bundle for the whole cascade: several fail-closed
        # edges fired; the latch folded them into one postmortem.
        st = rec.status()
        assert st["fail_closed_events"] >= 2
        assert st["postmortems"] == 1 and len(rec.postmortems) == 1
        assert st["armed"] is False
        assert st["tiers"]["mesh"] == 1  # reshaped rung on the gauge
        pm = rec.postmortems[0]
        assert pm["trigger"] == "mesh_ladder:full->fallback"
        assert pm["seq"] == demote["seq"]
        assert pm["reason"] == "device-call"
        # The bundle file: the triggering edge lands LAST (the ring is
        # snapshotted under the latch, before the cascade's later
        # edges append).
        assert pm["path"] is not None
        with open(pm["path"], encoding="utf-8") as f:
            bundle = json.load(f)
        assert bundle["trigger"] == pm["trigger"]
        assert bundle["events"][-1]["seq"] == demote["seq"]
        assert bundle["events"][-1]["edge"] == ["full", "fallback"]
        assert bundle["status"] is not None  # enrichment providers ran
        # The wire surface serves the same ring (MSG_TIMELINE RPC).
        reply = client.timeline(n=512, since=seq0, table="mesh_ladder")
        assert [tuple(e["edge"]) for e in reply["events"]] == ladder
        assert reply["timeline"]["postmortems"] == 1

        # -- heal: the ascent re-arms the latch ----------------------
        seq1 = rec.status()["seq"]
        svc._device_probe_fn = lambda dev: True
        _await_rung(svc, "full", client, mod, drive=True)
        evs = rec.events(n=512, since=seq1)
        # A probe already in flight may land one more lost->lost row;
        # the heal itself is exactly one lost->ok, probe-attributed.
        heal = [e for e in evs if e["table"] == "mesh_device"
                and tuple(e["edge"]) == ("lost", "ok")]
        assert len(heal) == 1 and heal[0]["reason"] == "probe-heal"
        assert heal[0]["device"] == "3"
        promote = [e for e in evs if e["table"] == "mesh_ladder"]
        assert [tuple(e["edge"]) for e in promote] == [
            ("reshaped", "full")
        ]
        assert promote[0]["reason"] == "repromote"
        # No NEW descent on the way up: the only fail-closed rows an
        # ascent may carry are straggler lost->lost re-attributions.
        assert all(tuple(e["edge"]) == ("lost", "lost")
                   for e in evs if e.get("fail_closed"))
        st = rec.status()
        assert st["armed"] is True
        assert st["tiers"]["mesh"] == 0

        # -- second descent: one bundle PER descent ------------------
        seq2 = rec.status()["seq"]
        _arm_named_loss(svc, 5)
        s2 = _conn(client, mod, 61, 3)
        res, out = s2.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        s2.close()
        _await_rung(svc, "reshaped")
        _await_bundles(rec, 2)
        assert rec.status()["postmortems"] == 2
        pm2 = rec.postmortems[-1]
        assert pm2["trigger"] == "mesh_ladder:full->fallback"
        assert pm2["seq"] > seq2
        dev2 = [e for e in rec.events(n=512, since=seq2)
                if e["table"] == "mesh_device"]
        assert {e["device"] for e in dev2} == {"5"}
        assert tuple(dev2[0]["edge"]) == ("ok", "lost")
    finally:
        if client is not None:
            client.close()
        if svc is not None:
            svc.stop()
        inst.reset_module_registry()


# --- ladder state across hitless restart (satellite 2) --------------------

def test_mesh_ladder_survives_hitless_restart(tmp_path):
    """snapshot_handoff carries the per-device health table and the
    degraded width; a restored successor starts DIRECTLY on the
    reshaped rung (no re-discovery outage) and can still walk back up
    once the device heals."""
    inst.reset_module_registry()
    svc = client = fresh = client2 = None
    path = str(tmp_path / "handoff-mesh.sock")
    try:
        cfg_kw = dict(
            batch_flows=64, batch_timeout_ms=0.0,
            mesh="on", mesh_rule_shards=2,
            device_reprobe_interval_s=1e9,
            mesh_reprobe_interval_s=0.05,
        )
        svc = VerdictService(path, DaemonConfig(**cfg_kw)).start()
        client = SidecarClient(svc.socket_path, timeout=120.0)
        mod = client.open_module([])
        assert client.policy_update(mod, [_policy()]) == int(
            FilterResult.OK
        )
        shim = _conn(client, mod, 1, 3)
        res, out = shim.on_io(False, b"HALT\r\n")
        assert out == b"HALT\r\n"
        _arm_named_loss(svc, 3)
        res, out = shim.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        _await_rung(svc, "reshaped")

        snap = svc.snapshot_handoff()
        assert snap["mesh"] == {"lost": [3], "reshapes": 1}
        assert snap["guard"]["devices"]["3"]["state"] == "lost"
        client.close()
        client = None
        svc.stop()
        svc = None

        fresh = VerdictService(path, DaemonConfig(**cfg_kw))
        assert fresh.restore_handoff(snap) is True
        # Device 3 is STILL dead across the restart.
        fresh._device_probe_fn = lambda dev: dev.id != 3
        fresh.start()
        client2 = SidecarClient(fresh.socket_path, timeout=120.0)
        mod2 = client2.open_module([])
        assert client2.policy_update(mod2, [_policy()]) == int(
            FilterResult.OK
        )
        # Mesh resolution is lazy (first engine build): drive a frame
        # before inspecting the inherited rung.
        s1 = _conn(client2, mod2, 1, 3)
        res, out = s1.on_io(False, b"HALT\r\n")
        assert res == int(FilterResult.OK) and out == b"HALT\r\n"
        s1.close()
        st = fresh.status()["mesh"]
        assert st["rung"] == "reshaped", st
        assert st["lost_devices"] == [3]
        assert st["reshapes"] == 1
        assert 0.0 < st["capacity_frac"] < 1.0
        assert 3 not in {d.id for d in fresh._mesh_serving.devices.flat}
        assert fresh.guard.device_table()["3"]["state"] == "lost"
        # Bit-identical service on the inherited reshaped rung.
        for i, (frame, remote, want) in enumerate(TRAFFIC):
            s2 = _conn(client2, mod2, 100 + i, remote)
            res, out = s2.on_io(False, frame)
            assert res == int(FilterResult.OK)
            assert (out == frame) == want, (frame, out)
            s2.close()
        # Heal walks back up — inherited degradation is not sticky.
        fresh._device_probe_fn = lambda dev: True
        st = _await_rung(fresh, "full", client2, mod2, drive=True)
        assert st["repromotions"] == 1
        assert fresh.guard.device_table()["3"]["state"] == "ok"
    finally:
        for c in (client, client2):
            if c is not None:
                c.close()
        for s in (svc, fresh):
            if s is not None:
                s.stop()
        inst.reset_module_registry()


# --- >32-wide layouts: degenerate shapes (satellite 1, ROADMAP 5b) --------

def test_mesh_extents_64_wide_and_auto_cap():
    from cilium_tpu.parallel import mesh_extents

    # Explicit 64-wide flow split is honored (no max_flow cap).
    assert mesh_extents("on", flow_shards=64, n_devices=64) == (64, 1)
    assert mesh_extents("on", rule_shards=2, flow_shards=64,
                        n_devices=128) == (64, 2)
    # AUTO derivation still caps at max_flow.
    assert mesh_extents("on", n_devices=128) == (32, 1)
    assert mesh_extents("on", n_devices=128, max_flow=64) == (64, 1)
    # pow2 floor; infeasible explicit extents resolve to None.
    assert mesh_extents("on", flow_shards=48, n_devices=64) == (32, 1)
    assert mesh_extents("on", flow_shards=64, n_devices=32) is None
    assert mesh_extents("off") is None


def test_reshape_mesh_rungs_on_real_devices():
    import jax

    from cilium_tpu.parallel import (
        FLOW_AXIS, RULE_AXIS, reshape_mesh,
    )

    devs = jax.devices()
    assert len(devs) == 8  # conftest forces 8 virtual CPU devices
    # 7 survivors, rule extent 2 preserved: pow2 floor -> 2x2.
    m = reshape_mesh(devs[:3] + devs[4:], rule_shards=2, max_flow=4)
    assert (m.shape[FLOW_AXIS], m.shape[RULE_AXIS]) == (2, 2)
    assert devs[3] not in set(m.devices.flat)
    # 3 survivors still fill rule extent 2 -> 1x2.
    m = reshape_mesh(devs[:3], rule_shards=2)
    assert (m.shape[FLOW_AXIS], m.shape[RULE_AXIS]) == (1, 2)
    # 2 survivors cannot fill rule extent 4 -> halved to 2.
    m = reshape_mesh(devs[:2], rule_shards=4)
    assert (m.shape[FLOW_AXIS], m.shape[RULE_AXIS]) == (1, 2)
    # A lone survivor is below the minimum mesh width.
    assert reshape_mesh(devs[:1], rule_shards=2) is None
    assert reshape_mesh([], rule_shards=1) is None


def test_sharded_split_64_wide_degenerate_shapes():
    """64-way splits of tiny row sets: empty shards get never-matching
    NFA rows, offsets stay monotone, and the stacked model's leading
    shard dim is the full 64 — the shapes a >32-device pod builds."""
    import jax

    from cilium_tpu.parallel.rulesharding import (
        build_sharded_r2d2_from_rows, shard_offsets, split_balanced,
    )

    rows = [
        ([1, 3], "READ", "/public/.*"),
        ([], "HALT", ""),
        ([9], "WRITE", "^/tmp/"),
        ([], "", "\\.txt$"),
    ]
    chunks = split_balanced(rows, 64)
    assert len(chunks) == 64
    assert [len(c) for c in chunks[:4]] == [1, 1, 1, 1]
    assert all(not c for c in chunks[4:])
    offs = np.asarray(shard_offsets(len(rows), 64))
    assert offs.shape == (64,)
    assert list(offs[:4]) == [0, 1, 2, 3]
    assert all(int(o) == 4 for o in offs[4:])
    assert all(b >= a for a, b in zip(offs, offs[1:]))

    model = build_sharded_r2d2_from_rows(rows, 64, bucket=True)
    lead = {
        int(x.shape[0])
        for x in jax.tree_util.tree_leaves(model)
        if hasattr(x, "shape") and x.shape
    }
    assert lead == {64}

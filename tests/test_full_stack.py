"""The complete control-to-data-plane path in one test:

  k8s CNP (fake apiserver) → watch loop → rule translation → policy
  repository → endpoint regeneration → NPDS push → live verdict
  service → datapath shim connection → per-request L7 verdicts,

the end-to-end slice the reference implements across
daemon/k8s_watcher.go → pkg/policy → pkg/endpoint → pkg/envoy (NPDS)
→ Envoy cilium.l7policy, here landing on the TPU verdict service."""

import time

import pytest

from cilium_tpu.daemon.daemon import Daemon
from cilium_tpu.k8s import FakeApiServer, K8sWatcher
from cilium_tpu.k8s.apiserver import KIND_CNP
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.parsers.http import HTTP_403
from cilium_tpu.proxylib.types import FilterResult
from cilium_tpu.sidecar.client import SidecarClient
from cilium_tpu.sidecar.service import VerdictService
from cilium_tpu.utils.option import DaemonConfig

NS = "team-a"


def wait_for(pred, timeout=8.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.02)
    return False


def cnp(name, spec):
    return {"metadata": {"name": name, "namespace": NS}, "spec": spec}


def test_k8s_cnp_to_sidecar_verdicts(tmp_path):
    inst.reset_module_registry()
    svc = VerdictService(
        str(tmp_path / "vs.sock"), DaemonConfig(batch_timeout_ms=2.0)
    ).start()
    d = Daemon(DaemonConfig(state_dir=str(tmp_path / "state"),
                            dry_mode=True, enable_health=False))
    apisrv = FakeApiServer()
    watcher = K8sWatcher(d, apisrv).start()
    shim = None
    try:
        # Workload endpoints (as the CNI would create them).
        ns_label = f"k8s:io.kubernetes.pod.namespace={NS}"
        client_ep = d.endpoint_create(
            21, ipv4="10.20.0.21",
            labels=["k8s:app=frontend", ns_label],
        )
        server_ep = d.endpoint_create(
            22, ipv4="10.20.0.22",
            labels=["k8s:app=api", ns_label],
        )

        # Operator applies a CNP through the (fake) apiserver.
        apisrv.upsert(KIND_CNP, cnp("api-allow", {
            "endpointSelector": {"matchLabels": {"app": "api"}},
            "ingress": [{
                "fromEndpoints": [{"matchLabels": {"app": "frontend"}}],
                "toPorts": [{
                    "ports": [{"port": "80", "protocol": "TCP"}],
                    "rules": {"http": [
                        {"method": "GET", "path": "/v1/.*"}
                    ]},
                }],
            }],
        }))
        watcher.sync()
        assert d.get_policy_repository().num_rules() == 1
        assert wait_for(lambda: server_ep.desired_l4_policy is not None)
        assert wait_for(
            lambda: len(server_ep.desired_l4_policy.ingress) > 0
        )

        # The daemon syncs the verdict service (NPDS push).
        pusher = d.attach_verdict_service(svc.socket_path)
        assert pusher.nacks == 0

        # Datapath: a shim registers the frontend->api connection.
        sc = SidecarClient(svc.socket_path, timeout=120.0)
        try:
            mod = sc.open_module([])
            res, shim = sc.new_connection(
                mod, "http", 31, True,
                client_ep.security_identity.id,
                server_ep.security_identity.id,
                "10.20.0.21:42000", "10.20.0.22:80", "10.20.0.22",
            )
            assert res == int(FilterResult.OK)

            ok = b"GET /v1/users HTTP/1.1\r\n\r\n"
            bad = b"DELETE /v1/users HTTP/1.1\r\n\r\n"
            _, out = shim.on_io(False, ok)
            assert out == ok  # the CNP's allow, enforced on device
            _, out = shim.on_io(False, bad)
            assert out == b""
            _, out = shim.on_io(True, b"")
            assert out == HTTP_403

            # Operator DELETES the CNP: the revocation propagates the
            # whole way back down to live verdicts.
            apisrv.delete(KIND_CNP, NS, "api-allow")
            watcher.sync()
            assert d.get_policy_repository().num_rules() == 0

            def revoked():
                r, s = sc.new_connection(
                    mod, "http", 32, True,
                    client_ep.security_identity.id,
                    server_ep.security_identity.id,
                    "10.20.0.21:42001", "10.20.0.22:80", "10.20.0.22",
                )
                if r != int(FilterResult.OK):
                    return False
                _, o = s.on_io(False, ok)
                return o == b""

            assert wait_for(revoked)
        finally:
            sc.close()
    finally:
        watcher.stop()
        d.close()
        svc.stop()
        inst.reset_module_registry()


def test_daemon_restart_restores_enforcement(tmp_path):
    """Checkpoint/resume through to the data plane: a restarted daemon
    restores its endpoints from disk, re-resolves policy, re-attaches
    to the verdict service, and the SAME rules enforce again
    (reference: restoreOldEndpoints + regenerateRestoredEndpoints,
    then the NPDS resync on proxy support start)."""
    import json as _json

    from cilium_tpu.policy import rules_from_json

    inst.reset_module_registry()
    state = str(tmp_path / "state")
    svc = VerdictService(
        str(tmp_path / "vs2.sock"), DaemonConfig(batch_timeout_ms=2.0)
    ).start()
    rule_json = _json.dumps([{
        "endpointSelector": {"matchLabels": {"app": "api"}},
        "labels": ["k8s:policy=restart-test"],
        "ingress": [{
            "fromEndpoints": [{"matchLabels": {"app": "frontend"}}],
            "toPorts": [{
                "ports": [{"port": "80", "protocol": "TCP"}],
                "rules": {"http": [{"method": "GET", "path": "/v1/.*"}]},
            }],
        }],
    }])

    cfg = lambda: DaemonConfig(run_dir=str(tmp_path), state_dir=state,
                               dry_mode=True, enable_health=False,
                               kvstore="file",
                               kvstore_opts={
                                   "path": str(tmp_path / "kv.json")})
    d1 = Daemon(cfg())
    d1.policy_add(rules_from_json(rule_json))
    c1 = d1.endpoint_create(41, ipv4="10.30.0.41",
                            labels=["k8s:app=frontend"])
    s1 = d1.endpoint_create(42, ipv4="10.30.0.42", labels=["k8s:app=api"])
    assert wait_for(lambda: s1.desired_l4_policy is not None)
    d1.build_queue.wait_idle(10)
    # dry mode skips the per-regeneration persist: checkpoint explicitly
    # (the reference equivalent of the endpoint state sync on shutdown)
    c1.write_state(d1._state_dir())
    s1.write_state(d1._state_dir())
    d1.close()  # "crash" with checkpointed endpoint state

    # Fresh daemon process: restore + re-add policy (the policy file /
    # k8s source re-applies rules on boot) + attach.
    d2 = Daemon(cfg())
    try:
        d2.policy_add(rules_from_json(rule_json))
        # bootstrap already restored from the state dir (restore_state
        # defaults on, mirroring restoreOldEndpoints in NewDaemon)
        assert len(d2.endpoint_manager) == 2
        s2 = d2.endpoint_manager.lookup(42)
        assert s2 is not None
        assert wait_for(lambda: s2.desired_l4_policy is not None)
        pusher = d2.attach_verdict_service(svc.socket_path)
        assert pusher.nacks == 0

        sc = SidecarClient(svc.socket_path, timeout=120.0)
        try:
            mod = sc.open_module([])
            res, shim = sc.new_connection(
                mod, "http", 51, True,
                s2 and d2.endpoint_manager.lookup(41).security_identity.id,
                s2.security_identity.id,
                "10.30.0.41:40000", "10.30.0.42:80", "10.30.0.42",
            )
            assert res == int(FilterResult.OK)
            ok = b"GET /v1/x HTTP/1.1\r\n\r\n"
            bad = b"POST /v1/x HTTP/1.1\r\n\r\n"
            _, out = shim.on_io(False, ok)
            assert out == ok
            _, out = shim.on_io(False, bad)
            assert out == b""
        finally:
            sc.close()
    finally:
        d2.close()
        svc.stop()
        inst.reset_module_registry()

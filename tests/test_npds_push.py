"""Daemon -> verdict-service NPDS push: the control-plane/data-plane
bridge (reference: pkg/envoy/server.go:607 getNetworkPolicy + :628
UpdateNetworkPolicy).  Policy added through the daemon's API must
change verdicts rendered by a live verdict service, end to end."""

import time

import pytest

from cilium_tpu.daemon.daemon import Daemon
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.parsers.http import HTTP_403
from cilium_tpu.proxylib.types import FilterResult
from cilium_tpu.sidecar.client import SidecarClient
from cilium_tpu.sidecar.service import VerdictService
from cilium_tpu.utils.option import DaemonConfig


def wait_for(pred, timeout=8.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.02)
    return False


HTTP_RULE = {
    "endpointSelector": {"matchLabels": {"app": "server"}},
    "labels": ["k8s:policy=http-test"],
    "ingress": [
        {
            "fromEndpoints": [{"matchLabels": {"app": "client"}}],
            "toPorts": [
                {
                    "ports": [{"port": "80", "protocol": "TCP"}],
                    "rules": {
                        "http": [{"method": "GET", "path": "/public/.*"}]
                    },
                }
            ],
        }
    ],
}


@pytest.fixture
def world(tmp_path):
    inst.reset_module_registry()
    svc = VerdictService(
        str(tmp_path / "vs.sock"), DaemonConfig(batch_timeout_ms=2.0)
    ).start()
    d = Daemon(DaemonConfig(state_dir=str(tmp_path / "state"),
                            dry_mode=True, enable_health=False))
    yield d, svc
    d.close()
    svc.stop()
    inst.reset_module_registry()


def test_daemon_policy_drives_verdict_service(world):
    d, svc = world
    # Control plane: policy + endpoints through the daemon's own API.
    import json

    from cilium_tpu.policy import rules_from_json

    rules = rules_from_json(json.dumps([HTTP_RULE]))
    rule = rules[0]
    d.policy_add(rules)
    client_ep = d.endpoint_create(11, ipv4="10.9.0.11",
                                  labels=["k8s:app=client"])
    server_ep = d.endpoint_create(12, ipv4="10.9.0.12",
                                  labels=["k8s:app=server"])
    assert wait_for(lambda: server_ep.desired_l4_policy is not None)

    # Bridge: attach the NPDS push to the live verdict service.
    pusher = d.attach_verdict_service(svc.socket_path)
    assert pusher.pushes >= 1 and pusher.nacks == 0

    # Data plane: a datapath shim registers a connection against the
    # endpoint's pushed policy (keyed by endpoint IP) with the CLIENT
    # endpoint's identity as the remote.
    shim_client = SidecarClient(svc.socket_path, timeout=120.0)
    try:
        mod = shim_client.open_module([])
        res, shim = shim_client.new_connection(
            mod, "http", 9001, True,
            client_ep.security_identity.id, server_ep.security_identity.id,
            "10.9.0.11:40000", "10.9.0.12:80", "10.9.0.12",
        )
        assert res == int(FilterResult.OK)

        ok_req = b"GET /public/index.html HTTP/1.1\r\n\r\n"
        bad_req = b"GET /admin HTTP/1.1\r\n\r\n"
        _, out = shim.on_io(False, ok_req)
        assert out == ok_req  # allowed by the daemon's rule
        _, out = shim.on_io(False, bad_req)
        assert out == b""  # denied
        _, out = shim.on_io(True, b"")
        assert out == HTTP_403

        # A remote that is NOT the client endpoint's identity is denied
        # even for the allowed path (fromEndpoints selector).
        res, shim2 = shim_client.new_connection(
            mod, "http", 9002, True,
            99999, server_ep.security_identity.id,
            "10.9.9.9:40000", "10.9.0.12:80", "10.9.0.12",
        )
        assert res == int(FilterResult.OK)
        _, out = shim2.on_io(False, ok_req)
        assert out == b""

        # Control-plane change propagates: delete the rule -> the next
        # regeneration pushes a policy with no HTTP allows.
        deleted_rev, deleted = d.policy_delete(rule.labels)
        assert deleted >= 1
        assert wait_for(
            lambda: pusher.pushes >= 2 and (
                shim_client.new_connection(
                    mod, "http", 9003, True,
                    client_ep.security_identity.id,
                    server_ep.security_identity.id,
                    "10.9.0.11:41000", "10.9.0.12:80", "10.9.0.12",
                )[1].on_io(False, ok_req)[1] == b""
            )
        )
    finally:
        shim_client.close()


def test_verdict_service_status_surfaces_in_daemon(world):
    """`cilium status` shows the attached verdict service's counters
    (the agent's proxy-admin scrape analog)."""
    d, svc = world
    assert d.status()["verdict_service"] is None  # not attached yet
    d.attach_verdict_service(svc.socket_path)
    st = d.status()["verdict_service"]
    assert st["state"] == "Ok"
    assert st["npds_pushes"] >= 0 and "dispatcher" in st
    assert "connections" in st and "requests" in st

"""HTTP through the parser seam and the sidecar verdict service
(reference: envoy/cilium_l7policy.cc — here served by proxylib-style
parsing + the HTTP batch model instead of an Envoy HTTP filter)."""

import json
from dataclasses import asdict

import pytest

from cilium_tpu.proxylib import (
    NetworkPolicy,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
)
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.parsers.http import HTTP_403, head_and_body_len
from cilium_tpu.proxylib.types import DROP, MORE, PASS, FilterResult
from cilium_tpu.sidecar.client import SidecarClient
from cilium_tpu.sidecar.service import VerdictService
from cilium_tpu.utils.option import DaemonConfig

from proxylib_harness import new_connection


def http_policy(name="http-pol"):
    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=[1, 3],
                        http_rules=[
                            {"method": "GET", "path": "/public/.*"},
                            {"method": "POST", "path": "/api/v[0-9]+/submit"},
                        ],
                    )
                ],
            )
        ],
    )


def req(method="GET", path="/", headers=(), body=b""):
    head = f"{method} {path} HTTP/1.1\r\n".encode()
    for h in headers:
        head += h.encode() + b"\r\n"
    if body:
        head += f"Content-Length: {len(body)}\r\n".encode()
    return head + b"\r\n" + body


def test_framing():
    r = req("GET", "/x", body=b"hello")
    assert head_and_body_len(r) == (len(r) - 5, 5)
    assert head_and_body_len(r[:-1]) is None  # body short
    assert head_and_body_len(b"GET / HTTP/1.1\r\n") is None  # head open


# --- streaming parser (the oracle) ----------------------------------------

@pytest.fixture
def conn():
    inst.reset_module_registry()
    mod = inst.open_module([], True)
    ins = inst.find_instance(mod)
    ins.policy_update([http_policy()])
    res, c = new_connection(
        mod, "http", True, 1, 2, "1.1.1.1:1", "2.2.2.2:80", "http-pol"
    )
    assert res == FilterResult.OK
    yield c
    inst.close_module(mod)
    inst.reset_module_registry()


def drive(c, reply, buf):
    ops = []
    c.on_data(reply, False, [buf], ops)
    return ops, c.reply_buf.take()


def test_parser_allow_deny_and_403(conn):
    r_ok = req("GET", "/public/a.html")
    ops, inj = drive(conn, False, r_ok)
    assert ops == [(PASS, len(r_ok)), (MORE, 1)]
    assert inj == b""

    r_bad = req("GET", "/private/x")
    ops, inj = drive(conn, False, r_bad)
    assert ops == [(DROP, len(r_bad)), (MORE, 1)]
    assert inj == HTTP_403

    # method must match too
    r_post = req("POST", "/public/a.html")
    ops, _ = drive(conn, False, r_post)
    assert ops[0][0] == DROP


def test_parser_body_rides_verdict_and_replies_pass(conn):
    r = req("POST", "/api/v2/submit", body=b"payload-bytes")
    ops, inj = drive(conn, False, r)
    assert ops == [(PASS, len(r)), (MORE, 1)]
    # partial frame: MORE until the body arrives
    r2 = req("POST", "/api/v2/submit", body=b"xyz")
    ops, _ = drive(conn, False, r2[:-2])
    assert ops == [(MORE, 1)]
    ops, _ = drive(conn, False, r2)
    assert ops == [(PASS, len(r2)), (MORE, 1)]
    # reply direction passes untouched
    resp = b"HTTP/1.1 200 OK\r\ncontent-length: 2\r\n\r\nok"
    ops, _ = drive(conn, True, resp)
    assert ops == [(PASS, len(resp)), (MORE, 1)]


# --- sidecar end-to-end ----------------------------------------------------

@pytest.fixture
def service(tmp_path):
    inst.reset_module_registry()
    svc = VerdictService(
        str(tmp_path / "http.sock"), DaemonConfig(batch_timeout_ms=2.0)
    ).start()
    yield svc
    svc.stop()
    inst.reset_module_registry()


def test_http_through_sidecar(service):
    client = SidecarClient(service.socket_path, timeout=120.0)
    try:
        mod = client.open_module([])
        assert mod != 0
        assert client.policy_update(mod, [http_policy()]) == int(
            FilterResult.OK
        )
        res, shim = client.new_connection(
            mod, "http", 7001, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "http-pol",
        )
        assert res == int(FilterResult.OK)

        r_ok = req("GET", "/public/index.html")
        _, out = shim.on_io(False, r_ok)
        assert out == r_ok

        # denied: dropped + 403 on the reply side
        r_bad = req("GET", "/secret")
        _, out = shim.on_io(False, r_bad)
        assert out == b""
        _, out = shim.on_io(True, b"")
        assert out == HTTP_403

        # frame split across calls, with body
        r3 = req("POST", "/api/v9/submit", body=b"0123456789")
        _, out_a = shim.on_io(False, r3[:20])
        _, out_b = shim.on_io(False, r3[20:])
        assert out_a + out_b == r3

        # disallowed remote: same request denied for identity 9
        res, shim9 = client.new_connection(
            mod, "http", 7002, True, 9, 2, "9.9.9.9:1", "2.2.2.2:80",
            "http-pol",
        )
        assert res == int(FilterResult.OK)
        _, out = shim9.on_io(False, r_ok)
        assert out == b""

        # the device path actually judged frames
        engines = [
            e for e in service._engines.values()
            if getattr(e, "proto", "") == "http"
        ]
        assert engines and engines[0].device_judged >= 1
    finally:
        client.close()


def test_negative_content_length_does_not_loop(conn):
    """A negative Content-Length must not walk framing backwards
    (unauthenticated DoS vector in the peek loop)."""
    evil = b"GET /public/a HTTP/1.1\r\ncontent-length: -44\r\n\r\n"
    assert head_and_body_len(evil) == (len(evil), 0)
    ops, _ = drive(conn, False, evil)
    assert ops[0][0] == PASS and ops[0][1] == len(evil)


def test_malformed_request_line_keeps_verdict_queue_aligned(service):
    """A frame whose request line cannot parse is denied WITHOUT a
    device verdict; a pipelined valid frame after it must still get ITS
    verdict, not the malformed frame's (policy-bypass regression)."""
    client = SidecarClient(service.socket_path, timeout=120.0)
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [http_policy()]) == int(
            FilterResult.OK
        )
        res, shim = client.new_connection(
            mod, "http", 7100, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "http-pol",
        )
        assert res == int(FilterResult.OK)
        bad = b"GET /\r\n\r\n"  # two tokens: parse_head rejects
        good = req("GET", "/public/ok")
        denied = req("GET", "/private/no")
        _, out = shim.on_io(False, bad + good + denied)
        # malformed frame dropped, good frame passed, denied dropped
        assert out == good
    finally:
        client.close()


def test_http_wave_batching_parity(tmp_path):
    """Aggregated rounds with MULTIPLE pipelined requests per conn run
    through the wave-batched slow path (nth entry of every conn judged
    in one device batch per wave) — verdict sequences must match the
    per-request oracle exactly."""
    import threading

    import numpy as np

    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.sidecar.client import SidecarClient
    from cilium_tpu.sidecar.service import VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    inst.reset_module_registry()
    svc = VerdictService(
        str(tmp_path / "wv.sock"), DaemonConfig(batch_timeout_ms=0.0)
    ).start()
    cl = SidecarClient(svc.socket_path, timeout=300.0)
    try:
        mod = cl.open_module([])
        assert cl.policy_update(mod, [http_policy()]) == int(FilterResult.OK)
        N = 4
        for cid in range(1, N + 1):
            res, _ = cl.new_connection(
                mod, "http", cid, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
                "http-pol",
            )
            assert res == int(FilterResult.OK)
        reqs = [
            b"GET /public/a HTTP/1.1\r\nHost: h\r\n\r\n",   # allow
            b"GET /private/b HTTP/1.1\r\nHost: h\r\n\r\n",  # deny
            b"GET /public/c HTTP/1.1\r\nHost: h\r\n\r\n",   # allow
        ]
        got: dict[int, object] = {}
        evt = threading.Event()

        def cb(vb):
            got[vb.seq] = vb
            evt.set()

        cl.verdict_callback = cb
        # ONE DataBatch carrying all three requests PER CONN (repeated
        # conn ids) — a single round whose slow set has three entries
        # per conn, deterministically exercising waves 0..2 and their
        # per-conn op attribution.
        ids = np.concatenate(
            [np.arange(1, N + 1, dtype=np.uint64)] * len(reqs)
        )
        lens = np.concatenate(
            [np.full(N, len(r), np.uint32) for r in reqs]
        )
        blob = b"".join(r * N for r in reqs)
        cl.send_batch(77, ids, np.zeros(len(ids), np.uint8), lens, blob)
        assert evt.wait(240), sorted(got)

        vb = got[77]
        assert vb.count == N * len(reqs)
        for j in range(vb.count):
            cid, res, ops, _io, ir = vb.entry(j)
            k = j // N  # request index (entries in send order)
            assert res == int(FilterResult.OK)
            kinds = [int(o) for o, _ in ops]
            allow = k != 1
            if allow:
                assert int(PASS) in kinds and int(DROP) not in kinds, (
                    k, cid, ops,
                )
                assert ir == b""
            else:
                assert int(DROP) in kinds, (k, cid, ops)
                assert b"403" in ir  # injected denial response
    finally:
        cl.close()
        svc.stop()
        inst.reset_module_registry()

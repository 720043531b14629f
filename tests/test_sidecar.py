"""Sidecar seam tests: wire protocol, dispatcher, and op/byte parity of
the service+shim path against the in-process oracle.

The service+shim pair must reproduce the exact FilterOp sequences the
in-process proxylib oracle produces (the reference's bit-exactness
contract, proxylib/proxylib/test_util.go) — including partial frames,
pipelined frames, reply traffic, denials with injected error replies,
and policy swaps.
"""

from __future__ import annotations

import random
import threading
import time

import numpy as np
import pytest

from cilium_tpu.proxylib import (
    NetworkPolicy,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
    FilterResult,
)
from cilium_tpu.proxylib import instance as inst
from cilium_tpu.proxylib.types import DROP, MORE, PASS
from cilium_tpu.sidecar import BatchDispatcher, SidecarClient, VerdictService
from cilium_tpu.sidecar import wire
from cilium_tpu.utils.option import DaemonConfig

from proxylib_harness import new_connection


def r2d2_policy(name="sidecar-pol"):
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
                    )
                ],
            )
        ],
    )


@pytest.fixture
def service(tmp_path):
    inst.reset_module_registry()
    cfg = DaemonConfig(batch_timeout_ms=2.0, batch_flows=512)
    svc = VerdictService(str(tmp_path / "verdict.sock"), cfg).start()
    yield svc
    svc.stop()
    inst.reset_module_registry()


@pytest.fixture
def client(service):
    # The service's first engine prewarm compiles every bucket lazily
    # inside whichever RPC triggers it; compile wall-time late in a
    # long pytest process can exceed the default 10s RPC timeout and
    # flake the test that got unlucky.
    c = SidecarClient(service.socket_path, timeout=60.0)
    yield c
    c.close()


def open_with_policy(client, policies=None):
    mod = client.open_module([])
    assert mod != 0
    assert client.policy_update(mod, policies or [r2d2_policy()]) == int(
        FilterResult.OK
    )
    return mod


# --- wire round trips ----------------------------------------------------

def test_wire_data_batch_roundtrip():
    blob = b"helloworldxy"
    payload = wire.pack_data_batch(7, [1, 2, 3], [0, 1, 2], [5, 5, 2], blob)
    b = wire.unpack_data_batch(payload)
    assert b.seq == 7 and b.count == 3
    assert b.entry(0) == (1, False, False, b"hello")
    assert b.entry(1) == (2, True, False, b"world")
    assert b.entry(2) == (3, False, True, b"xy")


def test_wire_verdict_batch_roundtrip():
    ops = np.zeros(3, wire.FILTER_OP)
    ops["op"] = [1, 2, 0]
    ops["n_bytes"] = [10, 4, 1]
    payload = wire.pack_verdict_batch(
        9, [5, 6], [0, 0], [2, 1], [1, 0], [3, 2], ops, b"XabcYZ"
    )
    v = wire.unpack_verdict_batch(payload)
    assert v.seq == 9 and v.count == 2
    assert v.entry(0) == (5, 0, [(1, 10), (2, 4)], b"X", b"abc")
    assert v.entry(1) == (6, 0, [(0, 1)], b"", b"YZ")


# --- dispatcher ----------------------------------------------------------

def test_dispatcher_fill_trigger():
    batches = []
    done = threading.Event()

    def proc(items):
        batches.append(list(items))
        done.set()

    d = BatchDispatcher(proc, max_batch=4, timeout_ms=10_000).start()
    try:
        for i in range(4):
            d.submit(i)
        assert done.wait(2)
        assert batches and len(batches[0]) == 4
        assert d.fill_dispatches == 1 and d.deadline_dispatches == 0
    finally:
        d.stop()


def test_dispatcher_deadline_trigger():
    got = threading.Event()
    latency = {}

    def proc(items):
        latency["t"] = time.perf_counter()
        got.set()

    d = BatchDispatcher(proc, max_batch=1_000_000, timeout_ms=5.0).start()
    try:
        t0 = time.perf_counter()
        d.submit("x")
        assert got.wait(2)
        waited = latency["t"] - t0
        assert 0.004 <= waited < 0.5, waited
        assert d.deadline_dispatches == 1
    finally:
        d.stop()


# --- service parity vs in-process oracle ---------------------------------

CORPUS = [
    b"READ /public/a.txt\r\n",
    b"READ /private/x\r\n",
    b"HALT\r\n",
    b"WRITE /public/b\r\n",
    b"RESET\r\n",
    b"READ /public/deep/path/c.dat\r\n",
]


def oracle_ops(policy, msgs, remote_id=1, reply_flags=None):
    """Run msgs through the in-process oracle, one on_data per msg,
    returning [(ops, reply_inject)]"""
    mod = inst.open_module([], True)
    ins = inst.find_instance(mod)
    ins.policy_update([policy])
    res, conn = new_connection(
        mod, "r2d2", True, remote_id, 2, "1.1.1.1:1", "2.2.2.2:80",
        policy.name,
    )
    assert res == FilterResult.OK
    out = []
    buf = {False: b"", True: b""}
    for i, m in enumerate(msgs):
        reply = bool(reply_flags[i]) if reply_flags else False
        buf[reply] += m
        ops = []
        conn.on_data(reply, False, [buf[reply]], ops)
        consumed = sum(n for op, n in ops if op in (PASS, DROP))
        buf[reply] = buf[reply][consumed:]
        out.append((list(ops), conn.reply_buf.take()))
    inst.close_module(mod)
    return out


def shim_ops(client, msgs, remote_id=1, reply_flags=None, conn_id=1000):
    mod = open_with_policy(client)
    res, shim = client.new_connection(
        mod, "r2d2", conn_id, True, remote_id, 2, "1.1.1.1:1",
        "2.2.2.2:80", "sidecar-pol",
    )
    assert res == int(FilterResult.OK)
    out = []
    for i, m in enumerate(msgs):
        reply = bool(reply_flags[i]) if reply_flags else False
        result, entries = client._on_data_rpc(shim.conn_id, reply, False, m)
        ops = []
        inj_reply = b""
        for _, r, eops, io, ir in entries:
            assert r == int(FilterResult.OK)
            ops.extend(eops)
            inj_reply += ir
        out.append((ops, inj_reply))
    shim.close()
    return out


def assert_parity(got, exp):
    assert len(got) == len(exp)
    for i, ((gops, ginj), (eops, einj)) in enumerate(zip(got, exp)):
        gops = [(int(o), int(n)) for o, n in gops]
        eops = [(int(o), int(n)) for o, n in eops]
        assert gops == eops, f"msg {i}: ops {gops} != {eops}"
        assert ginj == einj, f"msg {i}: inject {ginj!r} != {einj!r}"


def test_sidecar_parity_single_frames(client):
    exp = oracle_ops(r2d2_policy(), CORPUS)
    got = shim_ops(client, CORPUS)
    assert_parity(got, exp)


def test_sidecar_parity_denied_remote(client):
    # remote 9 not in remote_policies -> everything denied
    exp = oracle_ops(r2d2_policy(), CORPUS, remote_id=9)
    got = shim_ops(client, CORPUS, remote_id=9)
    assert_parity(got, exp)


def test_sidecar_parity_split_and_pipelined(client):
    msgs = [
        b"READ /pub",  # partial
        b"lic/a.txt\r\nHALT\r\nREAD /private/x\r\n",  # completes + 2 more
        b"WRI",
        b"TE /public/b\r\n",
    ]
    exp = oracle_ops(r2d2_policy(), msgs)
    got = shim_ops(client, msgs)
    assert_parity(got, exp)


def test_sidecar_parity_reply_direction(client):
    msgs = [b"READ /public/a.txt\r\n", b"OK data\r\n", b"HALT\r\n"]
    flags = [0, 1, 0]
    exp = oracle_ops(r2d2_policy(), msgs, reply_flags=flags)
    got = shim_ops(client, msgs, reply_flags=flags)
    assert_parity(got, exp)


def test_sidecar_parity_fuzz(client):
    rng = random.Random(42)
    msgs = []
    raw = b"".join(
        CORPUS[rng.randrange(len(CORPUS))] for _ in range(60)
    )
    # random re-chunking: partial/pipelined mix
    i = 0
    while i < len(raw):
        n = rng.randrange(1, 40)
        msgs.append(raw[i : i + n])
        i += n
    exp = oracle_ops(r2d2_policy(), msgs)
    got = shim_ops(client, msgs)
    assert_parity(got, exp)


def test_sidecar_shim_on_io_output_bytes(client):
    """End-to-end byte semantics: allowed frames forwarded, denied frames
    removed with the error reply injected into the reply direction."""
    mod = open_with_policy(client)
    res, shim = client.new_connection(
        mod, "r2d2", 2000, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
        "sidecar-pol",
    )
    assert res == int(FilterResult.OK)
    res, out = shim.on_io(False, b"READ /public/a.txt\r\nREAD /private/x\r\n")
    assert res == int(FilterResult.OK)
    assert out == b"READ /public/a.txt\r\n"  # denied frame removed
    # The denial error surfaces at the head of the next reply-direction IO.
    res, out = shim.on_io(True, b"SERVED\r\n")
    assert res == int(FilterResult.OK)
    assert out == b"ERROR\r\nSERVED\r\n"
    shim.close()


def test_sidecar_oracle_drains_large_backlog(client):
    """A single entry carrying thousands of buffered frames is fully
    verdicted in one response: the oracle drain loop has no fixed
    iteration cap (a quiescent peer would stall tail frames forever),
    and the backlog exceeds the 64KB drain window so the windowed
    re-parse path is exercised too."""
    mod = open_with_policy(client)
    res, shim = client.new_connection(
        mod, "r2d2", 4242, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
        "sidecar-pol",
    )
    assert res == int(FilterResult.OK)
    line = b"OK 0123456\r\n"
    n = 10_000  # ~120KB of reply frames, > the 64KB window
    burst = line * n
    result, out = shim.on_io(True, burst)
    assert result == int(FilterResult.OK)
    assert out == burst  # every reply frame passed, none stalled
    shim.close()


def test_sidecar_policy_swap(client):
    mod = open_with_policy(client)
    res, shim = client.new_connection(
        mod, "r2d2", 3000, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
        "sidecar-pol",
    )
    assert res == int(FilterResult.OK)
    _, out = shim.on_io(False, b"READ /public/a.txt\r\n")
    assert out == b"READ /public/a.txt\r\n"
    # Swap to a policy denying READ /public
    pol = r2d2_policy()
    pol.ingress_per_port_policies[0].rules[0].l7_rules = [{"cmd": "RESET"}]
    assert client.policy_update(mod, [pol]) == int(FilterResult.OK)
    _, out = shim.on_io(False, b"READ /public/a.txt\r\n")
    assert out == b""
    shim.close()


def test_sidecar_unknown_parser(client):
    mod = client.open_module([])
    res, shim = client.new_connection(
        mod, "no-such-proto", 4000, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80", "p",
    )
    assert res == int(FilterResult.UNKNOWN_PARSER)
    assert shim is None


def test_sidecar_unknown_connection(client):
    open_with_policy(client)
    result, entries = client._on_data_rpc(99999, False, False, b"HALT\r\n")
    assert result == int(FilterResult.UNKNOWN_CONNECTION)


def test_sidecar_fast_path_used(service, client):
    """Single complete frames from fresh flows ride the vectorized fast
    path (columnar access log records them)."""
    mod = open_with_policy(client)
    for cid in range(5000, 5008):
        res, shim = client.new_connection(
            mod, "r2d2", cid, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "sidecar-pol",
        )
        assert res == int(FilterResult.OK)
        _, out = shim.on_io(False, b"READ /public/a.txt\r\n")
        assert out == b"READ /public/a.txt\r\n"
    assert service.fast_log.requests >= 8


# --- verdict device (measured config) -------------------------------------

@pytest.mark.parametrize("device", ["default", "cpu"])
def test_sidecar_dispatch_modes_bit_identical(tmp_path, device):
    """The default device and the cpu-backed verdict device the
    co-located latbench mode uses render identical verdicts vs the
    oracle — the verdict device is performance config, never policy."""
    inst.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=2.0, batch_flows=512, verdict_device=device,
    )
    svc = VerdictService(
        str(tmp_path / f"verdict-{device}.sock"), cfg
    ).start()
    try:
        c = SidecarClient(svc.socket_path, timeout=60.0)
        try:
            exp = oracle_ops(r2d2_policy(), CORPUS)
            got = shim_ops(c, CORPUS)
            assert_parity(got, exp)
        finally:
            c.close()
    finally:
        svc.stop()
        inst.reset_module_registry()


# --- grouped matrix rounds + VERDICT_MULTI --------------------------------

def test_wire_verdict_multi_roundtrip():
    """One MULTI frame answers several seqs with one columnar body."""
    ops = np.zeros((4,), wire.FILTER_OP)
    ops["op"] = [PASS, MORE, DROP, MORE]
    ops["n_bytes"] = [10, 1, 5, 1]
    body = wire.pack_verdict_body(
        [7, 8], [0, 0], [2, 2], [0, 0], [0, 7], ops, b"ERROR\r\n"
    )
    payload = wire.pack_verdict_multi([21, 22], [1, 1], 2, body)
    vbs = wire.unpack_verdict_multi(payload)
    assert [vb.seq for vb in vbs] == [21, 22]
    assert vbs[0].entry(0) == (7, 0, [(PASS, 10), (MORE, 1)], b"", b"")
    assert vbs[1].entry(0) == (8, 0, [(DROP, 5), (MORE, 1)], b"", b"ERROR\r\n")


def test_grouped_matrix_round_multi_verdicts(tmp_path):
    """A greedy service aggregates several complete-flag matrix batches
    into ONE group round and answers each client with one frame; the
    verdicts stay bit-identical to the oracle."""
    inst.reset_module_registry()
    cfg = DaemonConfig(batch_timeout_ms=0.0, batch_flows=512)
    svc = VerdictService(str(tmp_path / "v2.sock"), cfg).start()
    c = SidecarClient(svc.socket_path, timeout=60.0)
    try:
        mod = open_with_policy(c)
        width = cfg.batch_width
        n_conns = 12
        for cid in range(1, n_conns + 1):
            res, _ = c.new_connection(
                mod, "r2d2", cid, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
                "sidecar-pol",
            )
            assert res == int(FilterResult.OK)

        msgs = [CORPUS[i % len(CORPUS)] for i in range(n_conns)]
        got: dict[int, object] = {}
        evt = threading.Event()

        def cb(vb):
            got[vb.seq] = vb
            if len(got) == 3:
                evt.set()

        c.verdict_callback = cb
        # Three matrix batches back to back: the first may cut through,
        # the rest aggregate behind the in-flight round.
        for b in range(3):
            ids = np.arange(
                1 + b * 4, 5 + b * 4, dtype=np.uint64
            )
            lens = np.array(
                [len(msgs[int(i) - 1]) for i in ids], np.uint32
            )
            rows = np.zeros((4, width), np.uint8)
            for j, i in enumerate(ids):
                m = msgs[int(i) - 1]
                rows[j, : len(m)] = np.frombuffer(m, np.uint8)
            c.send_matrix(100 + b, width, ids, lens, rows.tobytes(),
                          complete=True)
        assert evt.wait(10), f"verdicts missing: {sorted(got)}"

        exp = oracle_ops(r2d2_policy(), msgs)
        for b in range(3):
            vb = got[100 + b]
            for j in range(vb.count):
                cid, res, ops, _io, ir = vb.entry(j)
                eops, einj = exp[cid - 1]
                assert [(int(o), int(n)) for o, n in ops] == [
                    (int(o), int(n)) for o, n in eops
                ], (cid, ops, eops)
                assert ir == einj, (cid, ir, einj)
    finally:
        c.close()
        svc.stop()
        inst.reset_module_registry()

"""r2d2 batch model vs streaming oracle: bit-identical verdicts.

The device pipeline (frame -> tokenize -> NFA match) must produce, for every
frame, exactly the PASS/DROP decision and byte count the in-process oracle
produces — the reference's own bit-exactness strategy
(reference: proxylib/proxylib/test_util.go).
"""

import random

import numpy as np
import pytest

from cilium_tpu.models.base import ConstVerdict
from cilium_tpu.models.r2d2 import (
    build_r2d2_model,
    r2d2_verdicts,
    r2d2_verdicts_attr,
)
from cilium_tpu.proxylib import (
    DROP,
    MORE,
    PASS,
    FilterResult,
    NetworkPolicy,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
    find_instance,
    open_module,
    reset_module_registry,
)
from proxylib_harness import new_connection

POLICIES = {
    "allow-all-l7": [PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[])],
    "read-only": [PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[{"cmd": "READ"}])],
    "public-files": [
        PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[{"file": "/public/.*"}])
    ],
    "read-public": [
        PortNetworkPolicyRule(
            l7_proto="r2d2", l7_rules=[{"cmd": "READ", "file": "^/public/"}]
        )
    ],
    "multi-rule": [
        PortNetworkPolicyRule(
            l7_proto="r2d2",
            l7_rules=[{"cmd": "HALT"}, {"cmd": "READ", "file": "\\.txt$"}],
        )
    ],
    "remote-gated": [
        PortNetworkPolicyRule(
            remote_policies=[7, 9], l7_proto="r2d2", l7_rules=[{"cmd": "READ"}]
        ),
        PortNetworkPolicyRule(remote_policies=[5], l7_proto="r2d2", l7_rules=[{"cmd": "RESET"}]),
    ],
}

CMDS = ["READ", "WRITE", "HALT", "RESET", "FLY", "read", ""]
FILES = [
    "", "/public/a.txt", "/public/", "/private/a.txt", "x/public/y",
    "a.txt", "/PUBLIC/A", "/public/deep/nest.txt", "s", "spaces in name",
]


def _policy(name, rules):
    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[PortNetworkPolicy(port=80, rules=rules)],
    )


@pytest.fixture(autouse=True)
def fresh_registry():
    reset_module_registry()
    yield
    reset_module_registry()


def _oracle_verdict(mod, policy_name, src_id, msg: bytes):
    """Streaming oracle verdict for one framed message."""
    res, conn = new_connection(
        mod, "r2d2", True, src_id, 2, "1.1.1.1:34567", "2.2.2.2:80", policy_name
    )
    assert res == FilterResult.OK
    ops = []
    res = conn.on_data(False, False, [msg + b"\r\n"], ops)
    assert res == FilterResult.OK
    op, n = ops[0]
    assert op in (PASS, DROP)
    assert n == len(msg) + 2
    return op == PASS


def test_r2d2_model_bit_identical_fuzz():
    rng = random.Random(1234)
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([_policy(n, r) for n, r in POLICIES.items()])

    # Build one batch per policy across a msg corpus.
    msgs = []
    for _ in range(80):
        kind = rng.random()
        if kind < 0.6:
            msg = f"{rng.choice(CMDS)} {rng.choice(FILES)}".encode()
        elif kind < 0.8:
            msg = rng.choice(CMDS).encode()
        else:  # adversarial: extra spaces, garbage bytes
            msg = rng.choice(
                [b"READ a b", b"READ  two", b" READ x", b"READ\t/x", b"\x01\x02",
                 b"READ /public/\xc3\xa9.txt", b"", b" ", b"READ "]
            )
        msgs.append(msg)

    max_len = max(len(m) for m in msgs) + 2
    f = len(msgs)
    data = np.zeros((f, max_len), dtype=np.uint8)
    lengths = np.zeros((f,), dtype=np.int32)
    for i, m in enumerate(msgs):
        framed = m + b"\r\n"
        data[i, : len(framed)] = np.frombuffer(framed, dtype=np.uint8)
        lengths[i] = len(framed)

    for policy_name in POLICIES:
        policy = ins.policy_map().get(policy_name)
        for src_id in (1, 5, 7):
            model = build_r2d2_model(policy, ingress=True, port=80)
            remotes = np.full((f,), src_id, dtype=np.int32)
            if isinstance(model, ConstVerdict):
                allows = np.full((f,), model.allow)
                msg_lens = lengths
            else:
                complete, msg_len, allow = r2d2_verdicts(model, data, lengths, remotes)
                assert np.asarray(complete).all()
                allows = np.asarray(allow)
                msg_lens = np.asarray(msg_len)
            for i, m in enumerate(msgs):
                expected = _oracle_verdict(mod, policy_name, src_id, m)
                assert msg_lens[i] == len(m) + 2
                assert allows[i] == expected, (
                    f"policy={policy_name} src={src_id} msg={m!r}: "
                    f"device={allows[i]} oracle={expected}"
                )


def _benchmark_frames(rng, n):
    """The five frames of the benchmark's r2d2 traffic, CRLF stripped."""
    out = []
    for _ in range(n):
        k = rng.randrange(0, 997)
        out.append(rng.choice([
            f"READ /public/f{k}.txt", f"READ /private/f{k}",
            f"WRITE /public/f{k}.txt", "HALT", "RESET",
        ]).encode())
    return out


def test_r2d2_model_full_width_frames():
    """Short frames in 256-byte rows beside one frame whose file span
    ends in the row's last 8-byte block: the automaton scan stops after
    the round's longest span, so both the short and the long round must
    match the oracle."""
    rng = random.Random(28)
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([_policy(n, r) for n, r in POLICIES.items()])
    width = 256
    short = _benchmark_frames(rng, 40) + [b"WRITE /public/f996.txt"]
    long_file = b"/public/" + b"d/" * ((width - 19) // 2) + b"x.txt"
    long_msg = b"READ " + long_file.ljust(width - 7, b"t")
    assert len(long_msg) + 2 == width
    for msgs in (short, short + [long_msg]):
        f = len(msgs)
        data = np.zeros((f, width), dtype=np.uint8)
        lengths = np.zeros((f,), dtype=np.int32)
        for i, m in enumerate(msgs):
            framed = m + b"\r\n"
            data[i, : len(framed)] = np.frombuffer(framed, dtype=np.uint8)
            lengths[i] = len(framed)
        for policy_name in POLICIES:
            policy = ins.policy_map().get(policy_name)
            model = build_r2d2_model(policy, ingress=True, port=80)
            if isinstance(model, ConstVerdict):
                continue
            for src_id in (1, 5, 7):
                remotes = np.full((f,), src_id, dtype=np.int32)
                complete, msg_len, allow = r2d2_verdicts(
                    model, data, lengths, remotes
                )
                assert np.asarray(complete).all()
                np.testing.assert_array_equal(np.asarray(msg_len), lengths)
                for i, m in enumerate(msgs):
                    expected = _oracle_verdict(mod, policy_name, src_id, m)
                    assert bool(np.asarray(allow)[i]) == expected, (
                        f"policy={policy_name} src={src_id} msg={m!r}"
                    )


def test_r2d2_attr_one_executable():
    """Rounds whose longest file span ends at byte 3, 22 and 256 of one
    (F, 256) shape run one attributed executable: the scan's bound is
    traced, so no round compiles."""
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([_policy("public-files", POLICIES["public-files"])])
    model = build_r2d2_model(ins.policy_map()["public-files"], True, 80)
    rounds = (
        [b"R a\r\n", b"HALT\r\n"],
        [b"WRITE /public/f996.txt\r\n", b"HALT\r\n"],
        [b"WRITE /public/f996.txt\r\n", b"READ /public/" + b"a" * 243],
    )
    f, width = 16, 256
    sizes = []
    for frames in rounds:
        data = np.zeros((f, width), dtype=np.uint8)
        lengths = np.zeros((f,), dtype=np.int32)
        for i, m in enumerate(frames):
            data[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
            lengths[i] = len(m)
        complete, _, allow, rule = r2d2_verdicts_attr(
            model, data, lengths, np.ones((f,), np.int32)
        )
        sizes.append(r2d2_verdicts_attr._cache_size())
        # allow is computed for a partial row too: it reads the scan.
        want = [b" /public/" in m for m in frames]
        assert np.asarray(allow)[: len(frames)].tolist() == want
        assert np.asarray(rule)[: len(frames)].tolist() == [
            0 if w else -1 for w in want
        ]
    # The last round's partial row spans to byte 256 and is not complete.
    assert not bool(np.asarray(complete)[1])
    assert sizes[0] == sizes[1] == sizes[2], sizes


def test_r2d2_model_port_cascade():
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update(
        [
            NetworkPolicy(
                name="cascade",
                policy=2,
                ingress_per_port_policies=[
                    PortNetworkPolicy(
                        port=80,
                        rules=[PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[{"cmd": "READ"}])],
                    ),
                    PortNetworkPolicy(
                        port=0,
                        rules=[PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[{"cmd": "HALT"}])],
                    ),
                ],
            )
        ]
    )
    policy = ins.policy_map()["cascade"]
    model = build_r2d2_model(policy, ingress=True, port=80)
    data = np.zeros((3, 16), dtype=np.uint8)
    for i, m in enumerate([b"READ x\r\n", b"HALT\r\n", b"RESET\r\n"]):
        data[i, : len(m)] = np.frombuffer(m, dtype=np.uint8)
    lengths = np.array([8, 6, 7], dtype=np.int32)
    _, _, allow = r2d2_verdicts(model, data, lengths, np.ones((3,), np.int32))
    # READ allowed by port-80 rules; HALT by wildcard; RESET by neither.
    assert np.asarray(allow).tolist() == [True, True, False]


def test_r2d2_model_missing_policy_denies():
    model = build_r2d2_model(None, ingress=True, port=80)
    assert isinstance(model, ConstVerdict) and model.allow is False


def test_r2d2_model_incomplete_frame():
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([_policy("read-only", POLICIES["read-only"])])
    model = build_r2d2_model(ins.policy_map()["read-only"], True, 80)
    data = np.zeros((1, 16), dtype=np.uint8)
    partial = b"READ xss"
    data[0, : len(partial)] = np.frombuffer(partial, dtype=np.uint8)
    complete, _, _ = r2d2_verdicts(model, data, np.array([len(partial)], np.int32), np.ones((1,), np.int32))
    assert not bool(np.asarray(complete)[0])

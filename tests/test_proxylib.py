"""proxylib framework tests — op/byte-exact oracle scenarios.

Each test replicates a reference scenario from proxylib/proxylib_test.go or
proxylib/r2d2/r2d2parser_test.go with identical expected op sequences and
inject-buffer contents.
"""

import pytest

from cilium_tpu.proxylib import (
    DROP,
    ERROR,
    INJECT,
    MORE,
    NOP,
    PASS,
    FilterResult,
    MemoryAccessLogger,
    NetworkPolicy,
    PolicyParseError,
    PortNetworkPolicy,
    PortNetworkPolicyRule,
    find_instance,
    open_module,
    register_parser_factory,
    reset_module_registry,
)
from cilium_tpu.proxylib.types import OpError

from proxylib_harness import check_on_data, new_connection


@pytest.fixture(autouse=True)
def fresh_registry():
    reset_module_registry()
    yield
    reset_module_registry()


def _mod(**kwargs):
    mod = open_module([], True)
    assert mod != 0
    return mod


def _logger(mod) -> MemoryAccessLogger:
    return find_instance(mod).access_logger


# --- module lifecycle (reference: proxylib_test.go TestOpenModule) -------

def test_open_module_dedup():
    mod1 = open_module([], True)
    mod2 = open_module([], True)
    assert mod1 != 0 and mod2 == mod1
    assert open_module([("dummy-key", "v")], True) == 0
    mod4 = open_module([("access-log-path", "/tmp/x.sock")], True)
    assert mod4 != 0 and mod4 != mod1
    mod5 = open_module(
        [("access-log-path", "/tmp/x.sock"), ("node-id", "host~1~libcilium~dom")], True
    )
    assert mod5 not in (0, mod1, mod4)


# --- connection errors (reference: proxylib_test.go TestOnNewConnection) -

def test_on_new_connection_errors():
    mod = _mod()
    res, _ = new_connection(mod, "invalid-parser", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "policy-1")
    assert res == FilterResult.UNKNOWN_PARSER
    res, _ = new_connection(mod, "test.passer", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:XYZ", "policy-1")
    assert res == FilterResult.INVALID_ADDRESS
    res, _ = new_connection(mod, "test.passer", True, 1, 2, "1.1.1.1:34567", "2.2.2.2", "policy-1")
    assert res == FilterResult.INVALID_ADDRESS
    res, _ = new_connection(mod, "test.passer", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:0", "policy-1")
    assert res == FilterResult.INVALID_ADDRESS
    res, _ = new_connection(mod, "test.passer", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "invalid-policy")
    assert res == FilterResult.POLICY_DROP
    res, conn = new_connection(mod, "test.passer", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "policy-1")
    assert res == FilterResult.OK and conn is not None


# --- no policy: headerparser drops (reference: TestOnDataNoPolicy) -------

def test_on_data_no_policy():
    mod = _mod()
    res, conn = new_connection(
        mod, "test.headerparser", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "policy-1", buf_size=30
    )
    assert res == FilterResult.OK
    line1, line2, line3 = b"No policy\n", b"Dropped\n", b"foo"
    check_on_data(
        conn, False, False, [line1, line2 + line3],
        [(DROP, len(line1)), (DROP, len(line2)), (MORE, 1)],
        exp_reply_buf=b"Line dropped: " + line1 + b"Line dropped: " + line2,
    )
    check_on_data(conn, False, False, [line3], [(MORE, 1)])
    check_on_data(conn, False, False, [], [])
    assert _logger(mod).counts() == (0, 2)


# --- parser panic recovery (reference: TestOnDataPanic) ------------------

class _PanicParser:
    def on_data(self, reply, end_stream, data):
        if not reply:
            raise RuntimeError("panicing...")
        return NOP, 0


class _PanicParserFactory:
    def create(self, connection):
        return _PanicParser()


def test_on_data_panic():
    register_parser_factory("test.panicparser", _PanicParserFactory())
    mod = _mod()
    res, conn = new_connection(
        mod, "test.panicparser", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "policy-1", buf_size=30
    )
    assert res == FilterResult.OK
    check_on_data(conn, False, False, [b"foo"], [], exp_result=FilterResult.PARSER_ERROR)
    assert _logger(mod).counts() == (0, 1)


# --- policies ------------------------------------------------------------

def _policy(name, rules, port=80):
    return NetworkPolicy(
        name=name,
        policy=2,
        ingress_per_port_policies=[PortNetworkPolicy(port=port, rules=rules)],
    )


HEADER_LINES = [b"Beginning----\n", b"foo\n", b"----End\n", b"\n"]


def _header_conn(mod, policy_name="FooBar"):
    res, conn = new_connection(
        mod, "test.headerparser", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", policy_name, buf_size=80
    )
    assert res == FilterResult.OK
    return conn


def test_unsupported_l7_drops():
    """Unknown l7 parser => drop all on the port (reference:
    TestUnsupportedL7Drops)."""
    mod = _mod()
    find_instance(mod).policy_update(
        [_policy("FooBar", [PortNetworkPolicyRule(remote_policies=[1, 3], l7_proto="unknown-l7", l7_rules=[])])]
    )
    conn = _header_conn(mod)
    l1, l2, l3, l4 = HEADER_LINES
    check_on_data(
        conn, False, False, [l1 + l2 + l3 + l4],
        [(DROP, len(l1)), (DROP, len(l2)), (DROP, len(l3)), (DROP, len(l4))],
        exp_reply_buf=b"".join(b"Line dropped: " + l for l in HEADER_LINES),
    )
    assert _logger(mod).counts() == (0, 4)


def test_two_rules_same_port_first_no_l7():
    """First rule has no L7 (remote 11 only); second has header rules for
    remotes 1,3,4 (reference: TestTwoRulesOnSamePortFirstNoL7Generic)."""
    mod = _mod()
    find_instance(mod).policy_update(
        [
            _policy(
                "FooBar",
                [
                    PortNetworkPolicyRule(remote_policies=[11]),
                    PortNetworkPolicyRule(
                        remote_policies=[1, 3, 4],
                        l7_proto="test.headerparser",
                        l7_rules=[{"prefix": "Beginning"}, {"suffix": "End"}],
                    ),
                ],
            )
        ]
    )
    conn = _header_conn(mod)
    l1, l2, l3, l4 = HEADER_LINES
    # srcId=1 matches rule 2; prefix/suffix rules pass lines 1 and 3.
    check_on_data(
        conn, False, False, [l1 + l2 + l3 + l4],
        [(PASS, len(l1)), (DROP, len(l2)), (PASS, len(l3)), (DROP, len(l4))],
        exp_reply_buf=b"Line dropped: " + l2 + b"Line dropped: " + l4,
    )
    assert _logger(mod).counts() == (2, 2)


def test_mismatching_l7_types_rejected(monkeypatch):
    """Two L7 types on one port => policy update fails atomically
    (reference: TestTwoRulesOnSamePortMismatchingL7, which likewise
    registers a dummy HTTP rule parser first — restored afterwards, so
    later tests in this process parse HTTP rules for real)."""
    from cilium_tpu.proxylib import parser

    monkeypatch.setitem(
        parser._l7_rule_parsers, "http", lambda rule_config: []
    )
    mod = _mod()
    ins = find_instance(mod)
    with pytest.raises(PolicyParseError):
        ins.policy_update(
            [
                _policy(
                    "FooBar",
                    [
                        PortNetworkPolicyRule(
                            remote_policies=[11],
                            http_rules=[{"headers": [{"name": ":path", "exact_match": "/allowed"}]}],
                        ),
                        PortNetworkPolicyRule(
                            remote_policies=[1],
                            l7_proto="test.headerparser",
                            l7_rules=[{"prefix": "Beginning"}],
                        ),
                    ],
                )
            ]
        )
    assert not ins.has_policy("FooBar")  # old map untouched


def test_simple_policy_pass_drop():
    """(reference: TestSimplePolicy)."""
    mod = _mod()
    find_instance(mod).policy_update(
        [
            _policy(
                "FooBar",
                [
                    PortNetworkPolicyRule(
                        remote_policies=[1, 3, 4],
                        l7_proto="test.headerparser",
                        l7_rules=[{"prefix": "Beginning"}, {"suffix": "End"}],
                    )
                ],
            )
        ]
    )
    conn = _header_conn(mod)
    l1, l2, l3, l4 = HEADER_LINES
    check_on_data(
        conn, False, False, [l1 + l2 + l3 + l4],
        [(PASS, len(l1)), (DROP, len(l2)), (PASS, len(l3)), (DROP, len(l4))],
        exp_reply_buf=b"Line dropped: " + l2 + b"Line dropped: " + l4,
    )
    assert _logger(mod).counts() == (2, 2)


def test_allow_all_policy():
    """Rule with remotes but no L7 rules => allow all payloads
    (reference: TestAllowAllPolicy)."""
    mod = _mod()
    find_instance(mod).policy_update(
        [
            _policy(
                "FooBar",
                [PortNetworkPolicyRule(remote_policies=[1, 3, 4], l7_proto="test.headerparser", l7_rules=[])],
            )
        ]
    )
    conn = _header_conn(mod)
    l1, l2, l3, l4 = HEADER_LINES
    check_on_data(
        conn, False, False, [l1 + l2 + l3 + l4],
        [(PASS, len(l1)), (PASS, len(l2)), (PASS, len(l3)), (PASS, len(l4))],
    )
    assert _logger(mod).counts() == (4, 0)


def test_wrong_remote_id_drops():
    """Remote not in allowed set => deny."""
    mod = _mod()
    find_instance(mod).policy_update(
        [
            _policy(
                "FooBar",
                [PortNetworkPolicyRule(remote_policies=[11], l7_proto="test.headerparser", l7_rules=[{"prefix": "B"}])],
            )
        ]
    )
    conn = _header_conn(mod)  # srcId=1, not 11
    l1 = HEADER_LINES[0]
    check_on_data(
        conn, False, False, [l1], [(DROP, len(l1))],
        exp_reply_buf=b"Line dropped: " + l1,
    )


# --- line/block parsers (reference: lineparser/blockparser scenarios) ----

def test_line_parser_ops():
    mod = _mod()
    res, conn = new_connection(
        mod, "test.lineparser", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "p", buf_size=80
    )
    assert res == FilterResult.OK
    check_on_data(
        conn, False, False, [b"PASS line\n", b"DROP this\n", b"partial"],
        [(PASS, 10), (DROP, 10), (MORE, 1)],
    )
    # INJECT into reverse direction, then INSERT into current
    check_on_data(
        conn, False, False, [b"INJECT me\n"],
        [(DROP, 10)],
        exp_reply_buf=b"INJECT me\n",
    )
    ops = []
    res = conn.on_data(False, False, [b"INSERT x\n"], ops)
    assert res == FilterResult.OK
    assert ops == [(INJECT, 9), (DROP, 9)]
    assert conn.orig_buf.take() == b"INSERT x\n"


def test_block_parser_ops():
    mod = _mod()
    res, conn = new_connection(
        mod, "test.blockparser", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", "p", buf_size=80
    )
    assert res == FilterResult.OK
    # "7:PASS" -> block is '7:PASS' (7 bytes incl. prefix)
    check_on_data(conn, False, False, [b"7:PASS!9:DROP1234"], [(PASS, 7), (DROP, 9), (MORE, 1)])
    check_on_data(conn, False, False, [b"2"], [(MORE, 1)])
    check_on_data(conn, False, False, [], [])
    # Invalid length prefix: the parser yields ERROR; the OnData loop has no
    # ERROR break (reference: connection.go:141-172 breaks only on
    # NOP/MORE/full-inject), so the op repeats to capacity and the datapath
    # closes the connection on the first ERROR it applies.
    ops = []
    res = conn.on_data(False, False, [b"XYZ:foo"], ops)
    assert res == FilterResult.OK
    assert ops == [(ERROR, int(OpError.ERROR_INVALID_FRAME_LENGTH))] * 16


# --- r2d2 (reference: r2d2parser_test.go) --------------------------------

def _r2d2_policy(name, l7_rules):
    return _policy(
        name,
        [PortNetworkPolicyRule(remote_policies=[], l7_proto="r2d2", l7_rules=l7_rules)],
    )


def _r2d2_conn(mod, policy_name):
    res, conn = new_connection(
        mod, "r2d2", True, 1, 2, "1.1.1.1:34567", "2.2.2.2:80", policy_name
    )
    assert res == FilterResult.OK
    return conn


def test_r2d2_incomplete():
    mod = _mod()
    conn = _r2d2_conn(mod, "no-policy")
    check_on_data(conn, False, False, [b"READ xssss"], [(MORE, 1)])


def test_r2d2_basic_pass():
    mod = _mod()
    find_instance(mod).policy_update([_r2d2_policy("cp1", None)])
    conn = _r2d2_conn(mod, "cp1")
    msgs = [b"READ sssss\r\n", b"WRITE sssss\r\n", b"HALT\r\n", b"RESET\r\n"]
    check_on_data(
        conn, False, False, [b"".join(msgs)],
        [(PASS, len(m)) for m in msgs] + [(MORE, 1)],
    )


def test_r2d2_split_message():
    mod = _mod()
    find_instance(mod).policy_update([_r2d2_policy("cp1", None)])
    conn = _r2d2_conn(mod, "cp1")
    check_on_data(
        conn, False, False, [b"RE", b"SET\r\n"],
        [(PASS, 7), (MORE, 1)],
    )


def test_r2d2_allow_deny_cmd():
    mod = _mod()
    find_instance(mod).policy_update([_r2d2_policy("cp2", [{"cmd": "READ"}])])
    conn = _r2d2_conn(mod, "cp2")
    msg1, msg2 = b"READ xssss\r\n", b"WRITE xssss\r\n"
    check_on_data(
        conn, False, False, [msg1 + msg2],
        [(PASS, len(msg1)), (DROP, len(msg2)), (MORE, 1)],
        exp_reply_buf=b"ERROR\r\n",
    )
    assert _logger(mod).counts() == (1, 1)


def test_r2d2_allow_deny_regex():
    mod = _mod()
    find_instance(mod).policy_update([_r2d2_policy("cp3", [{"file": "s.*"}])])
    conn = _r2d2_conn(mod, "cp3")
    msg1, msg2 = b"READ ssss\r\n", b"WRITE yyyyy\r\n"
    check_on_data(
        conn, False, False, [msg1 + msg2],
        [(PASS, len(msg1)), (DROP, len(msg2)), (MORE, 1)],
        exp_reply_buf=b"ERROR\r\n",
    )


def test_r2d2_reply_passes():
    mod = _mod()
    find_instance(mod).policy_update([_r2d2_policy("cp1", [{"cmd": "READ"}])])
    conn = _r2d2_conn(mod, "cp1")
    check_on_data(conn, True, False, [b"OK data\r\n"], [(PASS, 9), (MORE, 1)])


def test_r2d2_rule_validation():
    mod = _mod()
    ins = find_instance(mod)
    with pytest.raises(PolicyParseError):
        ins.policy_update([_r2d2_policy("bad1", [{"cmd": "FLY"}])])
    with pytest.raises(PolicyParseError):
        ins.policy_update([_r2d2_policy("bad2", [{"cmd": "HALT", "file": "x"}])])
    with pytest.raises(PolicyParseError):
        ins.policy_update([_r2d2_policy("bad3", [{"bogus": "x"}])])


# --- wildcard port (reference: policymap.go:216-223) ---------------------

def test_wildcard_port():
    mod = _mod()
    find_instance(mod).policy_update(
        [_policy("wc", [PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[{"cmd": "READ"}])], port=0)]
    )
    conn = _r2d2_conn(mod, "wc")  # port 80, policy only has port 0
    check_on_data(conn, False, False, [b"READ f\r\n"], [(PASS, 8), (MORE, 1)])
    check_on_data(
        conn, False, False, [b"HALT\r\n"], [(DROP, 6), (MORE, 1)],
        exp_reply_buf=b"ERROR\r\n",
    )


def test_no_policy_for_port_drops():
    mod = _mod()
    find_instance(mod).policy_update(
        [_policy("p90", [PortNetworkPolicyRule(l7_proto="r2d2", l7_rules=[{"cmd": "READ"}])], port=90)]
    )
    conn = _r2d2_conn(mod, "p90")  # port 80; policy has only port 90, no wildcard
    check_on_data(
        conn, False, False, [b"READ f\r\n"], [(DROP, 8), (MORE, 1)],
        exp_reply_buf=b"ERROR\r\n",
    )

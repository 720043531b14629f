"""The plain reference and the comparison that decides ``correct``."""

import copy

import pytest

from benchmark import check, deploy
from benchmark.mixgen import Traffic
from benchmark.reference import Reference, apply_ops
from benchmark.tests.conftest import load


def _served_by_reference(tr, policies, conns, pushes=6):
    """Answers as a sound server gives them: the reference's own."""
    ref = Reference(policies, tr.policy)
    out = {}
    for i in conns:
        got = []
        for k in range(pushes):
            reply, data = tr.push(i, k)
            want = ref.feed(i, reply, data)
            if tr.lane[i]:
                want = (want[0], apply_ops(data, want[1]), want[3])
            got.append(want)
        out[i] = got
    return out


@pytest.fixture(scope="module")
def mixed():
    cfg, params = load("mixed-6k-rules", "mixed-closed")
    cfg = dict(cfg, http_policies=4, dns_policies=3)
    tr = Traffic(dict(params, conns=400, on_io_conns=12), cfg, 3, 256)
    return tr, deploy.policies(cfg), cfg


def test_reference_agrees_with_proxylib(mixed):
    """The in-process proxylib parsers are a second witness: the plain
    reference answers every generated push as they do."""
    from cilium_tpu.proxylib import instance as pl

    tr, policies, cfg = mixed
    mod = pl.open_module([], True)
    try:
        pl.find_instance(mod).policy_update(deploy.network_policies(cfg))
        ref = Reference(policies, tr.policy)
        conns = {}
        for i in range(tr.n):
            res, conns[i] = pl.on_new_connection(
                mod, str(tr.proto[i]), 10_000 + i, True, 1, 2, "1.1.1.1:1",
                f"2.2.2.2:{tr.port[i]}", tr.policy[i])
            assert int(res) == 0
        state: dict = {}
        for i in range(tr.n):
            for k in range(6):
                reply, data = tr.push(i, k)
                assert ref.feed(i, reply, data) == _proxylib_feed(
                    conns[i], state, i, reply, data), (i, k)
    finally:
        pl.close_module(mod)


def _proxylib_feed(oc, state, i, reply, data):
    """proxylib's OnData with the datapath's retained bytes."""
    from cilium_tpu.proxylib.types import DROP, PASS

    d = state.setdefault((i, reply), [bytearray(), 0, 0])
    take = min(d[1] or d[2], len(data))
    if d[1]:
        d[1] -= take
    elif d[2]:
        d[2] -= take
    d[0] += data[take:]
    ops: list = []
    res = oc.on_data(reply, False, [bytes(d[0])], ops)
    for op, n in ops:
        if op in (PASS, DROP):
            used = min(n, len(d[0]))
            del d[0][:used]
            d[1 if op == PASS else 2] += n - used
    return (int(res), [(int(o), int(n)) for o, n in ops],
            bytes(oc.orig_buf.take()), bytes(oc.reply_buf.take()))


def test_sound_answers_pass(mixed):
    tr, policies, _ = mixed
    served = _served_by_reference(tr, policies, range(0, tr.n, 7))
    out = check.compare(tr, check.expected(tr, policies, served), served)
    assert out["mismatches"] == out["unanswered"] == 0
    assert out["checked"] == sum(len(v) for v in served.values())
    assert check.is_correct(out)


def test_control_is_not_correct(mixed):
    """The control, each push answered as the conn's first was, goes
    through the same comparison and comes out not correct."""
    tr, policies, _ = mixed
    served = _served_by_reference(tr, policies, range(0, tr.n, 7))
    want = check.expected(tr, policies, served)
    out = check.compare(tr, want, check.stale_verdicts(want))
    assert out["mismatches"] > check.LIMITS["mismatches"]
    assert not check.is_correct(out)


@pytest.mark.parametrize("where", ["batch", "on_io"])
def test_one_flipped_verdict_fails(mixed, where):
    tr, policies, _ = mixed
    conns = [i for i in range(tr.n) if tr.lane[i] == (where == "on_io")]
    served = _served_by_reference(tr, policies, conns[:40])
    want = check.expected(tr, policies, served)
    bad = copy.deepcopy(served)
    i = conns[3]
    ans = bad[i][2]
    if where == "batch":
        ops = [(2 if op == 1 else 1 if op == 2 else op, n)
               for op, n in ans[1]]
        bad[i][2] = (ans[0], ops, ans[2], ans[3])
    else:
        bad[i][2] = (ans[0], ans[1] + b"x", ans[2])
    assert check.compare(tr, want, served)["mismatches"] == 0
    out = check.compare(tr, want, bad)
    assert out["mismatches"] == 1 and not check.is_correct(out)


def test_missing_answer_fails(mixed):
    tr, policies, _ = mixed
    served = _served_by_reference(tr, policies, range(10))
    want = check.expected(tr, policies, served)
    served[4][1] = None
    out = check.compare(tr, want, served)
    assert out["unanswered"] == 1 and out["mismatches"] == 0
    assert not check.is_correct(out)


def test_reference_judges_each_rule_tier():
    pols = deploy.policies(load("mixed-6k-rules", "mixed-closed")[0])
    names = [p["name"] for p in pols]
    ref = Reference(pols, names)
    h = names.index("http-007")
    get = b"GET %s HTTP/1.1\r\nHost: a\r\n\r\n"
    allowed = ["/svc007/r03/x", "/g02/ab9/item/z", "/n01/babbbbbbb/x"]
    denied = ["/svc008/r03/x", "/g02/AB/item/z", "/n01/bbbbbbbbb/x"]
    for path, ok in [(p, True) for p in allowed] + [(p, False)
                                                    for p in denied]:
        res = ref.feed(h, False, get % path.encode())
        assert res[1][0][0] == (1 if ok else 2), path
    d = names.index("dns-003")
    from benchmark.mixgen import encode_dns_query
    for name, ok in [("s05.p003.svc.local", True),
                     ("a.b.w02.svc.local", True),
                     ("s05.p004.svc.local", False), ("w02.svc.local", False)]:
        q = encode_dns_query(name)
        assert ref.feed(d, False, q)[1][0] == ((1 if ok else 2), len(q))
    r = names.index("r2d2")
    assert ref.feed(r, False, b"READ /public/a\r\n")[1][0] == (1, 16)
    assert ref.feed(r, False, b"WRITE /public/a\r\n")[3] == b"ERROR\r\n"
    assert ref.feed(r, True, b"OK\r\n")[1][0] == (1, 4)

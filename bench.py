#!/usr/bin/env python
"""Headline benchmarks: L7 verdicts/sec/chip + sidecar added latency.

Reproduces BASELINE.md's benchmark configs on the real chip:

  1. r2d2 line protocol (the flagship slice)      — headline metric
  2. HTTP  `GET /public/.*`                       — config 2
  3. Kafka produce/consume topic ACL              — config 3
  4. Cassandra CQL (action, table) ACL            — config 4
  plus the sidecar seam's added p50/p99 latency under Poisson load.

For each config the CPU oracle baseline is self-measured (the ported
in-process proxylib/policy matchers — BASELINE.md's requirement; the
reference publishes no absolute numbers) and device verdicts are
cross-checked bit-identical against the oracle before any number is
reported.

Output: one JSON line per metric on stdout; the HEADLINE r2d2 line is
printed LAST.  Detail goes to stderr.
"""

import json
import os
import random
import sys
import time

import numpy as np


def _fence(out):
    """Execution fence: a 1-element device→host readback of the last
    output forces the whole queued dependency chain to execute."""
    last = out[-1] if isinstance(out, tuple) else out
    np.asarray(last[:1])


def _timed_calls(fn, args, n: int) -> float:
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn(*args)
    _fence(out)
    return time.perf_counter() - t0


def _pipelined_rate(fn, args, batch_size):
    """Back-to-back batched calls; returns MARGINAL verdicts/sec.

    Dispatch style (eager per-op async vs one jit executable per call)
    is a transport property, not a code property — both are probed and
    the faster kept.  (r3 hard-coded eager from a measurement that
    predated the current stack; jit now wins by >3× on every config.)

    The rate is the marginal (t_high − t_low between two call counts),
    which cancels the constant per-measurement terms — the final fence
    readback RTT and any first-call sync — the way the serving path's
    overlapped completion drain does."""
    import jax

    # Stage host arrays on-device once (the serving path uploads a
    # batch exactly once): a numpy arg would re-cross the host->device
    # link on EVERY jit call and time the link, not the chip.
    args = jax.tree_util.tree_map(
        lambda a: jax.device_put(a) if isinstance(a, np.ndarray) else a,
        args,
    )
    candidates = [("jit", jax.jit(fn)), ("eager", fn)]
    probed = []
    for name, f in candidates:
        _fence(f(*args))  # warm/compile
        _fence(f(*args))
        probed.append((_timed_calls(f, args, 4), name, f))
    probed.sort(key=lambda t: t[0])
    _t0, _name, f = probed[0]

    def marginal() -> float:
        # Grow the call count until the timed window dominates the
        # constant fence/RTT terms, then report the marginal between
        # the last two sizes (constant terms cancel).
        t = _timed_calls(f, args, 4)
        n = 4
        while t < 1.0 and n < 4096:
            n2 = n * 4
            t2 = _timed_calls(f, args, n2)
            if t2 > max(1.0, 3 * t) or n2 >= 4096:
                if t2 > t:
                    return batch_size * (n2 - n) / (t2 - t)
                return batch_size * n2 / t2
            n, t = n2, t2
        return batch_size * n / t

    # Best of 2: a host stall landing inside one marginal window
    # only DEFLATES the rate (a 40x dip was observed once on the http
    # config), so the larger of two independent windows is the honest
    # de-noised reading — inflation artifacts are prevented separately
    # (device-bound calls; see the kafka K-loop).
    return max(marginal(), marginal())


def _emit(metric, value, unit, vs_baseline, **extra):
    line = {"metric": metric, "value": round(value, 3) if value < 100 else round(value), "unit": unit, "vs_baseline": round(vs_baseline, 3)}
    line.update(extra)
    print(json.dumps(line), flush=True)


# --- config 1: r2d2 ------------------------------------------------------

def bench_r2d2():
    import jax

    from cilium_tpu.models.r2d2 import build_r2d2_model
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        open_module,
        find_instance,
        reset_module_registry,
        FilterResult,
        PASS,
    )
    from cilium_tpu.proxylib.instance import on_new_connection

    policy_cfg = NetworkPolicy(
        name="bench",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
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
    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([policy_cfg])
    model = build_r2d2_model(ins.policy_map()["bench"], ingress=True, port=80)

    rng = random.Random(7)
    msgs = []
    for _ in range(1024):
        roll = rng.random()
        if roll < 0.35:
            msgs.append(f"READ /public/file{rng.randrange(1000)}.txt\r\n".encode())
        elif roll < 0.5:
            msgs.append(b"HALT\r\n")
        elif roll < 0.75:
            msgs.append(f"READ /private/file{rng.randrange(1000)}\r\n".encode())
        else:
            msgs.append(f"WRITE /public/f{rng.randrange(1000)}\r\n".encode())

    F, L = 65536, 64  # 64k: amortizes the ~2.5ms per-call launch
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        m = msgs[i % len(msgs)]
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    remotes = np.ones((F,), np.int32)

    fn = type(model).__call__  # dispatch style probed by _pipelined_rate
    rate = _pipelined_rate(fn, (model, data, lengths, remotes), F)

    # CPU oracle (full in-process proxylib parse+match) + cross-check.
    n_cpu = 2000
    res, conn = on_new_connection(
        mod, "r2d2", 1, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80", "bench"
    )
    assert res == FilterResult.OK
    t0 = time.perf_counter()
    oracle_allows = []
    for i in range(n_cpu):
        ops = []
        conn.on_data(False, False, [msgs[i % len(msgs)]], ops)
        oracle_allows.append(ops[0][0] == PASS)
        conn.reply_buf.take()
    cpu_rate = n_cpu / (time.perf_counter() - t0)

    dev_allow = np.asarray(fn(model, data, lengths, remotes)[2])
    mism = sum(
        1 for i in range(min(n_cpu, F))
        if bool(dev_allow[i]) != oracle_allows[i % len(oracle_allows)]
    )
    assert mism == 0, f"r2d2 device verdicts diverge from oracle ({mism})"
    print(f"bench r2d2: tpu={rate:,.0f}/s cpu={cpu_rate:,.0f}/s "
          f"mismatches=0/{n_cpu}", file=sys.stderr)
    return rate, cpu_rate


# --- config 2: HTTP ------------------------------------------------------

def bench_http():
    import jax
    import re

    from cilium_tpu.models.http import build_http_model
    from cilium_tpu.policy.api import PortRuleHTTP

    rule = PortRuleHTTP(method="GET", path="/public/.*")
    rule.sanitize()
    model = build_http_model([(frozenset(), rule)])

    rng = random.Random(11)
    reqs = []
    for _ in range(1024):
        roll = rng.random()
        path = (
            f"/public/a{rng.randrange(1000)}" if roll < 0.5
            else f"/private/b{rng.randrange(1000)}"
        )
        method = "GET" if rng.random() < 0.8 else "POST"
        reqs.append(
            f"{method} {path} HTTP/1.1\r\nHost: svc.local\r\n"
            f"User-Agent: bench\r\n\r\n".encode()
        )

    # 64k-flow batches amortize the per-call launch overhead over many
    # flows, so the figure reads the model, not the launch.
    F, L = 65536, 512
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        r = reqs[i % len(reqs)]
        data[i, : len(r)] = np.frombuffer(r, np.uint8)
        lengths[i] = len(r)
    remotes = np.ones((F,), np.int32)

    fn = type(model).__call__  # dispatch style probed by _pipelined_rate
    rate = _pipelined_rate(fn, (model, data, lengths, remotes), F)

    # CPU oracle: Envoy-side per-request regex walk (re over head).
    method_re = re.compile("GET")
    path_re = re.compile("/public/.*")
    n_cpu = 2000
    t0 = time.perf_counter()
    oracle_allows = []
    for i in range(n_cpu):
        head = reqs[i % len(reqs)].split(b"\r\n\r\n")[0].decode()
        m, p, _ = head.split("\r\n")[0].split(" ", 2)
        oracle_allows.append(
            bool(method_re.fullmatch(m)) and bool(path_re.fullmatch(p))
        )
    cpu_rate = n_cpu / (time.perf_counter() - t0)

    dev = np.asarray(fn(model, data, lengths, remotes)[2])
    mism = sum(
        1 for i in range(n_cpu)
        if bool(dev[i % F]) != oracle_allows[i]
    )
    assert mism == 0, f"http device verdicts diverge ({mism})"
    print(f"bench http: tpu={rate:,.0f}/s cpu={cpu_rate:,.0f}/s "
          f"mismatches=0/{n_cpu}", file=sys.stderr)
    return rate, cpu_rate


# --- config 3: Kafka -----------------------------------------------------

def bench_kafka():
    import jax

    from cilium_tpu.kafka.policy import matches_rule
    from cilium_tpu.kafka.request import RequestMessage
    from cilium_tpu.models.kafka import build_kafka_model, encode_requests
    from cilium_tpu.policy.api import PortRuleKafka

    rules = []
    for role in ("produce", "consume"):
        r = PortRuleKafka(role=role, topic="allowed-topic")
        r.sanitize()
        rules.append(r)
    model = build_kafka_model([(frozenset(), r) for r in rules])

    rng = random.Random(13)
    reqs = []
    for _ in range(1024):
        topic = "allowed-topic" if rng.random() < 0.5 else f"t{rng.randrange(50)}"
        api_key = rng.choice([0, 1, 2, 3])  # produce/fetch/offsets/metadata
        reqs.append(
            RequestMessage(
                api_key=api_key, api_version=1,
                correlation_id=rng.randrange(1 << 16),
                client_id="bench", topics=[topic], parsed=True,
            )
        )

    F = 65536  # 64k: amortizes the ~2.5ms per-call launch
    batch = encode_requests([reqs[i % len(reqs)] for i in range(F)])
    remotes = np.ones((F,), np.int32)
    assert not batch.overflow.any()

    fn = type(model).__call__

    # The kafka model is a tiny ACL-mask lookup — per-batch device time
    # is far below both the per-call dispatch cost AND the fence
    # readback RTT, so plain call-marginal timing
    # measures the HOST (r4's 36M-vs-144M mystery: 30-90% run-to-run
    # swings; scaling data in BENCH_NOTES.md).  Fix both constants at
    # once: K serially dependent model applications inside ONE jit call
    # (each iteration's remotes depend on the previous verdicts, so
    # XLA can neither hoist nor parallelize) make every call
    # device-bound, and the adaptive marginal harness then cancels the
    # fence RTT.  Cross-invocation variance <10% (BENCH_NOTES.md r5).
    import jax.numpy as jnp

    K = 256

    def k_loop(model_, batch_, remotes_):
        def body(_, carry):
            acc, rem = carry
            out = model_(batch_, rem)
            return acc + out.astype(jnp.int32), jnp.where(out, rem, rem + 1)

        return jax.lax.fori_loop(
            0, K, body, (jnp.zeros(F, jnp.int32), remotes_)
        )[0]

    rate = _pipelined_rate(k_loop, (model, batch, remotes), F * K)

    n_cpu = 2000
    t0 = time.perf_counter()
    oracle_allows = [
        matches_rule(reqs[i % len(reqs)], rules) for i in range(n_cpu)
    ]
    cpu_rate = n_cpu / (time.perf_counter() - t0)

    dev = np.asarray(fn(model, batch, remotes))
    mism = sum(
        1 for i in range(n_cpu) if bool(dev[i % F]) != oracle_allows[i]
    )
    assert mism == 0, f"kafka device verdicts diverge ({mism})"
    print(f"bench kafka: tpu={rate:,.0f}/s cpu={cpu_rate:,.0f}/s "
          f"mismatches=0/{n_cpu}", file=sys.stderr)
    return rate, cpu_rate


# --- config 4: Cassandra -------------------------------------------------

def bench_cassandra():
    import jax

    from cilium_tpu.models.cassandra import (
        build_cassandra_model,
        encode_cassandra_batch,
    )
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )
    from cilium_tpu.proxylib.policy import compile_policy

    policy = compile_policy(
        NetworkPolicy(
            name="bench",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=9042,
                    rules=[
                        PortNetworkPolicyRule(
                            l7_proto="cassandra",
                            l7_rules=[
                                {"query_action": "select",
                                 "query_table": "^public\\."},
                                {"query_action": "insert",
                                 "query_table": "^public\\."},
                            ],
                        )
                    ],
                )
            ],
        )
    )
    model = build_cassandra_model(policy, ingress=True, port=9042)

    rng = random.Random(17)
    tuples = []
    for _ in range(1024):
        action = rng.choice(["select", "insert", "update", "delete"])
        ks = "public" if rng.random() < 0.5 else "secret"
        tuples.append((action, f"{ks}.t{rng.randrange(40)}", False))

    F = 65536  # 64k: amortizes the ~2.5ms per-call launch
    data, alen, tlen, nq, overflow = encode_cassandra_batch(
        [tuples[i % len(tuples)] for i in range(F)]
    )
    assert not overflow.any()
    remotes = np.ones((F,), np.int32)

    fn = type(model).__call__  # dispatch style probed by _pipelined_rate
    rate = _pipelined_rate(fn, (model, data, alen, tlen, nq, remotes), F)

    # CPU oracle: the rule-walk the device replaces (match step on the
    # same pre-tokenized paths; CQL tokenization stays host-side in
    # both paths).
    n_cpu = 2000
    paths = [f"/query/{a}/{t}" for a, t, _ in tuples]
    t0 = time.perf_counter()
    oracle_allows = [
        policy.matches(True, 9042, 1, paths[i % len(paths)])
        for i in range(n_cpu)
    ]
    cpu_rate = n_cpu / (time.perf_counter() - t0)

    dev = np.asarray(fn(model, data, alen, tlen, nq, remotes))
    mism = sum(
        1 for i in range(n_cpu) if bool(dev[i % F]) != oracle_allows[i]
    )
    assert mism == 0, f"cassandra device verdicts diverge ({mism})"
    print(f"bench cassandra: tpu={rate:,.0f}/s cpu={cpu_rate:,.0f}/s "
          f"mismatches=0/{n_cpu}", file=sys.stderr)
    return rate, cpu_rate


def bench_memcached():
    """Memcached (command/opcode, key) ACL on-chip — the only protocol
    whose device rate had never been recorded (VERDICT r5 ask #5a).
    Text+binary mix over key-prefix, key-exact and key-regex rules;
    device verdicts cross-checked bit-identical against the in-process
    MemcacheRule walk (reference: proxylib/memcached/parser.go:186)."""
    from cilium_tpu.models.memcached import (
        build_memcache_model,
        encode_memcache_batch,
        memcache_verdicts,
    )
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )
    from cilium_tpu.proxylib.parsers.memcached import MemcacheMeta
    from cilium_tpu.proxylib.policy import compile_policy

    policy = compile_policy(
        NetworkPolicy(
            name="bench",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=11211,
                    rules=[
                        PortNetworkPolicyRule(
                            l7_proto="memcache",
                            l7_rules=[
                                {"command": "get", "keyPrefix": "user:"},
                                {"command": "set",
                                 "keyRegex": "^sess:[0-9]+$"},
                                {"command": "delete",
                                 "keyExact": "the-key"},
                            ],
                        )
                    ],
                )
            ],
        )
    )
    model = build_memcache_model(policy, ingress=True, port=11211)

    # (is_binary, opcode, command, keys): the steady-state single-key
    # shapes, half allowed / half denied, text and binary both.
    rng = random.Random(23)
    tuples = []
    for _ in range(1024):
        kind = rng.randrange(6)
        if kind == 0:
            tuples.append((False, 0, "get", [b"user:%d" % rng.randrange(99)]))
        elif kind == 1:
            tuples.append((False, 0, "get", [b"admin:%d" % rng.randrange(99)]))
        elif kind == 2:
            tuples.append((False, 0, "set", [b"sess:%d" % rng.randrange(99)]))
        elif kind == 3:
            tuples.append((False, 0, "set", [b"sess:x%d" % rng.randrange(99)]))
        elif kind == 4:
            # binary get (opcode 0) / getq (9)
            tuples.append((True, rng.choice([0, 9]),
                           "", [b"user:%d" % rng.randrange(99)]))
        else:
            # binary set (opcode 1) — denied (rule is text+bin 'set'
            # but key must match the sess regex)
            tuples.append((True, 1, "", [b"sess:%d" % rng.randrange(99)]))

    F = 65536
    frames = [tuples[i % len(tuples)] for i in range(F)]
    (key_data, key_len, has_key, is_binary, opcode, cmd_id,
     overflow) = encode_memcache_batch(frames)
    assert not overflow.any()
    remotes = np.ones((F,), np.int32)

    rate = _pipelined_rate(
        memcache_verdicts,
        (model, key_data, key_len, has_key, is_binary, opcode, cmd_id,
         remotes),
        F,
    )

    # CPU oracle: the per-request rule walk the device replaces.
    n_cpu = 2000
    metas = [
        MemcacheMeta(command=("" if b else cmd), opcode=(op if b else -1),
                     keys=list(keys))
        for b, op, cmd, keys in tuples
    ]
    t0 = time.perf_counter()
    oracle_allows = [
        policy.matches(True, 11211, 1, metas[i % len(metas)])
        for i in range(n_cpu)
    ]
    cpu_rate = n_cpu / (time.perf_counter() - t0)

    dev = np.asarray(memcache_verdicts(
        model, key_data, key_len, has_key, is_binary, opcode, cmd_id,
        remotes,
    ))
    mism = sum(
        1 for i in range(n_cpu) if bool(dev[i % F]) != oracle_allows[i]
    )
    assert mism == 0, f"memcached device verdicts diverge ({mism})"
    print(f"bench memcached: tpu={rate:,.0f}/s cpu={cpu_rate:,.0f}/s "
          f"mismatches=0/{n_cpu}", file=sys.stderr)
    return rate, cpu_rate


# --- config: DNS name-policy engine ---------------------------------------

def bench_dns():
    """DNS name-policy engine (ISSUE 13): model-level verdicts/s with a
    fenced per-call p99, an in-process CPU-oracle cross-check, and a
    service-level segment of split/pipelined DNS-over-TCP frames that
    must ENGAGE the columnar length-prefixed lane —
    ``status()["reasm"]["rounds_by_framing"]["dns"] > 0`` is asserted,
    so a silent fallback to the scalar rung cannot pass."""
    import threading

    import jax

    from cilium_tpu.models.dns import build_dns_model
    from cilium_tpu.proxylib import (
        FilterResult,
        NetworkPolicy,
        PASS,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        find_instance,
        open_module,
        reset_module_registry,
    )
    from cilium_tpu.proxylib.instance import on_new_connection
    from cilium_tpu.proxylib.parsers.dns import encode_dns_query

    policy_cfg = NetworkPolicy(
        name="bench",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=53,
                rules=[
                    PortNetworkPolicyRule(
                        l7_proto="dns",
                        l7_rules=[
                            {"matchName": "api.example.com"},
                            {"matchPattern": "*.svc.cluster.local"},
                            {"matchRegex": "^cdn[0-9]+[.]edge[.]net$"},
                        ],
                    )
                ],
            )
        ],
    )
    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([policy_cfg])
    model = build_dns_model(ins.policy_map()["bench"], ingress=True, port=53)

    rng = random.Random(13)
    msgs = []
    for _ in range(1024):
        roll = rng.random()
        if roll < 0.3:
            msgs.append(encode_dns_query("api.example.com"))
        elif roll < 0.55:
            msgs.append(encode_dns_query(
                f"pod{rng.randrange(1000)}.svc.cluster.local"
            ))
        elif roll < 0.7:
            msgs.append(encode_dns_query(
                f"cdn{rng.randrange(100)}.edge.net"
            ))
        else:
            msgs.append(encode_dns_query(
                f"evil{rng.randrange(1000)}.test"
            ))

    F, L = 65536, 64
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        m = msgs[i % len(msgs)]
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    remotes = np.ones((F,), np.int32)

    fn = type(model).__call__
    rate = _pipelined_rate(fn, (model, data, lengths, remotes), F)

    # Fenced per-call p99: each call's np.asarray readback IS the
    # fence, so the distribution is whole-batch wall time, not launch
    # time.
    d_dev = jax.device_put(data)
    l_dev = jax.device_put(lengths)
    r_dev = jax.device_put(remotes)
    jfn = jax.jit(fn)
    _fence(jfn(model, d_dev, l_dev, r_dev))
    lats = []
    for _ in range(12):
        t0 = time.perf_counter()
        _fence(jfn(model, d_dev, l_dev, r_dev))
        lats.append(time.perf_counter() - t0)
    lats.sort()
    p99_ms = lats[min(int(len(lats) * 0.99), len(lats) - 1)] * 1e3

    # CPU oracle (full in-process proxylib parse+match) + cross-check.
    n_cpu = 2000
    res, conn = on_new_connection(
        mod, "dns", 1, True, 1, 2, "1.1.1.1:1", "2.2.2.2:53", "bench"
    )
    assert res == FilterResult.OK
    t0 = time.perf_counter()
    oracle_allows = []
    for i in range(n_cpu):
        ops = []
        conn.on_data(False, False, [msgs[i % len(msgs)]], ops)
        oracle_allows.append(ops[0][0] == PASS)
        conn.reply_buf.take()
    cpu_rate = n_cpu / (time.perf_counter() - t0)
    dev_allow = np.asarray(fn(model, data, lengths, remotes)[2])
    mism = sum(
        1 for i in range(min(n_cpu, F))
        if bool(dev_allow[i]) != oracle_allows[i % len(oracle_allows)]
    )
    assert mism == 0, f"dns device verdicts diverge from oracle ({mism})"

    # --- service-level segment: the columnar length-prefixed lane ----
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.sidecar.client import SidecarClient
    from cilium_tpu.sidecar.service import VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    inst.reset_module_registry()
    path = "/tmp/cilium_tpu_bench_dns.sock"
    svc = VerdictService(path, DaemonConfig(
        batch_flows=256, batch_timeout_ms=0.25, batch_width=64,
        reasm=True, reasm_min_entries=1,
    )).start()
    try:
        cl = SidecarClient(path, timeout=120.0)
        smod = cl.open_module([])
        assert cl.policy_update(smod, [policy_cfg]) == int(FilterResult.OK)
        got, evt = {}, threading.Event()

        def cb(vb):
            got[vb.seq] = vb.count
            evt.set()

        cl.verdict_callback = cb
        n_conns = 32
        for cid in range(1, n_conns + 1):
            r, _ = cl.new_connection(
                smod, "dns", cid, True, 1, 2, "1.1.1.1:1",
                "2.2.2.2:53", "bench",
            )
            assert r == int(FilterResult.OK)
        seq = 0
        n_rounds = 24
        for rnd in range(n_rounds):
            entries = []
            for cid in range(1, n_conns + 1):
                f = msgs[(cid + rnd) % len(msgs)]
                if cid % 3 == 0:  # split mid-QNAME across round pairs
                    # Same frame on both halves (rnd//2 anchors the
                    # pick), so the carry really reassembles.
                    fs = msgs[(cid + rnd // 2) % len(msgs)]
                    half = len(fs) // 2
                    entries.append(
                        (cid, fs[:half] if rnd % 2 == 0 else fs[half:])
                    )
                elif cid % 3 == 1:  # pipelined pair
                    entries.append((cid, f + msgs[(cid + rnd + 1) % len(msgs)]))
                else:  # whole frame
                    entries.append((cid, f))
            seq += 1
            cids = np.array([e[0] for e in entries], np.uint64)
            fl = np.zeros(len(entries), np.uint8)
            lens = np.array([len(e[1]) for e in entries], np.uint32)
            cl.send_batch(seq, cids, fl, lens, b"".join(e[1] for e in entries))
            deadline = time.monotonic() + 60
            while seq not in got and time.monotonic() < deadline:
                evt.wait(0.2)
                evt.clear()
            assert seq in got, f"dns bench round {seq} unanswered"
        st = svc.status()["reasm"]
        dns_rounds = (st or {}).get("rounds_by_framing", {}).get("dns", 0)
        assert dns_rounds > 0, (
            "dns columnar lane never engaged (silent scalar fallback): "
            f"{st}"
        )
        cl.close()
    finally:
        svc.stop()
        inst.reset_module_registry()

    print(
        f"bench dns: tpu={rate:,.0f}/s fenced_p99={p99_ms:.2f}ms "
        f"cpu={cpu_rate:,.0f}/s reasm_dns_rounds={dns_rounds} "
        f"mismatches=0/{n_cpu}",
        file=sys.stderr,
    )
    return rate, p99_ms, cpu_rate, dns_rounds


def bench_kvstore_failover(cycles: int = 5):
    """Failover cost of the fenced cluster-state plane, measured
    through the chaos proxy: steady client write rate, then a full
    partition with the primary left alive; the outage is the wall time
    from partition to the first write acknowledged by the promoted
    follower.  Zero acknowledged writes may be lost each cycle (the
    fencing contract, tests/test_kvstore_partition.py).

    The outage sums heartbeat detection, reconnect budget, grace, and
    JITTERED retry backoff — single runs swing well past the --check
    guard's 10%; the reported figure is the MEDIAN of ``cycles``
    independent failovers (spread recorded alongside)."""
    from cilium_tpu.kvstore import (
        ChaosProxy,
        KvstoreFollower,
        KvstoreServer,
        NetBackend,
    )

    outages, steadies, total_acked = [], [], 0
    for cycle in range(cycles):
        primary = KvstoreServer()
        chaos = ChaosProxy(primary.address)
        follower = KvstoreFollower(
            chaos.address, repl_timeout=1.0, failover_grace=0.1
        )
        assert follower.synced.wait(5.0)
        client = NetBackend(
            f"{chaos.address},{follower.address}", timeout=30.0
        )
        acked = {}
        try:
            n0 = 200
            t0 = time.perf_counter()
            for i in range(n0):
                k, v = f"bench/pre/{i}", b"%d" % i
                client.set(k, v)
                acked[k] = v
            steadies.append(n0 / (time.perf_counter() - t0))

            # Quiesce: replication is ASYNC — a write acked by the
            # primary in the instant before the cut lives only on the
            # (fenced) old primary.  That lag window is the documented
            # cost of quorum-free snapshot shipping (net.py
            # docstring); the outage measurement cuts on a converged
            # pair so the loss check below exercises the fencing
            # contract, not the lag.
            last = f"bench/pre/{n0 - 1}"
            deadline = time.monotonic() + 10.0
            while (follower.backend.get(last) != acked[last]
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert follower.backend.get(last) == acked[last], "repl stalled"

            chaos.partition(reset_existing=True)
            t_part = time.perf_counter()
            # Blocks through redial + not_primary backoff + promotion.
            client.set("bench/first-after", b"x")
            outages.append(time.perf_counter() - t_part)
            acked["bench/first-after"] = b"x"
            assert follower.promoted.is_set()

            for i in range(n0):
                k, v = f"bench/post/{i}", b"%d" % i
                client.set(k, v)
                acked[k] = v

            lost = [
                k for k, v in acked.items()
                if follower.backend.get(k) != v
            ]
            assert not lost, (
                f"cycle {cycle}: acked writes lost: {lost[:5]}"
            )
            total_acked += len(acked)
        finally:
            client.close()
            follower.close()
            chaos.close()
            primary.close()

    outages.sort()
    median = outages[len(outages) // 2]
    steady = sorted(steadies)[len(steadies) // 2]
    print(
        f"bench kvstore failover: outage median={median:.3f}s "
        f"(min={outages[0]:.3f} max={outages[-1]:.3f}, n={cycles}) "
        f"steady={steady:,.0f} writes/s acked={total_acked} lost=0",
        file=sys.stderr,
    )
    return median, outages, steady, total_acked


# --- config 5: 10k-rule / 1M-flow stress ---------------------------------

# 250 HTTP policies x 20 rules + 50 Kafka policies x 100 rules = 10,000
# rules; 1M flows replayed (500k HTTP + 500k Kafka), spread evenly.
# Per-policy models are padded to ONE shared shape set so XLA compiles
# exactly one executable per protocol (reference scale analog:
# envoy/cilium_network_policy.h:50-76 per-identity compiled rule tables).
STRESS_HTTP_POLICIES = 250
STRESS_HTTP_RULES = 20
STRESS_KAFKA_POLICIES = 50
STRESS_KAFKA_RULES = 100
STRESS_CASS_POLICIES = 50
STRESS_CASS_RULES = 40
# DNS slice (ISSUE 13): 16 exact-name rules per policy (needle tier) +
# 4 wildcard patterns with policy-independent TEXT (shared automaton
# shape, same stacking constraint as the http regex tier).
STRESS_DNS_POLICIES = 50
STRESS_DNS_EXACT_RULES = 16
STRESS_DNS_PATTERN_RULES = 4
STRESS_DNS_FLOWS = 100_000
STRESS_FLOWS = 1_000_000


# Of the 20 rules per policy: this many are genuine regexes (character
# classes mid-pattern) that the tiered compiler MUST route through the
# automaton — the reference's normal case is a compiled regex per rule
# (reference: envoy/cilium_network_policy.h:50-76 std::regex) — and
# STRESS_HTTP_NFA_RULES of them are patterns whose DFA exceeds the
# 128-state int8 budget, forcing the dense-NFA tier to carry real load.
STRESS_HTTP_REGEX_RULES = 6
STRESS_HTTP_NFA_RULES = 2


def _stress_regex_path(j: int) -> str:
    # Policy-independent pattern TEXT (no per-policy digits): the NFA's
    # byte-class partition depends on the distinct literal bytes, so
    # per-policy digits would compile automata of different shapes and
    # the models could not stack into one [P, ...] pytree.  Sharing the
    # pattern text across policies (a common production shape: many
    # services, one API path convention) keeps all 250 automata
    # bit-identical in structure.
    return f"/g{j:02d}/[a-z0-9]+/item/.*"


def _stress_nfa_path(j: int) -> str:
    # The classic exponential-determinization shape (a|b)*a(a|b){7}:
    # its minimal DFA must remember the last 8 symbols (2^8 = 256
    # states > the 128-state int8 budget), so compile_automaton's
    # 'auto' path MUST fall back to the dense NFA — these rules carry
    # genuine NFA-tier load, not DFA load under another name.
    tail = "(a|b)" * 7
    return f"/n{j:02d}/(a|b)*a{tail}/x"


def _stress_dns_pattern(j: int) -> str:
    # Policy-independent pattern text (same reason as
    # _stress_regex_path: identical automaton shapes stack into one
    # [P, ...] pytree).
    return f"*.w{j:02d}.svc.local"


def _stress_dns_name(p: int, j: int) -> str:
    return f"s{j:02d}.p{p:03d}.svc.local"


def _stress_http_models():
    """Per policy: 12 literal-prefix rules (tier 1) + 6 DFA-tier regex
    rules + 2 NFA-tier regex rules (DFA state blowup).  The regex rules
    share one path convention across policies (a common production
    shape: many services, one API path scheme), so the compiler
    deduplicates them into ONE shared automaton per tier evaluated over
    the flattened flow batch — per-policy evaluation of an identical
    automaton would re-pay its cost 250× in tiny kernels (measured
    350k/s vs >1M/s deduplicated).  Verdict semantics are exact
    rule-set union: any-literal OR any-DFA-regex OR any-NFA-regex."""
    from cilium_tpu.models.http import build_http_model
    from cilium_tpu.ops.nfa import DeviceNfa
    from cilium_tpu.policy.api import PortRuleHTTP

    n_lit = (
        STRESS_HTTP_RULES - STRESS_HTTP_REGEX_RULES - STRESS_HTTP_NFA_RULES
    )
    models = []
    for p in range(STRESS_HTTP_POLICIES):
        rules = [
            (frozenset(),
             PortRuleHTTP(method="GET", path=f"/svc{p:03d}/r{j:02d}/.*"))
            for j in range(n_lit)
        ]
        m = build_http_model(rules)
        assert m.line_nfa is None, "literal split must stay tier-1"
        models.append(m)
    rx_rules = [
        (frozenset(), PortRuleHTTP(method="GET", path=_stress_regex_path(j)))
        for j in range(STRESS_HTTP_REGEX_RULES)
    ]
    # backend="dfa": per-pattern DFA blocks beat the dense NFA matmul
    # at this batch scale (the "auto" threshold tunes for small sets).
    rx_model = build_http_model(rx_rules, backend="dfa")
    assert rx_model.line_nfa is not None, (
        "stress mix must exercise the automaton tier"
    )
    nfa_rules = [
        (frozenset(), PortRuleHTTP(method="GET", path=_stress_nfa_path(j)))
        for j in range(STRESS_HTTP_NFA_RULES)
    ]
    nfa_model = build_http_model(nfa_rules, backend="auto")
    assert isinstance(nfa_model.line_nfa, DeviceNfa), (
        "DFA-blowup patterns must land on the dense NFA tier"
    )
    tier = type(rx_model.line_nfa).__name__
    return models, rx_model, nfa_model, (tier, STRESS_HTTP_REGEX_RULES)


def bench_stress():
    import jax

    from cilium_tpu.kafka.policy import matches_rule
    from cilium_tpu.kafka.request import RequestMessage
    from cilium_tpu.models.http import http_verdicts
    from cilium_tpu.models.kafka import (
        build_kafka_model,
        encode_requests,
        kafka_verdicts,
    )
    from cilium_tpu.policy.api import PortRuleKafka

    from cilium_tpu.models.dns import (
        build_dns_model_from_rows,
        dns_verdicts,
    )
    from cilium_tpu.proxylib.parsers.dns import (
        DNS_QNAME_OFF,
        DnsRequestData,
        DnsRule,
        encode_dns_query,
        parse_dns_query,
    )
    from cilium_tpu.models.cassandra import (
        build_cassandra_model,
        cassandra_verdicts,
        encode_cassandra_batch,
    )
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )
    from cilium_tpu.proxylib.policy import compile_policy

    rng = random.Random(23)
    n_http_flows = STRESS_FLOWS // 2
    n_cass_flows = STRESS_FLOWS // 5
    n_kafka_flows = STRESS_FLOWS - n_http_flows - n_cass_flows
    n_dns_flows = STRESS_DNS_FLOWS
    per_http = n_http_flows // STRESS_HTTP_POLICIES
    per_kafka = n_kafka_flows // STRESS_KAFKA_POLICIES
    per_cass = n_cass_flows // STRESS_CASS_POLICIES
    per_dns = n_dns_flows // STRESS_DNS_POLICIES

    t_build0 = time.perf_counter()
    http_models, http_rx_model, http_nfa_model, (http_tier, _) = (
        _stress_http_models()
    )
    kafka_rule_objs = []
    kafka_models = []
    for p in range(STRESS_KAFKA_POLICIES):
        rules = []
        for j in range(STRESS_KAFKA_RULES):
            kr = PortRuleKafka(
                role="produce" if j % 2 == 0 else "consume",
                topic=f"p{p}t{j}",
            )
            kr.sanitize()
            rules.append(kr)
        kafka_rule_objs.append(rules)
        kafka_models.append(build_kafka_model([(frozenset(), r) for r in rules]))

    # Cassandra policies: regex table rules (the reference's cassandra
    # parser matches query_table with a compiled regex per rule,
    # proxylib/cassandra/cassandraparser.go:605).  Rule TEXT is shared
    # across all 50 policies (one schema convention), so ONE model
    # serves the whole flattened flow batch — the same dedup the http
    # regex tier uses (per-policy evaluation of an identical automaton
    # would re-pay its cost 50× in small kernels).
    def _cass_rule(j: int) -> dict:
        return {
            "query_action": "select" if j % 2 == 0 else "insert",
            "query_table": f"^ks\\.(t{j:02d}|tmp{j:02d})[0-9]*$",
        }

    cass_rules = [_cass_rule(j) for j in range(STRESS_CASS_RULES)]
    cass_pol = compile_policy(
        NetworkPolicy(
            name="cass",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=9042,
                    rules=[
                        PortNetworkPolicyRule(
                            l7_proto="cassandra", l7_rules=cass_rules
                        )
                    ],
                )
            ],
        )
    )
    cass_model = build_cassandra_model(cass_pol, ingress=True, port=9042)
    build_s = time.perf_counter() - t_build0
    print(
        f"bench stress: built {STRESS_HTTP_POLICIES}x{STRESS_HTTP_RULES} http"
        f" ({http_tier} + {STRESS_HTTP_NFA_RULES} DeviceNfa) + "
        f"{STRESS_KAFKA_POLICIES}x{STRESS_KAFKA_RULES} kafka + "
        f"{STRESS_CASS_POLICIES}x{STRESS_CASS_RULES} cassandra-regex "
        f"rule tables in {build_s:.1f}s",
        file=sys.stderr,
    )

    # --- generate + pre-stage all flows, stacked on a leading POLICY
    # axis so the whole replay is ONE jit launch per protocol (one
    # device round trip; per-call launches serialize a link RTT each).
    L = 64
    http_data = np.zeros((STRESS_HTTP_POLICIES, per_http, L), np.uint8)
    http_len = np.zeros((STRESS_HTTP_POLICIES, per_http), np.int32)
    http_labels = np.zeros((STRESS_HTTP_POLICIES, per_http), bool)
    http_sample = []  # (req_bytes, policy, label) for the re oracle
    n_lit = (
        STRESS_HTTP_RULES - STRESS_HTTP_REGEX_RULES - STRESS_HTTP_NFA_RULES
    )
    for p in range(STRESS_HTTP_POLICIES):
        for i in range(per_http):
            roll = rng.random()
            if roll < 0.30:  # literal-tier hit
                j = rng.randrange(n_lit)
                method, path, ok = (
                    "GET", f"/svc{p:03d}/r{j:02d}/items/x{rng.randrange(1000)}",
                    True,
                )
            elif roll < 0.47:  # regex-tier hit: [a-z0-9]+ segment + /item/
                j = rng.randrange(STRESS_HTTP_REGEX_RULES)
                seg = f"ab{rng.randrange(1000)}z"
                method, path, ok = (
                    "GET", f"/g{j:02d}/{seg}/item/{rng.randrange(10)}",
                    True,
                )
            elif roll < 0.55:  # regex-tier miss: uppercase segment
                j = rng.randrange(STRESS_HTTP_REGEX_RULES)
                method, path, ok = (
                    "GET", f"/g{j:02d}/ABC/item/1", False,
                )
            elif roll < 0.63:  # NFA-tier hit: 8th-from-last symbol 'a'
                j = rng.randrange(STRESS_HTTP_NFA_RULES)
                seg = (
                    "ab" * rng.randrange(3) + "a"
                    + "".join(rng.choice("ab") for _ in range(7))
                )
                method, path, ok = "GET", f"/n{j:02d}/{seg}/x", True
            elif roll < 0.70:  # NFA-tier miss: 8th-from-last symbol 'b'
                j = rng.randrange(STRESS_HTTP_NFA_RULES)
                seg = "b" + "".join(rng.choice("ab") for _ in range(7))
                method, path, ok = "GET", f"/n{j:02d}/{seg}/x", False
            elif roll < 0.78:  # method miss
                j = rng.randrange(n_lit)
                method, path, ok = "POST", f"/svc{p:03d}/r{j:02d}/items/y", False
            elif roll < 0.9:  # unknown rule id
                j = rng.randrange(n_lit)
                method, path, ok = "GET", f"/svc{p:03d}/r{j + 50}/z", False
            else:  # cross-policy miss
                method, path, ok = (
                    "GET",
                    f"/svc{(p + 1) % STRESS_HTTP_POLICIES:03d}/q/", False,
                )
            req = f"{method} {path} HTTP/1.1\r\n\r\n".encode()
            http_data[p, i, : len(req)] = np.frombuffer(req, np.uint8)
            http_len[p, i] = len(req)
            http_labels[p, i] = ok
            if len(http_sample) < 500 and i < 2:
                http_sample.append((req, p, ok))

    kafka_stacked = None
    kafka_labels = np.zeros((STRESS_KAFKA_POLICIES, per_kafka), bool)
    kafka_samples = []  # (policy, [RequestMessage])
    kafka_parts = []
    for p in range(STRESS_KAFKA_POLICIES):
        reqs = []
        for i in range(per_kafka):
            n_topics = rng.choice([1, 1, 2])
            produce = rng.random() < 0.5
            topics, ok_all = [], True
            for _ in range(n_topics):
                j = rng.randrange(STRESS_KAFKA_RULES)
                if rng.random() < 0.6:
                    # Covered iff the rule's role matches the api key.
                    topics.append(f"p{p}t{j}")
                    ok_all &= (j % 2 == 0) == produce
                else:
                    topics.append(f"p{p}x{j}")
                    ok_all = False
            reqs.append(
                RequestMessage(
                    api_key=0 if produce else 1, api_version=1,
                    correlation_id=i, client_id="stress",
                    topics=topics, parsed=True,
                )
            )
            kafka_labels[p, i] = ok_all
        batch = encode_requests(reqs, topic_width=32)
        assert not batch.overflow.any()
        kafka_parts.append(batch)
        kafka_samples.append((p, reqs[:10]))
    kafka_stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *kafka_parts
    )

    # Cassandra flows: (action, table) tuples against the regex rules.
    cass_labels = np.zeros((STRESS_CASS_POLICIES, per_cass), bool)
    cass_parts = []
    cass_samples = []  # (action, table, ok) for the re oracle
    for p in range(STRESS_CASS_POLICIES):
        tuples = []
        for i in range(per_cass):
            roll = rng.random()
            j = rng.randrange(STRESS_CASS_RULES)
            rule_action = "select" if j % 2 == 0 else "insert"
            if roll < 0.45:  # rule hit (t or tmp variant, digit tail)
                base = "t" if rng.random() < 0.7 else "tmp"
                tail = str(rng.randrange(100)) if rng.random() < 0.6 else ""
                action, table, ok = (
                    rule_action, f"ks.{base}{j:02d}{tail}", True,
                )
            elif roll < 0.65:  # action miss on a covered table
                action, table, ok = "update", f"ks.t{j:02d}", False
            elif roll < 0.85:  # table miss: unknown table name
                action, table, ok = rule_action, f"ks.x{j:02d}", False
            else:  # keyspace miss
                action, table, ok = rule_action, f"other.t{j:02d}", False
            tuples.append((action, table, False))
            cass_labels[p, i] = ok
            if len(cass_samples) < 300 and i < 6:
                cass_samples.append((action, table, ok))
        data, alen, tlen, nq, overflow = encode_cassandra_batch(tuples)
        assert not overflow.any()
        cass_parts.append((data, alen, tlen, nq))
    cass_stacked = tuple(
        np.stack([part[k] for part in cass_parts]) for k in range(4)
    )

    # DNS policies: per-policy exact names (needle tier) + shared-text
    # wildcard patterns (automaton tier) — ISSUE 13's stress slice.
    dns_rule_objs = []
    dns_models = []
    for p in range(STRESS_DNS_POLICIES):
        rules = [
            DnsRule(name=_stress_dns_name(p, j))
            for j in range(STRESS_DNS_EXACT_RULES)
        ] + [
            DnsRule(pattern=_stress_dns_pattern(j))
            for j in range(STRESS_DNS_PATTERN_RULES)
        ]
        dns_rule_objs.append(rules)
        dns_models.append(
            build_dns_model_from_rows([(frozenset(), r) for r in rules])
        )
    L_DNS = 64
    dns_data = np.zeros((STRESS_DNS_POLICIES, per_dns, L_DNS), np.uint8)
    dns_len = np.zeros((STRESS_DNS_POLICIES, per_dns), np.int32)
    dns_labels = np.zeros((STRESS_DNS_POLICIES, per_dns), bool)
    dns_samples = []  # (frame, policy, ok) for the oracle spot-check
    for p in range(STRESS_DNS_POLICIES):
        for i in range(per_dns):
            roll = rng.random()
            if roll < 0.30:  # exact-name hit
                j = rng.randrange(STRESS_DNS_EXACT_RULES)
                frame, ok = encode_dns_query(_stress_dns_name(p, j)), True
            elif roll < 0.42:  # exact hit, mixed case (0x20 folding)
                j = rng.randrange(STRESS_DNS_EXACT_RULES)
                frame, ok = (
                    encode_dns_query(_stress_dns_name(p, j).upper()), True,
                )
            elif roll < 0.62:  # wildcard hit: one+ leading labels
                j = rng.randrange(STRESS_DNS_PATTERN_RULES)
                depth = "a.b." if rng.random() < 0.3 else f"h{i % 7}."
                frame, ok = (
                    encode_dns_query(f"{depth}w{j:02d}.svc.local"), True,
                )
            elif roll < 0.72:  # wildcard miss: zero leading labels
                j = rng.randrange(STRESS_DNS_PATTERN_RULES)
                frame, ok = encode_dns_query(f"w{j:02d}.svc.local"), False
            elif roll < 0.92:  # unknown name
                frame, ok = (
                    encode_dns_query(f"x{rng.randrange(100)}.other.local"),
                    False,
                )
            else:  # structurally invalid QNAME (compression pointer)
                bad = bytearray(encode_dns_query("bad.svc.local"))
                bad[DNS_QNAME_OFF] = 0xC0
                frame, ok = bytes(bad), False
            row = np.frombuffer(frame, np.uint8)
            dns_data[p, i, : len(row)] = row
            dns_len[p, i] = len(row)
            dns_labels[p, i] = ok
            if len(dns_samples) < 300 and i < 6:
                dns_samples.append((frame, p, ok))

    # Stack per-policy models into [P, ...] pytrees (shared shapes).
    import jax.numpy as jnp

    http_stack = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *http_models
    )
    kafka_stack = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *kafka_models
    )
    dns_stack = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *dns_models
    )
    rem_dns = np.ones((STRESS_DNS_POLICIES, per_dns), np.int32)
    rem_http = np.ones((STRESS_HTTP_POLICIES, per_http), np.int32)
    rem_kafka = np.ones((STRESS_KAFKA_POLICIES, per_kafka), np.int32)
    rem_cass = np.ones((STRESS_CASS_POLICIES, per_cass), np.int32)

    # lax.map (not vmap) over policies: per-policy intermediates (the
    # [F, R, S*C] DFA joint, the [F, T, R, W] kafka topic compare) stay
    # VMEM-tile-sized; vmapping would ask XLA to tile them with an extra
    # [P] axis — measured 4x slower on the http side.
    http_replay = jax.jit(
        lambda ms, ds, lns, rms: jax.lax.map(
            lambda args: http_verdicts(*args)[2], (ms, ds, lns, rms)
        )
    )
    # Shared regex tier: ONE automaton over the flattened flow batch,
    # chunked so the per-step joint tensor stays HBM-friendly.
    RX_CHUNKS = 20
    http_rx_replay = jax.jit(
        lambda m, ds, lns, rms: jax.lax.map(
            lambda args: http_verdicts(m, *args)[2], (ds, lns, rms)
        )
    )
    # The NFA tier reuses http_rx_replay (same wrapper, jit retraces on
    # the different model pytree).
    kafka_replay = jax.jit(
        lambda ms, bs, rms: jax.lax.map(
            lambda args: kafka_verdicts(args[0], args[1], args[2]),
            (ms, bs, rms),
        )
    )
    dns_replay = jax.jit(
        lambda ms, ds, lns, rms: jax.lax.map(
            lambda args: dns_verdicts(*args)[2], (ms, ds, lns, rms)
        )
    )
    # One SHARED cassandra model over the flattened flow batch (the
    # rule text is policy-independent, so per-policy evaluation would
    # re-pay the identical automaton 50× in small kernels — the same
    # dedup the http regex tier uses), chunked like the http tiers.
    CASS_CHUNKS = 50
    cass_replay = jax.jit(
        lambda m, ds, als, tls, nqs, rms: jax.lax.map(
            lambda args: cassandra_verdicts(m, *args),
            (ds, als, tls, nqs, rms),
        )
    )

    hd = jax.device_put(http_data)
    hl = jax.device_put(http_len)
    hr = jax.device_put(rem_http)
    hd_flat = jax.device_put(
        http_data.reshape(RX_CHUNKS, -1, http_data.shape[-1])
    )
    hl_flat = jax.device_put(http_len.reshape(RX_CHUNKS, -1))
    hr_flat = jax.device_put(rem_http.reshape(RX_CHUNKS, -1))
    kb = jax.tree_util.tree_map(jax.device_put, kafka_stacked)
    kr = jax.device_put(rem_kafka)
    cb = tuple(
        jax.device_put(
            x.reshape((CASS_CHUNKS, -1) + x.shape[2:])
        )
        for x in cass_stacked
    )
    cr = jax.device_put(rem_cass.reshape(CASS_CHUNKS, -1))
    dd = jax.device_put(dns_data)
    dl = jax.device_put(dns_len)
    dr = jax.device_put(rem_dns)

    # --- warm (compile) the executables, then the timed replay
    np.asarray(http_replay(http_stack, hd, hl, hr))
    np.asarray(dns_replay(dns_stack, dd, dl, dr))
    np.asarray(http_rx_replay(http_rx_model, hd_flat, hl_flat, hr_flat))
    np.asarray(http_rx_replay(http_nfa_model, hd_flat, hl_flat, hr_flat))
    np.asarray(kafka_replay(kafka_stack, kb, kr))
    np.asarray(cass_replay(cass_model, *cb, cr))

    t0 = time.perf_counter()
    http_allow = http_replay(http_stack, hd, hl, hr)
    http_rx_allow = http_rx_replay(
        http_rx_model, hd_flat, hl_flat, hr_flat
    )
    http_nfa_allow = http_rx_replay(
        http_nfa_model, hd_flat, hl_flat, hr_flat
    )
    kafka_allow = kafka_replay(kafka_stack, kb, kr)
    cass_allow = cass_replay(cass_model, *cb, cr)
    dns_allow = dns_replay(dns_stack, dd, dl, dr)
    http_allow = (
        np.asarray(http_allow)
        | np.asarray(http_rx_allow).reshape(
            STRESS_HTTP_POLICIES, per_http
        )
        | np.asarray(http_nfa_allow).reshape(
            STRESS_HTTP_POLICIES, per_http
        )
    )
    kafka_allow = np.asarray(kafka_allow)
    cass_allow = np.asarray(cass_allow).reshape(
        STRESS_CASS_POLICIES, per_cass
    )
    dns_allow = np.asarray(dns_allow)
    dt = time.perf_counter() - t0
    n_total = n_http_flows + n_kafka_flows + n_cass_flows + n_dns_flows
    rate = n_total / dt

    # --- bit-check every verdict against the generation labels
    mism = (
        int((http_allow != http_labels).sum())
        + int((kafka_allow != kafka_labels).sum())
        + int((cass_allow != cass_labels).sum())
        + int((dns_allow != dns_labels).sum())
    )
    assert mism == 0, f"stress verdicts diverge from labels ({mism})"

    # --- spot-check labels themselves against the reference oracles
    import re as _re

    for req, p, ok in http_sample[:200]:
        head = req.split(b"\r\n\r\n")[0].decode()
        m, path, _ = head.split(" ", 2)
        pats = (
            [f"/svc{p:03d}/r{j:02d}/.*" for j in range(n_lit)]
            + [_stress_regex_path(j) for j in range(STRESS_HTTP_REGEX_RULES)]
            + [_stress_nfa_path(j) for j in range(STRESS_HTTP_NFA_RULES)]
        )
        want = m == "GET" and any(_re.fullmatch(pt, path) for pt in pats)
        assert want == ok, f"http label oracle mismatch: {req!r}"
    for p, sample in kafka_samples[:10]:
        for i, r in enumerate(sample):
            want = matches_rule(r, kafka_rule_objs[p])
            assert want == kafka_labels[p, i], (
                f"kafka label oracle mismatch: {r!r}"
            )
    for frame, p, ok in dns_samples[:200]:
        name = parse_dns_query(frame)
        req = DnsRequestData(
            name=name if name is not None else "",
            valid=name is not None,
        )
        want = any(r.matches(req) for r in dns_rule_objs[p])
        assert want == ok, f"dns label oracle mismatch: {frame!r}"
    for action, table, ok in cass_samples[:200]:
        want = any(
            (_cass_rule(j)["query_action"] == action)
            and _re.search(_cass_rule(j)["query_table"], table)
            for j in range(STRESS_CASS_RULES)
        )
        assert want == ok, f"cassandra label oracle mismatch: {action} {table}"

    n_rules = (
        STRESS_HTTP_POLICIES * STRESS_HTTP_RULES
        + STRESS_KAFKA_POLICIES * STRESS_KAFKA_RULES
        + STRESS_CASS_POLICIES * STRESS_CASS_RULES
        + STRESS_DNS_POLICIES
        * (STRESS_DNS_EXACT_RULES + STRESS_DNS_PATTERN_RULES)
    )
    print(
        f"bench stress: {n_total:,} flows / {n_rules:,} rules in {dt:.2f}s "
        f"-> {rate:,.0f} verdicts/s (http {n_http_flows:,} @ "
        f"{STRESS_HTTP_POLICIES} policies incl {STRESS_HTTP_REGEX_RULES} "
        f"{http_tier} + {STRESS_HTTP_NFA_RULES} DeviceNfa regex rules, "
        f"kafka {n_kafka_flows:,} @ {STRESS_KAFKA_POLICIES}, cassandra-"
        f"regex {n_cass_flows:,} @ {STRESS_CASS_POLICIES}, dns "
        f"{n_dns_flows:,} @ {STRESS_DNS_POLICIES}), mismatches=0",
        file=sys.stderr,
    )
    return rate, dt, http_tier


# --- composed L3/L4 datapath ---------------------------------------------

def bench_datapath():
    """Composed CT -> LB -> ipcache -> policy pipeline, packets/sec
    (reference: bpf/bpf_lxc.c:684-760 handle_ipv4_from_lxc).  Tables at
    realistic per-endpoint scale: 4k CT entries, 64 services, 1k ipcache
    prefixes, 512 policy entries."""
    import ipaddress
    import random as _random

    from cilium_tpu.datapath.pipeline import (
        build_tables,
        datapath_verdicts,
        host_oracle,
    )
    from cilium_tpu.maps.ctmap import CtKey4, CtMap, PROTO_TCP
    from cilium_tpu.maps.ipcache import IpcacheMap
    from cilium_tpu.maps.lbmap import LbMap
    from cilium_tpu.maps.policymap import DIR_EGRESS, PolicyMap

    rng = _random.Random(29)
    ip4 = lambda s: int(ipaddress.IPv4Address(s))
    lb = LbMap()
    n_services = 64
    for s in range(n_services):
        vip = ip4(f"172.16.0.{s + 1}")
        lb.upsert_service(
            vip, 80,
            [(ip4(f"10.9.{s}.{b + 1}"), 8080) for b in range(3)],
            rev_nat_index=s + 1,
        )
    ipc = IpcacheMap()
    for i in range(1024):
        ipc.upsert(f"10.{i // 250}.{i % 250}.0/24", sec_label=256 + i)
    pol = PolicyMap()
    for i in range(510):
        pol.allow(256 + i, 8080 if i % 2 else 8000, PROTO_TCP, DIR_EGRESS,
                  proxy_port=15000 if i % 7 == 0 else 0)
    pol.allow(0, 443, PROTO_TCP, DIR_EGRESS)
    ct = CtMap()
    ct_keys = []
    for i in range(4096):
        k = CtKey4(
            daddr=ip4(f"10.{i % 4}.{i % 250}.{i % 200 + 1}"),
            saddr=ip4(f"10.200.0.{i % 250 + 1}"),
            dport=8000 + (i % 3), sport=1024 + i % 50000,
            nexthdr=PROTO_TCP,
        )
        ct.create(k)
        ct_keys.append(k)

    F = 65536  # 64k: amortizes the ~2.5ms per-call launch
    saddr = np.zeros((F,), np.int64)
    daddr = np.zeros((F,), np.int64)
    sport = np.zeros((F,), np.int64)
    dport = np.zeros((F,), np.int64)
    proto = np.full((F,), PROTO_TCP, np.int64)
    for i in range(F):
        roll = rng.random()
        if roll < 0.2:  # established flow: exercise the CT fast path
            k = ct_keys[rng.randrange(len(ct_keys))]
            saddr[i], daddr[i] = k.saddr, k.daddr
            sport[i], dport[i] = k.sport, k.dport
            continue
        saddr[i] = ip4(f"10.200.0.{rng.randrange(250) + 1}")
        if roll < 0.5:  # service VIP
            daddr[i] = ip4(f"172.16.0.{rng.randrange(n_services) + 1}")
            dport[i] = 80
        else:
            daddr[i] = ip4(
                f"10.{rng.randrange(5)}.{rng.randrange(250)}."
                f"{rng.randrange(200) + 1}"
            )
            dport[i] = rng.choice([8000, 8080, 443, 9999])
        sport[i] = rng.randrange(1024, 51024)
    as32 = lambda a: (a & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    saddr32, daddr32 = as32(saddr), as32(daddr)
    sport32, dport32 = sport.astype(np.int32), dport.astype(np.int32)
    proto32 = proto.astype(np.int32)

    tables = build_tables(ct, lb, ipc, pol)

    def fn(t, sa, da, sp, dp, pr):
        return datapath_verdicts(t, sa, da, sp, dp, pr)["verdict"]

    rate = _pipelined_rate(
        fn, (tables, saddr32, daddr32, sport32, dport32, proto32), F
    )

    # Host oracle cross-check + CPU rate on a sample.
    out = datapath_verdicts(
        tables, saddr32, daddr32, sport32, dport32, proto32
    )
    dev_verdict = np.asarray(out["verdict"])
    n_cpu = 1000
    t0 = time.perf_counter()
    mism = 0
    for i in range(n_cpu):
        want = host_oracle(
            ct, lb, ipc, pol, int(saddr[i]), int(daddr[i]),
            int(sport[i]), int(dport[i]), int(proto[i]),
        )
        if int(dev_verdict[i]) != want["verdict"]:
            mism += 1
    cpu_rate = n_cpu / (time.perf_counter() - t0)
    assert mism == 0, f"datapath verdicts diverge ({mism}/{n_cpu})"
    print(f"bench datapath: tpu={rate:,.0f}/s cpu={cpu_rate:,.0f}/s "
          f"mismatches=0/{n_cpu}", file=sys.stderr)
    return rate, cpu_rate


# --- sidecar latency -----------------------------------------------------

def bench_latency(colocated: bool = False, null_seam: bool = False):
    from cilium_tpu.sidecar import latbench

    out = latbench.run(
        "/tmp/cilium_tpu_bench_lat%s.sock"
        % ("_null" if null_seam else "_colo" if colocated else ""),
        rates=(100_000, 1_000_000) if (colocated or null_seam)
        else (100_000, 1_000_000, 5_000_000),
        n_requests=100_000,
        colocated=colocated,
        null_seam=null_seam,
    )
    print(
        f"bench latency{' (colocated)' if colocated else ''}: "
        f"oracle p50={out['oracle_p50_ms']:.4f}ms "
        f"device_rtt={out['device_rtt_ms']:.1f}ms",
        file=sys.stderr,
    )
    for r in out["rates"]:
        print(
            f"  rate={r.offered_rate:,.0f}/s achieved={r.achieved_rate:,.0f}/s "
            f"p50={r.p50_ms:.2f}ms p99={r.p99_ms:.2f}ms sat={r.gen_saturated}",
            file=sys.stderr,
        )
    return out


def bench_mixed():
    """Slow/oracle paths under a realistic mix (VERDICT r4 weak #4):
    80% edge-framed complete frames (vec path), 10% partial frames
    (split across rounds -> engine carry), 5% pipelined (two frames
    per read), 5% reply-direction bytes (oracle).  Steady-state wire-
    to-wire verdicts/s, vs the reference-architecture in-process
    parser on the same host."""
    from cilium_tpu.sidecar.mixbench import MixBench

    b = MixBench("/tmp/cilium_tpu_bench_mixed.sock")
    try:
        out = b.run(duration_s=12.0)
        out["oracle_per_sec"] = b.oracle_rate()
    finally:
        b.close()
    print(
        f"bench mixed: {out['verdicts_per_sec']:,.0f}/s "
        f"(slow_fraction={out['slow_fraction']:.2f}, "
        f"reasm_rounds={out['reasm_rounds']}, "
        f"in-process oracle={out['oracle_per_sec']:,.0f}/s)",
        file=sys.stderr,
    )
    # Floors (r06, columnar reassembler): 250k/s on a real accelerator
    # — the ISSUE-10 target is ≥4x the r05 chip reading of 122k/s, and
    # the 10% --check guard owns drift on top.  A chipless container
    # floors at the CPU-smoke level instead (the r06 CPU readings were
    # ~24k columnar vs ~13k scalar — both compute-bound on the host
    # backend, see BENCH_NOTES r06), so the config still proves the
    # lane works where there is no chip.  Either way the reassembler
    # must actually have ENGAGED: a silent fallback to the scalar rung
    # cannot hide behind the vec-path headline.
    import jax

    on_chip = any(d.platform != "cpu" for d in jax.devices())
    floor = 250_000 if on_chip else 15_000
    assert out["verdicts_per_sec"] >= floor, out["verdicts_per_sec"]
    assert out["reasm_rounds"] > 0, "columnar reassembler never engaged"
    return out


def bench_flow_cache():
    """Established-flow verdict cache (PR 12) on the long-lived-flow
    shape: 80% of the conn pool is admitted by a byte-free rule row
    (invariant-allow — armed at registration, served from the cache),
    20% by byte-constrained rows (every frame through the device).
    Paired runs over IDENTICAL traffic — cache on vs the cache-off
    control — so the delta IS the cache; the hit-rate floor is
    asserted so a silently-disarmed cache cannot pass, and the
    transport byte counters prove the shim-side short-circuit at the
    byte level (cached bytes never cross the seam)."""
    from cilium_tpu.sidecar.mixbench import FlowCacheBench

    def one(flow_cache: bool) -> dict:
        b = FlowCacheBench(
            "/tmp/cilium_tpu_bench_flowcache.sock",
            flow_cache=flow_cache,
        )
        try:
            return b.run(duration_s=8.0)
        finally:
            b.close()

    control = one(False)
    cached = one(True)
    print(
        f"bench flow_cache: {cached['verdicts_per_sec']:,.0f}/s cached "
        f"vs {control['verdicts_per_sec']:,.0f}/s control "
        f"(hit_rate={cached['hit_rate']:.2f}, "
        f"bytes {cached['bytes_pushed']:,} vs "
        f"{control['bytes_pushed']:,})",
        file=sys.stderr,
    )
    # The cacheable fraction is 0.8 and arming is static (registration
    # time), so the steady-state hit rate must sit near it: a
    # silently-disarmed cache (or a grant path that stopped flowing)
    # reads ~0 and fails here, never as a soft throughput drop.
    assert cached["hit_rate"] >= 0.5, cached
    assert control["hit_rate"] == 0.0, control
    # Byte-level proof of the shim short-circuit: strictly fewer
    # data-plane bytes cross the transport PER VERDICT with the cache
    # on (the closed loop completes more rounds when faster, so the
    # per-verdict normalization is the like-for-like comparison; the
    # raw totals ride along in the record).
    bpv_on = cached["bytes_pushed"] / max(cached["frames"], 1)
    bpv_off = control["bytes_pushed"] / max(control["frames"], 1)
    assert bpv_on < bpv_off, (bpv_on, bpv_off)
    # And a measured verdicts/s win on this shape (every cached frame
    # skips the device round AND the wire round trip).
    assert cached["verdicts_per_sec"] > control["verdicts_per_sec"], (
        cached["verdicts_per_sec"], control["verdicts_per_sec"],
    )
    cached["control_verdicts_per_sec"] = control["verdicts_per_sec"]
    cached["control_bytes_pushed"] = control["bytes_pushed"]
    cached["bytes_per_verdict"] = round(bpv_on, 1)
    cached["control_bytes_per_verdict"] = round(bpv_off, 1)
    return cached


def bench_fanin_concurrent(n_sessions: int = 16):
    """Multi-tenant fan-in (ISSUE 15): N independent shim sessions —
    one SidecarClient each, identity-named, disjoint conns — feeding
    ONE dispatcher, offered 2x the single-session capacity in
    aggregate.  Reports aggregate verdicts/s and per-session served
    p99 against the single-session number, and ASSERTS the fan-in
    contract in-bench: zero silent loss (every seq from every session
    answered exactly once, served OK or typed SHED) and zero
    cross-session reply misrouting (each client's verdicts name only
    conns it registered)."""
    import threading

    from cilium_tpu.proxylib import (
        NetworkPolicy, PortNetworkPolicy, PortNetworkPolicyRule,
        FilterResult,
    )
    from cilium_tpu.proxylib import instance as inst_mod
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    policy = NetworkPolicy(
        name="bench-fanin",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=[1],
                        l7_proto="r2d2",
                        l7_rules=[{"cmd": "READ", "file": "/public/.*"}],
                    )
                ],
            )
        ],
    )
    QUEUE_AGE_MS = 25.0
    inst_mod.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=0.0, batch_flows=512,
        shed_queue_entries=2048, shed_queue_age_ms=QUEUE_AGE_MS,
    )
    svc = VerdictService("/tmp/cilium_tpu_bench_fanin.sock", cfg).start()
    msg = b"READ /public/bench.txt\r\n"
    conns_per = 16
    clients: list = []
    try:
        # --- per-session plumbing ----------------------------------------
        metas: list[dict] = []
        for s in range(n_sessions):
            cl = SidecarClient(
                svc.socket_path, timeout=60.0, identity=f"bench-pod-{s}"
            )
            clients.append(cl)
            mod = cl.open_module([])
            assert cl.policy_update(mod, [policy]) == int(FilterResult.OK)
            base = 1000 * (s + 1)
            for k in range(conns_per):
                res, _ = cl.new_connection(
                    mod, "r2d2", base + k, True, 1, 2,
                    f"1.1.1.{s + 1}:{k + 1}", "2.2.2.2:80", "bench-fanin",
                )
                assert res == int(FilterResult.OK)
            ids = np.arange(base, base + conns_per, dtype=np.uint64)
            lens = np.full(conns_per, len(msg), np.uint32)
            lock = threading.Lock()
            answered: dict[int, tuple[float, bool]] = {}
            sent_ts: dict[int, float] = {}

            def cb(vb, _answered=answered, _lock=lock):
                now = time.perf_counter()
                ok = bool(vb.count) and int(vb.results[0]) == int(
                    FilterResult.OK
                )
                with _lock:
                    _answered[vb.seq] = (now, ok)

            cl.verdict_callback = cb
            metas.append({
                "client": cl, "ids": ids, "lens": lens,
                "answered": answered, "sent": sent_ts, "lock": lock,
                "blob": msg * conns_per,
            })

        def fire(m, seq):
            m["sent"][seq] = time.perf_counter()
            m["client"].send_batch(
                seq, m["ids"], [0] * conns_per, m["lens"], m["blob"]
            )

        def drain(m, upto, timeout_s):
            deadline = time.perf_counter() + timeout_s
            while time.perf_counter() < deadline:
                with m["lock"]:
                    if len(m["answered"]) >= upto:
                        return True
                time.sleep(0.002)
            return False

        # --- single-session baseline: closed-loop capacity + p99 ---------
        m0 = metas[0]
        warm = 20
        for s in range(1, warm + 1):
            fire(m0, s)
            assert drain(m0, s, 60.0), "warmup stalled"
        with m0["lock"]:
            m0["answered"].clear()
        m0["sent"].clear()
        t0 = time.perf_counter()
        n_cap = 200
        for s in range(100, 100 + n_cap):
            fire(m0, s)
            assert drain(m0, s - 99, 60.0), "capacity phase stalled"
        single_dt = time.perf_counter() - t0
        single_rate = n_cap * conns_per / single_dt
        with m0["lock"]:
            base_lat = sorted(
                (m0["answered"][s][0] - m0["sent"][s]) * 1e3
                for s in m0["sent"] if s in m0["answered"]
            )
        single_p99 = base_lat[min(int(len(base_lat) * 0.99),
                                  len(base_lat) - 1)]
        with m0["lock"]:
            m0["answered"].clear()
        m0["sent"].clear()

        # --- 16-session fan-in at 2x aggregate capacity -------------------
        offered = 2.0 * single_rate
        interval = conns_per / (offered / n_sessions)
        window = 128  # per-session un-answered batches in flight

        def open_loop(m, seq0, duration, t_start):
            seq = seq0
            next_fire = t_start
            while time.perf_counter() - t_start < duration:
                now = time.perf_counter()
                if now < next_fire:
                    time.sleep(min(next_fire - now, 0.001))
                    continue
                with m["lock"]:
                    outstanding = len(m["sent"]) - len(m["answered"])
                if outstanding >= window:
                    time.sleep(0.001)
                    continue
                seq += 1
                fire(m, seq)
                next_fire += interval

        def run_phase(duration, phase):
            # Phase-disjoint seq ranges: a late prime-phase verdict
            # must never collide with (and pre-answer) a measured-phase
            # seq — that would mask a genuinely lost measured batch
            # behind a stale answer stamped before its own fire().
            t_start = time.perf_counter() + 0.1
            threads = [
                threading.Thread(
                    target=open_loop,
                    args=(
                        m,
                        10_000_000 * phase + 100_000 * (i + 1),
                        duration, t_start,
                    ),
                    daemon=True,
                )
                for i, m in enumerate(metas)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(duration + 30)

        def quiesce(timeout_s):
            # Membership-based (every SENT seq answered): stale answers
            # from a prior phase can never satisfy it early.
            deadline = time.perf_counter() + timeout_s
            while time.perf_counter() < deadline:
                if all(
                    all(s in m["answered"] for s in m["sent"])
                    for m in metas
                ):
                    return
                time.sleep(0.01)

        # Prime (bucket compiles for the aggregated round shapes land
        # here, not in the measured window), then reset and measure.
        run_phase(2.0, phase=1)
        quiesce(30.0)
        for m in metas:
            with m["lock"]:
                m["answered"].clear()
            m["sent"].clear()
        duration = 4.0
        run_phase(duration, phase=2)
        quiesce(30.0)

        # --- the fan-in contract, asserted --------------------------------
        silent_loss = 0
        served_total = 0
        shed_total = 0
        per_session_p99: list[float] = []
        for m in metas:
            with m["lock"]:
                done = dict(m["answered"])
            silent_loss += sum(1 for s in m["sent"] if s not in done)
            lats = sorted(
                (done[s][0] - m["sent"][s]) * 1e3
                for s in m["sent"] if s in done and done[s][1]
            )
            served_total += len(lats) * conns_per
            shed_total += sum(
                conns_per for s in m["sent"]
                if s in done and not done[s][1]
            )
            if lats:
                per_session_p99.append(
                    lats[min(int(len(lats) * 0.99), len(lats) - 1)]
                )
        assert silent_loss == 0, (
            f"{silent_loss} batches never answered (silent loss)"
        )
        misroutes = sum(c.misrouted_verdicts for c in clients)
        assert misroutes == 0, (
            f"{misroutes} cross-session verdict misroutes"
        )
        assert len(per_session_p99) == n_sessions, (
            "a session served nothing"
        )
        aggregate_rate = served_total / duration
        st = svc.status()
        rows = st["sessions"]["live"]
        for row in rows:
            assert row["submitted"] == row["answered"], row
        session_shed = {
            r["identity"]: r["shed"] for r in rows if r["shed"]
        }
        return {
            "single_rate": single_rate,
            "single_p99_ms": single_p99,
            "aggregate_rate": aggregate_rate,
            "offered": offered,
            "per_session_p99_ms": [round(p, 3) for p in per_session_p99],
            "p99_worst_ms": max(per_session_p99),
            "p99_median_ms": sorted(per_session_p99)[n_sessions // 2],
            "served_entries": served_total,
            "shed_entries": shed_total,
            "session_shed": session_shed,
            "fair_share": st["sessions"]["fair_share"],
            "n_sessions": n_sessions,
        }
    finally:
        for cl in clients:
            cl.verdict_callback = None
            try:
                cl.close()
            except Exception:
                pass
        svc.stop()
        inst_mod.reset_module_registry()


def bench_verdict_overload():
    """Fail-closed overload behavior at 2x capacity (the robustness
    contract): capacity is measured closed-loop, then an open-loop
    generator offers 2x that rate against a bounded admission queue.
    Every entry must be answered — served OK or shed with a typed SHED
    verdict (zero silent loss) — and the p99 of SERVED verdicts stays
    bounded by the queue-age watermark instead of growing with the
    backlog."""
    import threading

    from cilium_tpu.proxylib import (
        NetworkPolicy, PortNetworkPolicy, PortNetworkPolicyRule,
        FilterResult,
    )
    from cilium_tpu.proxylib import instance as inst_mod
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    policy = NetworkPolicy(
        name="bench-ovl",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
                        remote_policies=[1],
                        l7_proto="r2d2",
                        l7_rules=[{"cmd": "READ", "file": "/public/.*"}],
                    )
                ],
            )
        ],
    )
    QUEUE_AGE_MS = 25.0
    inst_mod.reset_module_registry()
    # Greedy (co-located) mode: rounds complete inline, so end-to-end
    # latency = admission-queue wait + one round — both bounded (age
    # cap / round size), which is the degradation contract this bench
    # guards.  (Deadline mode pipelines completion asynchronously and
    # its in-flight depth is not admission-capped.)
    cfg = DaemonConfig(
        batch_timeout_ms=0.0, batch_flows=512,
        shed_queue_entries=2048, shed_queue_age_ms=QUEUE_AGE_MS,
    )
    svc = VerdictService("/tmp/cilium_tpu_bench_overload.sock", cfg).start()
    client = SidecarClient(svc.socket_path, timeout=60.0)
    msg = b"READ /public/bench.txt\r\n"
    n_conns = 64
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [policy]) == int(FilterResult.OK)
        for cid in range(1, n_conns + 1):
            res, _ = client.new_connection(
                mod, "r2d2", cid, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
                "bench-ovl",
            )
            assert res == int(FilterResult.OK)

        answered: dict[int, tuple[float, bool]] = {}
        lock = threading.Lock()
        sent_ts: dict[int, float] = {}

        def cb(vb):
            now = time.perf_counter()
            ok = bool(vb.count) and int(vb.results[0]) == int(FilterResult.OK)
            with lock:
                answered[vb.seq] = (now, ok)

        client.verdict_callback = cb
        ids = np.arange(1, n_conns + 1, dtype=np.uint64)
        lens = np.full(n_conns, len(msg), np.uint32)
        blob = msg * n_conns

        def fire(seq):
            sent_ts[seq] = time.perf_counter()
            client.send_batch(seq, ids, [0] * n_conns, lens, blob)

        def drain(upto, timeout_s):
            deadline = time.perf_counter() + timeout_s
            while time.perf_counter() < deadline:
                with lock:
                    if len(answered) >= upto:
                        return True
                time.sleep(0.002)
            return False

        # Closed-loop capacity: back-to-back batches, one in flight.
        warm = 20
        for s in range(1, warm + 1):
            fire(s)
            assert drain(s, 30.0), "warmup stalled"
        t0 = time.perf_counter()
        n_cap = 200
        for s in range(warm + 1, warm + n_cap + 1):
            fire(s)
            assert drain(s, 30.0), "capacity phase stalled"
        capacity = n_cap * n_conns / (time.perf_counter() - t0)

        # Open loop at 2x capacity, with a bounded in-flight window (a
        # real edge applies socket backpressure): without it, batches
        # pile up in the unix socket buffer BEFORE the service's
        # admission clock starts and the measured tail is wire-queue
        # time, not service behavior.  The first pass PRIMES and is
        # discarded — aggregated overload rounds hit jit bucket shapes
        # the closed loop never built, and those one-time compiles are
        # cold-start cost, not steady-state overload behavior.
        offered = 2.0 * capacity
        interval = n_conns / offered
        window = 1024  # max un-answered batches in flight

        def open_loop(seq0: int, duration: float) -> int:
            seq = seq0
            t_start = time.perf_counter()
            next_fire = t_start
            while time.perf_counter() - t_start < duration:
                now = time.perf_counter()
                if now < next_fire:
                    time.sleep(min(next_fire - now, 0.001))
                    continue
                with lock:
                    outstanding = (seq - seq0) - len(answered)
                if outstanding >= window:
                    time.sleep(0.001)
                    continue
                seq += 1
                fire(seq)
                next_fire += interval
            return seq - seq0

        with lock:
            answered.clear()
        sent_ts.clear()
        open_loop(50_000, 2.5)  # prime (compiles land here)
        time.sleep(1.0)
        with lock:
            answered.clear()
        sent_ts.clear()
        duration = 4.0
        n_sent = open_loop(100_000, duration)
        achieved_offer = n_sent * n_conns / duration
        deadline = time.perf_counter() + 30.0
        while time.perf_counter() < deadline:
            with lock:
                if all(s in answered for s in sent_ts):
                    break
            time.sleep(0.005)
        with lock:
            done = dict(answered)
        silent_loss = sum(1 for s in sent_ts if s not in done)
        served = [
            (done[s][0] - sent_ts[s]) * 1e3
            for s in sent_ts if s in done and done[s][1]
        ]
        shed = sum(1 for s in done.values() if not s[1])
        assert silent_loss == 0, f"{silent_loss} batches never answered"
        assert served, "overload run served nothing"
        served.sort()
        p50 = served[len(served) // 2]
        p99 = served[min(int(len(served) * 0.99), len(served) - 1)]
        shed_rate = shed / max(len(done), 1)
        st = svc.status()
        print(
            f"bench verdict_overload: capacity={capacity:,.0f}/s "
            f"offered={offered:,.0f}/s (achieved {achieved_offer:,.0f}/s) "
            f"served_p50={p50:.2f}ms served_p99={p99:.2f}ms "
            f"shed_rate={shed_rate:.2f} silent_loss=0 "
            f"(queue_age_cap={QUEUE_AGE_MS}ms)",
            file=sys.stderr,
        )
        return {
            "p99_ms": p99, "p50_ms": p50, "capacity": capacity,
            "offered": offered, "achieved_offer": achieved_offer,
            "shed_rate": shed_rate,
            "queue_age_cap_ms": QUEUE_AGE_MS,
            "shed_entries": st["containment"]["shed_entries"],
        }
    finally:
        client.verdict_callback = None
        client.close()
        svc.stop()
        inst_mod.reset_module_registry()


def bench_verdict_trace_overhead():
    """Cost of the always-on verdict-path stage metrics (PR 4): the
    latency-decomposition layer instruments the exact hot path the
    project exists to make fast, so it must prove its own overhead.

    Method (same `_pipelined_rate` harness as the throughput configs):
    the r2d2 model's per-round serving time at a realistic round size
    comes from `_pipelined_rate` (marginal rate, fence-cancelled); the
    tracer's per-round cost is measured directly over 20k rounds of
    exactly what the service adds per round — begin_round, the four
    boundary stamps, finish_round (6 stage observes + e2e observe +
    occupancy gauge + span sampling) — once with stage metrics ON and
    once DISABLED.  Implied throughput ratio = (round + cost_off) /
    (round + cost_on); the assertion bounds the loss at <2%.  This is
    CONSERVATIVE: the denominator is the model-only round time,
    excluding the wire/numpy/response work a real round also pays, so
    the true serving-path overhead is strictly smaller."""
    from cilium_tpu.models.r2d2 import build_r2d2_model
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        find_instance,
        open_module,
        reset_module_registry,
    )
    from cilium_tpu.sidecar.trace import VerdictTracer

    policy_cfg = NetworkPolicy(
        name="bench-trace",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
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
    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([policy_cfg])
    model = build_r2d2_model(
        ins.policy_map()["bench-trace"], ingress=True, port=80
    )
    rng = random.Random(11)
    F, L = 2048, 64  # a realistic aggregated-round size
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        m = f"READ /public/f{rng.randrange(1000)}.txt\r\n".encode()
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    remotes = np.ones((F,), np.int32)
    fn = type(model).__call__
    rate = _pipelined_rate(fn, (model, data, lengths, remotes), F)
    round_s = F / rate

    def tracer_cost(stage_metrics: bool) -> float:
        tr = VerdictTracer(
            sample_every=4096, slow_ms=1e9, ring=512,
            stage_metrics=stage_metrics, batch_capacity=F,
        )
        K = 20_000
        t0 = time.perf_counter()
        for i in range(K):
            rt = tr.begin_round("vec", F, 0.0)
            rt.formed()
            rt.submitted()
            rt.completed()
            rt.drained()
            tr.finish_round(rt, ((i, F, 0.0, 1),))
        return (time.perf_counter() - t0) / K

    # Best-of-3 each: a scheduler stall inside one window only ever
    # INFLATES a cost, so the minimum is the honest reading.
    cost_on = min(tracer_cost(True) for _ in range(3))
    cost_off = min(tracer_cost(False) for _ in range(3))
    rate_on = F / (round_s + cost_on)
    rate_off = F / (round_s + cost_off)
    overhead = max(1.0 - rate_on / rate_off, 0.0)
    print(
        f"bench verdict_trace_overhead: round={round_s * 1e6:.1f}us "
        f"tracer_on={cost_on * 1e6:.2f}us tracer_off={cost_off * 1e6:.2f}us "
        f"implied {rate_off:,.0f}/s -> {rate_on:,.0f}/s "
        f"({overhead:.4%} loss)",
        file=sys.stderr,
    )
    # The acceptance contract: always-on stage metrics cost <2%
    # throughput vs instrumentation disabled.
    assert overhead < 0.02, (
        f"stage-metrics overhead {overhead:.3%} exceeds the 2% budget"
    )
    reset_module_registry()
    return {
        "overhead_pct": overhead * 100.0,
        "round_us": round_s * 1e6,
        "tracer_on_us": cost_on * 1e6,
        "tracer_off_us": cost_off * 1e6,
        "implied_rate_on": rate_on,
        "implied_rate_off": rate_off,
    }


def bench_timeline_overhead():
    """Cost of the always-on flight recorder (PR 19): the blackbox
    rides the verdict round only through ``VerdictTracer.finish_round``
    calling ``FlightRecorder.sample_round`` once per ROUND (occupancy
    bucket fold + admission probe) — typestate edges, marks, and
    overload events fire on state CHANGES, not per round, so the
    serving path pays exactly this sample.  The recorder must prove
    that cost like the tracer and flow log proved theirs.

    Method (same `_pipelined_rate` harness as verdict_trace_overhead):
    the r2d2 model's per-round serving time at a realistic round size
    from `_pipelined_rate`; the per-round tracer cost measured over 20k
    rounds of exactly what the service adds per round, once with a
    recorder attached (stage metrics + occupancy sampling) and once
    with recorder=None (stage metrics only — the PR 4 baseline).
    Implied throughput ratio bounds the loss at <2%.  Conservative
    like the sibling benches: the denominator excludes wire/numpy/
    response work a real round also pays."""
    from cilium_tpu.models.r2d2 import build_r2d2_model
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        find_instance,
        open_module,
        reset_module_registry,
    )
    from cilium_tpu.sidecar.blackbox import FlightRecorder
    from cilium_tpu.sidecar.trace import VerdictTracer

    policy_cfg = NetworkPolicy(
        name="bench-timeline",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
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
    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([policy_cfg])
    model = build_r2d2_model(
        ins.policy_map()["bench-timeline"], ingress=True, port=80
    )
    rng = random.Random(11)
    F, L = 2048, 64
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        m = f"READ /public/f{rng.randrange(1000)}.txt\r\n".encode()
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    remotes = np.ones((F,), np.int32)
    fn = type(model).__call__
    rate = _pipelined_rate(fn, (model, data, lengths, remotes), F)
    round_s = F / rate

    def tracer_cost(with_recorder: bool) -> float:
        tr = VerdictTracer(
            sample_every=4096, slow_ms=1e9, ring=512,
            stage_metrics=True, batch_capacity=F,
        )
        if with_recorder:
            rec = FlightRecorder(ring=512)
            # The real probe reads two dispatcher attributes; mirror
            # that cost without spinning up a service.
            rec.occupancy_probe = lambda: (3, 0.5)
            tr.recorder = rec
        K = 20_000
        t0 = time.perf_counter()
        for i in range(K):
            rt = tr.begin_round("vec", F, 0.0)
            rt.formed()
            rt.submitted()
            rt.completed()
            rt.drained()
            tr.finish_round(rt, ((i, F, 0.0, 1),))
        return (time.perf_counter() - t0) / K

    cost_on = min(tracer_cost(True) for _ in range(3))
    cost_off = min(tracer_cost(False) for _ in range(3))
    rate_on = F / (round_s + cost_on)
    rate_off = F / (round_s + cost_off)
    overhead = max(1.0 - rate_on / rate_off, 0.0)
    print(
        f"bench timeline_overhead: round={round_s * 1e6:.1f}us "
        f"recorder_on={cost_on * 1e6:.2f}us "
        f"recorder_off={cost_off * 1e6:.2f}us "
        f"implied {rate_off:,.0f}/s -> {rate_on:,.0f}/s "
        f"({overhead:.4%} loss)",
        file=sys.stderr,
    )
    # The acceptance contract: the always-on flight recorder costs <2%
    # throughput vs the recorder detached.
    assert overhead < 0.02, (
        f"flight-recorder overhead {overhead:.3%} exceeds the 2% budget"
    )
    reset_module_registry()
    return {
        "overhead_pct": overhead * 100.0,
        "round_us": round_s * 1e6,
        "recorder_on_us": cost_on * 1e6,
        "recorder_off_us": cost_off * 1e6,
        "implied_rate_on": rate_on,
        "implied_rate_off": rate_off,
    }


def bench_ledger_overhead():
    """Cost of the always-on device-economics ledger (PR 20): the
    ledger rides the verdict round only through
    ``VerdictTracer.finish_round`` calling ``DeviceLedger.stamp_round``
    once per ROUND (one formation-provenance stamp: trigger counter,
    occupancy/age fold, µs histogram) — compile events fire on
    trace/compile, which warm serving performs zero of, so the serving
    path pays exactly this stamp.  Same paired methodology as
    timeline_overhead: per-round tracer cost over 20k rounds with the
    ledger attached vs detached (flight recorder attached in BOTH arms
    — this bench isolates the ledger's own cost), against the r2d2
    model's measured per-round serving time."""
    import threading as _threading

    from cilium_tpu.models.r2d2 import build_r2d2_model
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        find_instance,
        open_module,
        reset_module_registry,
    )
    from cilium_tpu.sidecar.blackbox import FlightRecorder
    from cilium_tpu.sidecar.ledger import DeviceLedger
    from cilium_tpu.sidecar.trace import VerdictTracer

    policy_cfg = NetworkPolicy(
        name="bench-ledger",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
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
    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([policy_cfg])
    model = build_r2d2_model(
        ins.policy_map()["bench-ledger"], ingress=True, port=80
    )
    rng = random.Random(13)
    F, L = 2048, 64
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        m = f"READ /public/f{rng.randrange(1000)}.txt\r\n".encode()
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    remotes = np.ones((F,), np.int32)
    fn = type(model).__call__
    rate = _pipelined_rate(fn, (model, data, lengths, remotes), F)
    round_s = F / rate

    def tracer_cost(with_ledger: bool) -> float:
        tr = VerdictTracer(
            sample_every=4096, slow_ms=1e9, ring=512,
            stage_metrics=True, batch_capacity=F,
        )
        rec = FlightRecorder(ring=512)
        rec.occupancy_probe = lambda: (3, 0.5)
        tr.recorder = rec
        if with_ledger:
            tr.ledger = DeviceLedger(ring=512)
        # The popping thread's formation stamp (what _pop_locked /
        # begin_inline_round brand the worker with) — present in BOTH
        # arms so begin_round's read is paid identically; only the
        # ledger's stamp_round differs.
        _threading.current_thread()._disp_pop = {
            "trigger": "size-full", "depth": 3, "age_s": 2e-4,
            "bytes": 65536,
        }
        K = 20_000
        try:
            t0 = time.perf_counter()
            for i in range(K):
                rt = tr.begin_round("vec", F, 0.0)
                rt.formed()
                rt.submitted()
                rt.completed()
                rt.drained()
                tr.finish_round(rt, ((i, F, 0.0, 1),))
            return (time.perf_counter() - t0) / K
        finally:
            del _threading.current_thread()._disp_pop

    cost_on = min(tracer_cost(True) for _ in range(3))
    cost_off = min(tracer_cost(False) for _ in range(3))
    rate_on = F / (round_s + cost_on)
    rate_off = F / (round_s + cost_off)
    overhead = max(1.0 - rate_on / rate_off, 0.0)
    print(
        f"bench ledger_overhead: round={round_s * 1e6:.1f}us "
        f"ledger_on={cost_on * 1e6:.2f}us "
        f"ledger_off={cost_off * 1e6:.2f}us "
        f"implied {rate_off:,.0f}/s -> {rate_on:,.0f}/s "
        f"({overhead:.4%} loss)",
        file=sys.stderr,
    )
    # The acceptance contract: the always-on ledger costs <2%
    # throughput vs the ledger detached.
    assert overhead < 0.02, (
        f"device-ledger overhead {overhead:.3%} exceeds the 2% budget"
    )
    reset_module_registry()
    return {
        "overhead_pct": overhead * 100.0,
        "round_us": round_s * 1e6,
        "ledger_on_us": cost_on * 1e6,
        "ledger_off_us": cost_off * 1e6,
        "implied_rate_on": rate_on,
        "implied_rate_off": rate_off,
    }


def bench_load_knee():
    """The p99-vs-throughput knee (ROADMAP item 4's regression floor),
    derived from the formation telemetry the ledger stamps per round.

    Method: the colocated open-loop harness (latbench — same seam-probe
    service and Poisson generator as the latency bench) measures a
    saturation reference by offering well past capacity and taking the
    achieved rate; then sweeps ~6 offered-load fractions of it.  Each
    point records the open-loop p99 and the service ledger's formation
    delta (per-trigger round counts, occupancy, queue age): below the
    knee formation is deadline/idle-driven with low occupancy, past it
    size-full rounds and queue age dominate and p99 inflects.  The
    knee is the highest swept fraction whose p99 stays within 2x the
    lightest point's p99 — the regression floor for latency-tiered
    dispatch work."""
    from cilium_tpu.sidecar import latbench

    sock = "/tmp/cilium_tpu_bench_knee.sock"
    bench = latbench.LatencyBench(
        sock,
        verdict_device="cpu",
        seam_probe=True,
        batch_timeout_ms=0.0,
        client_timeout_ms=0.3,
        batch_flows=8192,
        client_batch=2048,
    )
    try:
        # Saturation reference: offer far past capacity; the achieved
        # rate IS the closed-loop ceiling of this host.
        sat = bench.run_rate(5_000_000, 100_000, seed=3)
        max_rate = sat.achieved_rate
        svc = bench.service
        fracs = (0.2, 0.4, 0.6, 0.8, 0.9, 1.0)
        points = []
        prev_form = svc.ledger.formation()

        def _rounds(form):
            return {t: rec.get("rounds", 0) for t, rec in form.items()}

        for frac in fracs:
            rate = max(int(max_rate * frac), 1_000)
            n = min(60_000, max(20_000, int(rate * 0.5)))
            r = bench.run_rate(rate, n, seed=7)
            form = svc.ledger.formation()
            prev_r, cur_r = _rounds(prev_form), _rounds(form)
            delta = {
                t: cur_r.get(t, 0) - prev_r.get(t, 0)
                for t in cur_r
                if cur_r.get(t, 0) - prev_r.get(t, 0) > 0
            }
            points.append({
                "frac": frac,
                "offered_rate": rate,
                "achieved_rate": round(r.achieved_rate),
                "p99_ms": round(r.p99_ms, 3),
                "p50_ms": round(r.p50_ms, 3),
                "formation_rounds": delta,
                "occ_mean": {
                    t: rec.get("occ_mean", 0.0)
                    for t, rec in form.items()
                },
            })
            prev_form = form
        base_p99 = points[0]["p99_ms"]
        knee_frac, knee_p99 = fracs[0], base_p99
        for pt in points:
            if pt["p99_ms"] <= 2.0 * base_p99:
                knee_frac, knee_p99 = pt["frac"], pt["p99_ms"]
        print(
            f"bench load_knee: max_rate={max_rate:,.0f}/s knee at "
            f"{knee_frac:.0%} offered (p99 {knee_p99:.2f}ms, base "
            f"{base_p99:.2f}ms); sweep "
            + " ".join(
                f"{p['frac']:.0%}={p['p99_ms']:.2f}ms" for p in points
            ),
            file=sys.stderr,
        )
        return {
            "knee_throughput_frac": knee_frac,
            "knee_p99_ms": knee_p99,
            "max_rate": round(max_rate),
            "base_p99_ms": base_p99,
            "points": points,
        }
    finally:
        bench.close()


def bench_flow_observe_overhead():
    """Cost of always-on flow records + device-side rule attribution
    (PR 5): the flow observability layer rides the exact vec hot path,
    so it must prove its own overhead like verdict_trace_overhead did
    for the stage metrics.

    Method (same `_pipelined_rate` marginal/fence harness): the device
    term is measured directly — the ATTRIBUTED model call (verdict +
    first-match argmax fused) vs the plain call at a realistic round
    size; the host term is the per-round flow-record emission
    (one columnar add_round of F entries: verdict/rule arrays, metric
    aggregation, ring append) over 20k rounds.  Implied throughput
    ratio = attributed+recorded rate vs plain rate; the assertion
    bounds the loss at <2%.  Conservative like the tracer bench: the
    denominator excludes the wire/response work a real round also
    pays."""
    from cilium_tpu.flowlog import FlowLog
    from cilium_tpu.models.r2d2 import (
        build_r2d2_model,
        r2d2_verdicts,
        r2d2_verdicts_attr,
    )
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        find_instance,
        open_module,
        reset_module_registry,
    )

    policy_cfg = NetworkPolicy(
        name="bench-observe",
        policy=2,
        ingress_per_port_policies=[
            PortNetworkPolicy(
                port=80,
                rules=[
                    PortNetworkPolicyRule(
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
    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([policy_cfg])
    model = build_r2d2_model(
        ins.policy_map()["bench-observe"], ingress=True, port=80
    )
    rng = random.Random(17)
    F, L = 2048, 64  # a realistic aggregated-round size
    data = np.zeros((F, L), np.uint8)
    lengths = np.zeros((F,), np.int32)
    for i in range(F):
        m = f"READ /public/f{rng.randrange(1000)}.txt\r\n".encode()
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    remotes = np.ones((F,), np.int32)
    rate_plain = _pipelined_rate(
        r2d2_verdicts, (model, data, lengths, remotes), F
    )
    round_plain = F / rate_plain

    # Device term: the attributed call's MARGINAL cost over the plain
    # call, from PAIRED timed windows on device-staged args — each
    # trial times attr and plain back-to-back, so slow host
    # drift cancels inside the pair, and the minimum over 5 paired
    # differences (floored at 0) is the honest reading: any stall only
    # inflates a difference.  Two independent _pipelined_rate
    # measurements were tried first and rejected: their run-to-run
    # variance (several %) lands directly in the
    # subtraction and flaked the 2% assertion at a spurious 3.1%.
    import jax

    dev_args = tuple(jax.device_put(a) for a in (data, lengths, remotes))

    def timed(fn) -> float:
        return _timed_calls(fn, (model, *dev_args), 8) / 8

    jit_plain = jax.jit(r2d2_verdicts)
    jit_attr = jax.jit(r2d2_verdicts_attr)
    _fence(jit_plain(model, *dev_args))
    _fence(jit_attr(model, *dev_args))
    attr_extra = min(
        timed(jit_attr) - timed(jit_plain) for _ in range(5)
    )
    attr_extra = max(attr_extra, 0.0)

    def ring_cost() -> float:
        fl = FlowLog(capacity=8192)
        conn_ids = np.arange(F, dtype=np.int64)
        codes = np.zeros(F, np.int8)
        codes[::7] = 1
        rules = np.zeros(F, np.int32)
        rules[::7] = -1
        kinds = model.match_kinds
        K = 20_000
        t0 = time.perf_counter()
        for _ in range(K):
            fl.add_round("vec", conn_ids, codes, rules, kinds=kinds)
        return (time.perf_counter() - t0) / K

    # Best-of-3: a scheduler stall only ever INFLATES the cost.
    rec_cost = min(ring_cost() for _ in range(3))
    round_attr = round_plain + attr_extra
    rate_on = F / (round_attr + rec_cost)
    rate_off = rate_plain
    overhead = max(1.0 - rate_on / rate_off, 0.0)
    print(
        f"bench flow_observe_overhead: round_plain={round_plain * 1e6:.1f}us "
        f"attr_extra={attr_extra * 1e6:.2f}us "
        f"record={rec_cost * 1e6:.2f}us/round "
        f"implied {rate_off:,.0f}/s -> {rate_on:,.0f}/s "
        f"({overhead:.4%} loss)",
        file=sys.stderr,
    )
    # The acceptance contract: always-on flow records + attribution
    # cost <2% throughput vs disabled.
    assert overhead < 0.02, (
        f"flow-observe overhead {overhead:.3%} exceeds the 2% budget"
    )
    reset_module_registry()
    return {
        "overhead_pct": overhead * 100.0,
        "round_plain_us": round_plain * 1e6,
        "round_attr_us": round_attr * 1e6,
        "record_us": rec_cost * 1e6,
        "implied_rate_on": rate_on,
        "implied_rate_off": rate_off,
    }


def bench_policy_churn():
    """Non-stop policy churn (PR 9): continuous policy updates at N
    tables/s against live traffic.  Two paired phases over the same
    service/conns/traffic loop — a no-churn control, then the churn
    phase — so the served-latency delta isolates what table swaps cost
    the data path.  Emits:

    - ``churn_swap_p99_ms``: p99 of the swap pointer-flip hold (the
      bounded-stall contract; the off-path staged build is excluded by
      construction);
    - ``churn_served_p99_ms_delta``: p99 of per-request on_io latency
      during churn MINUS the paired no-churn control p99.

    Both registered smaller-better in the drift guard."""
    import threading

    from cilium_tpu.proxylib import (
        NetworkPolicy, PortNetworkPolicy, PortNetworkPolicyRule,
        FilterResult,
    )
    from cilium_tpu.proxylib import instance as inst_mod
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    def mk_policy(gen: int) -> NetworkPolicy:
        # Alternating table generations: same shape bucket on even/odd
        # flips (the executable-cache case), a distinct rule count
        # every 4th (the recompile case).
        rules = [{"cmd": "READ", "file": f"/public/g{gen % 2}/.*"},
                 {"cmd": "HALT"}]
        if gen % 4 == 0:
            rules.append({"cmd": "RESET"})
        return NetworkPolicy(
            name="bench-churn",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=80,
                    rules=[
                        PortNetworkPolicyRule(
                            remote_policies=[1],
                            l7_proto="r2d2",
                            l7_rules=rules,
                        )
                    ],
                )
            ],
        )

    UPDATES_PER_S = 10.0
    PHASE_S = 10.0
    inst_mod.reset_module_registry()
    cfg = DaemonConfig(batch_timeout_ms=0.0, batch_flows=512)
    svc = VerdictService("/tmp/cilium_tpu_bench_churn.sock", cfg).start()
    client = SidecarClient(svc.socket_path, timeout=60.0)
    msgs = [b"READ /public/g0/a.txt\r\n", b"READ /public/g1/a.txt\r\n",
            b"HALT\r\n", b"READ /secret\r\n"]
    n_conns = 32
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [mk_policy(0)]) == int(
            FilterResult.OK
        )
        shims = []
        for cid in range(1, n_conns + 1):
            res, shim = client.new_connection(
                mod, "r2d2", cid, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
                "bench-churn",
            )
            assert res == int(FilterResult.OK)
            shims.append(shim)

        # Warm every table-shape bucket the churn will cycle through:
        # steady-state churn is the measurement (same-bucket rebuilds
        # hit the executable cache); the one-time cold compile per NEW
        # shape is reported alongside, not smeared into the p99.
        cold_ms = []
        for gen in range(1, 5):
            t0 = time.perf_counter()
            assert client.policy_update(mod, [mk_policy(gen)]) == int(
                FilterResult.OK
            )
            cold_ms.append((time.perf_counter() - t0) * 1e3)
        cold_swap_ms = max(cold_ms)

        def traffic_phase(duration: float, stop_evt) -> list[float]:
            lat: list[float] = []
            end = time.perf_counter() + duration
            i = 0
            while time.perf_counter() < end and not stop_evt.is_set():
                shim = shims[i % n_conns]
                t0 = time.perf_counter()
                res, _ = shim.on_io(False, msgs[i % len(msgs)])
                lat.append(time.perf_counter() - t0)
                assert res == int(FilterResult.OK), res
                i += 1
            return lat

        # Phase 1: no-churn control.
        never = threading.Event()
        ctrl = traffic_phase(PHASE_S, never)

        # Phase 2: same loop under continuous updates.
        stop = threading.Event()
        swap_rtts: list[float] = []
        churn_fail = []

        def churner():
            gen = 5
            while not stop.is_set():
                t0 = time.perf_counter()
                st = client.policy_update(mod, [mk_policy(gen)])
                swap_rtts.append(time.perf_counter() - t0)
                if st != int(FilterResult.OK):
                    churn_fail.append(st)
                    return
                gen += 1
                sleep = 1.0 / UPDATES_PER_S - (time.perf_counter() - t0)
                if sleep > 0:
                    time.sleep(sleep)

        ct = threading.Thread(target=churner, daemon=True)
        ct.start()
        churned = traffic_phase(PHASE_S, stop)
        stop.set()
        ct.join(timeout=30)
        assert not churn_fail, f"policy update failed: {churn_fail}"
        pol = svc.status()["policy"]
        assert pol["swaps"] >= PHASE_S * UPDATES_PER_S * 0.25, pol
        assert pol["swap_failures"] == {}, pol

        def p99(xs):
            return float(np.percentile(np.asarray(xs), 99)) * 1e3

        # Swap stall: the flip hold is recorded per swap by the
        # service; its histogram p99 (registry) over THIS run.
        from cilium_tpu.utils import metrics as m

        swap_p99_ms = (m.PolicySwapSeconds.quantile(0.99) or 0.0) * 1e3
        return {
            "swap_p99_ms": swap_p99_ms,
            "served_delta_ms": p99(churned) - p99(ctrl),
            "served_p99_ms": p99(churned),
            "control_p99_ms": p99(ctrl),
            "update_rtt_p99_ms": p99(swap_rtts),
            "cold_swap_ms": cold_swap_ms,
            "swaps": pol["swaps"],
            "last_swap_ms": pol["last_swap_ms"],
            "requests": len(ctrl) + len(churned),
        }
    finally:
        client.close()
        svc.stop()
        inst_mod.reset_module_registry()


# --- hitless sidecar restart ---------------------------------------------

def bench_restart_blackout():
    """Hitless restart (ISSUE 16): repeated graceful service restarts
    under live traffic with the shim survival window armed.  Two
    threads hammer on_io through every restart cycle — one over
    GRANTED conns (invariant-allow remote: shim-local grants must keep
    serving straight through the blackout), one over NON-granted conns
    (every blackout op must come back typed RESTARTING, and the gap to
    the first post-replay OK is the blackout sample).  Emits:

    - ``restart_blackout_p99_ms`` (smaller better): p99 over cycles of
      the non-granted path's outage — last pre-restart OK to first
      post-replay OK;
    - ``restart_granted_served_frac`` (bigger better): fraction of
      granted-conn ops during blackouts answered OK from the shim
      grant table.

    Asserted in-bench: zero silent loss (every op returns a typed
    result; submitted==answered on the final service), zero double
    replies (client tripwire), zero misroutes, and survival hits
    strictly increasing during each blackout."""
    import threading

    from cilium_tpu.proxylib import (
        NetworkPolicy, PortNetworkPolicy, PortNetworkPolicyRule,
        FilterResult,
    )
    from cilium_tpu.proxylib import instance as inst_mod
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    def mk_policy():
        return NetworkPolicy(
            name="bench-restart",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=80,
                    rules=[
                        PortNetworkPolicyRule(
                            remote_policies=[1], l7_proto="r2d2",
                            l7_rules=[{}],
                        ),
                        PortNetworkPolicyRule(
                            remote_policies=[2], l7_proto="r2d2",
                            l7_rules=[
                                {"cmd": "READ", "file": "/public/.*"},
                                {"cmd": "HALT"},
                            ],
                        ),
                    ],
                )
            ],
        )

    CYCLES = 6
    path = "/tmp/cilium_tpu_bench_restart.sock"
    inst_mod.reset_module_registry()

    def mk_cfg():
        return DaemonConfig(
            batch_timeout_ms=0.0, batch_flows=256, flow_cache=True,
        )

    svc = VerdictService(path, mk_cfg()).start()
    client = SidecarClient(
        path, timeout=60.0, identity="bench-restart",
        flow_cache=True, auto_reconnect=True,
        restart_grace_s=30.0, restart_queue_frames=256,
    )
    ok = int(FilterResult.OK)
    # Every result a restart cycle may legitimately type a frame with:
    # served, queued-then-shed (survival window), the fencing
    # predecessor's shed, or a write failure racing the window-open.
    # Anything else (a policy flip, UNKNOWN_CONNECTION from a replay
    # race, silent loss) fails the bench.
    typed_ok = {
        ok, int(FilterResult.RESTARTING), int(FilterResult.SHED),
        int(FilterResult.SERVICE_UNAVAILABLE),
    }
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [mk_policy()]) == ok
        granted, plain = [], []
        for cid in range(1, 9):
            res, shim = client.new_connection(
                mod, "r2d2", cid, True, 1, 2, "1.1.1.1:1",
                "2.2.2.2:80", "bench-restart",
            )
            assert res == ok
            granted.append(shim)
        for cid in range(9, 17):
            res, shim = client.new_connection(
                mod, "r2d2", cid, True, 2, 2, "1.1.1.1:1",
                "2.2.2.2:80", "bench-restart",
            )
            assert res == ok
            plain.append(shim)
        # Warm both paths (and let the grant frames land).
        for shim in granted + plain:
            res, _ = shim.on_io(False, b"READ /public/warm\r\n")
            assert res == ok, res
        time.sleep(0.3)  # let the grant push land shim-side

        stop = threading.Event()
        granted_blackout_ok = [0]
        granted_blackout_total = [0]
        plain_results: list[tuple[float, int]] = []
        errs: list = []

        def granted_loop():
            i = 0
            try:
                while not stop.is_set():
                    shim = granted[i % len(granted)]
                    res, _ = shim.on_io(
                        False, b"READ /public/warm\r\n"
                    )
                    if not client._alive:
                        granted_blackout_total[0] += 1
                        if res == ok:
                            granted_blackout_ok[0] += 1
                    assert res in typed_ok, res
                    i += 1
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        def plain_loop():
            i = 0
            try:
                while not stop.is_set():
                    shim = plain[i % len(plain)]
                    t0 = time.perf_counter()
                    res, _ = shim.on_io(False, b"HALT\r\n")
                    plain_results.append((t0, res))
                    assert res in typed_ok, res
                    i += 1
                    time.sleep(0.0005)
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=granted_loop, daemon=True),
                   threading.Thread(target=plain_loop, daemon=True)]
        for t in threads:
            t.start()

        hits_deltas: list[int] = []
        for cycle in range(CYCLES):
            time.sleep(0.4)
            hits_before = client.survival_hits
            graceful = cycle % 2 == 1  # last cycle graceful: the
            # emitted generation/restore counters describe a handoff
            # successor, not a cold crash boot
            if graceful:
                # Envoy-hot-restart shape: successor pulls the handoff
                # (fencing the predecessor) BEFORE the old process
                # exits — the client fails over in one redial and the
                # blackout is the replay alone.
                successor = VerdictService(path, mk_cfg()).start()
                svc.stop()
            else:
                # Crash shape: the process is just GONE and nobody
                # listens for a while — the survival window is what
                # keeps granted flows serving through the gap.
                svc.stop()
                time.sleep(0.25)
                successor = VerdictService(path, mk_cfg()).start()
            svc = successor
            deadline = time.monotonic() + 30.0
            while not client._alive and time.monotonic() < deadline:
                time.sleep(0.005)
            assert client._alive, f"cycle {cycle}: replay never landed"
            time.sleep(0.3)
            if not graceful:
                hits_deltas.append(client.survival_hits - hits_before)
        stop.set()
        for t in threads:
            t.join(10)
        assert not errs, errs

        # Blackout windows from the plain-conn timeline: contiguous
        # non-OK stretches bounded by OKs on both sides.
        spans, start = [], None
        last_ok = None
        for t0, res in plain_results:
            if res == ok:
                if start is not None:
                    spans.append((t0 - start) * 1e3)
                    start = None
                last_ok = t0
            elif start is None:
                start = last_ok if last_ok is not None else t0
        assert len(spans) >= CYCLES // 2, (
            f"expected >={CYCLES // 2} blackout spans, got {len(spans)}"
        )
        # Hitless-restart proof: grants served through every cold gap.
        assert all(d > 0 for d in hits_deltas), hits_deltas
        assert client.double_replies == 0, client.double_replies
        assert client.misrouted_verdicts == 0
        # Zero silent loss: the final service's exactly-once surface
        # balances after quiesce.
        time.sleep(0.3)
        rows = svc.status()["sessions"]["live"]
        for row in rows:
            assert row["submitted"] == row["answered"], row
        frac = (granted_blackout_ok[0]
                / max(granted_blackout_total[0], 1))
        st = svc.status()["restart"]
        return {
            "blackout_p99_ms": float(
                np.percentile(np.asarray(spans), 99)
            ),
            "granted_served_frac": frac,
            "granted_blackout_ops": granted_blackout_total[0],
            "survival_hits": client.survival_hits,
            "cycles": CYCLES,
            "generation": st["generation"],
            "session_restores": st["session_restores"],
            "warm_shapes": st["warm_shapes"],
        }
    finally:
        stop_evt = locals().get("stop")
        if stop_evt is not None:
            stop_evt.set()
        client.close()
        svc.stop()
        inst_mod.reset_module_registry()


# --- multi-chip sharded serving ------------------------------------------

def _mesh_bench_policy():
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
        find_instance,
        open_module,
        reset_module_registry,
    )

    reset_module_registry()
    mod = open_module([], True)
    ins = find_instance(mod)
    ins.policy_update([
        NetworkPolicy(
            name="mesh-bench",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=80,
                    rules=[
                        PortNetworkPolicyRule(
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
    ])
    return ins.policy_map()["mesh-bench"]


def _mesh_bench_batch(f: int, width: int = 64):
    rng = random.Random(11)
    msgs = [
        b"READ /public/a.txt\r\n", b"HALT\r\n",
        b"READ /private/b\r\n", b"WRITE /x\r\n",
    ]
    data = np.zeros((f, width), np.uint8)
    lengths = np.zeros((f,), np.int32)
    for i in range(f):
        m = msgs[rng.randrange(len(msgs))]
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)
    return data, lengths, np.ones((f,), np.int32)


def bench_multichip_scaling():
    """Per-chip scaling curve: verdicts/s of the SHARDED step at 1, 2
    and 4 devices (flow-axis data parallel, the serving layout), with
    parity against the single-device model asserted before any number
    is reported.  Weak scaling: the per-device batch is constant, so
    ideal is rate(1) x N.  On a real chip mesh the linearity floor
    (>=0.7x ideal at 4) is ASSERTED; the CPU smoke (4 virtual devices
    sharing the same host cores — no real parallelism to win) emits
    the curve unasserted."""
    import jax

    from cilium_tpu.models.r2d2 import build_r2d2_model, r2d2_verdicts
    from cilium_tpu.parallel import flow_mesh
    from cilium_tpu.parallel.rulesharding import (
        build_sharded_r2d2_model,
        sharded_verdict_step,
    )

    devices = jax.devices()
    on_chip = devices[0].platform != "cpu"
    counts = [n for n in (1, 2, 4) if n <= len(devices)]
    policy = _mesh_bench_policy()
    ref = build_r2d2_model(policy, True, 80)
    per_dev = 16384  # constant per-device batch (weak scaling)
    curve: dict[int, float] = {}
    for nd in counts:
        mesh = flow_mesh(n_flow=nd, n_rule=1, devices=devices[:nd])
        stacked = build_sharded_r2d2_model(policy, True, 80, 1)
        step = sharded_verdict_step(mesh, r2d2_verdicts)
        f = per_dev * nd
        data, lengths, remotes = _mesh_bench_batch(f)
        # Bit-identity before any number is reported.
        _, _, got = step(stacked, data, lengths, remotes)
        _, _, want = r2d2_verdicts(ref, data, lengths, remotes)
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            f"sharded verdicts diverge at {nd} device(s)"
        )
        rate = _pipelined_rate(
            step, (stacked, data, lengths, remotes), f
        )
        curve[nd] = rate
        print(f"bench multichip: {nd} device(s) -> {rate:,.0f}/s",
              file=sys.stderr)
    n_max = counts[-1]
    ideal = curve[1] * n_max
    linearity = curve[n_max] / ideal if ideal else 0.0
    if on_chip and n_max >= 4:
        # The armed acceptance floor: >=0.7x ideal at 4 chips.
        assert linearity >= 0.7, (
            f"multichip scaling {linearity:.2f}x ideal at {n_max} "
            f"devices (floor 0.7) — curve {curve}"
        )
    return {
        "curve": curve,
        "linearity": linearity,
        "n_max": n_max,
        "on_chip": on_chip,
        "platform": devices[0].platform,
    }


def bench_rules_100k():
    """Capacity stress: a 100k-rule HTTP table (the 'millions of
    users' policy surface — literal method/path + remote-set tiers,
    whose per-rule compare tensors and hit-matrix width are what
    scale with R; the NFA tier's states-quadratic HBM story is the
    sharding math itself, see parallel/rulesharding.py) served
    rule-sharded across 4 shards vs the unsharded single-device
    table.  Reports per-batch latency p99 and rate for both; on a
    real chip mesh the p99 budget is ASSERTED for the sharded path
    (the unsharded table missing it, or failing to build, is the
    capacity asymmetry the config exists to show)."""
    import jax

    from cilium_tpu.models.http import build_http_model, http_verdicts
    from cilium_tpu.parallel import flow_mesh
    from cilium_tpu.parallel.rulesharding import (
        build_sharded_http_model,
        sharded_verdict_step,
    )
    from cilium_tpu.policy.api import PortRuleHTTP

    devices = jax.devices()
    on_chip = devices[0].platform != "cpu"
    n_rule = 4 if len(devices) >= 4 else len(devices)
    R = 100_000
    rng = random.Random(13)
    verbs = ("GET", "POST", "PUT", "DELETE")
    rows = [
        (
            frozenset(rng.sample(range(1, 50_000), rng.randrange(1, 4))),
            PortRuleHTTP(method=verbs[j % 4], path=f"/p{j:06d}"),
        )
        for j in range(R - 1)
    ]
    rows.append((frozenset(), PortRuleHTTP(method="HEAD")))
    f = 2048 if on_chip else 128
    width = 64
    data = np.zeros((f, width), np.uint8)
    lengths = np.zeros((f,), np.int32)
    remotes = np.ones((f,), np.int32)
    probe_allow = b"HEAD /anything HTTP/1.1\r\n\r\n"  # last row
    probe_deny = b"PATCH /nope HTTP/1.1\r\n\r\n"
    for i in range(f):
        m = probe_allow if i % 2 else probe_deny
        data[i, : len(m)] = np.frombuffer(m, np.uint8)
        lengths[i] = len(m)

    def timed_latencies(fn, args, n=8):
        lat = []
        _fence(fn(*args))  # warm/compile
        for _ in range(n):
            t0 = time.perf_counter()
            _fence(fn(*args))
            lat.append(time.perf_counter() - t0)
        lat.sort()
        p99 = lat[min(int(0.99 * len(lat)), len(lat) - 1)]
        return p99, f * n / sum(lat)

    mesh = flow_mesh(
        n_flow=max(len(devices) // n_rule, 1), n_rule=n_rule,
        devices=devices,
    )
    stacked = build_sharded_http_model(rows, n_rule)
    step = sharded_verdict_step(mesh, http_verdicts)
    sharded_p99, sharded_rate = timed_latencies(
        step, (stacked, data, lengths, remotes)
    )
    unsharded = {"p99_ms": None, "rate": None, "error": None}
    want = None
    try:
        ref = build_http_model(rows)
        fn = jax.jit(type(ref).__call__)
        u_p99, u_rate = timed_latencies(
            fn, (ref, data, lengths, remotes)
        )
        unsharded = {
            "p99_ms": round(u_p99 * 1e3, 2),
            "rate": round(u_rate), "error": None,
        }
        want = np.asarray(fn(ref, data, lengths, remotes)[2])
    except Exception as e:  # noqa: BLE001 — OOM IS the expected result
        unsharded["error"] = f"{type(e).__name__}"
        print(f"bench rules_100k: unsharded table failed ({e!r}) — "
              f"the capacity asymmetry the config exists to show",
              file=sys.stderr)
    got = np.asarray(step(stacked, data, lengths, remotes)[2])
    if want is not None:
        assert np.array_equal(got, want), "100k-rule sharded diverge"
    # Semantic spot check independent of the unsharded build.
    assert bool(got[1]) and not bool(got[0])
    budget_ms = 1.0
    if on_chip and n_rule >= 4:
        assert sharded_p99 * 1e3 <= budget_ms, (
            f"100k-rule sharded p99 {sharded_p99 * 1e3:.2f}ms over "
            f"the {budget_ms}ms budget"
        )
    print(
        f"bench rules_100k: sharded({n_rule}) p99="
        f"{sharded_p99 * 1e3:.2f}ms rate={sharded_rate:,.0f}/s "
        f"unsharded={unsharded}", file=sys.stderr,
    )
    return {
        "rules": R,
        "rule_shards": n_rule,
        "sharded_p99_ms": sharded_p99 * 1e3,
        "sharded_rate": sharded_rate,
        "unsharded": unsharded,
        "budget_ms": budget_ms,
        "on_chip": on_chip,
    }


def bench_mesh_degraded():
    """Partial mesh degradation (ISSUE 17): a live mesh-on service
    loses one named device mid-run under concurrent traffic.  The
    width ladder must demote typed, reshape off-path onto the
    survivor mesh, publish the degraded capacity fraction into
    admission, keep serving bit-correct verdicts the whole way, and
    re-promote to full width when the device heals.  Emits:

    - ``mesh_reshape_window_ms`` (smaller better): attributed fault
      to reshaped-rung flip, as published by the service;
    - ``mesh_degraded_capacity_frac`` (bigger better): the serving
      fraction the reshaped rung retains of full width.

    Asserted in-bench: zero silent loss (every op returns a typed
    result; submitted==answered per session after quiesce), zero
    double replies, the degraded admission cap strictly below the
    full-width cap, and the shed rate while degraded bounded by the
    capacity actually lost."""
    import threading

    from cilium_tpu.parallel.rulesharding import ShardedVerdictModel
    from cilium_tpu.proxylib import (
        NetworkPolicy, PortNetworkPolicy, PortNetworkPolicyRule,
        FilterResult,
    )
    from cilium_tpu.proxylib import instance as inst_mod
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils.option import DaemonConfig

    path = "/tmp/cilium_tpu_bench_mesh_degraded.sock"
    inst_mod.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=0.0, batch_flows=256, mesh="on", mesh_rule_shards=2,
        mesh_reprobe_interval_s=0.05,
        device_reprobe_interval_s=1e9,
    )
    svc = VerdictService(path, cfg).start()
    client = SidecarClient(path, timeout=120.0, identity="bench-mesh")
    ok = int(FilterResult.OK)
    # Reshape windows may legitimately shed (the admission cap is the
    # capacity story); anything else typed is a bench failure.
    typed_ok = {ok, int(FilterResult.SHED)}

    def await_rung(rung, timeout=60.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = svc.status()["mesh"]
            if st["rung"] == rung:
                return st
            time.sleep(0.01)
        raise AssertionError(
            f"rung {rung!r} never reached: {svc.status()['mesh']}"
        )

    try:
        mod = client.open_module([])
        res = client.policy_update(mod, [NetworkPolicy(
            name="bench-mesh",
            policy=2,
            ingress_per_port_policies=[
                PortNetworkPolicy(
                    port=80,
                    rules=[
                        PortNetworkPolicyRule(
                            remote_policies=[2], l7_proto="r2d2",
                            l7_rules=[
                                {"cmd": "READ", "file": "/public/.*"},
                                {"cmd": "HALT"},
                            ],
                        ),
                    ],
                )
            ],
        )])
        assert res == ok
        shims = []
        for cid in range(1, 9):
            res, shim = client.new_connection(
                mod, "r2d2", cid, True, 2, 2, f"1.1.1.{cid}:{cid}",
                "2.2.2.2:80", "bench-mesh",
            )
            assert res == ok
            shims.append(shim)
        # Warm every conn (first op resolves the mesh + builds the
        # sharded engine) and pin the full-width surface.
        for shim in shims:
            res, _ = shim.on_io(False, b"READ /public/warm\r\n")
            assert res == ok, res
        st_full = svc.status()["mesh"]
        assert st_full["rung"] == "full", st_full
        full_devices = st_full["serving_devices"]
        full_cap = svc.dispatcher.max_pending
        assert full_devices >= 4, (
            f"mesh_degraded needs a >=4-device full mesh, got "
            f"{full_devices}"
        )

        stop = threading.Event()
        results: list[tuple[float, int]] = []
        lock = threading.Lock()
        errs: list = []
        frames = (b"READ /public/warm\r\n", b"HALT\r\n")

        def loop(base):
            i = 0
            try:
                while not stop.is_set():
                    shim = shims[(base + i) % len(shims)]
                    t0 = time.perf_counter()
                    res, _ = shim.on_io(False, frames[i % 2])
                    with lock:
                        results.append((t0, res))
                    assert res in typed_ok, res
                    i += 1
                    time.sleep(0.0005)
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        threads = [threading.Thread(target=loop, args=(b,), daemon=True)
                   for b in (0, 4)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # steady full-width traffic

        # Mid-run device loss: the NEXT sharded dispatch raises a
        # PJRT-shaped error NAMING the device (the ladder's attribution
        # source), and the probe seam marks it dead.  Self-disarming —
        # the reshaped wrappers must serve cleanly after the fault.
        lost_dev = full_devices - 1
        orig = svc.__class__._jit_for.__get__(svc)

        def arm_loss():
            def lost_device(cache, model, trace_fn, arg_fn=None):
                if isinstance(model, ShardedVerdictModel):
                    def boom(*_a, **_k):
                        svc._jit_for = orig
                        raise RuntimeError(
                            f"PJRT_Error: transfer to device "
                            f"{lost_dev} failed"
                        )

                    return boom
                return orig(cache, model, trace_fn, arg_fn)

            svc._jit_for = lost_device
            svc._device_probe_fn = lambda dev: dev.id != lost_dev

        # Best-of-N (the bench's standard de-noising): full
        # fault->reshape->heal cycles; the smallest window is the
        # honest reading — a host stall or a cold-cache compile
        # landing inside one cycle only INFLATES its window.  Cycle 0
        # is compile-shadowed by construction (first executables at
        # the survivor width); the warm cycles are the steady-state
        # flip the metric tracks, so the cold one rides along in
        # windows_ms as evidence but never wins the min.
        CYCLES = 4
        windows: list[float] = []
        deg_spans: list[tuple[float, float]] = []
        st_deg = None
        deg_cap = full_cap
        for _cycle in range(CYCLES):
            arm_loss()
            st_deg = await_rung("reshaped")
            t_reshaped = time.perf_counter()
            windows.append(st_deg["reshape_window_ms"])
            assert st_deg["lost_devices"] == [lost_dev], st_deg
            assert 0.0 < st_deg["capacity_frac"] < 1.0, st_deg
            assert st_deg["serving_devices"] < full_devices, st_deg
            deg_cap = svc.dispatcher.max_pending
            assert 1 <= deg_cap < full_cap, (deg_cap, full_cap)

            # Degraded-rung serving window: cycle 0 long enough to
            # amortize the first post-flip dispatch (a fresh
            # executable on the survivor mesh) so the shed-vs-capacity
            # bound is measured over real steady-state traffic, not
            # one compile-shadowed op.
            time.sleep(2.0 if _cycle == 0 else 1.0)
            deg_spans.append((t_reshaped, time.perf_counter()))
            svc._device_probe_fn = lambda dev: True
            st_back = await_rung("full")
            assert st_back["repromotions"] == _cycle + 1, st_back
            assert svc.dispatcher.max_pending == full_cap

        time.sleep(0.2)
        stop.set()
        for t in threads:
            t.join(10)
        assert not errs, errs

        # Zero silent loss: every op above returned typed; the
        # exactly-once surface balances after quiesce.
        time.sleep(0.3)
        rows = svc.status()["sessions"]["live"]
        for row in rows:
            assert row["submitted"] == row["answered"], row
        assert client.double_replies == 0, client.double_replies
        assert client.misrouted_verdicts == 0

        # Shed rate vs capacity: while degraded, the shed fraction
        # must not exceed the capacity actually lost (plus slack for
        # the flip windows at both edges).
        deg_ops = [(t0, r) for t0, r in results
                   if any(a <= t0 < b for a, b in deg_spans)]
        n_shed = sum(1 for _, r in deg_ops if r != ok)
        shed_frac = n_shed / max(len(deg_ops), 1)
        lost_frac = 1.0 - st_deg["capacity_frac"]
        assert shed_frac <= lost_frac + 0.05, (
            f"degraded shed {shed_frac:.3f} over lost-capacity bound "
            f"{lost_frac:.3f}+0.05 ({n_shed}/{len(deg_ops)} ops)"
        )

        st = svc.status()["mesh"]
        assert st["reshapes"] == CYCLES and st["repromotions"] == CYCLES
        return {
            "reshape_window_ms": min(windows),
            "reshape_windows_ms": [round(w, 1) for w in windows],
            "capacity_frac": st_deg["capacity_frac"],
            "full_devices": full_devices,
            "degraded_devices": st_deg["serving_devices"],
            "lost_device": lost_dev,
            "reshapes": st["reshapes"],
            "repromotions": st["repromotions"],
            "admission_cap_full": full_cap,
            "admission_cap_degraded": deg_cap,
            "ops_total": len(results),
            "ops_degraded": len(deg_ops),
            "shed_frac_degraded": shed_frac,
        }
    finally:
        stop_evt = locals().get("stop")
        if stop_evt is not None:
            stop_evt.set()
        client.close()
        svc.stop()
        inst_mod.reset_module_registry()


def run_one(which: str) -> None:
    if which in ("multichip_scaling", "rules_100k", "mesh_degraded") \
            and os.environ.get(
        "CILIUM_TPU_MULTICHIP"
    ) != "chip":
        # CPU smoke: the mesh configs need >1 device.  Request 4
        # virtual CPU devices BEFORE the backend initializes; a real
        # chip-mesh run sets CILIUM_TPU_MULTICHIP=chip to skip this
        # and use the actual accelerators (where the linearity/budget
        # assertions arm).  An operator-set device count wins.
        if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""
        ):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            )
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    print(f"bench[{which}]: device={jax.devices()}", file=sys.stderr)
    if which == "http":
        rate, cpu = bench_http()
        _emit("http_l7_verdicts_per_sec_per_chip", rate, "verdicts/s",
              rate / 1_000_000, cpu_oracle_per_sec=round(cpu))
    elif which == "kafka":
        rate, cpu = bench_kafka()
        _emit("kafka_l7_verdicts_per_sec_per_chip", rate, "verdicts/s",
              rate / 1_000_000, cpu_oracle_per_sec=round(cpu),
              method="compute-bound: 256 serially-dependent model "
                     "applications per jit call + marginal-rate fence "
                     "cancellation (BENCH_NOTES.md round 5)")
    elif which == "cassandra":
        rate, cpu = bench_cassandra()
        _emit("cassandra_l7_verdicts_per_sec_per_chip", rate, "verdicts/s",
              rate / 1_000_000, cpu_oracle_per_sec=round(cpu))
    elif which == "memcached":
        rate, cpu = bench_memcached()
        _emit("memcached_l7_verdicts_per_sec_per_chip", rate, "verdicts/s",
              rate / 1_000_000, cpu_oracle_per_sec=round(cpu))
    elif which == "kvstore_failover":
        median, outages, steady, n_acked = bench_kvstore_failover()
        # Smaller is better; vs_baseline floors at 0.1s so a lucky
        # sub-100ms failover cannot score as infinitely good.
        _emit(
            "kvstore_failover_write_outage_s", median, "s",
            1.0 / max(median, 0.1),
            outages_s=[round(o, 3) for o in outages],
            steady_writes_per_sec=round(steady),
            acked_writes=n_acked, lost_writes=0,
        )
    elif which == "latency":
        lat = bench_latency()
        # The 1M/s point is the north-star latency config; vs_baseline
        # is the 1ms budget over the measured p99 (>1 = within budget).
        # The device round trip is reported alongside, so each figure
        # can be read against it.
        r1m = next(r for r in lat["rates"] if r.offered_rate == 1_000_000)
        r100k = next(r for r in lat["rates"] if r.offered_rate == 100_000)
        rtt = max(lat["device_rtt_ms"], 1e-9)
        # The 1M/s point can saturate the host->device uplink and then
        # measures queue depth, not the architecture; the 100k point and the uplink
        # figure are emitted alongside so the number can be read against
        # the transport it was taken on.
        _emit(
            "sidecar_added_latency_p99_ms_at_1M",
            r1m.added_p99_ms,
            "ms",
            1.0 / max(r1m.added_p99_ms, 1e-9),
            p50_ms=round(r1m.p50_ms, 3),
            achieved_rate=round(r1m.achieved_rate),
            device_rtt_ms=round(lat["device_rtt_ms"], 2),
            uplink_mbps=round(lat["uplink_mbps"], 1),
            rtt_multiples_p99=round(r1m.p99_ms / rtt, 2),
            p99_ms_at_100k=round(r100k.p99_ms, 2),
            rtt_multiples_p99_at_100k=round(r100k.p99_ms / rtt, 2),
        )
    elif which == "latency_colocated":
        # Device term removed (CPU-backed verdict models): measures the
        # seam architecture itself — the co-located sub-ms proof.
        # os_noise is the host's own scheduler-stall floor (measured in
        # a tight loop with nothing running): on the shared 1-core
        # bench VMs, external 1-17ms stalls occupy ~1-2% of wall time,
        # which bounds any honest p99 from below — p90/p95 and the
        # release-lateness split are emitted so the seam's own
        # contribution is auditable.
        # The control experiment (VERDICT r4 weak #1), PAIRED: each
        # seam run executes adjacent in time to a null-seam run (same
        # socket framing, same generator, verdict replaced by an
        # immediate constant), and the architecture-attributable added
        # p99 is the MEDIAN OF PER-PAIR (seam − null) DELTAS — pairing
        # cancels the host's drifting stall rate the way the null
        # server cancels the constant floor (unpaired blocks measured
        # 0.77ms and 1.02ms an hour apart on identical code).
        from cilium_tpu.sidecar import latbench

        out = latbench.run_paired_colocated(
            "/tmp/cilium_tpu_bench_lat_colo.sock"
        )
        r100k, n100k = out["seam_100k"], out["null_100k"]
        r1m, n1m = out["seam_1m"], out["null_1m"]
        print(
            f"bench latency (colocated, paired): seam p99 "
            f"{r100k.p99_ms:.2f}ms null p99 {n100k.p99_ms:.2f}ms "
            f"delta(median of pairs) {out['delta_p99_ms']:.3f}ms",
            file=sys.stderr,
        )
        _emit(
            "sidecar_seam_added_p99_ms_colocated",
            r100k.added_p99_ms,
            "ms",
            1.0 / max(r100k.added_p99_ms, 1e-9),
            p50_ms=round(r100k.p50_ms, 3),
            p90_ms=round(r100k.p90_ms, 3),
            p99_ms=round(r100k.p99_ms, 3),
            achieved_rate=round(r100k.achieved_rate),
            release_late_p50_ms=round(r100k.release_late_p50_ms, 3),
            release_late_p99_ms=round(r100k.release_late_p99_ms, 3),
            p99_runs_100k=out["seam_p99_runs"],
            os_noise=out["os_noise"],
            seam_stages_us=out.get("seam_stages_us", {}),
            null_seam_p50_ms=round(n100k.p50_ms, 3),
            null_seam_p99_ms=round(n100k.p99_ms, 3),
            null_p99_runs=out["null_p99_runs"],
        )
        # The number the <1ms north star is judged against.  The score
        # denominator floors at 0.25ms — a stall-struck window where
        # the pair-median lands at/below zero must not score as
        # infinitely good.
        _emit(
            "sidecar_seam_p99_minus_null_ms_colocated",
            max(out["delta_p99_ms"], 0.0),
            "ms",
            1.0 / max(out["delta_p99_ms"], 0.25),
            pair_deltas_ms=out["pair_deltas_ms"],
            seam_p99_ms=round(r100k.p99_ms, 3),
            null_p99_ms=round(n100k.p99_ms, 3),
            seam_p50_ms=round(r100k.p50_ms, 3),
            null_p50_ms=round(n100k.p50_ms, 3),
        )
        # The 1M/s colocated point (VERDICT r4 missing #2: measured but
        # never recorded before this round), paired with its own
        # adjacent null run.
        _emit(
            "sidecar_seam_added_p99_ms_colocated_at_1M",
            r1m.added_p99_ms,
            "ms",
            1.0 / max(r1m.added_p99_ms, 1e-9),
            p50_ms=round(r1m.p50_ms, 3),
            p99_ms=round(r1m.p99_ms, 3),
            achieved_rate=round(r1m.achieved_rate),
            gen_saturated=r1m.gen_saturated,
            null_seam_p99_ms=round(n1m.p99_ms, 3),
            null_gen_saturated=n1m.gen_saturated,
            seam_minus_null_p99_ms=round(
                max(r1m.p99_ms - n1m.p99_ms, 0.0), 3),
        )
    elif which == "shm_transport":
        # The zero-copy shared-memory seam (ISSUE 8): identical paired
        # methodology to latency_colocated — same generator, same
        # socket-null control run adjacent in time — but the seam
        # client rides the shm transport (data batches in a lock-free
        # ring, verdicts written back in the verdict ring, batched
        # doorbell/credit frames on the socket).  Because the null
        # control is the same socket floor in both configs, the
        # difference between this config's delta and
        # sidecar_seam_p99_minus_null_ms_colocated IS the socket
        # byte-copy seam the rings eliminate.
        from cilium_tpu.sidecar import latbench

        out = latbench.run_paired_colocated(
            "/tmp/cilium_tpu_bench_lat_shm.sock", transport="shm"
        )
        r100k, n100k = out["seam_100k"], out["null_100k"]
        r1m, n1m = out["seam_1m"], out["null_1m"]
        tstat = out.get("seam_transport", {})
        sess = tstat.get("session", {})
        print(
            f"bench shm_transport (paired): seam p99 "
            f"{r100k.p99_ms:.2f}ms null p99 {n100k.p99_ms:.2f}ms "
            f"delta(median of pairs) {out['delta_p99_ms']:.3f}ms "
            f"mode={tstat.get('mode')} "
            f"fallbacks={tstat.get('fallbacks')}",
            file=sys.stderr,
        )
        # Same scoring shape as the socket-seam metric (floor 0.25ms);
        # the acceptance target is "measurably below the ~0.8ms socket
        # baseline".  transport_mode/fallbacks ride along so a run that
        # silently demoted to the socket is readable as such.
        _emit(
            "sidecar_seam_p99_minus_null_ms_shm",
            max(out["delta_p99_ms"], 0.0),
            "ms",
            1.0 / max(out["delta_p99_ms"], 0.25),
            pair_deltas_ms=out["pair_deltas_ms"],
            seam_p99_ms=round(r100k.p99_ms, 3),
            null_p99_ms=round(n100k.p99_ms, 3),
            seam_p50_ms=round(r100k.p50_ms, 3),
            null_p50_ms=round(n100k.p50_ms, 3),
            p99_runs_100k=out["seam_p99_runs"],
            null_p99_runs=out["null_p99_runs"],
            os_noise=out["os_noise"],
            transport_mode=tstat.get("mode"),
            transport_fallbacks=tstat.get("fallbacks", {}),
            doorbells=sess.get("doorbells", 0),
            doorbell_batch_mean=sess.get("doorbell_batch_mean", 0.0),
            data_frames=sess.get("data_frames", 0),
            verdict_frames=sess.get("verdict_frames", 0),
        )
        # Wire-to-wire throughput over the rings: the 1M/s point's
        # achieved rate (the "close the gap to the device rate" half of
        # the acceptance criteria rides on the marginal-rate configs;
        # this records the shm seam's own sustained wire-fed rate).
        _emit(
            "shm_wire_rate_at_1M",
            r1m.achieved_rate,
            "verdicts/s",
            r1m.achieved_rate / 1_000_000,
            p99_ms=round(r1m.p99_ms, 3),
            gen_saturated=r1m.gen_saturated,
            null_p99_ms=round(n1m.p99_ms, 3),
            seam_minus_null_p99_ms=round(
                max(r1m.p99_ms - n1m.p99_ms, 0.0), 3),
        )
    elif which == "fanin_concurrent":
        out = bench_fanin_concurrent()
        print(
            f"bench fanin_concurrent: {out['n_sessions']} sessions "
            f"aggregate={out['aggregate_rate']:,.0f}/s "
            f"(single-session {out['single_rate']:,.0f}/s) "
            f"p99 worst={out['p99_worst_ms']:.2f}ms "
            f"median={out['p99_median_ms']:.2f}ms "
            f"(single {out['single_p99_ms']:.2f}ms) "
            f"shed={out['shed_entries']} silent_loss=0 misroutes=0",
            file=sys.stderr,
        )
        # Aggregate throughput under 16-way fan-in at 2x offered load
        # (bigger better, scored vs the single-session rate: >=1 means
        # fan-in costs nothing; the contract asserts are in-bench).
        _emit(
            "fanin_aggregate_verdicts_per_s", out["aggregate_rate"],
            "verdicts/s",
            out["aggregate_rate"] / max(out["single_rate"], 1.0),
            single_session_rate=round(out["single_rate"]),
            offered=round(out["offered"]),
            n_sessions=out["n_sessions"],
            served_entries=out["served_entries"],
            shed_entries=out["shed_entries"],
            session_shed=out["session_shed"],
            silent_loss=0,
            cross_session_misroutes=0,
            fair_share=out["fair_share"],
        )
        # Worst per-session served p99 under fan-in (smaller better;
        # the denominator floors at the single-session p99 so a
        # sub-baseline reading cannot score as infinitely good).
        _emit(
            "fanin_p99_ms_at_16", out["p99_worst_ms"], "ms",
            max(out["single_p99_ms"], 0.5)
            / max(out["p99_worst_ms"], 0.5),
            per_session_p99_ms=out["per_session_p99_ms"],
            p99_median_ms=round(out["p99_median_ms"], 3),
            single_session_p99_ms=round(out["single_p99_ms"], 3),
        )
    elif which == "verdict_overload":
        out = bench_verdict_overload()
        # Smaller is better (a served-verdict p99 under 2x-capacity
        # overload); the score denominator floors at the queue-age cap
        # — p99 below the cap is the contract being met, not a win to
        # chase.
        _emit(
            "verdict_overload_p99_ms_at_2x", out["p99_ms"], "ms",
            1.0 / max(out["p99_ms"], out["queue_age_cap_ms"]) * 10.0,
            p50_ms=round(out["p50_ms"], 3),
            capacity_verdicts_per_sec=round(out["capacity"]),
            offered_verdicts_per_sec=round(out["offered"]),
            shed_rate=round(out["shed_rate"], 3),
            shed_entries=out["shed_entries"],
            silent_loss=0,
            queue_age_cap_ms=out["queue_age_cap_ms"],
        )
    elif which == "verdict_trace_overhead":
        out = bench_verdict_trace_overhead()
        # Smaller is better; the score denominator floors at 0.1% so a
        # sub-noise reading cannot score as infinitely good.  The <2%
        # contract is asserted inside the bench itself.
        _emit(
            "verdict_trace_overhead_pct", out["overhead_pct"], "%",
            2.0 / max(out["overhead_pct"], 0.1),
            round_us=round(out["round_us"], 1),
            tracer_on_us=round(out["tracer_on_us"], 2),
            tracer_off_us=round(out["tracer_off_us"], 2),
            implied_rate_on=round(out["implied_rate_on"]),
            implied_rate_off=round(out["implied_rate_off"]),
            budget_pct=2.0,
        )
    elif which == "timeline_overhead":
        out = bench_timeline_overhead()
        # Smaller is better; same scoring shape as the trace-overhead
        # config.  The <2% contract is asserted inside the bench.
        _emit(
            "timeline_overhead_pct", out["overhead_pct"], "%",
            2.0 / max(out["overhead_pct"], 0.1),
            round_us=round(out["round_us"], 1),
            recorder_on_us=round(out["recorder_on_us"], 2),
            recorder_off_us=round(out["recorder_off_us"], 2),
            implied_rate_on=round(out["implied_rate_on"]),
            implied_rate_off=round(out["implied_rate_off"]),
            budget_pct=2.0,
        )
    elif which == "ledger_overhead":
        out = bench_ledger_overhead()
        # Smaller is better; same scoring shape as timeline_overhead.
        # The <2% contract is asserted inside the bench.
        _emit(
            "ledger_overhead_pct", out["overhead_pct"], "%",
            2.0 / max(out["overhead_pct"], 0.1),
            round_us=round(out["round_us"], 1),
            ledger_on_us=round(out["ledger_on_us"], 2),
            ledger_off_us=round(out["ledger_off_us"], 2),
            implied_rate_on=round(out["implied_rate_on"]),
            implied_rate_off=round(out["implied_rate_off"]),
            budget_pct=2.0,
        )
    elif which == "load_knee":
        out = bench_load_knee()
        # Higher knee fraction is better: the load level the service
        # sustains before p99 doubles off its light-load floor.
        _emit(
            "knee_throughput_frac", out["knee_throughput_frac"], "frac",
            out["knee_throughput_frac"],
            max_rate=out["max_rate"],
            base_p99_ms=out["base_p99_ms"],
            points=out["points"],
        )
        # Smaller is better: p99 AT the knee (the usable-load tail).
        _emit(
            "knee_p99_ms", out["knee_p99_ms"], "ms",
            1.0 / max(out["knee_p99_ms"], 0.25),
            knee_throughput_frac=out["knee_throughput_frac"],
        )
    elif which == "flow_observe_overhead":
        out = bench_flow_observe_overhead()
        # Smaller is better; same scoring shape as the trace-overhead
        # config.  The <2% contract is asserted inside the bench.
        _emit(
            "flow_observe_overhead_pct", out["overhead_pct"], "%",
            2.0 / max(out["overhead_pct"], 0.1),
            round_plain_us=round(out["round_plain_us"], 1),
            round_attr_us=round(out["round_attr_us"], 1),
            record_us=round(out["record_us"], 2),
            implied_rate_on=round(out["implied_rate_on"]),
            implied_rate_off=round(out["implied_rate_off"]),
            budget_pct=2.0,
        )
    elif which == "policy_churn":
        out = bench_policy_churn()
        # Smaller is better for both: the swap flip hold must stay in
        # the single-digit-ms class, and churn must cost the served
        # path ~nothing (the delta is vs the PAIRED no-churn control,
        # so host drift cancels).
        _emit(
            "churn_swap_p99_ms", out["swap_p99_ms"], "ms",
            10.0 / max(out["swap_p99_ms"], 0.1),
            swaps=out["swaps"],
            last_swap_ms=out["last_swap_ms"],
            update_rtt_p99_ms=round(out["update_rtt_p99_ms"], 2),
            cold_swap_ms=round(out["cold_swap_ms"], 1),
        )
        _emit(
            "churn_served_p99_ms_delta", out["served_delta_ms"], "ms",
            1.0 / max(out["served_delta_ms"], 0.1),
            served_p99_ms=round(out["served_p99_ms"], 3),
            control_p99_ms=round(out["control_p99_ms"], 3),
            requests=out["requests"],
            method="paired phases: identical traffic loop without, "
                   "then with, continuous policy updates at 10/s — "
                   "the delta IS the churn cost",
        )
    elif which == "mixed":
        out = bench_mixed()
        _emit(
            "mixed_path_verdicts_per_sec", out["verdicts_per_sec"],
            "verdicts/s", out["verdicts_per_sec"] / 1_000_000,
            slow_fraction=round(out["slow_fraction"], 3),
            split=out["split"],
            reasm_rounds=out["reasm_rounds"],
            reasm_frames=out["reasm_frames"],
            in_process_oracle_per_sec=round(out["oracle_per_sec"]),
            vs_in_process=round(
                out["verdicts_per_sec"] / max(out["oracle_per_sec"], 1), 2
            ),
        )
    elif which == "flow_cache":
        out = bench_flow_cache()
        _emit(
            "flow_cache_verdicts_per_s", out["verdicts_per_sec"],
            "verdicts/s", out["verdicts_per_sec"] / 1_000_000,
            control_verdicts_per_s=round(out["control_verdicts_per_sec"]),
            shim_hits=out["shim_hits"],
            service_hits=out["service_hits"],
            bytes_pushed=out["bytes_pushed"],
            control_bytes_pushed=out["control_bytes_pushed"],
            bytes_per_verdict=out["bytes_per_verdict"],
            control_bytes_per_verdict=out["control_bytes_per_verdict"],
            armed=out["armed"],
            method="paired cache-on vs cache-off runs over identical "
                   "long-lived-flow traffic; hit-rate floor + strict "
                   "byte reduction asserted in-bench",
        )
        _emit(
            "flow_cache_hit_rate", out["hit_rate"], "ratio",
            out["hit_rate"],
            floor=0.5,
        )
    elif which == "datapath":
        rate, cpu = bench_datapath()
        _emit("datapath_l34_pkts_per_sec_per_chip", rate, "pkts/s",
              rate / 1_000_000, cpu_oracle_per_sec=round(cpu))
    elif which == "stress":
        rate, dt, http_tier = bench_stress()
        _emit(
            "stress_10k_rules_1m_flows_verdicts_per_sec", rate,
            "verdicts/s", rate / 1_000_000,
            rules=STRESS_HTTP_POLICIES * STRESS_HTTP_RULES
            + STRESS_KAFKA_POLICIES * STRESS_KAFKA_RULES
            + STRESS_CASS_POLICIES * STRESS_CASS_RULES
            + STRESS_DNS_POLICIES
            * (STRESS_DNS_EXACT_RULES + STRESS_DNS_PATTERN_RULES),
            flows=STRESS_FLOWS + STRESS_DNS_FLOWS,
            replay_seconds=round(dt, 2),
            dns_policies=STRESS_DNS_POLICIES,
            http_tier_mix={
                "literal_rules_per_policy": STRESS_HTTP_RULES
                - STRESS_HTTP_REGEX_RULES - STRESS_HTTP_NFA_RULES,
                "regex_rules_per_policy": STRESS_HTTP_REGEX_RULES,
                "nfa_rules_per_policy": STRESS_HTTP_NFA_RULES,
                "automaton": http_tier,
                "nfa_automaton": "DeviceNfa",
            },
            cassandra_regex_policies=STRESS_CASS_POLICIES,
        )
    elif which == "multichip_scaling":
        out = bench_multichip_scaling()
        # Headline is the max-device rate; the per-chip curve and the
        # linearity ride along.  The >=0.7x-ideal floor is asserted
        # inside the bench on chip meshes; the CPU smoke's virtual
        # devices share cores, so its linearity is reported unarmed.
        _emit(
            "multichip_scaling_verdicts_per_sec",
            out["curve"][out["n_max"]], "verdicts/s",
            out["curve"][out["n_max"]] / 1_000_000,
            curve={str(k): round(v) for k, v in out["curve"].items()},
            linearity_at_max=round(out["linearity"], 3),
            devices=out["n_max"],
            platform=out["platform"],
            linearity_floor=0.7,
            assertion_armed=out["on_chip"],
        )
    elif which == "rules_100k":
        out = bench_rules_100k()
        # Smaller-better latency metric: a 100k-rule table must serve
        # within the p99 budget WHEN RULE-SHARDED (asserted on chip);
        # the unsharded table's miss/OOM rides along as evidence.
        _emit(
            "rules_100k_sharded_p99_ms", out["sharded_p99_ms"], "ms",
            out["budget_ms"] / max(out["sharded_p99_ms"], 1e-3),
            rules=out["rules"],
            rule_shards=out["rule_shards"],
            sharded_rate=round(out["sharded_rate"]),
            unsharded=out["unsharded"],
            budget_ms=out["budget_ms"],
            assertion_armed=out["on_chip"],
        )
    elif which == "dns":
        rate, p99_ms, cpu, dns_rounds = bench_dns()
        _emit("dns_l7_verdicts_per_sec_per_chip", rate, "verdicts/s",
              rate / 1_000_000,
              fenced_p99_ms=round(p99_ms, 3),
              cpu_oracle_per_sec=round(cpu),
              reasm_dns_rounds=dns_rounds,
              method="model-level pipelined rate + fenced per-call "
                     "p99; service segment with split/pipelined "
                     "frames asserts rounds_by_framing['dns'] > 0 "
                     "(silent scalar fallback cannot pass)")
    elif which == "restart_blackout":
        out = bench_restart_blackout()
        # Smaller-better: non-granted-path outage per graceful restart
        # (last pre-restart OK to first post-replay OK).  The granted
        # fraction rides along as its own bigger-better metric —
        # grants served straight through the blackout are the hitless
        # half of the claim.
        _emit(
            "restart_blackout_p99_ms", out["blackout_p99_ms"], "ms",
            1_000.0 / max(out["blackout_p99_ms"], 1e-3),
            cycles=out["cycles"],
            survival_hits=out["survival_hits"],
            generation=out["generation"],
            session_restores=out["session_restores"],
            warm_shapes=out["warm_shapes"],
        )
        _emit(
            "restart_granted_served_frac",
            out["granted_served_frac"], "frac",
            out["granted_served_frac"],
            granted_blackout_ops=out["granted_blackout_ops"],
        )
    elif which == "mesh_degraded":
        out = bench_mesh_degraded()
        # Smaller-better: attributed fault to reshaped-rung flip, as
        # published by the service's own ladder clock.  The capacity
        # fraction the reshaped rung retains is its own bigger-better
        # metric — the admission caps and the degraded shed fraction
        # ride along as the coupling evidence.  Zero-silent-loss and
        # shed-vs-capacity are asserted inside the bench.
        _emit(
            "mesh_reshape_window_ms", out["reshape_window_ms"], "ms",
            1_000.0 / max(out["reshape_window_ms"], 1e-3),
            windows_ms=out["reshape_windows_ms"],
            lost_device=out["lost_device"],
            reshapes=out["reshapes"],
            repromotions=out["repromotions"],
            ops_total=out["ops_total"],
            ops_degraded=out["ops_degraded"],
            shed_frac_degraded=round(out["shed_frac_degraded"], 4),
        )
        _emit(
            "mesh_degraded_capacity_frac",
            out["capacity_frac"], "frac",
            out["capacity_frac"],
            full_devices=out["full_devices"],
            degraded_devices=out["degraded_devices"],
            admission_cap_full=out["admission_cap_full"],
            admission_cap_degraded=out["admission_cap_degraded"],
        )
    elif which == "r2d2":
        rate, cpu = bench_r2d2()
        _emit("r2d2_l7_verdicts_per_sec_per_chip", rate, "verdicts/s",
              rate / 1_000_000, cpu_oracle_per_sec=round(cpu))
    else:
        raise SystemExit(f"unknown bench: {which}")


# Headline (r2d2) runs LAST so its JSON line is the final stdout line.
CONFIGS = (
    "http", "kafka", "cassandra", "memcached", "dns", "latency",
    "latency_colocated", "shm_transport", "mixed", "flow_cache",
    "datapath", "stress",
    "kvstore_failover", "verdict_overload", "fanin_concurrent",
    "verdict_trace_overhead",
    "flow_observe_overhead", "timeline_overhead", "ledger_overhead",
    "load_knee", "policy_churn",
    "multichip_scaling", "rules_100k",
    "restart_blackout",
    "mesh_degraded",
    "r2d2",
)


# Armed ON-CHIP measurement debt (the ROADMAP "standing debt" note):
# metric -> the CONFIGS entry that records it.  `--debt` diffs this
# declaration against the newest committed BENCH_FULL record so the
# outstanding chip-host campaign is a command, not archaeology.
ONCHIP_METRICS = (
    ("mixed_path_verdicts_per_sec", "mixed"),
    ("sidecar_seam_p99_minus_null_ms_shm", "shm_transport"),
    ("shm_wire_rate_at_1M", "shm_transport"),
    ("churn_swap_p99_ms", "policy_churn"),
    ("churn_served_p99_ms_delta", "policy_churn"),
    ("multichip_scaling_verdicts_per_sec", "multichip_scaling"),
    ("rules_100k_sharded_p99_ms", "rules_100k"),
    ("flow_cache_verdicts_per_s", "flow_cache"),
    ("flow_cache_hit_rate", "flow_cache"),
    ("fanin_aggregate_verdicts_per_s", "fanin_concurrent"),
    ("fanin_p99_ms_at_16", "fanin_concurrent"),
    ("knee_throughput_frac", "load_knee"),
    ("knee_p99_ms", "load_knee"),
)


def _print_debt() -> int:
    """`bench --debt`: list every armed on-chip metric missing from the
    newest committed BENCH_FULL_r*.json (rc 1 when debt remains, rc 0
    when the chip campaign has retired it all)."""
    import glob

    full_files = sorted(glob.glob("BENCH_FULL_r*.json"), key=_round_of)
    have: dict = {}
    src = "(no BENCH_FULL_r*.json committed)"
    if full_files:
        src = full_files[-1]
        try:
            rec = json.load(open(src))
        except (OSError, ValueError):
            rec = {}
        have = rec.get("metrics") or {}
    missing = [(m, cfg) for m, cfg in ONCHIP_METRICS if m not in have]
    for m, cfg in ONCHIP_METRICS:
        if m in have:
            v = _summary_value(have[m])
            print(f"bench --debt: recorded {m} = {v} ({src})")
    if not missing:
        print(f"bench --debt: no outstanding on-chip metrics vs {src}")
        return 0
    configs = sorted({cfg for _, cfg in missing})
    for m, cfg in missing:
        print(f"bench --debt: MISSING {m} (config: {cfg}) vs {src}")
    print(f"bench --debt: {len(missing)} metric(s) outstanding; run on a "
          f"chip host: {' '.join('--only ' + c for c in configs)}")
    return 1


def _round_of(path: str) -> int:
    import re

    m = re.search(r"_r(\d+)\.json$", path)
    return int(m.group(1)) if m else -1


def _summary_value(obj):
    """bench_summary 'metrics' values: plain numbers since r06, full
    metric objects before — accept both."""
    if isinstance(obj, dict):
        return obj.get("value")
    return obj


def _load_prev_metrics() -> tuple[str, dict]:
    """Metric values of the previous round for the drift guard.

    Two sources, merged: the committed BENCH_FULL_rNN.json (written by
    this script — complete by construction) and the driver's
    BENCH_rNN.json stdout tail (which historically truncated away all
    but the last lines, starving the guard).  The committed file wins
    whenever its round is at least as new; the tail still contributes
    anything the full record predates.  ('', {}) when neither exists.
    """
    import glob

    out: dict = {}
    tail_files = sorted(glob.glob("BENCH_r*.json"), key=_round_of)
    full_files = sorted(glob.glob("BENCH_FULL_r*.json"), key=_round_of)
    prev_file = ""

    if tail_files:
        prev_file = tail_files[-1]
        try:
            rec = json.load(open(tail_files[-1]))
        except (OSError, ValueError):
            rec = {}
        # Full-line parse (not a lazy regex): metric lines carry nested
        # objects (e.g. the stress http_tier_mix), which a non-greedy
        # \{.*?\} would truncate at the first inner brace.
        for line in rec.get("tail", "").splitlines():
            line = line.strip()
            if not line.startswith('{"metric"'):
                continue
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if d["metric"] == "bench_summary":
                # The truncation-proof aggregate: every metric of that
                # run in one line.
                for name, obj in (d.get("metrics") or {}).items():
                    out[name] = _summary_value(obj)
                continue
            out[d["metric"]] = d["value"]
        parsed = rec.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            if parsed["metric"] == "bench_summary":
                # Never store the aggregate under its own name — it
                # would then be demanded as a "metric" by the vanished
                # check.
                for name, obj in (parsed.get("metrics") or {}).items():
                    out[name] = _summary_value(obj)
            else:
                out[parsed["metric"]] = parsed["value"]

    if full_files and (
        not tail_files
        or _round_of(full_files[-1]) >= _round_of(tail_files[-1])
    ):
        try:
            full = json.load(open(full_files[-1]))
        except (OSError, ValueError):
            full = {}
        for name, obj in (full.get("metrics") or {}).items():
            v = obj.get("value") if isinstance(obj, dict) else obj
            if v is not None:
                out[name] = v
        prev_file = full_files[-1]

    out.pop("bench_summary", None)
    return prev_file, out


def _rebaselined() -> set:
    """Metrics whose baseline was deliberately reset, listed in
    BENCH_NOTES.md as lines starting with 'rebaseline:'."""
    try:
        text = open("BENCH_NOTES.md").read()
    except OSError:
        return set()
    return {
        line.split(":", 1)[1].split("—")[0].split("--")[0].strip()
        for line in text.splitlines()
        if line.strip().startswith("rebaseline:")
    }


def _check_regressions(lines: list[str],
                       prev_file: str | None = None,
                       prev: dict | None = None) -> int:
    """Regression guard: fail (rc 1) when any metric this run dropped
    >10% below the previous round without a documented rebaseline in
    BENCH_NOTES.md.  main() preloads (prev_file, prev) BEFORE writing
    this run's own BENCH_FULL record — loading here afterwards would
    compare the run against itself and pass everything."""
    if prev is None:
        prev_file, prev = _load_prev_metrics()
    if not prev:
        print("bench --check: no previous BENCH_r*.json; nothing to compare",
              file=sys.stderr)
        return 0
    allowed = _rebaselined()
    # Latency-style metrics: smaller is better.
    smaller_better = {"sidecar_added_latency_p99_ms_at_1M",
                      "sidecar_seam_added_p99_ms_colocated",
                      "sidecar_seam_added_p99_ms_colocated_at_1M",
                      "sidecar_seam_p99_minus_null_ms_colocated",
                      "kvstore_failover_write_outage_s",
                      "verdict_overload_p99_ms_at_2x",
                      "verdict_trace_overhead_pct",
                      "flow_observe_overhead_pct",
                      "timeline_overhead_pct",
                      "churn_swap_p99_ms",
                      "churn_served_p99_ms_delta",
                      "rules_100k_sharded_p99_ms",
                      "restart_blackout_p99_ms",
                      "mesh_reshape_window_ms"}
    rc = 0
    seen: set = set()
    for line in lines:
        try:
            d = json.loads(line)
        except ValueError:
            continue
        name, val = d.get("metric"), d.get("value")
        if name == "bench_summary":
            seen.update((d.get("metrics") or {}).keys())
            continue
        if name:
            seen.add(name)
        if name not in prev or not isinstance(val, (int, float)):
            continue
        old = prev[name]
        if not isinstance(old, (int, float)) or old == 0:
            continue
        drop = (old - val) / abs(old)
        if name in smaller_better:
            drop = (val - old) / abs(old)
        if drop > 0.10:
            if name in allowed:
                print(f"bench --check: {name} {old:,} -> {val:,} "
                      f"(rebaselined, see BENCH_NOTES.md)", file=sys.stderr)
            else:
                print(f"bench --check: REGRESSION {name} {old:,} -> {val:,} "
                      f"({drop:+.0%} vs {prev_file}); explain in "
                      f"BENCH_NOTES.md or fix", file=sys.stderr)
                rc = 1
    # A metric that VANISHED (config crashed, stopped emitting) is the
    # worst regression of all — never let it pass silently.
    for name in prev:
        if name not in seen and name not in allowed:
            print(f"bench --check: MISSING metric {name} (present in "
                  f"{prev_file}, absent this run)", file=sys.stderr)
            rc = 1
    return rc


def main():
    import argparse
    import subprocess

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=CONFIGS)
    ap.add_argument(
        "--check", action="store_true",
        help="after running, fail on >10%% drops vs the previous "
             "BENCH_r*.json unless rebaselined in BENCH_NOTES.md",
    )
    ap.add_argument(
        "--debt", action="store_true",
        help="list armed on-chip metrics absent from the newest "
             "committed BENCH_FULL record, then exit (runs nothing)",
    )
    args = ap.parse_args()
    if args.debt:
        sys.exit(_print_debt())
    if args.only:
        run_one(args.only)
        return

    # Each config runs in its own process: the device transport's eager
    # op cache degrades badly when many distinct model shapes share one
    # session (measured 10x cross-pollution), and per-process isolation
    # gives every config the same fresh-session conditions.
    emitted: list[str] = []
    for which in CONFIGS:
        proc = subprocess.run(
            [sys.executable, __file__, "--only", which],
            capture_output=True, text=True, timeout=900,
        )
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            print(f"bench[{which}] FAILED rc={proc.returncode}",
                  file=sys.stderr)
            continue
        sys.stdout.write(proc.stdout)
        sys.stdout.flush()
        emitted.extend(proc.stdout.splitlines())

    # Truncation-proof record, two layers (VERDICT r5 ask #3 — the r5
    # run again lost 10 of 11 metrics to the driver's 2,000-char tail
    # because the aggregate carried FULL objects and blew past it):
    #   1. bench_summary is metric→value pairs ONLY (~400 chars for 11
    #      metrics), emitted SECOND-TO-LAST so the tail always keeps
    #      it; the headline r2d2 line stays last for the driver's
    #      single-line parse.
    #   2. The full objects (runs arrays, pair deltas, splits) go to a
    #      committed BENCH_FULL_rNN.json, which _load_prev_metrics
    #      prefers — the >10% drift guard covers every metric even if
    #      the tail is truncated to nothing.
    metrics: dict[str, dict] = {}
    headline = None
    for line in emitted:
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if "metric" in d:
            metrics[d["metric"]] = d
            if d["metric"] == "r2d2_l7_verdicts_per_sec_per_chip":
                headline = line
    import glob

    # Snapshot the PREVIOUS round's metrics before this run's full
    # record lands on disk and becomes the newest candidate.
    prev_file, prev = _load_prev_metrics()
    round_no = 1 + max(
        [_round_of(f) for f in glob.glob("BENCH_r*.json")] or [0]
    )
    full_path = f"BENCH_FULL_r{round_no:02d}.json"
    with open(full_path, "w") as f:
        json.dump({"round": round_no, "metrics": metrics}, f, indent=1)
    print(f"bench: full record -> {full_path}", file=sys.stderr)
    summary = {
        "metric": "bench_summary",
        "value": len(metrics),
        "unit": "metrics",
        "vs_baseline": 1.0,
        "full_record": full_path,
        "metrics": {
            name: d.get("value") for name, d in metrics.items()
        },
    }
    print(json.dumps(summary))
    emitted.append(json.dumps(summary))
    if headline:
        print(headline)
    sys.stdout.flush()
    if args.check:
        sys.exit(_check_regressions(emitted, prev_file, prev))


if __name__ == "__main__":
    main()

"""loadinfo, pprof endpoint, flowdebug gate, Prometheus exposition
format, and the verdict-path latency decomposition (reference:
pkg/loadinfo, pkg/pprof, pkg/flowdebug, pkg/metrics)."""

import json
import logging
import threading
import time
import urllib.request

import pytest

from cilium_tpu.utils import flowdebug, loadinfo, pprofserve
from cilium_tpu.utils.metrics import (
    MICRO_BUCKETS,
    SUBMS_BUCKETS,
    Histogram,
    Registry,
)


# --- loadinfo --------------------------------------------------------------

def test_log_current_system_load_reports_load_and_memory():
    lines = []
    out = loadinfo.log_current_system_load(
        lambda fmt, *a: lines.append(fmt % a)
    )
    assert out["load"] is not None and len(out["load"]) == 3
    assert out["memory"] is not None and out["memory"]["total_mb"] > 0
    assert any("Load 1-min" in ln for ln in lines)
    assert any("Memory:" in ln for ln in lines)


def test_periodic_load_logger_ticks():
    lines = []
    with loadinfo.PeriodicLoadLogger(
        lambda fmt, *a: lines.append(fmt), interval=0.05
    ):
        time.sleep(0.2)
    n = len([ln for ln in lines if "Load" in ln])
    assert n >= 2  # immediate snapshot + at least one periodic tick


def test_proc_sampler_sees_busy_self():
    s = loadinfo._ProcSampler()
    s.sample()
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.3:  # burn CPU to cross the watermark
        sum(i * i for i in range(1000))
    import os

    busy = {pid for pid, _, _ in s.sample()}
    assert os.getpid() in busy


# --- pprof -----------------------------------------------------------------

def test_pprof_endpoints():
    srv = pprofserve.enable(("127.0.0.1", 0))
    try:
        host, port = srv.server_address[:2]
        base = f"http://{host}:{port}/debug/pprof"
        threads = urllib.request.urlopen(f"{base}/threads").read().decode()
        assert "--- thread" in threads and "MainThread" in threads
        # Burn CPU on a named background thread so the sampling
        # profiler (which must see ALL threads, not just the handler's)
        # has something to catch.
        import threading

        stop = threading.Event()

        def burner():
            while not stop.is_set():
                sum(i * i for i in range(500))

        t = threading.Thread(target=burner, name="prof-burner", daemon=True)
        t.start()
        try:
            prof = urllib.request.urlopen(
                f"{base}/profile?seconds=0.2"
            ).read().decode()
        finally:
            stop.set()
            t.join()
        assert prof.startswith("samples:")
        assert "burner" in prof  # captured the busy non-handler thread
        heap = urllib.request.urlopen(f"{base}/heap").read().decode()
        assert "objects" in heap or "size" in heap
        try:
            urllib.request.urlopen(f"{base}/nope")
            raise AssertionError("404 expected")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.shutdown()


def test_daemon_wires_pprof(tmp_path):
    from cilium_tpu.daemon.daemon import Daemon
    from cilium_tpu.utils.option import DaemonConfig

    d = Daemon(DaemonConfig(state_dir=str(tmp_path), dry_mode=True, pprof=True))
    try:
        host, port = d.pprof_server.server_address[:2]
        body = urllib.request.urlopen(
            f"http://{host}:{port}/debug/pprof/threads"
        ).read().decode()
        assert "--- thread" in body
    finally:
        d.close()


# --- flowdebug -------------------------------------------------------------

def test_flowdebug_gate(caplog):
    logger = logging.getLogger("flowdebug-test")
    flowdebug.disable()
    with caplog.at_level(logging.DEBUG, logger="flowdebug-test"):
        flowdebug.log(logger, "hidden %d", 1)
        assert not caplog.records
        flowdebug.enable()
        try:
            assert flowdebug.enabled()
            flowdebug.log(logger, "shown %d", 2)
        finally:
            flowdebug.disable()
    assert [r.getMessage() for r in caplog.records] == ["shown 2"]


def test_flowdebug_traces_proxylib_ops(caplog):
    """With the gate enabled, every parser op is traced per flow; with
    it disabled the hot loop logs nothing (reference: pkg/flowdebug
    consumers in pkg/proxy)."""
    from cilium_tpu.proxylib import (
        NetworkPolicy,
        PortNetworkPolicy,
        PortNetworkPolicyRule,
    )
    from cilium_tpu.proxylib import instance as inst
    from proxylib_harness import new_connection

    inst.reset_module_registry()
    mod = inst.open_module([], True)
    ins = inst.find_instance(mod)
    ins.policy_update([NetworkPolicy(
        name="fd", policy=2,
        ingress_per_port_policies=[PortNetworkPolicy(port=80, rules=[
            PortNetworkPolicyRule(l7_proto="r2d2",
                                  l7_rules=[{"cmd": "HALT"}])])],
    )])
    res, conn = new_connection(
        mod, "r2d2", True, 1, 2, "1.1.1.1:1", "2.2.2.2:80", "fd"
    )
    try:
        with caplog.at_level(logging.DEBUG, logger="cilium_tpu.proxylib.flow"):
            ops = []
            conn.on_data(False, False, [b"HALT\r\n"], ops)
            assert not caplog.records  # gate off: silent
            flowdebug.enable()
            try:
                ops = []
                conn.on_data(False, False, [b"HALT\r\n"], ops)
            finally:
                flowdebug.disable()
        msgs = [r.getMessage() for r in caplog.records]
        assert any("r2d2" in m and "PASS" in m for m in msgs)
    finally:
        inst.close_module(mod)
        inst.reset_module_registry()


# --- Prometheus text exposition (utils/metrics.py) -------------------------
# No test pinned this format before; consumers (daemon /metrics,
# `cilium metrics`, external scrapers) depend on every line shape here.

def test_histogram_cumulative_bucket_semantics():
    h = Histogram("t_seconds", "help", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    lines = list(h.collect())
    assert lines[0] == "# HELP t_seconds help"
    assert lines[1] == "# TYPE t_seconds histogram"
    # Cumulative: le=0.01 holds 1, le=0.1 holds 1+2, le=1 holds +1,
    # +Inf holds everything including the 5.0 overflow.
    assert 't_seconds_bucket{le="0.01"} 1' in lines
    assert 't_seconds_bucket{le="0.1"} 3' in lines
    assert 't_seconds_bucket{le="1"} 4' in lines
    assert 't_seconds_bucket{le="+Inf"} 5' in lines
    assert "t_seconds_sum 5.605" in lines
    assert "t_seconds_count 5" in lines


def test_histogram_le_is_inclusive():
    h = Histogram("x_seconds", "help", buckets=(0.5, 1.0))
    h.observe(0.5)  # exactly on a bound counts INTO that bound
    assert 'x_seconds_bucket{le="0.5"} 1' in list(h.collect())


def test_histogram_label_formatting_and_ordering():
    h = Histogram("l_seconds", "help", ("stage", "path"), buckets=(1.0,))
    h.observe(0.1, "queue", "vec")
    h.observe(0.2, "device", "vec")
    out = "\n".join(h.collect())
    # Labels render in declaration order with le appended last.
    assert 'l_seconds_bucket{stage="queue",path="vec",le="1"} 1' in out
    assert 'l_seconds_bucket{stage="device",path="vec",le="+Inf"} 1' in out
    assert 'l_seconds_sum{stage="queue",path="vec"} 0.1' in out
    assert 'l_seconds_count{stage="device",path="vec"} 1' in out


def test_registry_exposes_counter_gauge_histogram():
    r = Registry()
    c = r.counter("reqs_total", "requests", ("verdict",))
    g = r.gauge("depth", "queue depth")
    h = r.histogram("lat_seconds", "latency", buckets=(1.0,))
    c.inc("allow")
    c.inc("allow")
    g.set(7)
    h.observe(0.5)
    text = r.expose()
    assert "# TYPE cilium_tpu_reqs_total counter" in text
    assert 'cilium_tpu_reqs_total{verdict="allow"} 2' in text
    assert "# TYPE cilium_tpu_depth gauge" in text
    assert "cilium_tpu_depth 7" in text
    assert "# TYPE cilium_tpu_lat_seconds histogram" in text
    assert text.endswith("\n")


def test_histogram_concurrent_observe_safe():
    h = Histogram("c_seconds", "help", ("p",), buckets=MICRO_BUCKETS)
    N, T = 2000, 8

    def worker(k):
        for i in range(N):
            h.observe((i % 7) * 1e-5, "p%d" % (k % 2))

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(h.get_count("p%d" % j) for j in (0, 1))
    assert total == N * T
    # Exposition stays self-consistent: +Inf == _count for each series.
    out = "\n".join(h.collect())
    for j in (0, 1):
        assert f'c_seconds_bucket{{p="p{j}",le="+Inf"}} {N * T // 2}' in out


def test_micro_buckets_resolve_sub_ms():
    # DEFAULT_BUCKETS is seconds-scale; the microsecond presets must
    # discriminate inside the <1ms budget.
    assert MICRO_BUCKETS[0] <= 1e-6
    assert sum(1 for b in MICRO_BUCKETS if b < 1e-3) >= 8
    assert sum(1 for b in SUBMS_BUCKETS if b <= 1e-3) >= 5
    h = Histogram("m_seconds", "help", buckets=MICRO_BUCKETS)
    h.observe(3e-6)
    h.observe(3e-4)
    assert h.quantile(0.5) <= 1e-4
    assert h.quantile(0.99) <= 5e-4


def test_histogram_quantile_bounds():
    h = Histogram("q_seconds", "help", buckets=(0.01, 0.1))
    assert h.quantile(0.99) is None
    for _ in range(99):
        h.observe(0.005)
    h.observe(5.0)  # overflow: quantile clamps to the last bound
    assert h.quantile(0.5) == 0.01
    assert h.quantile(0.999) == 0.1


def test_cli_metrics_prefix_filter():
    from cilium_tpu.cli import _filter_metrics

    r = Registry()
    r.counter("verdict_stage_total", "a")
    r.counter("other_total", "b")
    text = r.expose()
    out = _filter_metrics(text, "verdict_")
    assert "cilium_tpu_verdict_stage_total" in out
    assert "other_total" not in out
    assert "# HELP cilium_tpu_verdict_stage_total a" in out
    # Full-name (namespaced) prefixes work too; empty prefix is identity.
    assert "cilium_tpu_other_total" in _filter_metrics(
        text, "cilium_tpu_other"
    )
    assert _filter_metrics(text, "") == text


# --- verdict-path latency decomposition (sidecar/trace.py) -----------------

def test_round_trace_stage_decomposition():
    from cilium_tpu.sidecar.trace import VerdictTracer

    tr = VerdictTracer(sample_every=0, slow_ms=1e9, ring=8,
                       batch_capacity=256)
    t0 = time.monotonic()
    rt = tr.begin_round("vec", 10, t0 - 0.010, t0)
    rt.formed()
    rt.submitted()
    rt.completed()
    rt.drained()
    stages = rt.stages()
    assert set(stages) == {
        "ring", "queue", "table_swap", "reasm", "cache", "batch_form",
        "device_submit", "device", "drain", "send",
    }
    assert stages["ring"] == 0.0  # socket-delivered round: no ring wait
    assert stages["table_swap"] == 0.0  # no epoch swap blocked this round
    assert stages["reasm"] == 0.0  # scalar round: no columnar reassembly
    assert stages["cache"] == 0.0  # no verdict-cache work this round
    assert 0.009 <= stages["queue"] <= 0.5
    assert all(v >= 0 for v in stages.values())
    # A shm-delivered round carves the ring wait OUT of the queue wait
    # (their sum is the admit->pop span either way).
    rt2 = tr.begin_round("vec", 10, t0 - 0.010, t0, ring_s=0.004)
    s2 = rt2.stages()
    assert abs(s2["ring"] - 0.004) < 1e-9
    assert abs((s2["ring"] + s2["queue"]) - stages["queue"]) < 1e-3
    tr.finish_round(rt, [(1, 10, t0 - 0.010, 42)])
    st = tr.status()
    assert st["rounds"] == 1 and st["entries"] == 10
    assert st["stages"]["vec"]["queue"]["rounds"] == 1


def test_tracer_sampling_slow_exemplars_and_ring():
    from cilium_tpu.monitor import Monitor
    from cilium_tpu.monitor.monitor import MSG_TYPE_TRACE
    from cilium_tpu.sidecar.trace import VerdictTracer

    events = []
    mon = Monitor()
    mon.add_listener(events.append, queued=False)

    class _Log:
        records: list = []

        def log(self, rec):
            self.records.append(rec)

    tr = VerdictTracer(sample_every=1, slow_ms=1e9, ring=4,
                       batch_capacity=64)
    tr.monitor = mon
    tr.access_logger = _Log()
    t0 = time.monotonic()
    rt = tr.begin_round("oracle", 3, t0, t0)
    tr.finish_round(rt, [(7, 3, t0, 11)])
    spans = tr.spans(10)
    assert len(spans) == 1 and spans[0]["kind"] == "sample"
    assert not events  # sampled spans are cheap: no monitor fan-out

    # Threshold forced to 0: EVERY batch becomes a slow exemplar, with
    # monitor + accesslog fan-out.
    tr.slow_s = 0.0
    rt = tr.begin_round("oracle", 2, t0, t0)
    tr.finish_round(rt, [(8, 2, t0, 12)])
    spans = tr.spans(10)
    assert spans[0]["kind"] == "slow" and spans[0]["path"] == "oracle"
    assert events and events[0].type == MSG_TYPE_TRACE
    assert events[0].payload["slow_verdict"]["seq"] == 8
    rec = _Log.records[0]
    assert rec.latency is not None and rec.latency.path == "oracle"
    assert "queue" in rec.latency.stages_us
    # Ring bound: overflow evicts oldest, never grows.
    for k in range(10):
        rt = tr.begin_round("oracle", 1, t0, t0)
        tr.finish_round(rt, [(100 + k, 1, t0, 1)])
    assert len(tr.spans(100)) == 4


def test_slow_verdict_monitor_format():
    from cilium_tpu.monitor import format_event
    from cilium_tpu.monitor.monitor import MSG_TYPE_TRACE, MonitorEvent

    line = format_event(MonitorEvent(MSG_TYPE_TRACE, {"slow_verdict": {
        "path": "vec", "seq": 9, "conn_id": 3, "entries": 2,
        "e2e_us": 1500.0, "stages_us": {"queue": 1200.0, "device": 300.0},
    }}))
    assert "SLOW-VERDICT" in line and "path=vec" in line
    assert "e2e=1.50ms" in line and "queue=1200us" in line


def test_accesslog_record_latency_roundtrip():
    from cilium_tpu.accesslog.record import LatencyInfo, LogRecord

    rec = LogRecord(latency=LatencyInfo(
        total_us=950.0, path="vec", stages_us={"queue": 100.0}
    ))
    d = rec.to_dict()
    assert d["latency"]["path"] == "vec"
    back = LogRecord.from_dict(json.loads(json.dumps(d)))
    assert back.latency.total_us == 950.0
    assert back.latency.stages_us == {"queue": 100.0}
    # Absent -> omitted from the dict entirely (None-filtered).
    assert "latency" not in LogRecord().to_dict()


# --- end-to-end: a served batch produces stage histograms + spans ----------

@pytest.mark.parametrize("greedy", [False, True])
def test_service_end_to_end_stage_histograms_and_spans(tmp_path, greedy):
    """CI acceptance: a real VerdictService round produces non-zero
    stage histograms, a sampled span, and a slow exemplar once the
    threshold is forced to 0 — in both completion modes (pipelined and
    greedy/inline)."""
    from cilium_tpu.monitor import Monitor
    from cilium_tpu.proxylib import FilterResult
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils import metrics as m
    from cilium_tpu.utils.option import DaemonConfig
    from test_sidecar import r2d2_policy

    inst.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=0.0 if greedy else 2.0,
        batch_flows=256,
        trace_sample_every=1,
        trace_slow_ms=1e6,  # nothing is "slow" yet
    )
    svc = VerdictService(str(tmp_path / "obs.sock"), cfg).start()
    events = []
    mon = Monitor()
    mon.add_listener(events.append, queued=False)
    svc.tracer.monitor = mon
    client = SidecarClient(svc.socket_path, timeout=60.0)
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [r2d2_policy()]) == int(
            FilterResult.OK
        )
        res, shim = client.new_connection(
            mod, "r2d2", 8801, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "sidecar-pol",
        )
        assert res == int(FilterResult.OK)

        def stage_count(path):
            return m.VerdictStageSeconds.get_count("queue", path)

        base_vec = stage_count("vec")
        base_spans = len(svc.tracer.spans(10_000))
        result, entries = client._on_data_rpc(
            shim.conn_id, False, False, b"READ /public/obs.txt\r\n"
        )
        assert result == int(FilterResult.OK)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if stage_count("vec") > base_vec:
                break
            time.sleep(0.01)
        # Non-zero stage histograms for the served (vec) round, every
        # stage observed.
        assert stage_count("vec") > base_vec
        for stage in ("batch_form", "device_submit", "device",
                      "drain", "send"):
            assert m.VerdictStageSeconds.get_count(stage, "vec") > 0
        assert m.VerdictE2ESeconds.get_count("vec") > 0
        # 1-in-1 sampling: the round left a sampled span in the ring.
        spans = svc.tracer.spans(10_000)
        assert len(spans) > base_spans
        assert any(s["kind"] == "sample" and s["path"] == "vec"
                   for s in spans)
        assert not events  # nothing crossed the slow threshold

        # Force the slow threshold to 0: the next served batch becomes
        # a slow exemplar (ring + monitor event).
        svc.tracer.slow_s = 0.0
        result, _ = client._on_data_rpc(
            shim.conn_id, False, False, b"READ /public/obs2.txt\r\n"
        )
        assert result == int(FilterResult.OK)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if any(s["kind"] == "slow" for s in svc.tracer.spans(10_000)):
                break
            time.sleep(0.01)
        slow = [s for s in svc.tracer.spans(10_000) if s["kind"] == "slow"]
        assert slow and slow[0]["path"] == "vec"
        assert slow[0]["stages_us"].keys() >= {"queue", "device", "send"}
        assert events and "slow_verdict" in events[0].payload

        # The trace RPC + CLI surface the same ring.
        out = client.trace(n=50)
        assert out["spans"] and out["latency"]["rounds"] > 0
        assert client.status()["latency"]["spans_sampled"] > 0
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()


def test_cli_sidecar_trace(tmp_path, capsys):
    from cilium_tpu.cli import main as cli_main
    from cilium_tpu.proxylib import FilterResult
    from cilium_tpu.proxylib import instance as inst
    from cilium_tpu.sidecar import SidecarClient, VerdictService
    from cilium_tpu.utils.option import DaemonConfig
    from test_sidecar import r2d2_policy

    inst.reset_module_registry()
    cfg = DaemonConfig(
        batch_timeout_ms=2.0, batch_flows=256,
        trace_sample_every=1, trace_slow_ms=0.0,
    )
    svc = VerdictService(str(tmp_path / "ctr.sock"), cfg).start()
    client = SidecarClient(svc.socket_path, timeout=60.0)
    try:
        mod = client.open_module([])
        assert client.policy_update(mod, [r2d2_policy()]) == int(
            FilterResult.OK
        )
        res, shim = client.new_connection(
            mod, "r2d2", 8901, True, 1, 2, "1.1.1.1:1", "2.2.2.2:80",
            "sidecar-pol",
        )
        assert res == int(FilterResult.OK)
        result, _ = client._on_data_rpc(
            shim.conn_id, False, False, b"HALT\r\n"
        )
        assert result == int(FilterResult.OK)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not svc.tracer.spans(1):
            time.sleep(0.01)
        rc = cli_main(["sidecar", "trace", "--address", svc.socket_path])
        assert rc == 0
        out = capsys.readouterr().out
        assert "span(s)" in out and "e2e=" in out
        rc = cli_main(
            ["sidecar", "trace", "--address", svc.socket_path, "--json"]
        )
        assert rc == 0
        parsed = json.loads(capsys.readouterr().out)
        assert parsed["spans"] and "latency" in parsed
        # Malformed trace payloads (valid JSON, wrong shape) must not
        # kill the shim connection's read loop — they degrade to the
        # defaults and the connection keeps serving.
        from cilium_tpu.sidecar import wire as sw

        for bad in (b"[1]", b'{"n": null}', b'{"n": "x", "kind": 7}'):
            got = client._control_rpc(
                lambda b=bad: (sw.MSG_TRACE, b), sw.MSG_TRACE_REPLY
            )
            assert "spans" in json.loads(got.decode())
        assert client.status()["connections"] >= 1  # still alive
        # status CLI shows the latency section
        rc = cli_main(["sidecar", "status", "--address", svc.socket_path])
        assert rc == 0
        assert "latency:" in capsys.readouterr().out
    finally:
        client.close()
        svc.stop()
        inst.reset_module_registry()

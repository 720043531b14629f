"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

This process holds the chip.  It places JAX's compile cache at
``<checkout>/.jax_cache``, starts a ``VerdictService`` with the
configuration's ``DaemonConfig`` overrides, and starts the load
generator (``gen.py``) as a child with ``JAX_PLATFORMS=cpu``, which
pushes the policy, binds the connections and drives the cell's traffic
through ``SidecarClient`` over the socket.  After the window it frees
the service and holds the answers of the connections the generator
drew to the plain reference (``check.py``).

``setup_s`` runs from this process's start to the window's first timed
push: JAX's start, the service, the policy, the binds with their
prewarm, and the warm-up traffic.  With ``--trace 0`` the result line
carries the cell's end-to-end metrics; with ``--trace 1`` its per-layer
metrics, read from the service's ``status()`` at the window's edges and
from a profiler trace of a few seconds inside it.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result line.  The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and last ``check``: each number compared
with its limit); the last lines of stderr repeat the check.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, deploy  # noqa: E402
from benchmark.cell import Cell  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SETUP_LIMIT_S = 1100.0  # a cold first run of a cell compiles everything
DRAIN_LIMIT_S = 120.0
TRACE_WAIT_S = 150.0  # writing the trace out, after the window


def log(msg: str) -> None:
    print(f"run: {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Every backend compile in this process, from JAX's own monitoring
    events, and the persistent cache's hits."""

    def __init__(self):
        import jax

        self.compiles: list = []
        self.cache = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append((time.monotonic(), secs))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1

    def between(self, t0: float, t1: float) -> list:
        return [c for c in self.compiles if t0 <= c[0] < t1]


class GcWatch:
    """Pauses of this process's cyclic garbage collector, which stop
    every thread of the service."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.pauses.append((self._t0, time.monotonic() - self._t0,
                                info["generation"]))

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def between(self, t0: float, t1: float) -> list:
        return [p for p in self.pauses if t0 <= p[0] < t1]


class Heartbeat(threading.Thread):
    """Wakes every 5 ms in the service's process.  Per whole second of
    ``time.monotonic()``: the longest gap between two wake-ups (more
    than the sleep is time in which it did not run: another thread held
    the interpreter, or the process or the whole machine stopped) and
    the deepest dispatcher queue it saw; and (start, seconds) of every
    gap over ``PAUSE_S``."""

    PAUSE_S = 0.05

    def __init__(self, svc):
        super().__init__(name="bench-heartbeat", daemon=True)
        self.svc = svc
        self.seconds: dict = {}
        self.pauses: list = []
        self.halt = threading.Event()

    def run(self) -> None:
        last = time.monotonic()
        while not self.halt.wait(0.005):
            now = time.monotonic()
            row = self.seconds.setdefault(int(now), [0.0, 0])
            row[0] = max(row[0], now - last)
            row[1] = max(row[1], self.svc.dispatcher.pending_weight)
            if now - last > self.PAUSE_S:
                self.pauses.append((last, now - last))
            last = now

    def between(self, t0: float, t1: float) -> list:
        """(second from ``t0``, longest gap ms, deepest queue) by second."""
        return [(s - t0, g * 1e3, d) for s, (g, d) in
                sorted(self.seconds.items()) if t0 - 1 < s < t1]


def place_cache() -> None:
    """JAX's persistent compile cache at the checkout's fixed path,
    holding every program however short its compile, so that a cell's
    later runs compile nothing; the TPU runtime's logs under ``TMPDIR``
    rather than its default fixed path.  Before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "bench-tpu-logs"))
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def daemon_config(overrides: dict):
    """DaemonConfig with each override whose field still exists."""
    from cilium_tpu.utils.option import DaemonConfig

    have = {f.name for f in dataclasses.fields(DaemonConfig)}
    applied = {k: v for k, v in overrides.items() if k in have}
    for k in overrides:
        if k not in have:
            log(f"override {k} skipped: DaemonConfig has no such field")
    log(f"DaemonConfig overrides applied: {json.dumps(applied)}")
    return DaemonConfig(**applied)


class Tracer(threading.Thread):
    """Takes one profiler trace of ``length`` seconds, ``lead`` seconds
    after the window opens."""

    def __init__(self, lead: float, length: float):
        super().__init__(name="bench-trace", daemon=True)
        self.lead, self.length = lead, length
        self.dir = tempfile.mkdtemp(prefix="bench-trace")
        self.window_s = None
        self.stop_s = None
        self.error = None

    def run(self) -> None:
        import jax

        time.sleep(self.lead)
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the runtime's own events only
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            t0 = time.monotonic()
            time.sleep(self.length)
            t1 = time.monotonic()
            jax.profiler.stop_trace()
            self.stop_s = time.monotonic() - t1
            self.window_s = t1 - t0
        except Exception as e:  # noqa: BLE001 -- reported, never fatal
            self.error = repr(e)


def _lines(stream, q: queue.Queue) -> None:
    for line in stream:
        q.put(line.strip())
    q.put(None)


def serve(cell: Cell, seed: int, seconds: float, trace: bool,
          rates: str = "") -> dict:
    """One set-up and one window of ``cell`` (or one window per offered
    rate, with ``rates``); returns what the result line is made of."""
    import jax

    from cilium_tpu.sidecar.service import VerdictService

    watch = CompileWatch()
    gcw = GcWatch()
    dev = jax.devices()[0]
    cfg = daemon_config(cell.config.get("daemon", {}))
    work = tempfile.mkdtemp(prefix="bench")
    svc = VerdictService(os.path.join(work, "v.sock"), cfg).start()
    beat = Heartbeat(svc)
    beat.start()
    out_path = os.path.join(work, "answers.pkl")
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "gen.py"),
           "--socket", svc.socket_path, "--config", cell.config_path,
           "--traffic", cell.traffic_path, "--seed", str(seed),
           "--seconds", str(seconds), "--width", str(cfg.batch_width),
           "--out", out_path]
    if rates:
        cmd += ["--rates", rates]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env)
    q: queue.Queue = queue.Queue()
    threading.Thread(target=_lines, args=(child.stdout, q),
                     daemon=True).start()
    marks: dict = {}
    status: dict = {}
    tracer = None
    deadline = time.monotonic() + SETUP_LIMIT_S
    try:
        while True:
            try:
                line = q.get(timeout=max(deadline - time.monotonic(), 0.1))
            except queue.Empty:
                raise RuntimeError("load generator stopped answering")
            if line is None or line == "DONE":
                break
            word, _, val = line.partition(" ")
            if word not in ("BOUND", "WINDOW", "CLOSE"):
                log(f"gen said: {line}")
                continue
            marks[word] = float(val)
            if word == "BOUND":
                log(f"bound at {marks['BOUND'] - T_START:.3f}s")
                windows = len(rates.split(",")) if rates else 1
                deadline = time.monotonic() + windows * (
                    seconds + DRAIN_LIMIT_S) + 120 + float(
                    cell.traffic["warmup_s"])
            elif word == "WINDOW":
                status["open"] = svc.status()
                if trace:
                    # Stopping a trace takes about a minute and a half
                    # of host time: end it near the close, so that the
                    # writing overlaps the drain, not the window.
                    length = min(3.0, 0.3 * seconds)
                    tracer = Tracer(max(seconds - length - 1.0, 0.0),
                                    length)
                    tracer.start()
            else:
                status["close"] = svc.status()
        if child.wait(timeout=DRAIN_LIMIT_S) != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        status["end"] = svc.status()
        if tracer is not None:
            tracer.join(timeout=TRACE_WAIT_S)
        stats = dev.memory_stats() or {}
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        beat.halt.set()
        beat.join()
        svc.stop()
        gcw.close()
    with open(out_path, "rb") as f:
        got = pickle.load(f)
    shutil.rmtree(work, ignore_errors=True)
    res = {"got": got, "marks": marks, "status": status, "watch": watch,
           "gc": gcw, "beat": beat,
           "memory_peak_bytes": stats.get("peak_bytes_in_use"),
           "device": dev, "devices": len(jax.devices()), "trace": None,
           "width": cfg.batch_width}
    if tracer is not None:
        if tracer.is_alive():
            log(f"trace not written {TRACE_WAIT_S}s after the window")
        elif tracer.error or tracer.window_s is None:
            log(f"trace failed: {tracer.error}")
        else:
            log(f"trace: {tracer.window_s:.3f}s window, written in "
                f"{tracer.stop_s:.3f}s")
            from benchmark import xtrace

            pd = xtrace.load(tracer.dir)
            res["trace"] = (xtrace.reduce(pd, tracer.window_s)
                            if pd is not None else None)
        shutil.rmtree(tracer.dir, ignore_errors=True)
    return res


def end_to_end(name: str, got: dict, setup_s: float) -> float | None:
    s = got["summary"]
    if name == "setup_s":
        return setup_s
    if name == "verdicts_per_s":
        return s["verdicts"] / s["window_s"] if "verdicts" in s else None
    if name == "verdict_p50_ms":
        return s.get("p50_ms")
    if name == "goodput_per_s":
        return s.get("goodput_per_s")
    raise KeyError(f"no end-to-end metric {name!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    place_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell.chips:
        log(f"no TPU for this cell: platform={dev.platform} "
            f"device_kind={dev.device_kind} count={len(devices)} "
            f"(the cell asks for {cell.chips})")
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {CACHE_DIR}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


def log_pace(s: dict, beat: list) -> None:
    """Where the generator's lateness came from, beside what the
    service's process did in the same seconds."""
    p = s["pace"]
    log(f"generator lateness: p50 {s['gen_late_p50_ms']} ms, p99 "
        f"{s['gen_late_p99_ms']} ms; offered {s['offered_per_s']}/s, "
        f"achieved {s['achieved_per_s']}/s")
    log(f"generator pacing: releasing {p['send_s']:.3f}s, of it in the "
        f"client's send calls {p['write_s']:.3f}s (longest release in them "
        f"{p['send_max_ms']:.1f} ms), not running {p['stall_s']:.3f}s "
        f"(longest {p['stall_max_ms']:.1f} ms); longest gap between "
        f"answers {p['answer_gap_max_ms']} ms")
    by_sec = {int(round(t)): (g, d) for t, g, d in beat}
    for sec, late, send, stall, answers in p["worst_seconds"]:
        g, d = by_sec.get(sec, (None, None))
        log(f"second {sec}: latest push {late:.1f} ms; in send calls "
            f"{send:.1f} ms, not running {stall:.1f} ms, {answers} "
            f"answers; service process: longest pause {g} ms, deepest "
            f"queue {d}")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool) -> dict:
    """Set up, serve one window, check it; returns the result line."""
    res = serve(cell, seed, seconds, trace)
    got, marks, st = res["got"], res["marks"], res["status"]
    setup_s = marks["WINDOW"] - T_START
    window = res["watch"].between(marks["WINDOW"], marks["CLOSE"])
    log(f"setup: bound {marks['BOUND'] - T_START:.3f}s, window opened "
        f"{setup_s:.3f}s; compiles {len(res['watch'].compiles)}, cache "
        f"{json.dumps(res['watch'].cache)}")
    log(f"compiles inside the window: {len(window)} "
        f"({sum(c[1] for c in window):.3f}s)")
    pauses = res["gc"].between(marks["WINDOW"], marks["CLOSE"])
    log(f"service-process GC inside the window: {len(pauses)} "
        f"collections ({sum(p[2] == 2 for p in pauses)} of generation 2), "
        f"{sum(p[1] for p in pauses):.3f}s in all, longest "
        f"{max((p[1] for p in pauses), default=0.0) * 1e3:.1f} ms")
    s = got["summary"]
    beat = res["beat"].between(marks["WINDOW"], marks["CLOSE"])
    pauses = [(round(t - marks["WINDOW"], 3), round(g * 1e3, 1))
              for t, g in res["beat"].pauses
              if marks["WINDOW"] <= t < marks["CLOSE"]]
    log(f"service-process pauses inside the window: longest "
        f"{max((g for _, g, _ in beat), default=0.0):.1f} ms; deepest "
        f"dispatcher queue {max((d for *_, d in beat), default=0)}; "
        f"window opened at monotonic {marks['WINDOW']!r}; pauses over "
        f"{Heartbeat.PAUSE_S * 1e3:.0f} ms (s from the open, ms): {pauses}")
    if "pace" in s:
        log_pace(s, beat)
    cont0, cont1 = st["open"]["containment"], st["end"]["containment"]
    fallback = cont1["fallback_entries"] - cont0["fallback_entries"]
    log(f"containment at the end: {json.dumps(cont1)}")
    log(f"pushes {got['pushes_total']} in {got['messages']} messages; "
        f"summary {json.dumps(s)}")

    # The reference runs once the window is closed and the service freed.
    from benchmark.mixgen import Traffic

    t0 = time.monotonic()
    tr = Traffic(cell.traffic, cell.config, seed, res["width"])
    want = check.expected(tr, deploy.policies(cell.config), got["checked"])
    cmp_ = check.compare(tr, want, got["checked"])
    log(f"reference: {cmp_['checked']} answers of {cmp_['conns']} conns "
        f"checked in {time.monotonic() - t0:.3f}s; {cmp_['failed']} typed "
        f"failures among them (warm-up included), not compared")
    for ex in cmp_["examples"]:
        log(f"MISMATCH {ex}")
    ctl = check.compare(tr, want, check.stale_verdicts(want))
    log(f"control stale_verdicts: mismatches={ctl['mismatches']} of "
        f"{ctl['checked']} limit={check.LIMITS['mismatches']} correct="
        f"{str(check.is_correct(ctl)).lower()}")
    correct = check.is_correct(cmp_)

    metrics = {}
    if trace:
        ctx = {"status": (st["open"], st["close"]), "trace": res["trace"],
               "summary": s}
        for m in cell.per_layer:
            val = cell.reader(m["name"])(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            val = end_to_end(m["name"], got, setup_s)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    dev = res["device"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": res["devices"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": s["attempted"],
           "failed": s["failed"] + fallback, "metrics": metrics,
           "device": device}
    if res["trace"] is not None:
        device["busy_s"] = res["trace"]["busy_s"]
        device["window_s"] = res["trace"]["window_s"]
        out["breakdown"] = res["trace"]["breakdown"]
    out["check"] = {k: {"value": cmp_[k], "limit": lim}
                    for k, lim in check.LIMITS.items()}
    for k, lim in check.LIMITS.items():
        log(f"check {k}={cmp_[k]} limit={lim}")
    return out


if __name__ == "__main__":
    sys.exit(main())

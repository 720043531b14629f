"""The verdict service: batched device models behind the wire seam.

The standalone-process analog of the reference's verdict library: where
the reference loads ``libcilium.so`` into Envoy and parses per request
(reference: envoy/cilium_proxylib.cc:125 OnIO -> proxylib OnData), this
service accepts per-connection byte batches from datapath shims over a
unix socket, aggregates them across shims with the adaptive
fill-vs-deadline dispatcher, renders verdicts with the batched TPU
models, and returns FilterOp lists.

Verdict paths, fastest first:

1. **Vectorized fast path** — request-direction entries that carry
   exactly one complete frame for a flow with no buffered remainder are
   lifted straight into a ``[n, width]`` device batch with O(1) numpy
   gathers (no per-flow Python state), and ops are emitted from the
   verdict arrays.  This is the steady-state hot loop.
2. **Engine slow path** — stateful flows (partial frames, pipelined
   frames, carried NFA state) go through the per-protocol batch engines
   (runtime/batch.py, runtime/engines.py), still device-batched.
3. **Oracle path** — protocols without a device model, and all reply
   direction traffic, run the in-process streaming parsers
   (proxylib/) — the same code that defines bit-exactness.

Access logs on the fast path are recorded columnarly (verdict counters +
the standard logger on a sampled subset is NOT used — every request is
logged, but via one appended batch record) to keep host Python off the
per-request critical path.
"""

from __future__ import annotations

import base64
import binascii
import functools
import json
import logging
import os
import queue
import re
import socket
import struct
import threading
import time
from collections import deque as _deque
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FuturesTimeout

import numpy as np

from ..flowlog import (
    CODE_DENIED,
    CODE_ERROR,
    CODE_FORWARDED,
    CODE_SHED,
    FlowLog,
)
from ..models.base import ConstVerdict
from ..proxylib import instance as pl
from ..analysis.protocols import (
    CACHE_ARMED,
    CACHE_DECLINED,
    CACHE_UNARMED,
    EPOCH_SWAP_PROTOCOL,
    FLOW_CACHE_PROTOCOL,
    MESH_FALLBACK,
    MESH_FULL,
    MESH_LADDER_PROTOCOL,
    MESH_RESHAPED,
    SWAP_COMMITTED,
    SWAP_REJECTED,
    SWAP_STAGED,
)
from ..proxylib.accesslog import EntryType, LogEntry
from ..proxylib.npds import policy_from_dict
from ..proxylib.types import DROP, ERROR, MORE, PASS, FilterResult, OpError
from ..runtime.batch import R2d2BatchEngine
from ..utils import flowdebug, metrics
from ..utils.jaxcache import configure_compile_cache
from ..utils.option import DaemonConfig
from ..utils.sockutil import shutdown_close
from . import blackbox, wire
from . import ledger as ledger_mod
from .dispatch import BatchDispatcher
from .guard import DeviceGuard
from .reasm import (
    FRAMING_CRLF,
    FRAMINGS,
    ByteArena,
    Reassembler,
    gather_segments,
)
from .shm import GenerationMismatch, RingError, sweep_stale_segments
from .trace import (
    PATH_CACHED,
    PATH_HOST,
    PATH_ORACLE,
    PATH_SHED,
    PATH_VEC,
    VerdictTracer,
)
from .transport import (
    CREDIT_FLAG_QUARANTINED,
    DEATH_ABRUPT,
    DEATH_CLOSED,
    DEATH_SEND_TIMEOUT,
    DEATH_WRITE_FAILED,
    QUARANTINE_FLOOD,
    QUARANTINE_RECONNECT_STORM,
    SESSION_DEAD,
    REASON_ATTACH_REJECTED,
    REASON_DISABLED,
    REASON_GENERATION,
    REASON_OVERSIZE,
    REASON_OVERSIZE_SPREE,
    REASON_PEER_DEATH,
    REASON_TORN_SLOT,
    REASON_VERDICT_RING_FULL,
    SHED_FENCED,
    SHED_SESSION_QUARANTINED,
    SHED_SESSION_QUOTA,
    TRANSPORT_SOCKET,
    SessionState,
    ShmPeer,
)

log = logging.getLogger(__name__)
# Per-flow debug stream, flowdebug-gated (one boolean when disabled).
_flow_log = logging.getLogger("cilium_tpu.sidecar.flow")

# Protocols served by a device batch engine (everything else rides the
# in-process oracle), and the subset whose single-frame payloads may
# take the vectorized fast path (engines framing whole requests the
# model can judge from one row: r2d2 on CRLF, DNS on its length
# prefix — the per-framing gate is reasm.FRAMINGS).
ENGINE_PROTOS = ("r2d2", "cassandra", "memcache", "http", "dns")
FAST_PROTOS = ("r2d2", "dns")


def _engine_framing(engine):
    """The reasm Framing an engine's declared ``reasm_spec`` resolves
    to, or None when the engine (or its framing) is not columnar-
    capable — THE per-framing dispatch gate (ISSUE 13): the columnar
    lane, the vec/matrix whole-frame checks and the verdict-cache
    alignment tiers all route through this one lookup."""
    if engine is None or not getattr(engine, "reasm_columnar", False):
        return None
    spec = getattr(engine, "reasm_spec", None)
    if spec is None:
        return None
    return FRAMINGS.get(spec())


# In-process executable-cache handoff (keyed by socket path): a
# surrendering service deposits its shape-keyed prewarm ledger here so a
# same-process successor rebuilding the restored rule sources skips its
# warm launches entirely.  jax's jit executable cache is process-global
# and shape-keyed (the module-level _call_model trace twins), so
# unchanged tables recompile NOTHING across a graceful handoff — this
# ledger carries the "which shape signatures are fully warmed" half
# that would otherwise die with the instance.  A cross-process
# successor simply finds no deposit (cold prewarm; correct either way).
_HANDOFF_SHAPE_CACHE: dict[str, dict] = {}


def _gather_model(model, blob, offs, lens, remotes, width: int,
                  attr: bool = False):
    """On-device row build: gather each entry's bytes from the flat
    payload blob into the [n, width] layout the batch models consume,
    masking the padding tail to zero.  ``attr`` routes through the
    model's attributed variant (verdict + deciding-rule argmax in the
    same fused executable)."""
    import jax.numpy as jnp

    col = jnp.arange(width, dtype=jnp.int32)[None, :]
    g = jnp.clip(offs[:, None] + col, 0, blob.shape[0] - 1)
    rows = jnp.where(col < lens[:, None], blob[g], 0)
    if attr:
        return model.verdicts_attr(rows, lens, remotes)
    return model(rows, lens, remotes)


def _call_model(model, data, lens, remotes):
    """Model-as-argument trace twin of ``model(...)`` for the shape-
    keyed dispatch cache: the model's tables are jit INPUTS, so same-
    shaped rebuilds (policy churn) share one executable."""
    return model(data, lens, remotes)


def _call_model_attr(model, data, lens, remotes):
    """Model-as-argument trace twin of ``model.verdicts_attr``."""
    return model.verdicts_attr(data, lens, remotes)


class _SidecarConn:
    """Service-side state for one datapath connection."""

    __slots__ = ("conn", "client", "bufs", "engine", "fast_ok", "skip",
                 "module_id", "demoted_mod", "columnar_dead")

    def __init__(self, conn, client, engine, module_id: int = 0):
        self.conn = conn  # in-process oracle Connection
        self.client = client
        # Mirror of the datapath's unconsumed buffer, per direction
        # (False=orig/request, True=reply).
        self.bufs = {False: bytearray(), True: bytearray()}
        self.engine = engine  # batch engine for request direction, or None
        self.fast_ok = engine is not None
        # Bytes already covered by an earlier PASS/DROP verdict that
        # overshot the then-buffered input (a parser may decide on a
        # frame prefix, reference: libcilium.h OnData comment); they are
        # consumed on arrival without re-parsing.
        self.skip = {False: 0, True: 0}
        self.module_id = module_id
        # Set while this conn has been demoted off a quarantined device
        # engine onto the oracle path; remembers the module so the
        # engine can be rebound once the device heals and the oracle
        # residue drains.
        self.demoted_mod = None
        # Columnar lane-exit dead latch: the arena's overflow latch
        # when the conn left the lane with NO engine to adopt it (the
        # scalar twin of FlowState.overflowed).  The overflowed bytes
        # are gone, so every further request entry must answer a typed
        # protocol error — resuming the parse mid-stream would emit
        # wrong op byte counts on the wire.
        self.columnar_dead = False


class EpochParityError(AssertionError):
    """A staged epoch's device tables disagreed with the host oracle —
    the swap is rejected and the old epoch keeps serving."""


class _SwapJob:
    """One staged policy-table swap riding the builder queue."""

    __slots__ = ("module_id", "staged_map", "done", "status", "epoch",
                 "phase")

    def __init__(self, module_id: int, staged_map):
        self.module_id = module_id
        self.staged_map = staged_map
        self.done = threading.Event()
        self.status = int(FilterResult.UNKNOWN_ERROR)
        self.epoch = -1
        # Typestate: staged -> committed | rejected, mediated through
        # EPOCH_SWAP_PROTOCOL (a job never leaves the terminal states).
        self.phase = SWAP_STAGED


class _TabSnap:
    """One-round consistent view of the vectorized-path conn tables,
    taken under the registry lock at the start of each dispatch round so
    eligibility checks and chunk issue never race policy_update /
    new_connection table mutations (including engine slot reuse).

    Holds only the rows for the round's (sorted, unique) conn ids —
    O(round conns), not O(table size).  Out-of-range ids materialize as
    engine=-1 / dirty=1 so they fail vec eligibility naturally."""

    __slots__ = ("ids", "engine", "src", "dirty", "objs", "single",
                 "swap_s", "cache", "cache_epoch", "cache_rule", "epoch")

    def __init__(self, ids, engine, src, dirty, objs, single=False,
                 cache=None, cache_epoch=None, cache_rule=None,
                 epoch=0):
        self.ids = ids
        self.engine = engine
        self.src = src
        self.dirty = dirty
        self.objs = objs
        # True when the snapshot rows are exactly one item's conn_ids in
        # arrival order — lookups are then the identity (no search).
        self.single = single
        # Time this snapshot's lock acquisition spent blocked behind an
        # epoch-swap pointer flip (the round books it as table_swap).
        self.swap_s = 0.0
        # Verdict-cache columns for the round's conns (armed state /
        # claim epoch / claimed rule row) plus the policy epoch
        # captured under the SAME lock — a hit requires the claim epoch
        # to equal this captured epoch, so a round snapshotted before a
        # flip serves the flip-preceding epoch consistently (exactly
        # the in-flight-round contract engine rounds already follow).
        n = len(ids)
        self.cache = (
            cache if cache is not None else np.zeros(n, np.uint8)
        )
        self.cache_epoch = (
            cache_epoch if cache_epoch is not None
            else np.full(n, -1, np.int64)
        )
        self.cache_rule = (
            cache_rule if cache_rule is not None
            else np.full(n, -1, np.int32)
        )
        self.epoch = epoch

    def lookup(self, cids: np.ndarray) -> np.ndarray:
        """Positions of cids in the snapshot rows (every data-item conn
        id is in self.ids by construction)."""
        n = len(cids)
        if self.single and n == len(self.ids) and n <= len(_IDENTITY):
            return _IDENTITY[:n]
        return np.searchsorted(self.ids, cids.astype(np.int64))


# Shared identity-permutation prefix for single-item snapshot lookups.
_IDENTITY = np.arange(1 << 14)


class _ColumnarLog:
    """Batched access-log sink for the fast path: one record per device
    batch instead of one Python object per request.  The per-batch ring
    is bounded; the running counters are exact."""

    def __init__(self, maxlen: int = 4096):
        from collections import deque

        self.batches = deque(maxlen=maxlen)
        self.requests = 0
        self.denied = 0

    def log_batch(self, proto: str, n: int, denied: int) -> None:
        self.requests += n
        self.denied += denied
        self.batches.append({"proto": proto, "n": n, "denied": denied})


class VerdictService:
    """Unix-socket verdict service.

    One acceptor thread, one reader thread per shim connection, one
    dispatcher worker owning all device dispatch (so device models are
    only ever called from a single thread — jit caches stay warm and
    per-flow engine state needs no locking beyond the dispatcher's
    serialization).
    """

    def __init__(self, socket_path: str, config: DaemonConfig | None = None):
        self.socket_path = socket_path
        self.config = config or DaemonConfig()
        # Overload & fault containment: the guard owns the quarantine
        # state machine (device -> quarantine -> host fallback), the
        # dispatcher enforces the admission cap and the round watchdog
        # (-> shed).  All rungs of the ladder are typed and observable.
        self.guard = DeviceGuard(
            timeout_s=self.config.device_call_timeout_s,
            reprobe_interval_s=self.config.device_reprobe_interval_s,
            fail_threshold=self.config.device_fail_threshold,
            on_change=self._on_quarantine_change,
        )
        self._queue_age_s = self.config.shed_queue_age_ms / 1000.0
        self.dispatcher = BatchDispatcher(
            self._process,
            max_batch=self.config.batch_flows,
            timeout_ms=self.config.batch_timeout_ms,
            max_pending=self.config.shed_queue_entries,
            stall_timeout_s=self.config.device_call_timeout_s,
            on_batch_error=self._on_batch_error,
            on_stall=self._on_dispatch_stall,
        )
        # Latency decomposition: per-round stage stamps -> microsecond
        # histograms + sampled spans / slow exemplars (trace.py).  The
        # tracer is always constructed; trace_stage_metrics=False turns
        # the metric observes off (the bench's disabled baseline).
        self.tracer = VerdictTracer(
            sample_every=self.config.trace_sample_every,
            slow_ms=self.config.trace_slow_ms,
            ring=self.config.trace_ring,
            stage_metrics=self.config.trace_stage_metrics,
            batch_capacity=self.config.batch_flows,
        )
        # Flight recorder: always-on incident timeline fed from the
        # protocols.py transition observer (every mediated typestate
        # edge), overload markers, and a per-round occupancy sampler
        # riding the tracer's finish_round.  Fail-closed edges trigger
        # postmortem bundles on a detached thread (blackbox.py) — the
        # enrichment providers below take this service's locks, which
        # is exactly why they must never run on the transition thread.
        self.recorder = blackbox.FlightRecorder(
            ring=self.config.timeline_ring,
            bundle_dir=self.config.timeline_bundle_dir,
            slow_only=self.config.timeline_slow_only,
        )
        self.recorder.stage_provider = self.tracer.status
        self.recorder.status_provider = self._postmortem_status
        self.recorder.occupancy_probe = self._occupancy_probe
        self.recorder.install()
        self.tracer.recorder = self.recorder
        # Device-economics ledger: every executable-producing site
        # routes through ledger.record_compile (lint R23 proves it)
        # and every dispatch round's formation stamp rides the
        # tracer's finish_round — compile causes and batch-formation
        # provenance become recorded data (ledger.py).
        self.ledger = ledger_mod.DeviceLedger(
            ring=self.config.timeline_ring,
        )
        self.ledger.install()
        self.tracer.ledger = self.ledger
        # Containment telemetry (status/metrics).
        self.shed_entries = 0
        self.batch_crashes = 0
        self.fallback_entries = 0
        self.error_entries = 0
        self._lock = threading.Lock()  # conn/engine registry
        self._conns: dict[int, _SidecarConn] = {}
        self._engines: dict[tuple, object] = {}
        self._listener: socket.socket | None = None
        self._threads: list[threading.Thread] = []
        self._clients: list["_ClientHandler"] = []
        self._stopped = False
        self.fast_log = _ColumnarLog()
        # Per-batch-size scratch for verdict frame assembly (op pattern
        # template + constant columns) — bounds per-frame numpy work to
        # one template copy and two strided stores.
        self._frame_tpl: dict[int, tuple] = {}
        # Per-stage CPU accounting of the group fast path (seam_probe
        # runs only): stage -> [calls, thread-CPU seconds].  This is the
        # published seam breakdown the latency bench reports.
        self.seam_stages: dict[str, list] = {}
        # Vectorized-path conn table: parallel arrays indexed by conn_id
        # (grown on demand) so batch eligibility and remote-identity
        # lookups are O(1) numpy gathers instead of per-entry dict walks.
        self._tab_size = 0
        self._tab_engine = np.empty(0, np.int32)  # engine idx, -1 = none
        self._tab_src = np.empty(0, np.int32)  # remote identity (src_id)
        self._tab_dirty = np.empty(0, np.uint8)  # 1 = residual state
        # In-flight columnar-round refcount per conn (guarded by _lock,
        # bulk np.add.at updates): the array twin of _async_pending for
        # the reassembler lane, consulted by the sync-round deferral,
        # the epoch flip and the stale-conn catch-up so a later round
        # can never overtake an issued-not-finished columnar round.
        self._tab_async = np.empty(0, np.uint32)
        # Established-flow verdict cache (policy/invariance.py): per-
        # conn byte-invariance claims as parallel arrays so the hit
        # check is one vectorized mask per round.  State: 0 unchecked,
        # 1 armed (invariant-allow), 2 checked-no-claim.  A hit
        # additionally requires the claim epoch to equal the round's
        # snapshot epoch — the structural invalidation: every pointer
        # flip retires all armed rows without touching them.
        self._flow_cache_on = self.config.flow_cache
        self._tab_cache = np.empty(0, np.uint8)
        self._tab_cache_epoch = np.empty(0, np.int64)
        self._tab_cache_rule = np.empty(0, np.int32)
        # Last-HIT recency stamp per armed row: at the
        # flow_cache_entries cap the least-recently-hit row is evicted
        # (LRU) instead of new flows silently never arming.
        self._tab_seen_tick = np.empty(0, np.int64)
        self._cache_tick = 0
        self._cache_armed = 0  # armed rows (flow_cache_entries cap)
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_invalidations = 0
        self.cache_evictions = 0
        self._engine_objs: list[object] = []
        self._engine_idx: dict[int, int] = {}  # id(engine) -> table idx
        self._engine_free: list[int] = []
        self._objs_cache: tuple | None = None  # invalidated on mutation
        # Flow-level verdict observability: the per-node record ring
        # MSG_OBSERVE / `cilium observe` reads.  flow_observe=False
        # removes record emission and the attributed device call (the
        # flow_observe_overhead bench's disabled baseline).
        self._flow_observe = self.config.flow_observe
        self.flowlog = (
            FlowLog(capacity=self.config.flowlog_ring,
                    opts=self.config.opts)
            if self._flow_observe else None
        )
        # id(model) -> (model, jitted fn); the model reference pins the
        # id so a gc'd model can never alias a cache entry.
        self._jit_cache: dict[int, tuple] = {}
        self._jit_gather: dict[int, tuple] = {}
        self._jit_attr: dict[int, tuple] = {}
        # Shape signatures prewarm has fully warmed (every bucket, both
        # row and gather paths): a churn rebuild whose tables land in
        # the same buckets skips its warm launches entirely.
        self._prewarmed_shapes: dict = {}
        self._exec_device = None
        if self.config.verdict_device == "cpu":
            import jax

            self._exec_device = jax.devices("cpu")[0]
        # Multi-chip sharded serving (parallel/rulesharding.py): the
        # (flows, rules) mesh resolves lazily at the FIRST engine
        # build (a service that never dispatches must not initialize a
        # backend) and is guarded by _mesh_lock.  A faulting mesh rung
        # walks a WIDTH LADDER instead of collapsing binary: full mesh
        # -> reshaped mesh over the surviving devices -> single-chip
        # fallback -> quarantine/host-oracle, every transition typed
        # (the guard's quarantine/heal ladder keeps owning
        # single-device health on the rung below).
        self._mesh = None
        self._mesh_resolved = False
        self._mesh_lock = threading.Lock()
        self._mesh_demoted: str | None = None
        self.mesh_demotions: dict[str, int] = {}
        # Width-ladder rung state.  The rung is DERIVED, never stored:
        # full = (_mesh_demoted None, _mesh_serving None); reshaped =
        # (None, Mesh over survivors); fallback = (reason, *).
        # _mesh_serving is the degraded mesh the engines currently
        # dispatch on; _mesh_lost is the attributed dead device-id set
        # (mirrors the DeviceGuard per-device health table).
        self._mesh_serving = None
        self._mesh_lost: set[int] = set()
        self.mesh_reshapes = 0
        self.mesh_reshape_failures: dict[str, int] = {}
        # Fallback-width window of the LAST completed reshape (fault
        # stamp -> reshaped flip), the bench drift-guard metric.
        self.mesh_reshape_window_ms = 0.0
        self._mesh_fault_at = 0.0
        # Capacity fraction of the current rung (1.0 full, width ratio
        # reshaped, 1/width fallback) — scales the admission queue cap
        # and the DRR credit windows so a degraded mesh sheds typed at
        # its actual capacity.
        self._mesh_capacity = 1.0
        # Test seam: per-device probe callable (device -> bool).  None
        # uses the real put+readback probe.
        self._device_probe_fn = None
        # Mesh ladder state staged by restore_handoff, consumed at
        # _resolve_mesh (a successor resumes reshaped instead of
        # re-probing a known-dead chip).
        self._handoff_mesh: dict | None = None
        # ROADMAP 5b: an explicit flow extent wider than the smallest
        # dispatch bucket grows the minimum bucket to match (set at
        # _resolve_mesh, read by the _min_bucket property).
        self._mesh_min_bucket = 0
        # Guarded re-promotion (ROADMAP 1b): demotion is no longer
        # sticky-until-restart — a timed re-probe (mirroring the
        # DeviceGuard quarantine heal, but on the policy-builder
        # thread) rebuilds one sharded executable off-path,
        # parity-probes it against the single-chip fallback, and flips
        # the retained sharded wrappers back in one pointer pass.
        self._mesh_reprobe_last = 0.0
        self._mesh_reprobe_inflight = False
        self.mesh_repromotions = 0
        # ROADMAP 1c: demotion-era engines re-sharded by the heal's
        # queued rebinds (status surface; see _run_mesh_rebuild).
        self.mesh_rebind_rebuilds = 0
        self.vec_batches = 0
        self.vec_entries = 0
        # Rounds (and their entries) judged whole by _run_mat_group: one
        # gather, one issue and one answer per client for the round.
        self.whole_rounds = 0
        self.whole_entries = 0
        # Completion pipeline: the dispatcher issues device calls without
        # blocking (jax arrays are futures); this FIFO queue + worker
        # materializes results and sends responses, so host batch
        # assembly overlaps device compute and the device round-trip
        # latency never stalls the dispatch loop.  FIFO order preserves
        # per-connection op order across vec and entrywise rounds.
        self._completions: "queue.Queue" = queue.Queue()
        self._completion_thread: threading.Thread | None = None
        self._sends: "queue.Queue" = queue.Queue()
        self._send_thread: threading.Thread | None = None
        # Greedy dispatch (batch_timeout_ms == 0) implies a co-located
        # device whose readback is cheap: complete rounds inline on the
        # dispatcher thread — one fewer thread handoff per verdict.
        # ALL sends must then go inline (vec and entrywise) so per-conn
        # FIFO order is owned by one thread.
        self._inline_complete = self.config.batch_timeout_ms <= 0
        # Conns with an issued-but-unfinished async entrywise round
        # (refcounts; guarded by _lock).  Sync rounds touching them are
        # deferred to the send thread — see _process_entrywise.
        self._async_pending: dict[int, int] = {}
        # Columnar reassembly engine (sidecar/reasm.py): the mixed-path
        # slow lane's carry buffers, frame splitting and op assembly as
        # array passes per ROUND.  Pipelined mode only (greedy rounds
        # are 1-2 small messages — the columnar fixed cost loses); the
        # scalar engine path survives as the oracle/fallback rung.
        self._reasm = (
            Reassembler(
                cap_per_conn=self.config.max_flow_buffer,
                arena_capacity=self.config.reasm_arena_bytes,
            )
            if self.config.reasm and not self._inline_complete
            else None
        )
        # Columnar rounds that bailed back to the scalar rung, by
        # reason (status surface: a silent fallback must be visible).
        self.reasm_fallbacks: dict[str, int] = {}
        # Cut-through telemetry (greedy mode): rounds processed directly
        # on the shim reader thread, skipping the dispatcher handoff.
        self.inline_batches = 0
        self._prev_switch_interval: float | None = None
        # Transport ladder telemetry: attach rejections (no peer object
        # to count them on) and ring-delivered entry totals.  Per-
        # session ring/fallback state lives on each _ClientHandler.
        self.transport_rejects: dict[str, int] = {}
        self.shm_entries = 0
        # Multi-tenant fan-in: one SessionState per accepted shim
        # connection (transport.py).  _sess_lock guards the registry
        # only — never held across blocking work.  Dead sessions are
        # retained (bounded) so an operator can attribute a shed or
        # quarantine to a pod AFTER it died.
        self._sess_lock = threading.Lock()
        self._sessions: dict[int, SessionState] = {}
        self._dead_sessions: "deque[dict]" = _deque(maxlen=32)
        self._session_seq = 0
        # Reconnect-storm tracking per announced identity (bounded LRU
        # — see _session_hello): monotonic connect stamps inside the
        # rolling window.  _metric_idents is the bounded Prometheus
        # label vocabulary for per-session metrics.
        self._ident_connects: dict[str, "deque[float]"] = {}
        self._metric_idents: set[str] = set()
        # DRR admission fairness: the per-session credit window
        # (outstanding entries), recomputed lazily at most every 50ms.
        self._share_val = self.config.shed_queue_entries
        self._share_ts = 0.0
        # Segment-reclaim timers for sessions that died without
        # MSG_SHM_DETACH (cancelled at stop()).
        self._reclaim_timers: list[threading.Timer] = []
        self.shm_reclaims = 0
        # Policy-table epochs (guarded by _lock where noted).  Every
        # committed rule-table generation gets a monotonic epoch:
        # engines are stamped with the epoch they were compiled under,
        # in-flight rounds finish on the epoch their snapshot captured,
        # and flow records carry the epoch so a rule id is never
        # resolved against a table it did not index.
        self.policy_epoch = 0
        # Staged compile-then-swap runs on ONE builder thread so the
        # dispatch path never pays an XLA compile: the handler stages
        # the host-compiled policy map, the builder rebuilds device
        # engines + asserts per-epoch parity OFF-PATH, and the commit
        # is a pointer flip under _lock (bounded; surfaced as the
        # round decomposition's table_swap stage).
        self._build_queue: "queue.Queue" = queue.Queue()
        self._builder_thread: threading.Thread | None = None
        # Conn ids with an in-flight builder rebind (quarantine-heal
        # path) so the dispatch loop never compiles and never
        # double-submits; guarded by _lock.
        self._rebind_inflight: set[int] = set()
        # Conns a swap could not rebind (in-flight deferred round /
        # undrained engine ops at flip time): they finish on their
        # captured engine, and the entrywise path catches them up to
        # the current epoch — migrating the retained buffer — once the
        # round drains.  Guarded by _lock; read lock-free (set
        # membership) on the dispatch path.
        self._stale_conns: set[int] = set()
        # Most recent swap's lock-hold window (monotonic start, end):
        # rounds whose snapshot acquisition overlapped it book the
        # overlap as their table_swap stage.
        self._swap_window = (0.0, 0.0)
        self.policy_swaps = 0
        self.policy_swap_failures: dict[str, int] = {}
        self.last_swap_ms = 0.0
        # Hitless restart (Envoy-hot-restart-style handoff + PR 1
        # fencing semantics).  restart_generation is the monotonic
        # fencing token: a successor that pulled our snapshot runs at
        # generation+1, and the surrendered (fenced) predecessor
        # rejects every late write TYPED — policy updates NACK
        # FilterResult.FENCED, data frames shed SHED_FENCED — so a
        # zombie old process can never serve a verdict the successor's
        # epoch would contradict.
        self.restart_generation = 1
        self._fenced = False
        self.fence_rejects = 0
        self._path_released = False  # surrendered the socket path
        self.handoff_at = 0.0  # monotonic: when WE surrendered
        self.handoff_loaded_at = 0.0  # monotonic: snapshot restored
        self.handoff_ts = 0.0  # predecessor's wall-clock stamp
        # Restored-but-not-yet-replayed state from a predecessor's
        # snapshot: consumed (popped) as clients replay their sessions,
        # conns and grants against us — a replayed row matching the
        # snapshot revalidates in place (counted); anything left over
        # is just forgotten (the client replay is authoritative).
        self._handoff_sessions: dict[str, dict] = {}
        self._handoff_conns: dict[int, dict] = {}
        self._handoff_grants: dict[int, tuple] = {}
        self._handoff_residue: dict[int, dict] = {}
        self._handoff_rules: list = []
        self.handoff_session_restores = 0
        self.handoff_conn_restores = 0
        self.handoff_grant_restores = 0
        self.handoff_residue_restores = 0
        self.handoff_warm_shapes = 0
        self.handoff_refused: dict[str, int] = {}
        self.shm_stale_swept = 0  # startup /dev/shm orphan sweep

    # -- lifecycle --------------------------------------------------------

    # GIL switch interval while a greedy (co-located) service is up.
    # The interpreter default is 5ms — on a small host one Python thread
    # mid-bytecode can stall every other seam thread for 5ms, which IS
    # the latency tail.  0.5ms was chosen by sweep: lower values (50µs)
    # make jax's internal mutexes spin under contention (measured
    # ~400µs of burned thread-CPU per device call), higher ones grow
    # the convoy tail.
    GIL_SWITCH_INTERVAL_S = float(
        os.environ.get("CILIUM_TPU_GIL_SWITCH_S", 5e-4)
    )

    def start(self) -> "VerdictService":
        configure_compile_cache()
        if self._inline_complete:
            import sys

            self._prev_switch_interval = sys.getswitchinterval()
            sys.setswitchinterval(self.GIL_SWITCH_INTERVAL_S)
        # Startup stale-segment sweep: a kill -9'd predecessor's shm
        # orphans (owner pid dead, lease expired) are force-unlinked
        # before serving — in-service reclaim timers die with their
        # service, so without this sweep crash orphans leak until
        # reboot.
        self.shm_stale_swept = sweep_stale_segments(
            self.config.shm_lease_s
        )
        if self.shm_stale_swept:
            metrics.SidecarStaleSegmentsSwept.inc(
                amount=self.shm_stale_swept
            )
            log.info(
                "swept %d stale predecessor shm segments",
                self.shm_stale_swept,
            )
        # Graceful takeover: if a live predecessor still owns the
        # socket path, pull its handoff snapshot over the side channel
        # BEFORE unlinking the path out from under it.  Any failure
        # falls through to the cold-boot path below — cold state is
        # always correct (stale-segment reclaim + grant revalidation +
        # client replay), it just isn't warm.
        self._pull_handoff()
        if os.path.exists(self.socket_path):
            os.unlink(self.socket_path)
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self.socket_path)
        self._listener.listen(16)
        self.dispatcher.start()
        self._completion_thread = threading.Thread(
            target=self._completion_loop, name="verdict-complete", daemon=True
        )
        self._completion_thread.start()
        self._send_thread = threading.Thread(
            target=self._send_loop, name="verdict-send", daemon=True
        )
        self._send_thread.start()
        self._builder_thread = threading.Thread(
            target=self._policy_builder_loop, name="policy-builder",
            daemon=True,
        )
        self._builder_thread.start()
        t = threading.Thread(target=self._accept_loop, daemon=True)
        t.start()
        self._threads.append(t)
        return self

    def stop(self) -> None:
        self._stopped = True
        # Deregister from the process-wide transition observer first: a
        # stopping service must not record (or bundle) its neighbors'
        # edges in multi-service processes (handoff).
        self.recorder.uninstall()
        self.ledger.uninstall()
        # shutdown BEFORE close: the acceptor thread parked in accept()
        # holds the fd, and a bare close() defers the kernel teardown —
        # the listener would keep accepting into its backlog and a
        # reconnecting shim would attach to this ZOMBIE service (whose
        # dispatcher is dead) instead of failing over to the restarted
        # one.  Unlink the path immediately for the same reason.
        if self._listener is not None:
            shutdown_close(self._listener)
        if not self._path_released:
            # A surrendered (fenced) service already released the path
            # to its successor — unlinking here would delete the
            # SUCCESSOR's fresh socket.
            try:
                os.unlink(self.socket_path)
            except OSError:
                pass
        # Close shim connections so their reader/writer peers see EOF
        # immediately (a restarting shim must not block in recv on a
        # dead service).
        with self._lock:
            clients = list(self._clients)
        for client in clients:
            shutdown_close(client.sock)
        self.dispatcher.stop()
        if self._builder_thread is not None:
            self._build_queue.put(None)
            self._builder_thread.join(timeout=5)
        if self._completion_thread is not None:
            self._completion_put(("stop",))
            self._completion_thread.join(timeout=5)
        if self._send_thread is not None:
            self._send_thread.join(timeout=5)
        # Pending shm-segment reclaims die with the service (the lease
        # contract is per-service-life; a replacement service cannot
        # tell a leased orphan from a live session's rings anyway).
        with self._sess_lock:
            timers, self._reclaim_timers = self._reclaim_timers, []
        for t in timers:
            t.cancel()
        # (The socket path was unlinked up front — a second unlink here
        # could delete a RESTARTED service's fresh socket.)
        if self._prev_switch_interval is not None:
            import sys

            sys.setswitchinterval(self._prev_switch_interval)
            self._prev_switch_interval = None

    def _accept_loop(self) -> None:
        while not self._stopped:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            if self._stopped:
                # Raced stop(): never hand a connection to a dead
                # service — the peer must see EOF and fail over.
                shutdown_close(sock)
                return
            client = _ClientHandler(self, sock)
            with self._lock:
                self._clients.append(client)
            t = threading.Thread(target=client.read_loop, daemon=True)
            t.start()
            self._threads.append(t)

    # -- hitless restart: handoff snapshot / restore / fencing ------------

    def snapshot_handoff(self) -> dict:
        """Serialize the state a successor needs to serve warm: policy
        epoch, session identities, conn registry rows, armed grant
        rows, per-conn flow-buffer residue, the live rule-source index
        and the quarantine latch — one versioned JSON-safe dict (the
        Envoy hot-restart parent->child state transfer, over our side
        channel).  Every field written here is consumed by
        ``restore_handoff`` or explicitly versioned-out (lint R17
        audits the pair)."""
        with self._sess_lock:
            sessions = [
                {
                    "identity": s.identity,
                    "submitted": int(s.submitted),
                    "answered": int(s.answered),
                }
                for s in self._sessions.values()
                if s.named
            ]
        conns: list = []
        grants: list = []
        residue: list = []
        rules: set = set()
        with self._lock:
            epoch = self.policy_epoch
            for key in self._engines:
                _mod, policy_name, ingress, port, proto = key
                rules.add((policy_name, bool(ingress), int(port or 0),
                           proto))
            for cid, sc in self._conns.items():
                c = sc.conn
                conns.append({
                    "conn_id": int(cid),
                    "policy": c.policy_name,
                    "ingress": bool(c.ingress),
                    "src_id": int(c.src_id),
                    "proto": c.parser_name,
                })
                # Residue lives wherever the conn's lane keeps it: the
                # engine flow buffer (fast path), the columnar arena
                # carry, or the oracle mirror in sc.bufs — composed in
                # _demote_to_oracle's order (engine bytes precede
                # arena carry precede the mirror) so the successor's
                # oracle parses the stream exactly as the predecessor
                # would have.  Reads are non-destructive: the conn
                # keeps serving unchanged if the handoff aborts.
                ro = b""
                flows = getattr(sc.engine, "flows", None)
                if flows is not None:
                    flow = flows.get(cid)
                    if flow is not None and getattr(
                        flow, "buffer", None
                    ):
                        ro = bytes(flow.buffer)
                if self._reasm is not None:
                    ro += self._reasm.arena.peek(cid)
                ro += bytes(sc.bufs[False])
                rr = bytes(sc.bufs[True])
                if ro or rr or sc.skip[False] or sc.skip[True]:
                    residue.append({
                        "conn_id": int(cid),
                        "orig": base64.b64encode(ro).decode("ascii"),
                        "reply": base64.b64encode(rr).decode("ascii"),
                        "skip_orig": int(sc.skip[False]),
                        "skip_reply": int(sc.skip[True]),
                    })
                if (
                    self._flow_cache_on
                    and cid < self._tab_size
                    and self._tab_cache[cid] == 1
                ):
                    grants.append({
                        "conn_id": int(cid),
                        "epoch": int(self._tab_cache_epoch[cid]),
                        "rule": int(self._tab_cache_rule[cid]),
                    })
        return {
            "version": wire.HANDOFF_VERSION,
            "generation": self.restart_generation,
            "ts": time.time(),
            "socket_path": self.socket_path,
            "policy_epoch": epoch,
            "sessions": sessions,
            "conns": conns,
            "grants": grants,
            "residue": residue,
            "rules": [
                {"policy": p, "ingress": i, "port": pt, "proto": pr}
                for p, i, pt, pr in sorted(rules)
            ],
            "guard": self.guard.snapshot_state(),
            # Mesh width-ladder rung: the successor resumes RESHAPED
            # around the known-dead chips instead of re-probing them
            # through a fault (consumed at _resolve_mesh).
            "mesh": {
                "lost": sorted(int(x) for x in self._mesh_lost),
                "reshapes": int(self.mesh_reshapes),
            },
        }

    def restore_handoff(self, snap: dict) -> bool:
        """Successor half: adopt a predecessor's snapshot.  Version-
        gated (a FUTURE snapshot version is refused typed — cold boot
        serves correctly); restores the committed policy epoch, the
        restart generation (+1 — the fencing token), the quarantine
        latch, and stages sessions/conns/grants/residue for the client
        replay to revalidate row by row."""
        try:
            version = int(snap.get("version", -1))
            generation = int(snap["generation"])
            epoch = int(snap["policy_epoch"])
        except (KeyError, TypeError, ValueError):
            self.handoff_refused["malformed"] = (
                self.handoff_refused.get("malformed", 0) + 1
            )
            return False
        if version < 1 or version > wire.HANDOFF_VERSION:
            # Versioned-out: a snapshot from a NEWER schema is refused
            # whole (never half-parsed) — cold boot is always correct.
            self.handoff_refused["version"] = (
                self.handoff_refused.get("version", 0) + 1
            )
            return False
        if snap.get("socket_path") != self.socket_path:
            self.handoff_refused["path-mismatch"] = (
                self.handoff_refused.get("path-mismatch", 0) + 1
            )
            return False
        self.restart_generation = generation + 1
        self.policy_epoch = epoch
        self.handoff_ts = float(snap.get("ts") or 0.0)
        self.handoff_loaded_at = time.monotonic()
        self._handoff_sessions = {
            r["identity"]: r
            for r in snap.get("sessions") or []
            if r.get("identity")
        }
        self._handoff_conns = {
            int(r["conn_id"]): r for r in snap.get("conns") or []
        }
        self._handoff_grants = {
            int(r["conn_id"]): (int(r["epoch"]), int(r["rule"]))
            for r in snap.get("grants") or []
        }
        self._handoff_residue = {
            int(r["conn_id"]): r for r in snap.get("residue") or []
        }
        self._handoff_rules = list(snap.get("rules") or [])
        self.guard.restore_state(snap.get("guard") or {})
        # Versioned-in mesh ladder state (.get: absent in pre-PR-17
        # snapshots — cold mesh resolution is always correct).  Staged
        # only; consumed when the mesh actually resolves.
        mesh_row = snap.get("mesh")
        if isinstance(mesh_row, dict):
            try:
                self._handoff_mesh = {
                    "lost": sorted(
                        {int(x) for x in mesh_row.get("lost") or ()}
                    ),
                    "reshapes": int(mesh_row.get("reshapes") or 0),
                }
            except (TypeError, ValueError):
                self._handoff_mesh = None
        # Executable-cache adoption (same-process successor only): the
        # restored rule sources rebuild into the SAME shape signatures,
        # so the deposited prewarm ledger makes churn rebuilds skip
        # their warm launches — no cold recompile of unchanged tables.
        warmed = _HANDOFF_SHAPE_CACHE.pop(self.socket_path, None)
        if warmed:
            self._prewarmed_shapes.update(warmed)
            self.handoff_warm_shapes = len(warmed)
        metrics.SidecarRestartGeneration.set(
            float(self.restart_generation)
        )
        log.info(
            "handoff snapshot restored: generation %d -> %d, epoch %d, "
            "%d sessions, %d conns, %d grants, %d residue rows, "
            "%d warm shapes",
            generation, self.restart_generation, epoch,
            len(self._handoff_sessions), len(self._handoff_conns),
            len(self._handoff_grants), len(self._handoff_residue),
            self.handoff_warm_shapes,
        )
        return True

    def handoff_surrender(
        self, successor_gen: int, deadline_s: float
    ) -> tuple[dict | None, str]:
        """Predecessor half (runs on the requesting handler's reader
        thread): quiesce, snapshot, fence, release the socket path.
        After this returns the service is a ZOMBIE — it answers
        nothing new (typed rejects only) and exists solely so late
        writers get their typed refusal instead of silence.  A stale
        claimant (generation <= ours, PR 1 fencing semantics) and a
        second claimant (already fenced) are both refused typed."""
        if 0 < successor_gen <= self.restart_generation:
            self.handoff_refused["stale-generation"] = (
                self.handoff_refused.get("stale-generation", 0) + 1
            )
            return None, (
                f"stale successor generation {successor_gen} <= "
                f"{self.restart_generation}"
            )
        with self._lock:
            if self._fenced:
                self.handoff_refused["already-fenced"] = (
                    self.handoff_refused.get("already-fenced", 0) + 1
                )
                return None, "already fenced by an earlier successor"
            self._fenced = True
        # Quiesce bounded by the successor's declared deadline: rounds
        # in flight at surrender are answered by THIS process (the
        # cross-restart exactly-once contract's "old process" arm).
        # The fence above already stops new data admission
        # (_fanin_admit sheds SHED_FENCED), so the queue only drains.
        self.dispatcher.flush(timeout=max(deadline_s, 0.0))
        self.dispatcher.fenced = True
        snap = self.snapshot_handoff()
        # Deposit the warm-shape ledger for a same-process successor
        # (see _HANDOFF_SHAPE_CACHE).
        if self._prewarmed_shapes:
            _HANDOFF_SHAPE_CACHE[self.socket_path] = dict(
                self._prewarmed_shapes
            )
        # Release the listener and the path so the successor can bind:
        # shutdown (not bare close) pops the acceptor thread out of
        # accept() immediately.
        listener = self._listener
        if listener is not None:
            shutdown_close(listener)
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
        self._path_released = True
        self.handoff_at = time.monotonic()
        metrics.SidecarHandoffSurrenders.inc()
        log.warning(
            "handoff surrendered (generation %d, epoch %d): fenced, "
            "socket path released", self.restart_generation,
            snap["policy_epoch"],
        )
        return snap, ""

    def _pull_handoff(self) -> None:
        """Successor half of the side channel: dial the predecessor's
        socket (we have not bound yet), request its snapshot
        (MSG_HANDOFF), restore it.  Every failure — no predecessor,
        dead socket (crash restart), timeout, refusal, malformed reply
        — degrades to the cold-boot path, which is always correct."""
        if not self.config.restart_handoff:
            return
        if not os.path.exists(self.socket_path):
            return
        deadline = self.config.handoff_deadline_s
        try:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.settimeout(deadline)
            sock.connect(self.socket_path)
        except OSError:
            return  # crash restart: the path is a dead remnant
        try:
            wire.send_msg(
                sock, wire.MSG_HANDOFF, wire.pack_handoff(0, deadline)
            )
            reader = wire.BufferedReader(sock)
            while True:
                msg_type, payload = reader.recv_msg()
                if msg_type == wire.MSG_HANDOFF_REPLY:
                    break
            snap, err = wire.unpack_handoff_reply(payload)
            if snap is None:
                self.handoff_refused["predecessor"] = (
                    self.handoff_refused.get("predecessor", 0) + 1
                )
                log.warning("handoff refused by predecessor: %s", err)
                return
            self.restore_handoff(snap)
        except Exception:  # noqa: BLE001 — cold boot serves correctly
            log.warning(
                "handoff pull failed; starting cold", exc_info=True
            )
        finally:
            shutdown_close(sock)

    # -- control plane (called from client reader threads) ----------------

    def open_module(self, params, debug: bool) -> int:
        return pl.open_module(params, debug)

    def status(self) -> dict:
        """Service counters for operators/status/bugtool (the
        reference's nearest analog is the Envoy admin surface the agent
        scrapes for `cilium status`)."""
        with self._lock:
            n_conns = len(self._conns)
            n_engines = len(self._engines)
            clients = list(self._clients)
        return {
            "connections": n_conns,
            "engines": n_engines,
            # Transport ladder (shm fast path vs socket fallback): one
            # entry per live shim session — mode, ring occupancy/credit
            # cursors, doorbell batching, fallback counters.
            "transport": {
                "sessions": [c.transport_status() for c in clients],
                "rejects": dict(self.transport_rejects),
                "shm_entries": self.shm_entries,
                "shm_reclaims": self.shm_reclaims,
            },
            # Fan-in sessions: one row per live shim session (identity,
            # state, exactly-once counters, per-reason sheds/
            # quarantines) plus the bounded post-mortem ring — the
            # operator's per-pod attribution surface.
            "sessions": {
                "live": [
                    s.status() for s in sorted(
                        self._session_rows(), key=lambda s: s.id
                    )
                ],
                "dead": list(self._dead_sessions),
                "fair_share": self._share_val,
            },
            # Multi-chip mesh rung: layout + demotion state; None when
            # multi-chip serving is off or no engine has resolved it.
            "mesh": self._mesh_status(),
            # Policy-table epoch churn: the committed epoch, swap
            # counters, and typed fail-closed rejections (the old
            # epoch kept serving through every one of them).
            "policy": {
                "epoch": self.policy_epoch,
                "swaps": self.policy_swaps,
                "swap_failures": dict(self.policy_swap_failures),
                "pending_builds": self._build_queue.qsize(),
                "last_swap_ms": self.last_swap_ms,
            },
            # Hitless-restart surface: the fencing generation, handoff
            # age/restore counters (successor side), the zombie's typed
            # rejects (predecessor side), and the startup orphan sweep.
            "restart": {
                "generation": self.restart_generation,
                "fenced": self._fenced,
                "fence_rejects": self.fence_rejects,
                "handoff_age_s": (
                    round(time.monotonic() - self.handoff_loaded_at, 3)
                    if self.handoff_loaded_at else None
                ),
                "handoff_refused": dict(self.handoff_refused),
                "session_restores": self.handoff_session_restores,
                "conn_restores": self.handoff_conn_restores,
                "grant_restores": self.handoff_grant_restores,
                "residue_restores": self.handoff_residue_restores,
                "warm_shapes": self.handoff_warm_shapes,
                "stale_segments_swept": self.shm_stale_swept,
            },
            "requests": self.fast_log.requests,
            "denied": self.fast_log.denied,
            "vec_batches": self.vec_batches,
            "vec_entries": self.vec_entries,
            # How often the whole-round matrix path engages (of the
            # vec_batches/vec_entries above); the rest of the matrix
            # rounds took the per-item route.
            "vec": {
                "whole_rounds": self.whole_rounds,
                "whole_entries": self.whole_entries,
            },
            "inline_batches": self.inline_batches,
            "dispatcher": {
                "batches": self.dispatcher.batches,
                "entries": self.dispatcher.entries,
                "fill": self.dispatcher.fill_dispatches,
                "deadline": self.dispatcher.deadline_dispatches,
                "queue_depth": self.dispatcher.pending_weight,
                "queue_oldest_ms": round(
                    self.dispatcher.oldest_age_s() * 1e3, 3
                ),
                "stall_deposals": self.dispatcher.stall_deposals,
                "shed_submits": self.dispatcher.shed_submits,
                "busy_seconds": round(self.dispatcher.busy_seconds, 3),
            },
            # Latency decomposition (sidecar/trace.py): per-stage means
            # by serving path + span/exemplar counters.
            "latency": self.tracer.status(),
            # Flight recorder (sidecar/blackbox.py): timeline ring
            # occupancy, fail-closed event/bundle counters, unified
            # serving-tier rungs.
            "timeline": self.recorder.status(),
            # Device-economics ledger (sidecar/ledger.py): compile
            # causes, the dispatch-path-compile invariant counter,
            # resident executables, and per-trigger batch-formation
            # provenance.
            "ledger": {
                **self.ledger.status(),
                "formation": self.ledger.formation(),
            },
            # Flow-record ring occupancy (flowlog/): None = disabled.
            "flowlog": (
                self.flowlog.stats() if self.flowlog is not None else None
            ),
            # Columnar reassembly engine (sidecar/reasm.py): round/
            # frame counters + arena occupancy; None = disabled (greedy
            # mode or reasm=False).  The tier-1 mixed smoke asserts
            # rounds > 0 so a silent fallback to the scalar rung can
            # never go green.
            "reasm": (
                {**self._reasm.status(),
                 "fallbacks": dict(self.reasm_fallbacks)}
                if self._reasm is not None else None
            ),
            # Established-flow verdict cache: armed rows + hit/miss/
            # invalidation counters; None = disabled (flow_cache off —
            # the true baseline).
            "flow_cache": (
                {
                    "armed": self._cache_armed,
                    "cap": self.config.flow_cache_entries,
                    "hits": self.cache_hits,
                    "misses": self.cache_misses,
                    "invalidations": self.cache_invalidations,
                    "evictions": self.cache_evictions,
                }
                if self._flow_cache_on else None
            ),
            # Degradation ladder: device -> quarantine -> host fallback
            # -> shed.  Every rung typed and counted.
            "containment": {
                "shed_entries": self.shed_entries,
                "error_entries": self.error_entries,
                "batch_crashes": self.batch_crashes,
                "fallback_entries": self.fallback_entries,
                **self.guard.status(),
            },
        }

    def _session_rows(self) -> list:
        with self._sess_lock:
            return list(self._sessions.values())

    def trace_dump(self, n: int = 100, kind: str | None = None,
                   session: int | None = None) -> dict:
        """Span-ring snapshot + tracer status for `cilium sidecar
        trace` (MSG_TRACE).  ``session`` filters spans to one fan-in
        session so a shed/slow exemplar can be pinned to a pod."""
        return {
            "spans": self.tracer.spans(n, kind, session=session),
            "latency": self.tracer.status(),
        }

    def timeline_dump(self, n: int = 100, since: int = 0,
                      table: str | None = None) -> dict:
        """Timeline snapshot for `cilium sidecar timeline`
        (MSG_TIMELINE): declared-edge events (filtered by minimum seq
        and/or table), occupancy buckets, postmortem summaries, and
        the recorder's own status."""
        return self.recorder.dump(n=n, since=since, table=table)

    def ledger_dump(self, n: int = 100, since: int = 0,
                    cause: str | None = None) -> dict:
        """Ledger snapshot for `cilium sidecar ledger` (MSG_LEDGER):
        compile events (filtered by minimum seq and/or cause), the
        per-trigger formation summary, and the ledger's own status."""
        return self.ledger.dump(n=n, since=since, cause=cause)

    def _postmortem_status(self) -> dict:
        """The status() sections a postmortem bundle carries — the
        fail-closed-relevant subset (mesh rung, guard ladder, policy
        epoch, dispatcher depth), NOT the full status: bundles must
        stay small enough to write under incident load.  Runs on the
        recorder's bundle thread only (takes this service's locks)."""
        full = self.status()
        return {
            k: full.get(k)
            for k in ("mesh", "containment", "policy", "dispatcher",
                      "sessions", "transport", "flow_cache")
        }

    def _occupancy_probe(self) -> tuple:
        """Queue depth + admission headroom for the occupancy sampler
        (plain attribute reads — called once per dispatch round)."""
        d = self.dispatcher
        cap = d.max_pending
        depth = d.pending_weight
        headroom = (max(cap - depth, 0) / cap) if cap else None
        return depth, headroom

    def close_module(self, module_id: int) -> None:
        pl.close_module(module_id)

    def policy_update(self, module_id: int,
                      policies_json: bytes) -> tuple[int, int]:
        """Non-stop policy churn entry: stage, build off-path, swap.

        Parse + host policy compile run here (fast, and a failure NACKs
        with the active policy untouched — the old contract).  The
        expensive half — device table rebuild + jit prewarm + per-epoch
        parity — runs on the builder thread so no dispatch round ever
        pays a compile; the commit is one pointer flip under _lock.
        Returns (status, committed epoch): OK means the new epoch IS
        serving; any failure is fail-closed — the previous epoch keeps
        serving bit-identically and the failure is typed
        (policy_swap_failures_total{reason})."""
        if self._fenced:
            # Zombie predecessor: the successor owns the epoch line now.
            # Typed NACK — the caller retries against the new socket.
            self.fence_rejects += 1
            metrics.SidecarFenceRejects.inc("policy_update")
            self._swap_failed("fenced")
            return int(FilterResult.FENCED), self.policy_epoch
        ins = pl.find_instance(module_id)
        if ins is None:
            return int(FilterResult.INVALID_INSTANCE), self.policy_epoch
        try:
            configs = [policy_from_dict(d) for d in json.loads(policies_json)]
        except Exception:  # noqa: BLE001 — NACK, active policy untouched
            log.exception("policy update rejected (parse)")
            self._swap_failed("parse")
            return int(FilterResult.POLICY_DROP), self.policy_epoch
        try:
            staged_map = ins.policy_prepare(configs)
        except Exception:  # noqa: BLE001 — NACK, active policy untouched
            log.exception("policy update rejected (host compile)")
            self._swap_failed("host-compile")
            return int(FilterResult.POLICY_DROP), self.policy_epoch
        job = _SwapJob(module_id, staged_map)
        self._build_queue.put(("swap", job))
        if not job.done.wait(self.config.policy_swap_timeout_s):
            # The build keeps running and will still swap when it
            # lands; only the CONFIRMATION timed out.  Typed so the
            # caller can re-poll status()["policy"]["epoch"].
            self._swap_failed("ack-timeout")
            return int(FilterResult.UNKNOWN_ERROR), self.policy_epoch
        return job.status, job.epoch

    # -- policy epoch builder (one thread; the only epoch incrementer) -----

    def _swap_failed(self, reason: str) -> None:
        self.policy_swap_failures[reason] = (
            self.policy_swap_failures.get(reason, 0) + 1
        )
        metrics.PolicySwapFailures.inc(reason)

    def _policy_builder_loop(self) -> None:
        while True:
            item = self._build_queue.get()
            if item is None:
                # Drain: pending jobs fail typed instead of stranding
                # their handlers until the ack timeout.
                while True:
                    try:
                        kind, job = self._build_queue.get_nowait()
                    except queue.Empty:
                        return
                    if kind == "swap":
                        self._swap_failed("shutdown")
                        with blackbox.annotate(reason="shutdown",
                                               epoch=self.policy_epoch):
                            job.phase = EPOCH_SWAP_PROTOCOL.advance(
                                job.phase, SWAP_REJECTED
                            )
                        job.status = int(FilterResult.UNKNOWN_ERROR)
                        job.epoch = self.policy_epoch
                        job.done.set()
            kind, job = item
            try:
                if kind == "swap":
                    self._run_swap(job)
                elif kind == "rebind":
                    self._run_rebind(*job)
                elif kind == "grants":
                    # Grant delivery queued off the dispatcher: the
                    # blocking client.send must never run inside the
                    # per-entry classification loop (revalidation in
                    # _send_cache_grants makes late delivery safe).
                    self._send_cache_grants(job)
                elif kind == "mesh_reprobe":
                    self._run_mesh_ladder(immediate=False)
                elif kind == "mesh_reshape":
                    # Queued by _demote_mesh right at the fault: walk
                    # DOWN the width ladder around the attributed dead
                    # devices (never up — promotion is owned by the
                    # paced re-probe above).
                    self._run_mesh_ladder(immediate=True)
                elif kind == "mesh_rebuild":
                    self._run_mesh_rebuild(*job)
            except Exception:  # noqa: BLE001 — builder must survive
                log.exception("policy builder job failed")
                if kind == "swap":
                    self._swap_failed("device-build")
                    if job.phase == SWAP_STAGED:
                        # A job that already reached a terminal phase
                        # inside _run_swap stays there.
                        with blackbox.annotate(reason="device-build",
                                               epoch=self.policy_epoch):
                            job.phase = EPOCH_SWAP_PROTOCOL.advance(
                                job.phase, SWAP_REJECTED
                            )
                    job.status = int(FilterResult.POLICY_DROP)
                    job.epoch = self.policy_epoch
                    job.done.set()

    def _engine_key_for(self, module_id: int, conn) -> tuple:
        return (module_id, conn.policy_name, conn.ingress, conn.port,
                conn.parser_name)

    def _run_swap(self, job: "_SwapJob") -> None:
        """Builder-thread half of one epoch: rebuild every live engine
        for the module against the STAGED policy map (prewarm included
        — shape-bucketed, so repeat churn hits the executable cache),
        re-assert device/host bit-identity, then commit with one
        pointer flip.  Any failure before the flip leaves the live
        tables untouched: the old epoch keeps serving."""
        module_id = job.module_id
        ins = pl.find_instance(module_id)
        if ins is None:
            self._swap_failed("no-instance")
            with blackbox.annotate(reason="no-instance",
                                   epoch=self.policy_epoch):
                job.phase = EPOCH_SWAP_PROTOCOL.advance(job.phase,
                                                        SWAP_REJECTED)
            job.status = int(FilterResult.INVALID_INSTANCE)
            job.epoch = self.policy_epoch
            job.done.set()
            return
        epoch = self.policy_epoch + 1  # sole incrementer: this thread
        # Modules are refcounted onto instances: every module id bound
        # to THIS instance serves the swapped map, so all their engines
        # rebuild with the epoch (a conn opened under a sibling module
        # id must not keep a superseded table).
        mods = {module_id}
        with self._lock:
            for sc in self._conns.values():
                if sc.conn.instance is ins:
                    mods.add(sc.module_id)
            keys = {k for k in self._engines if k[0] in mods}
            for sc in self._conns.values():
                if sc.conn.instance is ins and sc.conn.parser_name in (
                    ENGINE_PROTOS
                ):
                    keys.add(self._engine_key_for(sc.module_id, sc.conn))
            prior_engines = dict(self._engines)
        new_engines: dict[tuple, object] = {}
        try:
            # Any trace the rebuild provokes is churn by definition;
            # _rebuild_cause refines new-shape vs. vocab per engine, the
            # scope catches jit misses the classifier can't see.
            with ledger_mod.cause_scope(
                ledger_mod.CAUSE_CHURN_NEW_SHAPE, epoch=epoch
            ):
                for key in sorted(keys, key=repr):
                    _mod, policy_name, ingress, port, proto = key
                    policy = job.staged_map.get(policy_name)
                    with self._device_ctx():
                        eng = self._make_engine(
                            ins, policy, policy_name, ingress, port,
                            proto, prior=prior_engines.get(key),
                        )
                    if (
                        self.config.policy_epoch_parity
                        and not self.config.seam_probe
                    ):
                        if proto == "r2d2":
                            self._assert_epoch_parity(
                                eng, policy, ingress, port
                            )
                        elif proto == "dns":
                            self._assert_epoch_parity_dns(
                                eng, policy, ingress, port
                            )
                    eng.epoch = epoch
                    new_engines[key] = eng
        except EpochParityError:
            log.exception("policy swap rejected (epoch parity)")
            self._swap_failed("parity")
            with blackbox.annotate(reason="parity", epoch=epoch):
                job.phase = EPOCH_SWAP_PROTOCOL.advance(job.phase,
                                                        SWAP_REJECTED)
            job.status = int(FilterResult.POLICY_DROP)
            job.epoch = self.policy_epoch
            job.done.set()
            return
        except Exception:  # noqa: BLE001 — fail closed, old epoch serves
            log.exception("policy swap rejected (device build)")
            self._swap_failed("device-build")
            with blackbox.annotate(reason="device-build", epoch=epoch):
                job.phase = EPOCH_SWAP_PROTOCOL.advance(job.phase,
                                                        SWAP_REJECTED)
            job.status = int(FilterResult.POLICY_DROP)
            job.epoch = self.policy_epoch
            job.done.set()
            return
        # Revoke shim-side cache grants BEFORE the flip: a shim that
        # processed the revoke cannot short-circuit on the superseded
        # epoch once the new one serves (the service-side epoch key is
        # structural regardless; this closes the client half to the
        # revoke's delivery lag).
        self._send_cache_revokes(epoch)
        self._commit_epoch(ins, mods, job.staged_map, new_engines,
                           epoch)
        with blackbox.annotate(reason="committed", epoch=epoch):
            job.phase = EPOCH_SWAP_PROTOCOL.advance(job.phase,
                                                    SWAP_COMMITTED)
        job.status = int(FilterResult.OK)
        job.epoch = epoch
        job.done.set()

    def _commit_epoch(self, ins, mods: set, staged_map,
                      new_engines: dict, epoch: int) -> None:
        """The pointer flip: publish the staged host map and the staged
        engine table, rebind live conns, and migrate engine-retained
        flow bytes — all under _lock, bounded-time (no compile, no
        I/O).  Rounds blocked behind this hold book the overlap as
        their table_swap stage."""
        t0 = time.monotonic()
        with self._lock:
            ins.policy_commit(staged_map)
            # Re-resolve sibling modules AT COMMIT TIME: a module
            # bound to this instance during the (slow) staged build is
            # not in the pre-build ``mods`` snapshot, and leaving its
            # engines in place would keep a superseded table alive for
            # a later rebind to find.
            for k in self._engines:
                if k[0] not in mods and pl.find_instance(k[0]) is ins:
                    mods.add(k[0])
            dropped = [
                v for k, v in self._engines.items() if k[0] in mods
            ]
            self._engines = {
                k: v for k, v in self._engines.items()
                if k[0] not in mods
            }
            self._engines.update(new_engines)
            self._release_engines(dropped)
            for eng in dropped:
                # Id-keyed jit entries die with their model; the
                # shape-keyed entries are the churn executable cache
                # and deliberately survive the swap.
                mid = id(getattr(eng, "model", None))
                for cache in (self._jit_cache, self._jit_gather,
                              self._jit_attr):
                    if cache.pop(mid, None) is not None:
                        self.ledger.executable_evicted((id(cache), mid))
            async_pending = set(self._async_pending)
            # Verdict-cache invalidation is the epoch key itself (a
            # stale hit is structurally impossible once policy_epoch
            # moves below); this sweep just retires the rows so the
            # armed count and the invalidation counter stay truthful,
            # and re-arms rebound conns against the NEW tables.
            invalidated = 0
            grants: list = []
            if self._flow_cache_on and self._tab_size:
                armed = self._tab_cache == 1
                invalidated = int(armed.sum())
                with blackbox.annotate(reason="epoch-flip", epoch=epoch):
                    self._tab_cache[self._tab_cache != 0] = (
                        FLOW_CACHE_PROTOCOL.require_edges(
                            (CACHE_ARMED, CACHE_DECLINED), CACHE_UNARMED
                        )
                    )
                self._tab_cache_epoch[:] = -1
                self._tab_cache_rule[:] = -1
                self._cache_armed = 0
                self.cache_invalidations += invalidated
            rebinds = []
            for cid, sc in self._conns.items():
                if sc.conn.instance is not ins:
                    continue
                old_eng = sc.engine
                engine_proto = sc.conn.parser_name in ENGINE_PROTOS
                if old_eng is None and engine_proto and (
                    sc.bufs[False] or sc.skip[False]
                ):
                    # Demoted conn with undrained oracle-mirror
                    # request residue: binding an engine NOW would
                    # strand those bytes (engine entries never consume
                    # sc.bufs) — keep the oracle serving and let
                    # _maybe_rebind bind after the residue drains
                    # (pointer reads only; the engines now exist).
                    sc.demoted_mod = sc.module_id
                    self._tab_set_engine(cid, None)
                    continue
                if old_eng is not None and (
                    cid in async_pending
                    or (cid < self._tab_size and self._tab_async[cid])
                    or not self._flow_migratable(old_eng, cid)
                ):
                    # In-flight deferred round (or undrained engine
                    # ops): the conn finishes on the epoch it
                    # snapshotted — its state stays on the OLD engine
                    # and the stale-epoch catch-up on the dispatch
                    # path rebinds (and migrates the buffer) once the
                    # round drains.  The freed slot keeps it off the
                    # vec path meanwhile.
                    self._stale_conns.add(cid)
                    self._tab_set_engine(cid, None)
                    continue
                eng = new_engines.get(
                    self._engine_key_for(sc.module_id, sc.conn)
                )
                if eng is not None and old_eng is not None \
                        and eng is not old_eng:
                    self._migrate_flow(old_eng, eng, cid, sc)
                sc.engine = eng
                sc.fast_ok = (
                    eng is not None and sc.conn.parser_name in FAST_PROTOS
                )
                sc.demoted_mod = None
                self._tab_set_engine(cid, eng)
                g = self._arm_flow_cache(cid, sc)
                if g is not None:
                    grants.append(g)
                if (
                    eng is None
                    and engine_proto
                    and cid not in self._rebind_inflight
                ):
                    # Opened mid-build under a key the staged set did
                    # not cover: rebuild off-path (oracle serves until
                    # the bind lands) — never leave an engine-capable
                    # conn stranded on the slow path.
                    self._rebind_inflight.add(cid)
                    rebinds.append((sc.module_id, cid))
            self.policy_epoch = epoch
            t1 = time.monotonic()
            self._swap_window = (t0, t1)
        for job in rebinds:
            self._build_queue.put(("rebind", job))
        if invalidated:
            metrics.VerdictCacheInvalidations.inc(
                "epoch-flip", amount=invalidated
            )
        if grants:
            # Fresh grants under the NEW epoch (after the flip, so a
            # shim can never receive a grant it must immediately treat
            # as stale).
            self._send_cache_grants(grants)
        hold = t1 - t0
        self.policy_swaps += 1
        self.last_swap_ms = round(hold * 1e3, 3)
        metrics.PolicySwapsTotal.inc()
        metrics.PolicySwapSeconds.observe(hold)
        metrics.PolicyEpochGauge.set(float(epoch))
        log.info(
            "policy epoch %d committed for module(s) %s (%d engine(s), "
            "flip %.2fms)", epoch, sorted(mods), len(new_engines),
            hold * 1e3,
        )

    @staticmethod
    def _flow_migratable(old_eng, conn_id: int) -> bool:
        """True when the conn can adopt a new epoch's engine NOW.
        Two flow shapes:

        - r2d2 ``FlowState``: ops is a LIST, reply_inject a bytearray —
          migratable once both are drained (the byte buffer itself
          moves in _migrate_flow);
        - l7 ``_EngineFlow``: ops/bufs/skip are per-direction dicts,
          and the parser state behind a buffered partial frame is not
          portable across policy objects — the conn stays on its
          captured epoch until the frame drains at a boundary (a frame
          judged half-old-half-new would be worse than a briefly-stale
          conn; the stale-conn catch-up retries per entry)."""
        fl = old_eng.flows.get(conn_id) if hasattr(old_eng, "flows") \
            else None
        if fl is None:
            return True
        ops = getattr(fl, "ops", None)
        if isinstance(ops, dict):  # l7 _EngineFlow
            if any(ops.values()):
                return False
            return not (
                fl.bufs[False] or fl.bufs[True]
                or fl.skip[False] or fl.skip[True]
            )
        return not (ops or getattr(fl, "reply_inject", None))

    @staticmethod
    def _migrate_flow(old_eng, new_eng, conn_id: int, sc) -> None:
        """Carry a conn's engine-retained request bytes across the
        epoch swap so no byte is lost or replayed.  Callers gate on
        _flow_migratable / async-pending first — a conn whose state is
        still owed to an in-flight round (or holds an unportable l7
        partial frame) is deferred to the stale-conn catch-up
        instead."""
        fl = old_eng.flows.get(conn_id) if hasattr(old_eng, "flows") \
            else None
        if fl is None:
            return
        buf = getattr(fl, "buffer", None)
        if buf is None:
            # l7 _EngineFlow: gated EMPTY by _flow_migratable — nothing
            # to move; the inert flow dies with the released engine and
            # the new engine builds a fresh one on first feed.
            return
        if buf:
            conn = sc.conn
            nf = new_eng.flow(
                conn_id, remote_id=fl.remote_id,
                policy_name=conn.policy_name, ingress=conn.ingress,
                dst_id=conn.dst_id, src_addr=conn.src_addr,
                dst_addr=conn.dst_addr,
            )
            nf.buffer += bytes(buf)
            buf.clear()
        old_eng.flows.pop(conn_id, None)

    def _run_rebind(self, module_id: int, conn_id: int) -> None:
        """Builder-thread engine (re)bind for a conn whose key had no
        live engine (quarantine heal): the compile happens HERE, never
        on the dispatch path.  The conn keeps serving on the oracle
        until the bind lands.  A device that re-quarantined before this
        job ran is handled by _bind_engine itself — it re-demotes
        (sets demoted_mod) so the heal path retries, never a silent
        drop."""
        with self._lock:
            sc = self._conns.get(conn_id)
        grant = None
        try:
            if sc is not None and sc.engine is None:
                with ledger_mod.cause_scope(ledger_mod.CAUSE_HEAL_REBIND,
                                            epoch=self.policy_epoch):
                    self._bind_engine(module_id, sc)
                with self._lock:
                    if self._conns.get(conn_id) is sc:
                        self._tab_set_engine(
                            conn_id, sc.engine if sc.fast_ok else None
                        )
                        grant = self._arm_flow_cache(conn_id, sc)
        finally:
            with self._lock:
                self._rebind_inflight.discard(conn_id)
        if grant is not None:
            self._send_cache_grants([grant])

    # Deterministic per-epoch parity probe: every valid command crossed
    # with distinctive files; remotes are drawn from the candidate
    # model's own remote table plus never-allowed sentinels.
    _PARITY_PROBES = (
        ("READ", "/public/app"), ("READ", "/etc/shadow"), ("READ", ""),
        ("WRITE", "/public/app"), ("WRITE", "/data/x"),
        ("HALT", ""), ("RESET", ""),
    )

    def _assert_epoch_parity(self, engine, policy, ingress: bool,
                             port: int) -> None:
        """Re-assert device-model vs host-oracle bit-identity for a
        staged engine before its epoch can commit: one prewarmed-shape
        device batch over the probe grid, compared against the staged
        policy's host walk.  A mismatch raises EpochParityError and
        fails the swap typed — a miscompiled table can never serve."""
        model = engine.model
        if isinstance(model, ConstVerdict):
            return
        from ..proxylib.parsers.r2d2 import R2d2RequestData

        rem_tab = np.asarray(model.remote_ids).ravel()
        remotes = sorted(set(int(r) for r in rem_tab if r > 0))[:4]
        remotes += [1, 999983]  # a common id + a never-allocated one
        cases = [
            (cmd, f, rem)
            for cmd, f in self._PARITY_PROBES
            for rem in remotes
        ]
        b = self._min_bucket
        while b < len(cases):
            b *= 2
        width = self.config.batch_width
        data = np.zeros((b, width), np.uint8)
        lens = np.zeros(b, np.int32)
        rems = np.zeros(b, np.int32)
        for i, (cmd, f, rem) in enumerate(cases):
            frame = (f"{cmd} {f}\r\n" if f else f"{cmd}\r\n").encode()
            row = np.frombuffer(frame, np.uint8)
            data[i, : len(row)] = row
            lens[i] = len(row)
            rems[i] = rem
        out = self._model_call_attr(model, data, lens, rems)
        allow = np.asarray(out[2])[: len(cases)]
        for i, (cmd, f, rem) in enumerate(cases):
            host = policy is not None and policy.matches(
                ingress, port, rem, R2d2RequestData(cmd, f)
            )
            if bool(allow[i]) != bool(host):
                raise EpochParityError(
                    f"epoch parity violation: probe "
                    f"(cmd={cmd!r} file={f!r} remote={rem}) device="
                    f"{bool(allow[i])} host={host}"
                )

    # DNS probe names: an exact candidate, a subdomain (wildcard tier),
    # an unrelated name, the root, and a structurally invalid query —
    # enough to exercise the needle, automaton, byte-free and validity
    # tiers of a staged DNS table.
    _DNS_PARITY_NAMES = (
        "www.example.com", "api.internal.example.com", "evil.test",
        "example.com", "",
    )

    def _assert_epoch_parity_dns(self, engine, policy, ingress: bool,
                                 port: int) -> None:
        """DNS twin of _assert_epoch_parity: staged device table vs
        the staged policy's host walk over a probe grid of query
        frames (the invalid-QNAME probe included — the validity gate
        is part of the contract)."""
        model = engine.model
        if isinstance(model, ConstVerdict):
            return
        from ..proxylib.parsers.dns import (
            DNS_QNAME_OFF,
            DnsRequestData,
            encode_dns_query,
            parse_dns_query,
        )

        rem_tab = np.asarray(model.remote_ids).ravel()
        remotes = sorted(set(int(r) for r in rem_tab if r > 0))[:4]
        remotes += [1, 999983]  # a common id + a never-allocated one
        frames = [
            encode_dns_query(n) for n in self._DNS_PARITY_NAMES
        ]
        # Invalid probe: a compression pointer where a label length
        # belongs (denied by every name-constrained row on both rungs).
        bad = bytearray(encode_dns_query("bad.example.com"))
        bad[DNS_QNAME_OFF] = 0xC0
        frames.append(bytes(bad))
        cases = [(f, rem) for f in frames for rem in remotes]
        b = self._min_bucket
        while b < len(cases):
            b *= 2
        width = self.config.batch_width
        while width < max(len(f) for f in frames):
            width *= 2
        data = np.zeros((b, width), np.uint8)
        lens = np.zeros(b, np.int32)
        rems = np.zeros(b, np.int32)
        for i, (frame, rem) in enumerate(cases):
            row = np.frombuffer(frame, np.uint8)
            data[i, : len(row)] = row
            lens[i] = len(row)
            rems[i] = rem
        out = self._model_call_attr(model, data, lens, rems)
        allow = np.asarray(out[2])[: len(cases)]
        for i, (frame, rem) in enumerate(cases):
            name = parse_dns_query(frame)
            req = DnsRequestData(
                name=name if name is not None else "",
                valid=name is not None,
            )
            host = policy is not None and policy.matches(
                ingress, port, rem, req
            )
            if bool(allow[i]) != bool(host):
                raise EpochParityError(
                    f"epoch parity violation: dns probe "
                    f"(name={req.name!r} valid={req.valid} "
                    f"remote={rem}) device={bool(allow[i])} host={host}"
                )

    def new_connection(self, module_id, conn_id, ingress, src_id, dst_id,
                       proto, src_addr, dst_addr, policy_name, flags=0,
                       client=None):
        """Returns ``(result, grant_or_None, result_flags)``.  The
        registration grant is NOT sent here: the caller delivers it
        AFTER the MSG_CONN_RESULT reply, so the shim's post-RPC
        stale-grant drop (conn-id reuse) is socket-ordered before the
        fresh grant and can never erase it.  ``flags`` carries the
        shim's CONN_FLAG_RETAINED claim (session replay: its retained
        buffers survived the restart untouched); ``result_flags``
        answers with CONN_RESULT_FLAG_RESIDUE_ADOPTED when the
        predecessor's mid-frame residue was installed for this conn."""
        if self._fenced:
            self.fence_rejects += 1
            metrics.SidecarFenceRejects.inc("new_connection")
            return int(FilterResult.FENCED), None, 0
        res, conn = pl.on_new_connection(
            module_id, proto, conn_id, ingress, src_id, dst_id,
            src_addr, dst_addr, policy_name,
        )
        if res != FilterResult.OK:
            return int(res), None, 0
        sc = _SidecarConn(conn, client, None, module_id=module_id)
        self._bind_engine(module_id, sc)
        rebind = False
        adopted = False
        with self._lock:
            # Re-resolve against the CURRENT epoch's table: an epoch
            # swap may have committed between the bind above and this
            # registration, and the conn must never enter the registry
            # holding a superseded engine (it would serve the old
            # policy until the next swap touched it).
            if sc.engine is not None:
                cur = self._engines.get(
                    self._engine_key_for(module_id, conn)
                )
                if cur is not None and cur is not sc.engine:
                    sc.engine = cur
                elif cur is None:
                    # The key vanished under a racing swap (our freshly
                    # built engine was dropped with the old epoch):
                    # serve on the oracle and rebuild off-path.
                    sc.engine = None
                    sc.fast_ok = False
                    if conn_id not in self._rebind_inflight:
                        self._rebind_inflight.add(conn_id)
                        rebind = True
            self._conns[conn_id] = sc
            # Handoff restore: if the predecessor knew this conn under
            # the SAME identity tuple, adopt its mid-frame flow-buffer
            # residue so a frame split across the restart reassembles
            # instead of misparsing.  Adoption is DOUBLY gated: the
            # identity tuple must match (conn-id reuse across the
            # restart drops the residue — fresh state is correct,
            # stale bytes are not) AND the shim must claim RETAINED
            # (its retained-buffer mirror survived the blackout with
            # no typed-failed round).  Without the claim the shim has
            # dropped its copy fail-closed, and installing the
            # predecessor's bytes here would put the parser AHEAD of
            # the shim's buffer — every subsequent op would land
            # shifted, silently passing or dropping the wrong bytes.
            # Grants are NOT restored here: _arm_flow_cache re-derives
            # them under the restored epoch (revalidate-or-revoke), we
            # only count the matches.
            prev = self._handoff_conns.pop(conn_id, None)
            if prev is not None:
                if (
                    prev.get("policy") == policy_name
                    and prev.get("ingress") == bool(ingress)
                    and prev.get("src_id") == int(src_id)
                    and prev.get("proto") == proto
                ):
                    self.handoff_conn_restores += 1
                    res_row = self._handoff_residue.pop(conn_id, None)
                    if res_row is not None and (
                        flags & wire.CONN_FLAG_RETAINED
                    ):
                        try:
                            sc.bufs[False] = bytearray(
                                base64.b64decode(res_row["orig"])
                            )
                            sc.bufs[True] = bytearray(
                                base64.b64decode(res_row["reply"])
                            )
                            sc.skip[False] = int(res_row["skip_orig"])
                            sc.skip[True] = int(res_row["skip_reply"])
                            adopted = bool(
                                sc.bufs[False] or sc.bufs[True]
                                or sc.skip[False] or sc.skip[True]
                            )
                        except (KeyError, TypeError, ValueError,
                                binascii.Error):
                            sc.bufs = {False: bytearray(),
                                       True: bytearray()}
                            sc.skip = {False: 0, True: 0}
                    if adopted:
                        # Residue must be CONSUMED, and engine entries
                        # never drain sc.bufs: enter through the
                        # demoted-to-oracle state (exactly the
                        # quarantine-demotion shape) so the oracle
                        # serves the reassembled frame and
                        # _maybe_rebind restores the device path once
                        # the carry drains.  The racing-swap rebind
                        # queued above would bind an engine over the
                        # residue — cancel it; the heal path re-queues
                        # after the drain.
                        self.handoff_residue_restores += 1
                        sc.engine = None
                        sc.fast_ok = False
                        sc.demoted_mod = module_id
                        if rebind:
                            rebind = False
                            self._rebind_inflight.discard(conn_id)
                else:
                    self._handoff_residue.pop(conn_id, None)
                    self._handoff_grants.pop(conn_id, None)
            if self._tab_ensure(conn_id):
                self._tab_src[conn_id] = conn.src_id
                self._tab_dirty[conn_id] = 0
            self._tab_set_engine(conn_id, sc.engine if sc.fast_ok else None)
            # Verdict cache: the byte-invariance claim is per-epoch
            # static, so a flow arms AT REGISTRATION — pure-L3/L4 and
            # allow-all tables never pay a single device round.
            grant = self._arm_flow_cache(conn_id, sc)
            hg = self._handoff_grants.pop(conn_id, None)
            if hg is not None and grant is not None and hg[1] == grant[3]:
                # Predecessor's grant survived revalidation: the fresh
                # arm landed on the SAME rule row.  The epoch is NOT
                # compared — the replay re-commits policy before conns
                # register, so the re-derived grant is expected to
                # carry the successor's newer epoch.
                self.handoff_grant_restores += 1
        if rebind:
            self._build_queue.put(("rebind", (module_id, conn_id)))
        if self.flowlog is not None:
            # Connection metadata registered ONCE here (and dropped at
            # close) so per-round record emission stores bare arrays —
            # the query side joins against this registry.  The session
            # id rides along so `cilium observe --session` can
            # attribute records to one shim.
            sess = getattr(client, "session", None)
            self.flowlog.register_conn(
                conn_id, policy_name, ingress, src_id, dst_id,
                src_addr, dst_addr, proto, conn.port,
                session=sess.id if sess is not None else 0,
            )
        return int(res), grant, (
            wire.CONN_RESULT_FLAG_RESIDUE_ADOPTED if adopted else 0
        )

    _TAB_MAX = 1 << 22  # conns with larger ids use the entrywise path

    def _tab_ensure(self, conn_id: int) -> bool:
        """Grow the conn table to cover conn_id; False if out of range."""
        if conn_id >= self._TAB_MAX:
            return False
        if conn_id >= self._tab_size:
            new_size = max(4096, self._tab_size)
            while new_size <= conn_id:
                new_size *= 2
            for name, fill, dt in (
                ("_tab_engine", -1, np.int32),
                ("_tab_src", 0, np.int32),
                ("_tab_dirty", 0, np.uint8),
                ("_tab_async", 0, np.uint32),
                ("_tab_cache", 0, np.uint8),
                ("_tab_cache_epoch", -1, np.int64),
                ("_tab_cache_rule", -1, np.int32),
                ("_tab_seen_tick", 0, np.int64),
            ):
                arr = np.full(new_size, fill, dt)
                arr[: self._tab_size] = getattr(self, name)
                setattr(self, name, arr)
            self._tab_size = new_size
        return True

    def _tab_set_engine(self, conn_id: int, engine) -> None:
        if not self._tab_ensure(conn_id):
            return
        if engine is None:
            self._tab_engine[conn_id] = -1
            return
        idx = self._engine_idx.get(id(engine))
        if idx is None:
            if self._engine_free:
                idx = self._engine_free.pop()
                self._engine_objs[idx] = engine
            else:
                idx = len(self._engine_objs)
                self._engine_objs.append(engine)
            self._engine_idx[id(engine)] = idx
            self._objs_cache = None
        self._tab_engine[conn_id] = idx

    def _release_engines(self, engines: list) -> None:
        """Return dropped engines' table slots to the free list so
        superseded models (and their device buffers) can be collected."""
        for eng in engines:
            idx = self._engine_idx.pop(id(eng), None)
            if idx is not None:
                self._engine_objs[idx] = None
                self._engine_free.append(idx)
                self._objs_cache = None

    def _conn_residual_dirty(self, conn_id: int, sc: "_SidecarConn") -> bool:
        """The single definition of 'this conn holds residual state':
        engine flow buffer(s), oracle buffers, skip counts, or a
        columnar-arena carry (the reassembler's per-conn residue lives
        OUTSIDE the engine flow — see sidecar/reasm.py)."""
        if self._reasm is not None and self._reasm.arena.has_residue(
            conn_id
        ):
            return True
        flow = sc.engine.flows.get(conn_id) if sc.engine is not None else None
        buffered = False
        if flow is not None:
            if hasattr(flow, "buffer"):  # simple batch engines
                buffered = bool(flow.buffer)
            else:  # device-assisted engines: per-direction buffers
                buffered = bool(flow.bufs[False] or flow.bufs[True])
            # A flow that tripped the retained-bytes cap is dead: keep
            # it off the vec path so every further entry re-surfaces
            # the typed error through the engine feed.
            buffered = buffered or getattr(flow, "overflowed", False)
        return bool(
            buffered
            or sc.bufs[False]
            or sc.bufs[True]
            or sc.skip[False]
            or sc.skip[True]
        )

    def _tab_mark_many(self, pairs: list) -> None:
        """Batch dirty-flag refresh: one lock acquisition for a whole
        round's worth of conns instead of one per entry (the per-entry
        variant measured ~1.6k lock trips per mixed round)."""
        updates = [
            (conn_id, 1 if self._conn_residual_dirty(conn_id, sc) else 0)
            for conn_id, sc in pairs
        ]
        with self._lock:
            size = self._tab_size
            for conn_id, dirty in updates:
                if conn_id < size:
                    self._tab_dirty[conn_id] = dirty

    def _tab_mark(self, conn_id: int, sc: "_SidecarConn") -> None:
        """Refresh the dirty flag from actual residual state."""
        dirty = self._conn_residual_dirty(conn_id, sc)
        # Write under the lock: _tab_ensure (new_connection, another
        # thread) reallocates the table arrays, and a lock-free store
        # could land in the discarded old array, leaving a stale-clean
        # dirty bit that re-admits a stateful conn to the vec path.
        with self._lock:
            if conn_id < self._tab_size:
                self._tab_dirty[conn_id] = 1 if dirty else 0

    # -- established-flow verdict cache (policy/invariance.py) -------------

    def _arm_flow_cache(self, conn_id: int, sc: "_SidecarConn"):
        """Compute/refresh this conn's byte-invariance claim from its
        bound engine (caller holds ``_lock``; the conn table row is
        ensured).  Arms engines whose framing is registered in
        reasm.FRAMINGS — the cache tiers' frame-alignment gate is that
        framing's whole-frame check (CRLF tail for r2d2, the
        length-prefix walk for DNS) — and only on ALLOW claims (denied
        frames carry per-frame inject side effects the short-circuit
        would skip).  At the ``flow_cache_entries`` cap the least-
        recently-HIT armed row is evicted to make room
        (verdict_cache_evictions_total) — eviction is capacity
        management, not invalidation: the victim's claim stays true
        for its epoch, so an already-delivered shim grant needs no
        revoke.  Returns the ``(client, conn_id, epoch, rule,
        framing_kind)`` grant to send OUTSIDE the lock, or None.
        Shim-local grants carry the conn's framing kind (ROADMAP 3c):
        the shim keys its pre-push alignment check off the grant row —
        CRLF tail for r2d2, the length-prefix walk for DNS — so every
        framing registered in reasm.FRAMINGS gets the local tier."""
        if not self._flow_cache_on or conn_id >= self._tab_size:
            return None
        engine = sc.engine
        framing = _engine_framing(engine)
        claim = None
        epoch = self.policy_epoch
        if framing is not None and hasattr(engine, "verdict_invariant"):
            claim = engine.verdict_invariant(sc.conn.src_id)
            epoch = getattr(engine, "epoch", 0)
        was_armed = self._tab_cache[conn_id] == 1
        if claim is not None and claim[0]:
            if (
                not was_armed
                and self._cache_armed >= self.config.flow_cache_entries
            ):
                self._evict_flow_cache_lru()
            if was_armed or (
                self._cache_armed < self.config.flow_cache_entries
            ):
                rule = int(claim[1])
                if not was_armed:
                    self._cache_armed += 1
                with blackbox.annotate(reason="arm", conn=conn_id,
                                       epoch=epoch):
                    self._tab_cache[conn_id] = (
                        FLOW_CACHE_PROTOCOL.advance(
                            self._tab_cache[conn_id], CACHE_ARMED
                        )
                    )
                self._tab_cache_epoch[conn_id] = epoch
                self._tab_cache_rule[conn_id] = rule
                self._tab_seen_tick[conn_id] = self._next_cache_tick()
                client = sc.client
                if client is not None and getattr(
                    client, "cache_ok", False
                ):
                    return client, conn_id, epoch, rule, framing.kind
                return None
        if was_armed:
            self._cache_armed -= 1
            self.cache_invalidations += 1
            # Mirror the status counter: an armed row losing its claim
            # on re-arm is an invalidation in both surfaces.
            metrics.VerdictCacheInvalidations.inc("re-arm")
        with blackbox.annotate(reason="no-claim", conn=conn_id,
                               epoch=epoch):
            self._tab_cache[conn_id] = FLOW_CACHE_PROTOCOL.advance(
                self._tab_cache[conn_id], CACHE_DECLINED
            )
        self._tab_cache_epoch[conn_id] = epoch
        self._tab_cache_rule[conn_id] = -1
        return None

    def _next_cache_tick(self) -> int:
        """Monotonic recency stamp for the armed-row LRU (round-grain:
        one tick per touch event, bulk touches share a tick)."""
        self._cache_tick += 1
        return self._cache_tick

    def _touch_cache_rows(self, conn_ids) -> None:
        """Refresh the last-HIT stamp of armed rows after a cache-hit
        group (one vectorized store per round, never per entry)."""
        ids = np.asarray(conn_ids, np.int64)
        ids = ids[(ids >= 0) & (ids < self._tab_size)]
        if len(ids):
            # lint: disable=R19 -- deliberately lock-free on the dispatch hot path: _tab_seen_tick is an advisory LRU recency stamp; a race with table growth costs at worst one stale stamp (a marginally suboptimal eviction), never correctness, and taking _lock here would serialize every cache-hit round
            self._tab_seen_tick[ids] = self._next_cache_tick()

    def _evict_flow_cache_lru(self) -> None:
        """Drop the least-recently-hit armed row to make room at the
        ``flow_cache_entries`` cap (caller holds ``_lock``).  Counted
        separately from invalidations: the victim's claim is still
        TRUE for its epoch — this is capacity management, so the
        (advisory) shim grant, if any, keeps its local short-circuit
        and stays correct."""
        armed = np.flatnonzero(self._tab_cache[: self._tab_size] == 1)
        if not len(armed):
            return
        victim = int(armed[np.argmin(self._tab_seen_tick[armed])])
        # Back to unarmed: re-armable later.
        with blackbox.annotate(reason="lru-evict", conn=victim):
            self._tab_cache[victim] = FLOW_CACHE_PROTOCOL.advance(
                self._tab_cache[victim], CACHE_UNARMED
            )
        self._tab_cache_epoch[victim] = -1
        self._tab_cache_rule[victim] = -1
        self._cache_armed -= 1
        self.cache_evictions += 1
        metrics.VerdictCacheEvictions.inc()

    def _disarm_flow_cache(self, conn_id: int, reason: str | None) -> None:
        """Drop one conn's cache row (caller holds ``_lock``): lane
        transitions (quarantine demotion) and close.  The claim itself
        stays table-valid — the rebind path re-arms from the fallback
        engine once the conn's residue drains."""
        if conn_id >= self._tab_size:
            return
        if self._tab_cache[conn_id] == 1:
            self._cache_armed -= 1
            self.cache_invalidations += 1
            if reason is not None:
                metrics.VerdictCacheInvalidations.inc(reason)
        with blackbox.annotate(reason=reason or "close", conn=conn_id):
            self._tab_cache[conn_id] = FLOW_CACHE_PROTOCOL.advance(
                self._tab_cache[conn_id], CACHE_UNARMED
            )
        self._tab_cache_epoch[conn_id] = -1
        self._tab_cache_rule[conn_id] = -1

    def _send_cache_grants(self, grants: list) -> None:
        """Deliver collected (client, conn_id, epoch, rule) grants.
        Each is revalidated against the LIVE conn row under ``_lock``
        right before packing — a conn that closed or was re-registered
        since collection must never receive the stale grant (a reused
        conn id would inherit the old identity's allow at the shim) —
        then sent outside the lock (a grant is advisory: a lost frame
        only costs the shim its local short-circuit, never
        correctness).  Callers hold no ``_lock``."""
        live: list = []
        with self._lock:
            for client, conn_id, epoch, rule, fkind in grants:
                sc = self._conns.get(conn_id)
                if (
                    sc is not None
                    and sc.client is client
                    and conn_id < self._tab_size
                    and self._tab_cache[conn_id] == 1
                    and self._tab_cache_epoch[conn_id] == epoch
                    and self._tab_cache_rule[conn_id] == rule
                ):
                    live.append(
                        (client,
                         wire.pack_cache_grant(
                             conn_id, epoch, rule,
                             flags=wire.CACHE_FLAG_ALLOW,
                             framing=fkind,
                         ))
                    )
        for client, payload in live:
            try:
                client.send(wire.MSG_CACHE_GRANT, payload)
            except Exception:  # noqa: BLE001 — client may be gone
                log.exception("cache grant send failed")

    def _send_cache_revokes(self, epoch: int) -> None:
        """Pre-flip revocation: tell every opted-in shim the NEW epoch
        so grants under older epochs die at the client BEFORE the
        pointer flip commits.  Sent from the builder thread (bounded by
        the handlers' SO_SNDTIMEO); the service-side epoch key stays
        the structural guarantee regardless."""
        if not self._flow_cache_on:
            return
        with self._lock:
            clients = [
                c for c in self._clients if getattr(c, "cache_ok", False)
            ]
        payload = wire.pack_cache_revoke(epoch)
        for client in clients:
            try:
                client.send(wire.MSG_CACHE_REVOKE, payload)
            except Exception:  # noqa: BLE001 — client may be gone
                log.exception("cache revoke send failed")

    def _record_cached_entries(self, hits: list) -> None:
        """Cached-path flow records for scalar-tier hits: per-entry
        (rule, kind, epoch) resolved against the engine CAPTURED at hit
        time (slot-reuse-safe), one columnar add_round for the round."""
        if self.flowlog is None or not hits:
            return
        n = len(hits)
        conn_ids = np.fromiter(
            (h[2] for h in hits), np.int64, count=n
        )
        rules = np.fromiter((h[3] for h in hits), np.int32, count=n)
        kinds = [
            self._kind_for(getattr(h[4], "model", None), h[3])
            for h in hits
        ]
        epochs = np.fromiter(
            (getattr(h[4], "epoch", 0) for h in hits), np.int64,
            count=n,
        )
        self.flowlog.add_round(
            PATH_CACHED,
            conn_ids,
            np.full(n, CODE_FORWARDED, np.int8),
            rules,
            cols={"match_kind": kinds, "epoch": epochs},
        )

    def _record_cached_round(self, conn_ids, rules, kinds, epoch) -> None:
        """Flow records for one cached group: path ``cached``, the
        ORIGINAL attributed rule rows, the claim epoch — one columnar
        add_round, never per entry."""
        if self.flowlog is None or not len(conn_ids):
            return
        self.flowlog.add_round(
            PATH_CACHED,
            np.asarray(conn_ids, np.int64),
            np.full(len(conn_ids), CODE_FORWARDED, np.int8),
            np.asarray(rules, np.int32),
            kinds=kinds,
            epoch=epoch,
        )

    def _bind_engine(self, module_id: int, sc: _SidecarConn) -> None:
        """Attach the device batch engine for this connection's
        (policy, direction, port, proto), building the model on first
        use.  Epoch-safe: the build reads the policy map of ONE epoch;
        if a swap commits while the build runs, the stale engine is
        discarded and the bind retries against the new epoch (never
        inserted — a swap must not be undone by a racing first-bind)."""
        conn = sc.conn
        proto = conn.parser_name
        if proto not in ENGINE_PROTOS:
            return  # other protocols: oracle path
        if self.guard.quarantined:
            # Never build/prewarm against a quarantined device (the
            # compile would hang this reader thread).  The conn starts
            # on the oracle path and is bound once the device heals.
            sc.demoted_mod = module_id
            return
        key = (module_id, conn.policy_name, conn.ingress, conn.port, proto)
        for _attempt in range(4):
            with self._lock:
                eng = self._engines.get(key)
                epoch0 = self.policy_epoch
            if eng is not None:
                break
            # Build and prewarm OUTSIDE the registry lock: XLA compiles
            # are slow and must not stall unrelated control/data traffic.
            # Built under the configured verdict device so the model's
            # tables are colocated with its dispatch.  This is the
            # first-bind cold path (once per key); churn rebuilds ride
            # the async builder instead.
            ins = pl.find_instance(module_id)
            policy = ins.policy_map().get(conn.policy_name)
            with self._device_ctx():
                # lint: disable=R12 -- first-bind cold path off the dispatch loop (reader/builder thread, once per engine key); churn recompiles ride the policy builder
                # lint: disable=R23 -- the cold first-bind IS ledgered: no cause_scope here is the contract — record_compile inside _make_engine defaults the cause to "cold", and _run_rebind wraps this call in the heal-rebind scope (an inner scope here would mask it)
                built = self._make_engine(
                    ins, policy, conn.policy_name, conn.ingress,
                    conn.port, proto,
                )
            built.epoch = epoch0
            with self._lock:
                if self.policy_epoch != epoch0:
                    continue  # epoch moved under the build: retry
                # Double-checked insert: a racing binder may have won.
                eng = self._engines.setdefault(key, built)
            break
        if eng is None:
            return  # persistent epoch churn: serve on the oracle path
        sc.engine = eng
        # Whole-frame engines (r2d2, dns) are vectorized-path capable.
        sc.fast_ok = proto in FAST_PROTOS

    def _make_engine(self, ins, policy, policy_name: str, ingress: bool,
                     port: int, proto: str, prior=None):
        """Compile one engine from an EXPLICIT policy object — shared
        by the first-bind path (live map) and the epoch builder
        (staged map), so the two can never drift.

        ``prior`` is the engine this build replaces (epoch swaps pass
        the outgoing generation); the ledger uses its model's shape key
        to classify the rebuild as vocab churn vs. new-shape churn."""
        t0 = time.perf_counter()
        if proto in ("r2d2", "dns"):
            if proto == "r2d2":
                from ..models.r2d2 import build_r2d2_model

                if self.config.seam_probe:
                    from ..models.base import SeamProbe

                    model = SeamProbe()
                else:
                    mesh = self._serving_mesh()
                    if mesh is not None:
                        # Multi-chip build: rule rows split-balanced and
                        # padded across RULE_AXIS, single-chip fallback
                        # compiled alongside (the device-loss rung).
                        from ..parallel.rulesharding import (
                            mesh_r2d2_model,
                        )

                        model = mesh_r2d2_model(policy, ingress, port,
                                                mesh)
                    else:
                        model = build_r2d2_model(policy, ingress, port)
                cls = R2d2BatchEngine
            else:
                # The DNS engine rung: same scalar contract as r2d2 (the
                # flagship FlowState machinery, subclassed with the
                # length-prefix framing hooks), mesh-aware build with
                # the single-chip fallback compiled alongside.
                from ..models.dns import build_dns_model
                from ..runtime.dnsengine import DnsBatchEngine

                mesh = self._serving_mesh()
                if mesh is not None:
                    from ..parallel.rulesharding import mesh_dns_model

                    model = mesh_dns_model(policy, ingress, port, mesh)
                else:
                    model = build_dns_model(policy, ingress, port)
                cls = DnsBatchEngine
            eng = cls(
                model,
                capacity=self.config.batch_flows,
                width=self.config.batch_width,
                logger=ins.access_logger,
                max_buffer=self.config.max_flow_buffer,
                attr_enabled=self._flow_observe,
                min_rows=self._min_bucket,
            )
            # The pump launches through the service, like every other
            # round path: one executable per shape, ledgered, mesh-rung
            # guarded (set before the prewarm below).
            eng.judge_dispatch = functools.partial(
                self._engine_judge_dispatch, eng
            )
            self._finish_engine_build(eng, proto, prior, t0)
            return eng
        from ..runtime.l7engine import (
            CassandraBatchEngine,
            HttpSidecarEngine,
            MemcacheBatchEngine,
        )

        if proto == "cassandra":
            from ..models.cassandra import build_cassandra_model

            model = build_cassandra_model(policy, ingress, port)
            cls = CassandraBatchEngine
        elif proto == "http":
            from ..models.http import build_http_model_for_port

            mesh = self._serving_mesh()
            if mesh is not None:
                from ..parallel.rulesharding import mesh_http_model

                model = mesh_http_model(policy, ingress, port, mesh)
            else:
                model = build_http_model_for_port(policy, ingress, port)
            cls = HttpSidecarEngine
        else:
            from ..models.memcached import build_memcache_model

            model = build_memcache_model(policy, ingress, port)
            cls = MemcacheBatchEngine
        eng = cls(
            policy, ingress, port, model,
            logger=ins.access_logger,
            capacity=self.config.batch_flows,
            max_buffer=self.config.max_flow_buffer,
            attr_enabled=self._flow_observe,
        )
        # Verdict-cache judge tier (flow_cache): byte-invariant
        # identities are answered host-side from the claim instead of
        # riding the device batch (cassandra/memcached make no claim,
        # so the flag is inert there).
        eng.cache_enabled = self._flow_cache_on
        # Containment hooks: the judge step is skipped while the device
        # is quarantined (host policy.matches fallback, bit-identical),
        # and judge crashes count toward the poisoned-engine threshold.
        eng.device_gate = lambda: not self.guard.quarantined
        eng.device_fail_hook = lambda exc: self._record_contained_failure(
            f"judge-crash: {type(exc).__name__}"
        )
        # Judge dispatch through the service (shared jit caches + the
        # mesh demotion rung): device loss on a sharded l7 model
        # demotes to the single-chip fallback instead of host-judging
        # every subsequent round through the crash containment.
        eng.judge_dispatch = functools.partial(
            self._engine_judge_dispatch, eng
        )
        if hasattr(eng, "judge_shapes"):
            # Judge executables compile at their declared buckets here,
            # before traffic (a compile inside a round could outlast the
            # device-call watchdog).
            self._finish_engine_build(eng, proto, prior, t0)
            return eng
        # Other l7 engines have no prewarm rung (the judge executable
        # traces lazily through the shared jit caches, where the
        # ledger's shim times it); the recorded unit here is the
        # host-side automaton build itself.
        try:
            self.ledger.record_compile(
                proto, time.perf_counter() - t0,
                cause=self._rebuild_cause(model, prior),
                shape=self._model_shape_key(model),
                rules=self._rule_bucket_of(model),
                kind="engine-build", epoch=self.policy_epoch,
            )
        except Exception:  # noqa: BLE001 — ledger must not cost the build
            pass
        return eng

    def _finish_engine_build(self, eng, proto: str, prior, t0: float) -> None:
        """Prewarm a freshly built engine and ledger the build — but
        ONLY when the prewarm actually launched a trace.  A same-bucket
        epoch swap lands on warm executables end to end and must record
        ZERO compile events; that silence is the asserted invariant the
        churn soak pins (warm churn performs no compiles)."""
        warmed = self.prewarm(eng)
        if not warmed:
            return
        model = getattr(eng, "model", None)
        try:
            self.ledger.record_compile(
                proto, time.perf_counter() - t0,
                # Explicit cause when we can classify the rebuild from
                # the shape delta; None falls through to the enclosing
                # cause_scope (mesh-reshape / repromotion / heal-rebind)
                # and finally to "cold" on the first bind.
                cause=self._rebuild_cause(model, prior),
                shape=self._model_shape_key(model),
                rules=self._rule_bucket_of(model),
                kind="engine-build", epoch=self.policy_epoch,
            )
        except Exception:  # noqa: BLE001 — ledger must not cost the build
            pass

    def _rebuild_cause(self, model, prior):
        """Classify an epoch rebuild from the shape delta against the
        engine it replaces: rule bucket held but automaton axes moved →
        vocab churn (new DFA/NFA state counts at the same bucket); any
        bucket/structure change → new-shape churn.  None (→ enclosing
        scope / cold) when there is no prior generation."""
        if prior is None:
            return None
        prior_model = getattr(prior, "model", None)
        if prior_model is None or model is None:
            return ledger_mod.CAUSE_CHURN_NEW_SHAPE
        old_b = self._rule_bucket_of(prior_model)
        new_b = self._rule_bucket_of(model)
        if old_b is not None and old_b == new_b:
            return ledger_mod.CAUSE_CHURN_VOCAB
        return ledger_mod.CAUSE_CHURN_NEW_SHAPE

    @staticmethod
    def _rule_bucket_of(model):
        """Best-effort padded rule-row bucket: the leading dim of the
        per-rule match table (cmd_len for r2d2, name_len for dns);
        None for models without one (SeamProbe, l7 judge models)."""
        for attr in ("cmd_len", "name_len"):
            v = getattr(model, attr, None)
            shp = getattr(v, "shape", None)
            if shp:
                return int(shp[0])
        return None

    def _engine_judge_dispatch(self, eng, data, lengths, remotes):
        """(complete, len, allow, rule-or-None) for an engine's own
        device step (the r2d2/DNS pump, an l7 judge) through the
        launcher — reads eng.model at CALL time so a mesh demotion's
        pointer flip (or an epoch swap) takes effect mid-stream."""
        return self._model_call_attr(eng.model, data, lengths, remotes)

    def close_connection(self, conn_id: int, expect=None) -> None:
        # Routed through the dispatcher by the caller so in-flight data
        # for this conn is processed first.  ``expect`` pins the
        # connection object captured at submit time: if the id was
        # reused for a NEW connection before the deferred close ran, the
        # fresh connection must survive.
        with self._lock:
            sc = self._conns.get(conn_id)
            if sc is None or (expect is not None and sc is not expect):
                return
            del self._conns[conn_id]
            self._stale_conns.discard(conn_id)
            self._rebind_inflight.discard(conn_id)
            if conn_id < self._tab_size:
                self._tab_engine[conn_id] = -1
                self._tab_dirty[conn_id] = 0
            self._disarm_flow_cache(conn_id, "close")
        if sc.engine is not None:
            sc.engine.close_flow(conn_id)
        if self._reasm is not None:
            self._reasm.arena.drop(conn_id)
        pl.close_connection(conn_id)
        if self.flowlog is not None:
            self.flowlog.forget_conn(conn_id)

    # -- fan-in sessions (N shims, one dispatcher) ------------------------

    def _new_session(self) -> SessionState:
        with self._sess_lock:
            self._session_seq += 1
            sess = SessionState(self._session_seq)
            self._sessions[sess.id] = sess
            metrics.SidecarSessionsActive.set(float(len(self._sessions)))
        return sess

    def _session_dead(self, sess: SessionState, reason: str) -> None:
        """Retire one session from the live registry; idempotent per
        session.  Only DATA-PLANE sessions (named, or having submitted
        work) enter the bounded post-mortem ring and the deaths
        metric: a monitoring loop's control connections would
        otherwise cycle the ring and bury the one dead row that
        mattered (the pod that crashed)."""
        relevant = sess.named or sess.submitted > 0
        # Both arms route through the declared-edge mediation (R18):
        # the control-plane arm records the death reason without
        # bumping the typed metric, instead of flipping the state
        # field bare (which would also skip the dead-stays-dead and
        # declared-edge checks mark_dead enforces).
        sess.mark_dead(sess.death_reason or reason, counted=relevant)
        with self._sess_lock:
            if self._sessions.pop(sess.id, None) is not None and relevant:
                self._dead_sessions.append(sess.status())
            metrics.SidecarSessionsActive.set(float(len(self._sessions)))

    # Bounded label/storm-table vocabularies: identities are
    # wire-supplied, so both the Prometheus label set and the
    # reconnect-history table must be capped — a shim cycling pod
    # names (or a crash-looping deployment renaming per restart) must
    # not grow either without bound for the node's lifetime.
    _METRIC_IDENT_CAP = 256
    _STORM_TABLE_CAP = 1024

    def _session_hello(self, sess: SessionState, identity: str) -> None:
        """Identity announcement: name the session and run crash-loop
        detection — an identity reconnecting faster than the storm
        threshold starts this session QUARANTINED (typed), so a
        crash-looping pod costs one latch check per flood frame instead
        of full classification, and its neighbors nothing at all.  The
        control plane (module/policy/conn replay) still serves, so a
        healed pod exits the latch by simply staying up.  Only the
        FIRST hello on a session is honored (set_identity), and the
        metric label falls back to 'other' past the bounded identity
        vocabulary — status rows always carry the full identity."""
        if sess.named:
            return  # one identity per session; later hellos ignored
        sess.set_identity(identity)
        if not identity:
            return
        identity = sess.identity  # length-capped form
        # Handoff restore: a known identity reconnecting right after a
        # graceful restart is EXEMPT from the storm history — the
        # restart drove the reconnect, the pod is not crash-looping.
        # (The exactly-once audit spans the boundary as a sum: old-
        # process answers + new-process answers + typed local sheds.)
        restored = self._handoff_sessions.pop(identity, None) is not None
        if restored:
            self.handoff_session_restores += 1
        storm_n = self.config.session_reconnect_storm
        now = time.monotonic()
        window = self.config.session_reconnect_window_s
        with self._sess_lock:
            if (
                identity in self._metric_idents
                or len(self._metric_idents) < self._METRIC_IDENT_CAP
            ):
                self._metric_idents.add(identity)
                sess.metric_identity = identity
            else:
                sess.metric_identity = "other"
            if not storm_n or restored:
                return
            hist = self._ident_connects.get(identity)
            if hist is None:
                while len(self._ident_connects) >= self._STORM_TABLE_CAP:
                    # Bounded LRU: evict the least-recently-connecting
                    # identity (dict preserves insertion order; re-
                    # inserting on every hello keeps it recency-ordered).
                    self._ident_connects.pop(
                        next(iter(self._ident_connects))
                    )
                # Sized from the configured threshold: a fixed cap
                # below storm_n would silently disable detection
                # (len(hist) could never exceed the threshold).
                hist = _deque(maxlen=storm_n + 1)
            else:
                del self._ident_connects[identity]
            self._ident_connects[identity] = hist
            hist.append(now)
            while hist and now - hist[0] > window:
                hist.popleft()
            storm = len(hist) > storm_n
        if storm and not sess.quarantined_now():
            log.warning(
                "session %d (%s): reconnect storm (%d connects in "
                "%.1fs); quarantining for %.1fs",
                sess.id, identity, storm_n, window,
                self.config.session_quarantine_s,
            )
            sess.quarantine(
                QUARANTINE_RECONNECT_STORM,
                self.config.session_quarantine_s,
            )

    def _drr_share(self) -> int:
        """Per-session queue share: the admission queue split across
        CONNECTED sessions plus one headroom slot, floored at
        session_share_min.  Connected — not recently-active: an
        activity-windowed count is unstable under the very starvation
        it exists to prevent (a flooder's giant rounds slow its
        neighbors until they look idle, which GROWS the flooder's
        share — a feedback loop measured at 2s neighbor p99).  The +1
        headroom slot is load-bearing too: splitting by sessions alone
        hands a lone flooder the entire queue, and a neighbor's first
        submission then meets the GLOBAL cap — a typed queue_full
        shed, but still a denial of service.  Recomputed lazily
        (≤ every 50ms) — the per-batch fast path pays one float
        compare."""
        now = time.monotonic()
        if now - self._share_ts > 0.05:
            with self._sess_lock:
                # Data-plane sessions only: a control-plane connection
                # (each `cilium sidecar status`/`trace` invocation is a
                # short-lived unnamed session that never submits data)
                # must not shrink every real pod's share.
                n_sessions = sum(
                    1 for x in self._sessions.values()
                    if x.named or x.submitted
                )
            # The numerator is the mesh rung's ACTUAL capacity (PR 15
            # queue split x the ladder's capacity fraction): a
            # half-width mesh halves every session's credit window so
            # degraded overload sheds typed at admission instead of
            # queueing into deadline-shed p99 explosions.
            entries = int(
                self.config.shed_queue_entries * self._mesh_capacity
            )
            self._share_val = max(
                entries // max(n_sessions + 1, 2),
                self.config.session_share_min,
            )
            self._share_ts = now
        return self._share_val

    def _fanin_admit(self, sess, batch) -> str:
        """Fan-in admission gate, run on the submitting session's own
        reader thread before any queue/cut-through hand-off.  Returns
        '' to admit, else the typed shed reason the caller owes the
        batch (quarantine latch, then the DRR credit window).

        The quota is a per-session OUTSTANDING window: credits are
        entries, spent at admission and returned only when the entry's
        typed answer is written (submitted − answered — the same
        counters the exactly-once surface audits, so the window is
        correct across the dispatcher queue AND the completion
        pipeline; a queued-weight quota alone lets a flooder shift its
        backlog into the issued-not-answered FIFO where neighbors
        still queue behind it).  A session under its share is never
        refused — work conserving — and a flood's buffering lands on
        the flooder, typed, not on its neighbors' latency."""
        if self._fenced:
            # Fenced zombie predecessor: every data-plane frame after
            # surrender is refused typed (never silently) so a slow
            # shim that has not reconnected yet sees a clean shed.
            self.fence_rejects += 1
            metrics.SidecarFenceRejects.inc("data")
            return SHED_FENCED
        if sess is None:
            return ""
        if sess.quarantined_now():
            return SHED_SESSION_QUARANTINED
        # Classic-DRR one-batch overshoot: the PRE-batch outstanding is
        # compared against the share (``submitted`` already counts this
        # batch — the caller bumps it before the gate — so subtract it
        # back).  A session at or under its share is never refused, no
        # matter the batch size: comparing post-batch outstanding would
        # permanently shed (and eventually 'flood'-quarantine) an IDLE
        # session whose single wire batch exceeds the share.  The
        # window overshoot is bounded by one wire batch.
        if (
            sess.submitted - batch.count - sess.answered
            > self._drr_share()
        ):
            # Over-quota strike: sustained flooding escalates to the
            # session quarantine latch (cheaper than re-classifying
            # every flood frame, and typed for the operator).  The
            # clock is read HERE only — the under-share happy path
            # stays at one subtraction and one compare.
            strikes = self.config.session_flood_strikes
            if strikes:
                now = time.monotonic()
                if now - sess.strike_window_start > (
                    self.config.session_strike_window_s
                ):
                    sess.strike_window_start = now
                    sess.strikes = 0
                sess.strikes += 1
                if sess.strikes >= strikes:
                    sess.strikes = 0
                    sess.quarantine(
                        QUARANTINE_FLOOD,
                        self.config.session_quarantine_s,
                    )
            return SHED_SESSION_QUOTA
        return ""

    def _schedule_shm_reclaim(self, peer: ShmPeer) -> None:
        """A session died holding attached rings and never sent
        MSG_SHM_DETACH: the creator (the dead shim) will never unlink
        its segments, so the survivor must — after the attach lease
        expires (a shim alive behind a half-open socket reconnects
        with FRESH segments, so a post-lease unlink can never pull a
        live ring out from under anyone)."""
        t = threading.Timer(
            max(self.config.shm_lease_s, 0.0),
            self._reclaim_shm_segments, args=(peer,),
        )
        t.daemon = True
        t.name = "shm-reclaim"
        with self._sess_lock:
            self._reclaim_timers = [
                x for x in self._reclaim_timers if x.is_alive()
            ]
            self._reclaim_timers.append(t)
        t.start()

    def _reclaim_shm_segments(self, peer: ShmPeer) -> None:
        if not peer.reclaim():
            # Nothing to unlink: the creator beat us to it (e.g. a
            # half-open-socket shim that reconnected and later closed
            # orderly).  Counting this would make the leak-detection
            # metric report phantom recoveries.
            return
        self.shm_reclaims += 1
        metrics.SidecarShmReclaims.inc()
        log.info(
            "reclaimed orphaned shm segments (generation %d) after "
            "lease expiry", peer.generation,
        )

    # -- data plane (dispatcher worker thread only) -----------------------

    @staticmethod
    def _batch_nbytes(batch) -> int:
        """Payload bytes a queued batch will put on the device at
        issue (blob length for DataBatch, summed row lengths for
        MatrixBatch) — the byte-weighted half of queue occupancy."""
        blob = getattr(batch, "blob", None)
        if blob is not None:
            return len(blob)
        lens = getattr(batch, "lengths", None)
        return int(lens.sum()) if lens is not None else 0

    def submit_data(self, client, batch: wire.DataBatch,
                    backlogged: bool = False) -> None:
        if not batch.arrival:  # wire unpack stamps ingress; keep it
            batch.arrival = time.monotonic()
        sess = getattr(client, "session", None)
        if sess is not None:
            sess.submitted += batch.count
        item = ("data", client, batch)
        reason = self._fanin_admit(sess, batch)
        if reason:
            self._shed_item(item, reason)
            return
        if not backlogged and self._try_cut_through(item):
            return
        if not self.dispatcher.submit(item, weight=batch.count,
                                      session=sess,
                                      nbytes=self._batch_nbytes(batch)):
            self._shed_item(item, "queue_full")

    def submit_matrix(self, client, mb: wire.MatrixBatch,
                      backlogged: bool = False) -> None:
        if not mb.arrival:  # wire unpack stamps ingress; keep it
            mb.arrival = time.monotonic()
        sess = getattr(client, "session", None)
        if sess is not None:
            sess.submitted += mb.count
        item = ("mat", client, mb)
        reason = self._fanin_admit(sess, mb)
        if reason:
            self._shed_item(item, reason)
            return
        if not backlogged and self._try_cut_through(item):
            return
        if not self.dispatcher.submit(item, weight=mb.count,
                                      session=sess,
                                      nbytes=self._batch_nbytes(mb)):
            self._shed_item(item, "queue_full")

    def _try_cut_through(self, item) -> bool:
        """Greedy-mode cut-through: process the round directly on the
        shim reader thread when the service is idle — removes the
        reader→dispatcher thread handoff (a GIL-scheduling wait, not a
        fixed cost).  Under load the reader routes to the dispatcher
        instead, whose busy-worker queueing is what aggregates the
        backlog into large rounds.  (Full reader-side drain-and-process
        was tried and reverted: it keeps rounds at 1-2 messages, so
        per-round fixed costs multiply and the tail worsens ~2×.)

        Per-connection FIFO is preserved: a connection's data arrives on
        exactly one reader thread, so an earlier item from this client is
        either already processed or sitting in the dispatcher queue — in
        which case the queue is non-empty and we line up behind it.
        """
        if not self._inline_complete:
            return False
        disp = self.dispatcher
        # Lock-free peek: queued or popped-but-unprocessed work anywhere
        # means this item must line up behind it (the _busy set-before-
        # clear ordering in dispatch._pop_locked makes this peek safe).
        if disp._pending or disp._busy:
            return False
        # Non-blocking: if a round is mid-process, queue to the
        # dispatcher so the worker coalesces everything that arrived
        # during the in-flight round into ONE device call.  Capture the
        # lock OBJECT (mirroring BatchDispatcher._run): the stall
        # watchdog swaps _in_process_lock for a fresh one at deposal —
        # reachable while cut-through holds it (a popped batch blocking
        # on this lock trips the watchdog) — and a re-read release would
        # raise RuntimeError on the unheld replacement out of
        # submit_data while leaking this lock held forever.
        lock = disp._in_process_lock
        if not lock.acquire(blocking=False):
            return False
        released = False
        try:
            if lock is not disp._in_process_lock:
                # Deposed between read and acquire: a replacement
                # generation owns the queue (and a new lock) — line up
                # behind it rather than racing its rounds.
                return False
            # Arm the stall watchdog for this inline round (rechecks
            # pending/busy under the dispatcher condition): a device
            # call hung HERE on an idle service would otherwise never
            # be detected — no deposal, no quarantine, no typed reply,
            # one wedged shim reader.
            rid = disp.begin_inline_round(
                [item], nbytes=self._batch_nbytes(item[2])
            )
            if rid is None:
                return False
            self.inline_batches += 1
            try:
                self._process([item])
            except Exception as exc:  # noqa: BLE001 — reader must survive
                log.exception("cut-through process failed")
                # Same crash containment as the dispatcher path: every
                # entry gets a typed error verdict, never a silent drop
                # (suppressed if the watchdog already shed this round).
                try:
                    self._on_batch_error([item], exc)
                except Exception:  # noqa: BLE001
                    log.exception("cut-through error containment failed")
            finally:
                # Release BEFORE closing the round, mirroring _run's
                # release-then-clear-_busy ordering: the watchdog treats
                # a free in-process lock as "process() returned, its
                # verdicts are sent" and skips deposal.  Closing the
                # round first would leave a window (busy=True, lock
                # held, verdicts already sent) where a round completing
                # just past the deadline gets deposed and its served
                # seq double-replied with a SHED batch.
                released = True
                lock.release()
                disp.end_inline_round(rid)
                threading.current_thread()._disp_round = None
        finally:
            if not released:
                lock.release()
        return True

    @staticmethod
    def _batch_desc(batch, client=None) -> tuple:
        """(seq, n, arrival, first conn, session) — the tracer's
        per-wire-batch descriptor for e2e observation and span naming.
        The session id (0 = unknown) lets `cilium sidecar trace
        --session` attribute an exemplar to one shim."""
        sess = getattr(client, "session", None)
        return (
            batch.seq, batch.count, batch.arrival,
            int(batch.conn_ids[0]) if batch.count else 0,
            sess.id if sess is not None else 0,
        )

    @staticmethod
    def _oldest_arrival(items: list) -> float:
        """Oldest ingress stamp across a round's data items (the
        tracer's admit boundary — worst queue wait in the round)."""
        arr = [it[2].arrival for it in items if it[2].arrival]
        return min(arr) if arr else 0.0

    @staticmethod
    def _ring_wait(items: list) -> float:
        """Worst shm slot-commit → doorbell-drain wait across a round's
        data items — the tracer's STAGE_RING input (0 for socket-
        delivered rounds, whose arrival IS the frame decode)."""
        waits = [it[2].ring_wait for it in items if it[2].ring_wait]
        return max(waits) if waits else 0.0

    def _run_mat_group(self, items: list, t_pop: float) -> bool:
        """Whole-round path: every item is a complete-flag matrix batch
        of the configured width, judged as ONE round — one concatenation,
        one eligibility gather of engine, dirty and remotes under one
        _lock trip, one (chunked) device issue with the remotes in hand,
        and one verdict frame per client.  This collapses the per-item
        costs that dominate aggregated rounds (measured: eligibility
        17µs + frame 14µs + client unpack 8µs per item).  Both
        completion modes run it; only the completion differs
        (_issue_mat_round).  Returns False — with no side effects — when
        the round needs the per-item route."""
        stages = self.seam_stages if self.config.seam_probe else None
        t0 = time.thread_time() if stages is not None else 0.0

        def mark(stage: str) -> None:
            nonlocal t0
            if stages is None:
                return
            t1 = time.thread_time()
            rec = stages.setdefault(stage, [0, 0.0])
            rec[0] += 1
            rec[1] += t1 - t0
            t0 = t1

        if len(items) == 1:
            mb0 = items[0][2]
            ids = mb0.conn_ids
            lengths = mb0.lengths
            rows = mb0.rows
        else:
            ids = np.concatenate([it[2].conn_ids for it in items])
            lengths = np.concatenate([it[2].lengths for it in items])
            rows = np.concatenate([it[2].rows for it in items])
        n = len(ids)
        if n == 0:
            return False
        # Range check on the raw u64 ids: an int64 view wraps ids past
        # 2**63 negative, and a negative fancy index reads another
        # conn's row.
        top = int(ids.max())
        idx = ids.astype(np.int64)
        mark("concat")
        t_before = time.monotonic()
        with self._lock:
            swap_s = self._swap_overlap(t_before)
            if top >= self._tab_size:
                return False
            eng_idx = self._tab_engine[idx]
            e0 = int(eng_idx[0])
            if e0 < 0 or (eng_idx != e0).any():
                return False
            if self._tab_dirty[idx].any():
                return False
            remotes = self._tab_src[idx]
            engine = self._engine_objs[e0]
        if (
            engine is None
            or isinstance(engine.model, ConstVerdict)
            or _engine_framing(engine) is None
        ):
            return False
        if int(lengths.min()) < 2 or int(lengths.max()) > self.config.batch_width:
            return False
        mark("eligibility")
        rt = self.tracer.begin_round(
            PATH_VEC, n, self._oldest_arrival(items), t_pop,
            ring_s=self._ring_wait(items), swap_s=swap_s,
        )
        rt.formed()
        self.whole_rounds += 1
        self.whole_entries += n
        metrics.VerdictWholeRounds.inc()
        metrics.VerdictWholeEntries.inc(amount=n)
        self._issue_mat_round(items, engine, ids, lengths, rows, remotes,
                              rt, mark)
        return True

    def _issue_mat_round(self, items: list, engine, ids, lengths, rows,
                         remotes, rt, mark=lambda _stage: None) -> None:
        """Issue a formed matrix group — whole round or the per-item
        route's engine group — with its remotes in hand, then complete
        it as the mode says: greedy reads back inline and answers on
        this thread; pipelined hands ONE record to the completion
        pipeline, tagged with this round's id so a round the stall
        watchdog shed is never also answered, and the send loop emits
        it in FIFO order."""
        n = len(ids)
        issued = self._issue_chunks(
            engine, rows, lengths.astype(np.int32), remotes
        )
        mark("device_issue")
        rt.submitted()
        if not self._inline_complete:
            self._completion_put(
                ("mat", issued, n, items, rt, engine, ids, lengths)
            )
            return
        allow, rules = self._readback_chunks(issued, n)
        # Device-complete is this FENCED boundary (np.asarray readback)
        # — block_until_ready was observed returning pre-execution
        # (BENCH_NOTES r4) and would book device time into the send stage.
        rt.completed()
        mark("readback")
        self._answer_mat_round(items, engine, ids, lengths, allow, rules, rt)
        mark("respond")

    def _answer_mat_round(self, items: list, engine, ids, lengths, allow,
                          rules, rt) -> None:
        """Answer a matrix group from its verdict arrays: one frame per
        client — a plain VERDICT_BATCH for a single seq, a VERDICT_MULTI
        covering all its seqs otherwise — each body built by one
        _verdict_body over the client's slice of the round."""
        n = len(ids)
        self.fast_log.log_batch(
            getattr(engine, "proto", "r2d2"), n, int(n - allow.sum())
        )
        self.vec_batches += 1
        self.vec_entries += n
        metrics.ProxyBatches.inc()
        per_client: dict[int, list] = {}
        start = 0
        for _, client, mb in items:
            rec = per_client.get(id(client))
            if rec is None:
                rec = per_client[id(client)] = [client, [], [], [], []]
            rec[1].append(mb.seq)
            rec[2].append(mb.count)
            rec[3].append((start, start + mb.count))
            rec[4].append(mb)
            start += mb.count
        rt.drained()
        deny_inject = getattr(engine, "DENY_INJECT", None)
        for client, seqs, counts, spans, mbs in per_client.values():
            # ``batches=mbs``: send() marks every covered wire batch
            # answered under the write lock before writing, so a stall
            # deposal tripped by a LATER client's wedged send in this
            # same round can never SHED-double-reply a seq served here.
            try:
                if len(seqs) == 1:
                    a, b = spans[0]
                    client.send(
                        wire.MSG_VERDICT_BATCH,
                        self._verdict_frame(
                            seqs[0], ids[a:b], lengths[a:b], allow[a:b],
                            deny_inject,
                        ),
                        batches=mbs,
                    )
                    continue
                if spans[-1][1] - spans[0][0] == sum(counts):
                    # Contiguous spans (the single-client round and any
                    # unbroken run): zero-copy views.
                    sel = slice(spans[0][0], spans[-1][1])
                else:
                    sel = np.concatenate(
                        [np.arange(a, b) for a, b in spans]
                    )
                client.send(
                    wire.MSG_VERDICT_MULTI,
                    wire.pack_verdict_multi(
                        seqs, counts, sum(counts),
                        self._verdict_body(
                            ids[sel], lengths[sel], allow[sel], deny_inject
                        ),
                    ),
                    batches=mbs,
                )
            except Exception:  # noqa: BLE001
                # A frame that could not be built: fail closed for the
                # client's batches this round has not answered.
                log.exception("verdict answer failed")
                for mb in mbs:
                    if not mb.answered:
                        self._answer_error(client, mb)
        if not self._round_thread_suppressed():
            self.tracer.finish_round(
                rt, [self._batch_desc(it[2], it[1]) for it in items]
            )
            self._record_vec_round(engine, ids, allow, rules)

    def _readback_chunks(self, issued: list, n: int):
        """Materialize a round's (allow, rule) chunk futures into host
        arrays.  np.asarray per array beats one batched device_get for
        the typical 1-2 co-located chunks (measured 3µs vs 20µs).
        Device errors deny (and unattribute) the chunk; a failed rule
        readback only unattributes it — the rule array exists for
        OBSERVABILITY and must never flip verdicts that materialized."""
        vals = []
        for fut, rfut, _, _, _ in issued:
            try:
                vals.append(np.asarray(fut))
            except Exception:  # noqa: BLE001 — deny on device error
                log.exception("device readback failed")
                vals.append(None)
            if rfut is not None:
                try:
                    vals.append(np.asarray(rfut))
                except Exception:  # noqa: BLE001 — unattribute only
                    log.exception("rule-attribution readback failed")
                    vals.append(None)
        return self._chunk_values(issued, n, vals)

    def _record_vec_round(self, engine, conn_ids, allow, rules) -> None:
        """One flow-record batch for a vec/matrix round: columnar
        arrays straight from the readback, ONE ring append (R7: no
        per-entry work on the hot path).  Epoch and kinds legend both
        come from the CAPTURED engine — the tables the rule ids
        actually index — never from a re-read that churn could have
        rebound."""
        if self.flowlog is None:
            return
        self.flowlog.add_round(
            PATH_VEC,
            conn_ids,
            np.where(allow, CODE_FORWARDED, CODE_DENIED).astype(np.int8),
            rules,
            kinds=getattr(engine.model, "match_kinds", ()),
            epoch=getattr(engine, "epoch", 0),
        )

    @staticmethod
    def _entry_code(result: int, ops) -> int | None:
        """Flow-record verdict code for one entrywise response: first
        DROP/ERROR op decides, else PASS forwards; a MORE-only entry
        made no decision (no record)."""
        if result == int(FilterResult.SHED):
            return CODE_SHED
        if result != int(FilterResult.OK):
            return CODE_ERROR
        has_pass = False
        for op, _n in ops:
            if op == int(DROP):
                return CODE_DENIED
            if op == int(ERROR):
                return CODE_ERROR
            if op == int(PASS):
                has_pass = True
        return CODE_FORWARDED if has_pass else None

    @staticmethod
    def _kind_for(model, rule: int) -> str:
        kinds = getattr(model, "match_kinds", ()) if model is not None else ()
        return kinds[rule] if 0 <= rule < len(kinds) else ""

    def _engine_rule_kind(self, engine, conn_id: int,
                          sc=None) -> tuple[int, str, int]:
        """(rule, kind, epoch) for an entry decided by a CAPTURED
        engine — the slot-reuse-safe attribution: churn may free and
        reuse the engine's table slot (or rebind sc.engine) before the
        record is emitted, so the rule id must resolve against the
        engine that judged it, stamped with that engine's epoch."""
        fl = engine.flows.get(conn_id)
        if fl is not None:
            conn = getattr(fl, "conn", None)
            rule = (
                conn.last_rule_id if conn is not None
                else getattr(fl, "last_rule_id", -1)
            )
            return (
                int(rule),
                self._kind_for(engine.model, int(rule)),
                getattr(engine, "epoch", 0),
            )
        if sc is not None:
            return int(sc.conn.last_rule_id), "", self.policy_epoch
        return -1, "", -1

    def _entry_rule_kind(self, sc, conn_id: int) -> tuple[int, str]:
        """Rule attribution for an entrywise entry decided inside an
        engine pump or the oracle parser: the device-assisted engines
        and the oracle stamp Connection.last_rule_id (via matches_at /
        the precomputed-verdict queue), the r2d2 pump stamps
        FlowState.last_rule_id.  EMISSION-time fallback only — decision
        layers capture via _engine_rule_kind instead wherever the
        engine is snapshotted (rules_out), so churn cannot rebind
        sc.engine between decision and record."""
        if sc is None:
            return -1, ""
        eng = sc.engine
        if eng is not None:
            fl = eng.flows.get(conn_id)
            if fl is not None:
                conn = getattr(fl, "conn", None)
                rule = (
                    conn.last_rule_id if conn is not None
                    else getattr(fl, "last_rule_id", -1)
                )
                return int(rule), self._kind_for(eng.model, int(rule))
        # Oracle path (no engine): the in-process Connection's walk.
        return int(sc.conn.last_rule_id), ""

    def _record_entrywise(self, path: str, items: list, responses: dict,
                          rules_out: dict | None,
                          cached: set | None = None) -> None:
        """One flow-record batch for an entrywise round: the hot loop
        builds plain lists; the ring lock is taken ONCE in add_round
        (R7: per-round, never per-entry-under-the-lock).  ``cached``
        holds (item_id, entry_idx) keys already recorded on the
        `cached` path at decision time — skipped here so a hit is
        never double-recorded under the wrong path label."""
        if self.flowlog is None:
            return
        # Plain reference: per-key dict reads are GIL-atomic, and a conn
        # closed mid-iteration just materializes without metadata.
        conns = self._conns
        conn_ids: list[int] = []
        codes: list[int] = []
        rules: list[int] = []
        kinds: list[str] = []
        epochs: list[int] = []
        for item in items:
            resp = responses.get(id(item))
            if resp is None:
                continue
            batch = item[2]
            for i in range(batch.count):
                r = resp[i]
                if r is None:
                    continue
                if cached is not None and (id(item), i) in cached:
                    continue  # recorded on the `cached` path already
                conn_id, result, ops = r[0], r[1], r[2]
                code = self._entry_code(result, ops)
                if code is None:
                    continue
                sc = conns.get(conn_id)
                judged = (
                    rules_out.get((id(item), i)) if rules_out else None
                )
                if judged is not None:
                    rule, kind, ep = judged  # captured at judge time
                    if code != CODE_FORWARDED:
                        # A non-forwarded entry must not borrow a
                        # stale allowing rule (see the else arm).
                        rule, kind = -1, ""
                elif code == CODE_FORWARDED:
                    rule, kind = self._entry_rule_kind(sc, conn_id)
                    ep = self.policy_epoch
                else:
                    # last_rule_id is the LAST decision's rule; a
                    # non-forwarded entry (its first DROP decided) must
                    # not borrow a later allowing frame's rule —
                    # denied/shed/error records are unattributed, like
                    # the vec path's deny rows.
                    rule, kind, ep = -1, "", -1
                conn_ids.append(conn_id)
                codes.append(code)
                rules.append(rule)
                kinds.append(kind)
                epochs.append(ep)
        if conn_ids:
            self.flowlog.add_round(
                path,
                np.asarray(conn_ids, np.int64),
                np.asarray(codes, np.int8),
                np.asarray(rules, np.int32),
                cols={
                    "match_kind": kinds,
                    "epoch": np.asarray(epochs, np.int64),
                },
            )

    def observe_dump(self, req: dict) -> dict:
        """Flow-record query for MSG_OBSERVE (`cilium observe`)."""
        if self.flowlog is None:
            return {"records": [], "stats": {"disabled": True}}
        records = self.flowlog.query(
            n=int(req.get("n", 100)),
            verdict=req.get("verdict"),
            path=req.get("path"),
            rule=req.get("rule"),
            conn=req.get("conn"),
            since=req.get("since"),
            epoch=req.get("epoch"),
            session=req.get("session"),
        )
        return {"records": records, "stats": self.flowlog.stats()}

    def submit_ring(self, client, records: list,
                    reader_backlog: bool = False) -> None:
        """Admission for one drained doorbell batch.  A single-record
        drain keeps the cut-through path (an idle stream's latency win
        survives the transport swap); a multi-record drain enqueues in
        ONE dispatcher lock trip (submit_many) so a deep doorbell does
        not pay a lock round trip per frame — the worker aggregates it
        into one device round exactly like a socket backlog.  Fan-in
        fairness runs per frame here too: the ring IS the credit loop,
        so an over-quota frame shed typed at this gate frees its slot
        immediately (head already advanced at drain) — DRR credit
        issuance, with the refusal accounted to the one session."""
        if len(records) == 1:
            kind, batch = records[0]
            self.shm_entries += batch.count
            if kind == "data":
                self.submit_data(client, batch, backlogged=reader_backlog)
            else:
                self.submit_matrix(client, batch,
                                   backlogged=reader_backlog)
            return
        sess = getattr(client, "session", None)
        items = []
        for kind, batch in records:
            self.shm_entries += batch.count
            if sess is not None:
                sess.submitted += batch.count
            item = (kind, client, batch)
            reason = self._fanin_admit(sess, batch)
            if reason:
                self._shed_item(item, reason)
            else:
                items.append((item, batch.count,
                              self._batch_nbytes(batch)))
        for item in self.dispatcher.submit_many(items, session=sess):
            self._shed_item(item, "queue_full")

    def submit_close(self, conn_id: int) -> None:
        with self._lock:
            sc = self._conns.get(conn_id)
        # force: a close must never be shed, or the conn leaks.
        self.dispatcher.submit(("close", conn_id, sc), weight=0, force=True)

    # -- fault containment -------------------------------------------------

    def _on_quarantine_change(self, quarantined: bool) -> None:
        metrics.DeviceQuarantined.set(1.0 if quarantined else 0.0)
        if quarantined:
            metrics.DeviceQuarantineEvents.inc()

    def _typed_entries(self, batch, result: int) -> list:
        """One typed (conn_id, result, no-ops) response per entry — the
        fail-closed shape for shed/crash verdicts (any non-OK result is
        a connection error to the datapath consumer)."""
        return [
            (int(cid), int(result), [], b"", b"")
            for cid in batch.conn_ids
        ]

    def _shed_item(self, item, reason: str) -> None:
        """Fail-closed DROP with a typed SHED response — the admission
        queue never hangs or silently drops an entry.  An item whose
        real verdicts already went out (a multi-group round can serve
        its vec group, then hang in a later group before deposal) is
        skipped: round-id suppression only stops sends issued AFTER the
        shed, it cannot retract one already on the wire, and a second
        reply for a consumed seq desyncs the shim.  The early
        ``answered`` read only saves building the reply; the
        AUTHORITATIVE check-and-mark happens under the client write
        lock inside send_verdicts, which also covers a real-verdict
        sendall still in flight (the wedged send that tripped the
        watchdog marks its batches before writing)."""
        _, client, batch = item
        if batch.answered:
            return
        n = batch.count
        try:
            sent = client.send_verdicts(
                batch.seq,
                self._typed_entries(batch, FilterResult.SHED),
                batch=batch,
            )
        except Exception:  # noqa: BLE001 — client may be gone
            log.exception("shed response send failed")
            return
        if sent:
            # Counted only when THIS reply answered the seq: a real-
            # verdict send that won the race under the write lock means
            # the entry was served, and booking it as shed too would
            # double-count it (status and the overload bench's shed
            # rate would over-report).
            self.shed_entries += n
            metrics.SidecarShedTotal.inc(reason, amount=n)
            # Overload marker for the incident timeline: one coalesced
            # ring event per shed reason per window, never per entry.
            self.recorder.record_overload(reason, n)
            sess = getattr(client, "session", None)
            if sess is not None:
                # Session-scoped attribution (fan-in): the operator can
                # pin a shed to the one pod that caused it.
                sess.count_shed(reason, n)
            self.tracer.record_shed(
                batch.seq, n, batch.arrival,
                int(batch.conn_ids[0]) if n else 0, reason,
                session=sess.id if sess is not None else 0,
            )
            if self.flowlog is not None:
                # One columnar batch per shed wire batch (cold path).
                self.flowlog.add_round(
                    PATH_SHED,
                    batch.conn_ids,
                    np.full(n, CODE_SHED, np.int8),
                    reason=reason,
                )

    def _on_batch_error(self, items: list, exc: BaseException) -> None:
        """Crash containment: a failed process(batch) produces typed
        per-entry error verdicts for EVERY entry in the batch instead of
        being swallowed — no client blocks on a crashed round."""
        self.batch_crashes += 1
        metrics.SidecarBatchCrashes.inc()
        self._record_contained_failure(
            f"batch-crash: {type(exc).__name__}"
        )
        for it in items:
            if it[0] == "close":
                try:
                    self.close_connection(*it[1:])
                except Exception:  # noqa: BLE001
                    log.exception("close during crash containment failed")
                continue
            _, client, batch = it
            if batch.answered:
                # This item's real verdicts (or its SHED reply) already
                # went out — e.g. a greedy multi-group round that served
                # its vec group inline before a later group crashed.  A
                # second reply would desync the shim; an in-flight send
                # is caught by the same check under the client write
                # lock inside send_verdicts.
                continue
            try:
                sent = client.send_verdicts(
                    batch.seq,
                    self._typed_entries(batch, FilterResult.UNKNOWN_ERROR),
                    batch=batch,
                )
            except Exception:  # noqa: BLE001
                log.exception("error response send failed")
                continue
            if sent:  # see _shed_item: never double-book served entries
                self.error_entries += batch.count
                sess = getattr(client, "session", None)
                if sess is not None:
                    sess.count_shed("error", batch.count)
                if self.flowlog is not None:
                    self.flowlog.add_round(
                        PATH_SHED,
                        batch.conn_ids,
                        np.full(batch.count, CODE_ERROR, np.int8),
                        reason="batch-crash",
                    )

    def _on_dispatch_stall(self, items: list) -> None:
        """Watchdog deposed a stuck round (device hang): quarantine the
        device and shed the stuck batch with typed verdicts — the stuck
        round's own late sends (from its thread or from pipeline
        records it queued) are round-suppressed."""
        self.guard.record_stall("dispatch-stall")
        metrics.DeviceStalls.inc()
        self.recorder.record_overload("stall_deposal", len(items))
        # A wedged round on a mesh is indistinguishable here from a
        # lost mesh device: drop to the single-chip rung BEFORE the
        # quarantine ladder re-probes, so the heal path resumes on an
        # executable that cannot be waiting on a dead device's
        # collective.
        if self._mesh is not None and self._mesh_demoted is None:
            self._demote_mesh("device-stall")
        for it in items:
            if it[0] == "close":
                # Re-queue for the replacement worker; never lost.
                self.dispatcher.submit(it, weight=0, force=True)
                continue
            self._shed_item(it, "stall")

    def _device_probe(self) -> None:
        """One real device round (used by quarantine re-probes): an
        r2d2 engine's model through the launcher, at a prewarmed shape;
        a bare device op when no row-shaped model exists.  Raises/hangs
        exactly when the device path is still unhealthy."""
        with self._lock:
            eng = next(
                (
                    e for e in self._engines.values()
                    if isinstance(e, R2d2BatchEngine)
                    and not isinstance(e.model, ConstVerdict)
                ),
                None,
            )
        if eng is not None:
            b = self._min_bucket
            w = self.config.batch_width
            out = self._model_call_attr(
                eng.model,
                np.zeros((b, w), np.uint8),
                np.zeros(b, np.int32),
                np.zeros(b, np.int32),
            )
            np.asarray(out[2])
            return
        import jax
        import jax.numpy as jnp

        with self._device_ctx():
            jax.device_get(jnp.ones(8))

    def _admit(self, items: list) -> list:
        """Admission pass at dispatch time: shed entries whose wire
        deadline or queue age passed while queued, pace quarantine
        re-probes, and sample queue-depth telemetry."""
        self.guard.maybe_reprobe(self._device_probe)
        self._maybe_mesh_reprobe()
        metrics.SidecarQueueDepth.set(float(self.dispatcher.pending_weight))
        now = time.monotonic()
        kept = []
        for it in items:
            if it[0] == "close":
                kept.append(it)
                continue
            b = it[2]
            expired = (
                b.deadline is not None and now > b.deadline
            ) or (
                self._queue_age_s
                and b.arrival
                and now - b.arrival > self._queue_age_s
            )
            if expired:
                self._shed_item(it, "deadline")
            else:
                kept.append(it)
        return kept

    def _demote_to_oracle(self, conn_id: int, sc: "_SidecarConn") -> None:
        """Move a conn off a quarantined pure-device engine onto the
        in-process oracle path, migrating the engine's retained request
        bytes into the oracle buffer mirror so no byte is lost or
        replayed.  The oracle IS the definition of bit-exactness, so
        verdicts keep flowing unchanged while the device is out."""
        engine = sc.engine
        if engine is None:
            return
        if self._reasm is not None:
            # Columnar-arena carry precedes the engine flow buffer (an
            # arena conn holds its residue THERE, never in the flow);
            # the dead/overflowed latch is dropped exactly like the
            # popped flow's below — the oracle serves fresh.
            residue, _dead = self._reasm.arena.release(conn_id)
            if residue:
                sc.bufs[False] = bytearray(residue) + sc.bufs[False]
        flow = engine.flows.pop(conn_id, None)
        if flow is not None and getattr(flow, "buffer", None):
            # Engine-retained request bytes precede anything the oracle
            # mirror may hold for this direction.
            sc.bufs[False] = bytearray(flow.buffer) + sc.bufs[False]
        sc.engine = None
        sc.fast_ok = False
        sc.demoted_mod = sc.module_id
        with self._lock:
            if conn_id < self._tab_size:
                self._tab_engine[conn_id] = -1
                self._tab_dirty[conn_id] = 1
            # The claim stays table-valid, but this conn now carries
            # migrated residue the cache's clean-flow gate must see;
            # the heal rebind re-arms from the (fallback) engine.
            self._disarm_flow_cache(conn_id, "demote")

    def _maybe_rebind(self, conn_id: int, sc: "_SidecarConn") -> None:
        """Un-demote after the device heals: once the oracle residue
        has drained, bind the engine back so the conn resumes the
        device path.  Runs on the DISPATCH path, so it never compiles:
        an existing engine for the key binds inline (pointer reads
        only); a missing one is built by the policy builder thread
        while the conn keeps serving on the oracle."""
        if (
            sc.demoted_mod is None
            or sc.bufs[False]
            or sc.bufs[True]
            or sc.skip[False]
            or sc.skip[True]
        ):
            return
        mod = sc.demoted_mod
        key = self._engine_key_for(mod, sc.conn)
        grant = None
        with self._lock:
            eng = self._engines.get(key)
            if eng is not None:
                sc.demoted_mod = None
                sc.engine = eng
                sc.fast_ok = sc.conn.parser_name in FAST_PROTOS
                self._tab_set_engine(
                    conn_id, eng if sc.fast_ok else None
                )
                # Quarantine healed: re-arm the invariance claim from
                # the rebound engine (the demotion disarmed it).
                grant = self._arm_flow_cache(conn_id, sc)
            elif conn_id not in self._rebind_inflight:
                self._rebind_inflight.add(conn_id)
                sc.demoted_mod = None
                eng = False  # sentinel: queue the off-path rebuild
        if eng is False:
            self._build_queue.put(("rebind", (mod, conn_id)))
        elif grant is not None:
            # Dispatch path: hand the (blocking) grant send to the
            # builder thread — advisory delivery, revalidated there.
            self._build_queue.put(("grants", [grant]))

    def _process(self, items: list) -> None:
        """Dispatcher entry: triage aggregated items.

        Whole DATA batches that are homogeneous (request direction,
        single complete frame per entry, stateless conns on one engine)
        take the fully vectorized path — O(1) numpy ops + one device
        call, no per-entry Python.  Everything else falls to the
        entrywise path below.  A vec-eligible batch is demoted if it
        shares a connection with an entrywise batch in the same round,
        preserving per-connection op order.
        """
        self.guard.round_start()
        # Queue-pop boundary for the latency decomposition: everything
        # before this stamp is admission-queue time.
        t_pop = time.monotonic()
        items = self._admit(items)
        closes = [it[1:] for it in items if it[0] == "close"]
        data_items = [it for it in items if it[0] in ("data", "mat")]
        # Quarantined device: the whole round bypasses the vectorized
        # paths and renders through the host fallback (entrywise) —
        # bounded-latency degradation, never a hang.
        quarantined = self.guard.quarantined
        # Established-flow verdict cache, whole-item tier: items whose
        # EVERY entry hits (armed conn, matching epoch, clean, frame-
        # aligned) are answered straight from the claim — no device
        # round, no engine state, bytes already at the service but the
        # (flows, rules) round never happens.  Offered BEFORE the
        # mat-group fast path so the greedy whole-round shape (the
        # hottest serving lane) also short-circuits; mixed items fall
        # through to the columnar Phase-A per-entry mask.
        # The _cache_armed read is racy-by-design: 0 skips the tier's
        # snapshot + per-item masks entirely (cache-on but nothing
        # armed must not tax the greedy fast path below, which runs
        # snapshot-free), and a conn arming concurrently just waits
        # one round for its first short-circuit.
        snap = None
        if (
            self._flow_cache_on
            and not quarantined
            and data_items
            and self._cache_armed > 0
        ):
            snap = self._tab_snapshot(data_items)
            if snap is not None:
                data_items = self._serve_cached_items(
                    data_items, snap, t_pop
                )
                if not data_items:
                    for close_args in closes:
                        self.close_connection(*close_args)
                    self._round_record_ok()
                    return
        # Whole-round path (either completion mode): every data item a
        # complete-flag matrix batch of the configured width — one
        # grouped eligibility/issue/response pass; a round it declines
        # falls through, untouched, to the per-item route below.
        if (
            not quarantined
            and data_items
            and all(
                it[0] == "mat"
                and (it[2].flags & wire.MAT_FLAG_COMPLETE)
                and it[2].width == self.config.batch_width
                for it in data_items
            )
            and self._run_mat_group(data_items, t_pop)
        ):
            # Misses by definition: offered to the cache tier above
            # and not served (or the tier skipped with zero armed
            # rows — same thing).  No-op counter when the cache is
            # off.
            self._count_cache_misses(
                sum(it[2].count for it in data_items)
            )
            for close_args in closes:
                self.close_connection(*close_args)
            self._round_record_ok()
            return
        # Snapshot the conn tables under the lock once per round: the
        # eligibility checks and chunk issue below run lock-free on the
        # dispatcher thread while policy_update/new_connection mutate
        # the tables (including _engine_objs slot reuse), so every read
        # in this round must come from one consistent view.
        if snap is None:
            snap = self._tab_snapshot(data_items)
        vec: list[tuple] = []  # (item, engine) — item kind "data" or "mat"
        general: list = []  # (arrival_idx, item)
        for k, it in enumerate(data_items):
            if quarantined:
                eng = None
                if it[0] == "mat":
                    it = ("data", it[1], _matrix_to_batch(it[2]))
            elif it[0] == "mat":
                eng = self._matrix_eligible(it[2], snap)
                if eng is None:
                    it = ("data", it[1], _matrix_to_batch(it[2]))
            else:
                eng = self._vec_eligible(it[2], snap)
            if eng is not None:
                vec.append((k, it, eng))
            else:
                general.append((k, it))
        if vec and general:
            gen_conns = np.unique(
                np.concatenate([it[2].conn_ids for _, it in general])
            )
            kept = []
            for k, it, eng in vec:
                if np.isin(it[2].conn_ids, gen_conns).any():
                    if it[0] == "mat":
                        it = ("data", it[1], _matrix_to_batch(it[2]))
                    general.append((k, it))
                else:
                    kept.append((k, it, eng))
            if len(kept) != len(vec):
                # Re-establish arrival order among entrywise items.
                general.sort(key=lambda rec: rec[0])
            vec = kept
        if vec:
            self._run_vec([(it, eng) for _, it, eng in vec], snap, t_pop)
        if general:
            self._process_entrywise(
                [it for _, it in general], t_pop,
                swap_s=snap.swap_s if snap is not None else 0.0,
            )
        for close_args in closes:
            self.close_connection(*close_args)
        # The round completed without raising — reset the poisoned-
        # engine crash streak.
        self._round_record_ok()

    def _swap_overlap(self, t_before: float) -> float:
        """Portion of a just-finished _lock acquisition that was spent
        blocked behind the epoch-swap pointer flip: the overlap of
        [t_before, now] with the last swap's lock-hold window.  Zero
        for every round that did not actually contend with a swap."""
        w0, w1 = self._swap_window
        if not w1:
            return 0.0
        return max(0.0, min(w1, time.monotonic()) - max(w0, t_before))

    def _round_thread_suppressed(self) -> bool:
        """True on a thread whose guard bookkeeping must be dropped —
        the same deposed-worker/shed-round predicate that suppresses
        sends.  A zombie round unsticking minutes after deposal must
        touch NEITHER direction of the streak: its record_ok would
        reset a genuine streak the replacement worker is accumulating
        (or consume a live round's taint), and its record_failure
        would taint the live rounds for a crash the deposal already
        booked via record_stall."""
        disp = self.dispatcher
        return disp.thread_is_deposed() or disp.thread_round_is_shed()

    def _round_record_ok(self) -> None:
        """guard.record_ok for a completed round — see
        _round_thread_suppressed."""
        if not self._round_thread_suppressed():
            self.guard.record_ok()

    def _record_contained_failure(self, reason: str) -> None:
        """guard.record_failure for a contained in-round failure —
        gated like record_ok; covers every crash-streak input reachable
        from an abandoned thread (batch crash, engine pump crash, the
        device-assisted engines' judge-crash hook)."""
        if not self._round_thread_suppressed():
            self.guard.record_failure(reason)

    def _tab_snapshot(self, data_items: list) -> "_TabSnap | None":
        if not data_items:
            return None
        single = False
        if len(data_items) == 1:
            one = data_items[0][2].conn_ids.astype(np.int64)
            # Single-item rounds with already strictly-increasing ids
            # (the common matrix-batch shape) skip the unique() sort and
            # mark the snapshot identity-ordered for O(1) lookups.
            if len(one) and np.all(one[1:] > one[:-1]):
                ids = one
                single = True
            else:
                ids = np.unique(one)
        else:
            ids = np.unique(
                np.concatenate(
                    [it[2].conn_ids for it in data_items]
                ).astype(np.int64)
            )
        t_before = time.monotonic()
        want_cache = self._flow_cache_on
        with self._lock:
            swap_s = self._swap_overlap(t_before)
            epoch = self.policy_epoch
            if self._tab_size == 0:
                snap = _TabSnap(
                    ids,
                    np.full(len(ids), -1, np.int32),
                    np.zeros(len(ids), np.int32),
                    np.ones(len(ids), np.uint8),
                    (),
                    single,
                    epoch=epoch,
                )
                snap.swap_s = swap_s
                return snap
            objs = self._objs_cache
            if objs is None:
                objs = self._objs_cache = tuple(self._engine_objs)
            if len(ids) and int(ids[-1]) < self._tab_size:
                # All in range (ids sorted): plain gathers — the fancy
                # index copies, which IS the snapshot.
                snap = _TabSnap(
                    ids,
                    self._tab_engine[ids],
                    self._tab_src[ids],
                    self._tab_dirty[ids],
                    objs,
                    single,
                    cache=(
                        self._tab_cache[ids] if want_cache else None
                    ),
                    cache_epoch=(
                        self._tab_cache_epoch[ids] if want_cache
                        else None
                    ),
                    cache_rule=(
                        self._tab_cache_rule[ids] if want_cache
                        else None
                    ),
                    epoch=epoch,
                )
                snap.swap_s = swap_s
                return snap
            in_range = ids < self._tab_size
            clipped = np.where(in_range, ids, 0)
            engine = np.where(
                in_range, self._tab_engine[clipped], -1
            ).astype(np.int32)
            src = np.where(in_range, self._tab_src[clipped], 0).astype(np.int32)
            dirty = np.where(
                in_range, self._tab_dirty[clipped], 1
            ).astype(np.uint8)
            cache = cache_epoch = cache_rule = None
            if want_cache:
                cache = np.where(
                    in_range, self._tab_cache[clipped], 0
                ).astype(np.uint8)
                cache_epoch = np.where(
                    in_range, self._tab_cache_epoch[clipped], -1
                ).astype(np.int64)
                cache_rule = np.where(
                    in_range, self._tab_cache_rule[clipped], -1
                ).astype(np.int32)
        snap = _TabSnap(ids, engine, src, dirty, objs, single,
                        cache=cache, cache_epoch=cache_epoch,
                        cache_rule=cache_rule, epoch=epoch)
        snap.swap_s = swap_s
        return snap

    def _matrix_eligible(self, mb: wire.MatrixBatch, snap: "_TabSnap"):
        """Engine for a fixed-width matrix batch, or None to fall back."""
        n = mb.count
        if n == 0 or mb.width != self.config.batch_width:
            return None
        pos = snap.lookup(mb.conn_ids)
        eng_idx = snap.engine[pos]
        e0 = int(eng_idx[0])
        if e0 < 0 or (eng_idx != e0).any():
            return None
        if snap.dirty[pos].any():
            return None
        lengths = mb.lengths
        if int(lengths.min()) < 2 or int(lengths.max()) > mb.width:
            return None
        engine = snap.objs[e0]
        if engine is None or isinstance(engine.model, ConstVerdict):
            return None
        framing = _engine_framing(engine)
        if framing is None:
            return None
        if mb.flags & wire.MAT_FLAG_COMPLETE:
            # The edge declared whole-frame rows (it owns framing);
            # skip the per-row content scan.
            return engine
        if not framing.rows_single_frame(mb.rows, lengths).all():
            return None
        return engine

    def _vec_eligible(self, batch: wire.DataBatch, snap: "_TabSnap"):
        """The engine serving every entry of this batch vectorized, or
        None if any entry needs the entrywise path."""
        n = batch.count
        if n == 0:
            return None
        if batch.flags.any():  # reply or end_stream entries
            return None
        pos = snap.lookup(batch.conn_ids)
        eng_idx = snap.engine[pos]
        e0 = int(eng_idx[0])
        if e0 < 0 or (eng_idx != e0).any():
            return None
        if snap.dirty[pos].any():
            return None
        lengths = batch.lengths
        if int(lengths.min()) < 2 or int(lengths.max()) > self.config.batch_width:
            return None
        engine = snap.objs[e0]
        if engine is None or isinstance(engine.model, ConstVerdict):
            return None
        framing = _engine_framing(engine)
        if framing is None:
            return None
        blob = np.frombuffer(batch.blob, np.uint8)
        if len(blob) != int(lengths.sum()):
            return None
        # Exactly one whole frame per entry, ending at the entry
        # boundary — the engine's declared framing owns the check
        # (CRLF tail + single CR for r2d2, the length-prefix walk for
        # DNS).
        if not framing.segments_single_frame(
            blob, batch.offsets[:-1].astype(np.int64),
            lengths.astype(np.int64),
        ).all():
            return None
        return engine

    # Fixed device batch buckets: padded shapes are drawn from this small
    # set so XLA compiles each (bucket, width) once and never again — the
    # anti-churn guard for mixed batch sizes.  Greedy (co-located) mode
    # uses a smaller floor: its common round is one ~10-30-entry message
    # processed inline; the batched path keeps the 256 floor so prewarm
    # pays 3 fewer buckets of compiles.
    MIN_BUCKET = 256
    MIN_BUCKET_GREEDY = 32

    @property
    def _min_bucket(self) -> int:
        # ROADMAP 5b: a mesh flow extent wider than the base floor
        # grows the minimum bucket to match (set at _resolve_mesh), so
        # every padded batch still divides across a >32-wide mesh.
        base = (
            self.MIN_BUCKET_GREEDY if self._inline_complete
            else self.MIN_BUCKET
        )
        return max(base, self._mesh_min_bucket)

    def _buckets(self) -> list[int]:
        out = [self._min_bucket]
        while out[-1] < self.config.batch_flows:
            out.append(out[-1] * 2)
        return out

    def _device_ctx(self):
        """Context routing model build/dispatch to the configured
        verdict device ('cpu' removes the device-link term)."""
        if self._exec_device is None:
            import contextlib

            return contextlib.nullcontext()
        import jax

        return jax.default_device(self._exec_device)

    def _jit_for(self, cache: dict, model, trace_fn, arg_fn=None):
        """Jit-dispatch cache, two keying modes.

        **Shape-keyed** (models exposing ``dispatch_bare()``, the r2d2
        path): the executable takes the model as a pytree ARGUMENT, so
        the cache key is the model's tree structure + leaf
        shapes/dtypes — NOT its identity.  Policy churn that rebuilds
        same-bucketed tables (models/r2d2.py pads rule rows to power-
        of-two buckets) then reuses the compiled executable and only
        uploads fresh arrays; these entries deliberately survive epoch
        swaps.  ``arg_fn(model, *args)`` is the trace function.

        **Id-keyed** (everything else): the stored model reference pins
        the id so a gc'd model can never alias an entry.  (Binding the
        device via in_shardings instead of the default-device ctx was
        tried and reverted: 15µs/call isolated but ~400µs of spinning
        thread-CPU under multi-thread contention on a small host.)"""
        key = self._model_shape_key(model) if arg_fn is not None else None
        if key is not None:
            fn = cache.get(key)  # lint: disable=R13 -- shape-keyed executable cache: keys are TABLE SHAPES, not table contents, so entries are epoch-independent by construction and deliberately survive swaps (the churn executable cache)
            if fn is None:
                self._evict_shape_entries(cache)
                # lint: disable=R12 -- cache-miss only: every serving shape is prewarmed off-path at engine build/swap; a miss here is a shape no prewarm declares (an HTTP head wider than the judge base width, or a shape evicted and reused)
                fn = self._ledgered_jit(cache, key, arg_fn, model)
                cache[key] = fn  # lint: disable=R13 -- shape-keyed by design (see the read above): same-bucketed churn MUST hit this entry across epochs
            return functools.partial(fn, model.dispatch_bare())
        ent = cache.get(id(model))  # lint: disable=R13 -- id-keyed entries die WITH their model: _commit_epoch pops them at the pointer flip, so no entry can outlive its epoch
        if ent is None:
            # lint: disable=R12 -- cache-miss only: prewarm traces every bucket shape at engine build (builder/reader thread); dispatch rounds only ever hit this dict
            fn = self._ledgered_jit(cache, id(model), trace_fn, model,
                                    id_keyed=True)
            ent = (model, fn)
            cache[id(model)] = ent  # lint: disable=R13 -- id-keyed: popped by _commit_epoch at the flip (see the read above)
        return ent[1]

    def _ledgered_jit(self, cache: dict, key, trace_fn, model,
                      id_keyed: bool = False):
        """THE jit half of the ledger choke point (ledger.py): wrap a
        fresh executable so its FIRST invocation — where jax actually
        traces and compiles — is timed and recorded, then swap the
        bare executable into the cache (zero steady-state overhead:
        later lookups bypass the shim entirely).  The cause comes from
        the recording thread's ledger scope (the first call runs
        immediately after the miss, on the missing thread, so the
        miss-site scope is still live); a miss whose shape key was
        previously EVICTED — unscoped, or re-warmed by prewarm —
        records churn-new-shape (the evict-then-reuse retrace is churn
        cost, not a cold start), and any other unscoped miss records
        cold."""
        import jax

        # lint: disable=R12 -- this IS the ledger choke point the hot-path pragmas above refer to; the wrap is lazy (trace happens at first call) and misses only ever happen for un-prewarmed shapes
        jfn = jax.jit(trace_fn)
        led = self.ledger
        rkey = (id(cache), key)
        cause = None
        scope = ledger_mod.current_scope()
        if led.was_evicted(rkey) and (
            scope is None or scope["cause"] == ledger_mod.CAUSE_PREWARM
        ):
            cause = ledger_mod.CAUSE_CHURN_NEW_SHAPE
        led.executable_resident(rkey)
        family = type(model).__name__
        shape_sig = None if id_keyed else key
        # Which executable FAMILY this cache serves: the same model
        # shape legitimately traces once per role (gather vs direct vs
        # attribution are distinct executables), and the census must
        # keep them apart or a first-use attr trace masks a gather
        # re-trace.
        role = (
            "gather" if cache is self._jit_gather
            else "attr" if cache is self._jit_attr
            else "direct"
        )
        done = []

        def shim(*args):
            t0 = time.perf_counter()
            out = jfn(*args)
            if not done:
                done.append(True)
                try:
                    led.record_compile(
                        family, time.perf_counter() - t0, cause=cause,
                        shape=shape_sig, kind="jit", role=role,
                        epoch=self.policy_epoch,
                    )
                    # Retire the shim: the cache entry becomes the
                    # bare executable.
                    if id_keyed:
                        ent = cache.get(key)
                        if ent is not None and ent[1] is shim:
                            cache[key] = (ent[0], jfn)  # lint: disable=R13 -- same id-keyed entry being replaced in place (epoch lifecycle unchanged)
                    elif cache.get(key) is shim:
                        cache[key] = jfn  # lint: disable=R13 -- same shape-keyed entry being replaced in place (see _jit_for)
                except Exception:  # noqa: BLE001 -- accounting must not cost the round
                    pass
            return out

        return shim

    # Distinct table-shape signatures a shape-keyed cache may hold
    # before the oldest are evicted: bounds executable memory on a
    # long-running service under regex-vocabulary churn (each new
    # automaton state count is a new shape).  Well above any
    # steady-state working set — eviction is the runaway backstop, not
    # a tuning knob.
    SHAPE_CACHE_MAX = 64

    def _evict_shape_entries(self, cache: dict) -> None:
        """Evict the oldest shape-keyed entries once the cache holds
        SHAPE_CACHE_MAX distinct shapes (dict order = insertion order;
        id-keyed entries are untouched — their lifecycle is the engine
        drop at swap)."""
        shape_keys = [k for k in cache if isinstance(k, tuple)]
        while len(shape_keys) >= self.SHAPE_CACHE_MAX:
            victim = shape_keys.pop(0)
            cache.pop(victim, None)
            self._prewarmed_shapes.pop(victim, None)
            # THE resident-executable decrement (one definition,
            # ledger-owned): the gauge moves here and at the id-keyed
            # epoch retirement, nowhere else — and the ledger's
            # evicted-key memory makes a later reuse of this shape
            # record churn-new-shape, not cold.
            self.ledger.executable_evicted((id(cache), victim))

    # -- multi-chip mesh rung ---------------------------------------------

    def _resolve_mesh(self):
        """The service's (flows, rules) device mesh, or None when
        multi-chip serving is off.  'auto' requires more than one REAL
        accelerator device (virtual CPU devices share the host's cores
        — a collective there only adds overhead); 'on' forces a mesh
        at any device count (the CPU-mesh tests and smoke benches).
        The flow extent is floored to a power of two so every
        power-of-two dispatch bucket divides it, and capped at the
        smallest bucket."""
        if self._mesh_resolved:
            return self._mesh
        with self._mesh_lock:
            if self._mesh_resolved:
                return self._mesh
            mesh = None
            if self.config.mesh != "off":
                from ..parallel.mesh import FLOW_AXIS, RULE_AXIS, serving_mesh

                with self._device_ctx():
                    mesh = serving_mesh(
                        self.config.mesh,
                        self.config.mesh_rule_shards,
                        self.config.mesh_flow_shards,
                        max_flow=self.MIN_BUCKET_GREEDY,
                    )
                if mesh is not None:
                    log.info(
                        "mesh serving: %d device(s) as (flows=%d, "
                        "rules=%d)", mesh.size,
                        mesh.shape[FLOW_AXIS], mesh.shape[RULE_AXIS],
                    )
                    # ROADMAP 5b: an EXPLICIT flow extent beyond the
                    # smallest dispatch bucket grows the minimum
                    # bucket to the extent, so >32-device pods shard
                    # the flow axis fully and every padded batch
                    # still divides across the mesh.
                    base = (
                        self.MIN_BUCKET_GREEDY if self._inline_complete
                        else self.MIN_BUCKET
                    )
                    fl = mesh.shape[FLOW_AXIS]
                    if fl > base:
                        self._mesh_min_bucket = fl
                        log.info(
                            "mesh flow extent %d grows the minimum "
                            "dispatch bucket (%d -> %d)", fl, base, fl,
                        )
                elif self.config.mesh == "on":
                    log.warning(
                        "mesh=on but no (flows=%s, rules=%s) mesh "
                        "fits the available devices; serving "
                        "single-chip",
                        self.config.mesh_flow_shards or "auto",
                        max(self.config.mesh_rule_shards, 1),
                    )
            self._mesh = mesh
            self._mesh_resolved = True
            if mesh is not None and self._handoff_mesh:
                self._adopt_handoff_mesh(mesh)
            self._handoff_mesh = None
            metrics.MeshActive.set(
                1.0 if mesh is not None and self._mesh_demoted is None
                else 0.0
            )
            self._publish_mesh_capacity()
        return mesh

    def _adopt_handoff_mesh(self, mesh) -> None:
        """Resume the predecessor's ladder rung (under _mesh_lock, at
        resolution): its attributed dead devices that still exist in
        OUR mesh are marked lost up front, and serving starts directly
        on the reshaped rung — a successor never re-probes a
        known-dead chip through a fault.  Device ids that no longer
        resolve are dropped (the backend was re-enumerated; the paced
        re-probe re-adjudicates)."""
        from ..parallel.mesh import FLOW_AXIS, RULE_AXIS, reshape_mesh

        ho = self._handoff_mesh or {}
        mesh_ids = {d.id for d in mesh.devices.flat}
        lost = {int(x) for x in ho.get("lost") or ()} & mesh_ids
        self.mesh_reshapes = int(ho.get("reshapes") or 0)
        if not lost:
            return
        self._mesh_lost = set(lost)
        already = set(self.guard.lost_devices())
        for dev_id in sorted(lost):
            if str(dev_id) not in already:
                self.guard.record_device_fault(dev_id, "handoff")
        metrics.MeshLostDevices.set(float(len(lost)))
        survivors = [d for d in mesh.devices.flat if d.id not in lost]
        target = None
        if self.config.mesh_reshape:
            with self._device_ctx():
                target = reshape_mesh(
                    survivors, mesh.shape[RULE_AXIS],
                    max_flow=mesh.shape[FLOW_AXIS],
                )
        if target is not None:
            with blackbox.annotate(reason="handoff-resume"):
                MESH_LADDER_PROTOCOL.advance(self._mesh_rung(),
                                             MESH_RESHAPED)
            self._mesh_serving = target
            log.warning(
                "mesh resumes RESHAPED from handoff: %d device(s) "
                "lost %s, serving (flows=%d, rules=%d)", len(lost),
                sorted(lost), target.shape[FLOW_AXIS],
                target.shape[RULE_AXIS],
            )
        else:
            with blackbox.annotate(reason="handoff-degraded"):
                MESH_LADDER_PROTOCOL.advance(self._mesh_rung(),
                                             MESH_FALLBACK)
            self._mesh_demoted = "handoff-degraded"
            self.mesh_demotions["handoff-degraded"] = (
                self.mesh_demotions.get("handoff-degraded", 0) + 1
            )
            metrics.MeshDemotions.inc("handoff-degraded")
            log.warning(
                "mesh handoff carried %d lost device(s) and no "
                "reshaped width fits: serving single-chip", len(lost),
            )

    def _serving_mesh(self):
        """Mesh for NEW engine builds: the current rung's mesh — the
        reshaped survivor mesh while degraded, None once demoted to
        the fallback rung (every model compiled there is
        single-chip)."""
        mesh = self._resolve_mesh()
        if self._mesh_demoted is not None:
            return None
        return self._mesh_serving or mesh

    def _live_model(self, model):
        """Mesh-rung resolution for one dispatch: a demoted service
        serves every sharded model's single-chip fallback executable
        (bit-identical by the sharding parity contract)."""
        fb = getattr(model, "fallback", None)
        if fb is not None and self._mesh_demoted is not None:
            return fb
        return model

    # Device-id attribution over a fault's text: backend runtimes name
    # the failing chip ("TPU_3", "device 2", "cpu:1") in transfer and
    # collective errors; the match is intersected with the mesh's own
    # id set so a stray number never marks a device.
    _DEV_ID_RE = re.compile(
        r"(?:cpu|tpu|gpu|device)[ _:]{0,2}(\d+)", re.IGNORECASE
    )

    def _attribute_fault_devices(self, exc) -> set:
        """Which mesh devices did this fault name?  Three sources, all
        intersected with the full mesh's device ids: an explicit
        ``failed_devices`` attribute on the exception, device ids
        parsed from the message text, and devices that VANISHED from
        the backend's device set (unplugged chip).  Empty when the
        fault is not attributable to a chip — the demotion then holds
        for the paced re-probe to adjudicate."""
        mesh = self._mesh
        if mesh is None:
            return set()
        ids: set = set()
        if exc is not None:
            for d in getattr(exc, "failed_devices", ()) or ():
                try:
                    ids.add(int(getattr(d, "id", d)))
                except (TypeError, ValueError):
                    continue
            for m in self._DEV_ID_RE.finditer(str(exc)):
                ids.add(int(m.group(1)))
        mesh_ids = {d.id for d in mesh.devices.flat}
        try:
            import jax

            live = {d.id for d in jax.devices()}
            ids |= mesh_ids - live
        except Exception:  # noqa: BLE001 — a dead backend attributes nothing
            pass
        return ids & mesh_ids

    def _probe_mesh_device(self, dev) -> bool:
        """One tiny put+readback against a single device: True when it
        answers.  Runs off-path (builder thread / probe pool) only."""
        import jax

        arr = jax.device_put(np.arange(8, dtype=np.int32), dev)
        return int(np.asarray(arr).sum()) == 28

    def _probe_mesh_devices(self, devices) -> set:
        """Probe every full-mesh device in a disposable bounded pool
        (a HUNG device must cost one timeout, not wedge the builder
        thread serially per chip) and return the dead id set; each
        failure is recorded in the guard's per-device health table.
        ``_device_probe_fn`` is the test seam."""
        dead: set = set()
        probe = self._device_probe_fn or self._probe_mesh_device
        timeout = self.guard.timeout_s or 5.0
        ex = ThreadPoolExecutor(
            max_workers=min(max(len(devices), 1), 8),
            thread_name_prefix="mesh-probe",
        )
        try:
            futs = [(ex.submit(probe, d), d) for d in devices]
            for fut, dev in futs:
                try:
                    ok = bool(fut.result(timeout))
                except Exception:  # noqa: BLE001 — raise/timeout == dead
                    ok = False
                if not ok:
                    dead.add(dev.id)
                    self.guard.record_device_fault(
                        dev.id, "probe-failed"
                    )
        finally:
            ex.shutdown(wait=False)
        return dead

    def _publish_mesh_capacity(self) -> None:
        """Publish the current rung's capacity fraction and scale
        admission by it: the dispatcher's global queue cap and the DRR
        credit numerator (_drr_share) both shrink to the degraded
        width, so a half-width mesh sheds typed at its ACTUAL capacity
        instead of queueing into deadline-shed p99 explosions."""
        full = self._mesh
        if full is None or full.size <= 0:
            frac = 1.0
        elif self._mesh_demoted is not None:
            frac = 1.0 / float(full.size)
        elif self._mesh_serving is not None:
            frac = float(self._mesh_serving.size) / float(full.size)
        else:
            frac = 1.0
        self._mesh_capacity = frac
        metrics.MeshCapacity.set(frac)
        entries = self.config.shed_queue_entries
        if entries:
            # Floor deep degradation at session_share_min so the cap
            # never starves admission entirely — but the floor must
            # never RAISE a small configured cap above its full-width
            # value (the operator's bound wins at frac=1.0).
            self.dispatcher.scale_admission(
                min(entries,
                    max(int(entries * frac),
                        self.config.session_share_min))
            )
        # Invalidate the lazy DRR share so the very next admission
        # sees the new fraction (not up to 50ms later).
        self._share_ts = 0.0

    def _mesh_rung(self) -> str:
        """The CURRENT width-ladder rung, derived from the two mesh
        pointers (the ladder is a ``derived``-kind typestate: no single
        stored field, so flip sites validate their edge through
        MESH_LADDER_PROTOCOL.advance against this derivation)."""
        if self._mesh_demoted is not None:
            return MESH_FALLBACK
        if self._mesh_serving is not None:
            return MESH_RESHAPED
        return MESH_FULL

    def _demote_mesh(self, reason: str, exc=None) -> None:
        """PR 2 ladder, mesh rung: a lost/erroring mesh device demotes
        the whole service to the single-chip executables — one pointer
        pass under _lock, typed (mesh_demotions_total{reason}) and
        counted, never a wedged round.  The dispatch path never
        resumes collectives on its own: the fault is attributed to its
        device(s) (health table + _mesh_lost) and an IMMEDIATE
        off-path reshape job walks the width ladder down around them
        (_run_mesh_ladder) — the fallback rung covers only the rebuild
        window; un-attributable faults hold demoted until the timed
        re-probe re-adjudicates.  With mesh_reprobe_interval_s = 0 the
        pre-PR-12 sticky-until-restart behavior holds."""
        attributed = self._attribute_fault_devices(exc)
        swapped = 0
        first = False
        with self._lock:
            # Fold the attribution in even when already demoted (a
            # second chip dying on the fallback rung still belongs in
            # the health table and the next reshape's dead set).
            self._mesh_lost |= attributed
            if self._mesh_demoted is None:
                first = True
                with blackbox.annotate(reason=reason):
                    MESH_LADDER_PROTOCOL.advance(self._mesh_rung(),
                                                 MESH_FALLBACK)
                self._mesh_demoted = reason
                self._mesh_serving = None
                self._mesh_fault_at = time.monotonic()
                # Pace the first re-probe one full interval after the
                # demotion (a device that just failed rarely heals
                # instantly).
                self._mesh_reprobe_last = self._mesh_fault_at
                for eng in self._engines.values():
                    m = getattr(eng, "model", None)
                    fb = getattr(m, "fallback", None)
                    if fb is not None:
                        # Retain the sharded wrapper for
                        # re-promotion: its tables are
                        # host-rebuildable state, and a flip back
                        # after a successful probe is one pointer
                        # pass.  A demotion FROM the reshaped rung
                        # keeps the earlier FULL-width retained
                        # wrapper (the reshaped model is rebuilt,
                        # never retained).  If the devices are still
                        # bad, the next sharded dispatch demotes
                        # again, typed — never a crashed round.
                        if getattr(eng, "_mesh_model", None) is None:
                            eng._mesh_model = m
                        eng.model = fb
                        # Sharded models are shape-keyed
                        # (dispatch_bare), so no per-id cache entry
                        # exists to drop; the compiled mesh
                        # executables stay in the shape cache as
                        # inert entries (demoted dispatch resolves
                        # through _live_model before any lookup).
                        swapped += 1
        for dev_id in sorted(attributed):
            self.guard.record_device_fault(dev_id, reason)
        if not first:
            return
        self.mesh_demotions[reason] = (
            self.mesh_demotions.get(reason, 0) + 1
        )
        metrics.MeshDemotions.inc(reason)
        metrics.MeshActive.set(0.0)
        metrics.MeshLostDevices.set(float(len(self._mesh_lost)))
        self._publish_mesh_capacity()
        log.error(
            "mesh serving demoted to single-chip executables (%s): "
            "%d engine(s) flipped, %d device(s) attributed", reason,
            swapped, len(attributed),
        )
        # Walk the ladder DOWN off-path right away (no paced wait):
        # with attributed/probed-dead devices the builder rebuilds a
        # reshaped mesh over the survivors and the fallback rung lasts
        # only the rebuild window.
        if self.config.mesh_reshape and self.config.mesh_reprobe_interval_s:
            self._build_queue.put(("mesh_reshape", None))

    def _maybe_mesh_reprobe(self) -> None:
        """Traffic-driven re-promotion pacing (called once per dispatch
        round, like guard.maybe_reprobe): while BELOW the full rung
        (demoted or reshaped), queue at most one off-path ladder walk
        per mesh_reprobe_interval_s onto the policy-builder thread —
        the walk promotes back up (reshaped -> full, fallback ->
        reshaped/full) as devices heal.  0 disables (sticky)."""
        interval = self.config.mesh_reprobe_interval_s
        if not interval or (
            self._mesh_demoted is None and self._mesh_serving is None
        ):
            return
        if self.guard.quarantined:
            # Never queue a compile+dispatch against a quarantined
            # device: a HUNG device (the case quarantine exists for)
            # would wedge the builder thread — and with it every
            # future swap/rebind — behind the probe.  The pacing
            # clock retries after the guard's own re-probe heals.
            return
        now = time.monotonic()
        with self._lock:
            if self._mesh_reprobe_inflight:
                return
            if now - self._mesh_reprobe_last < interval:
                return
            self._mesh_reprobe_inflight = True
            self._mesh_reprobe_last = now
        self._build_queue.put(("mesh_reprobe", None))

    # Probe rows for the re-promotion parity check: a remote-gated
    # literal row, a regex row, and an always-match row — enough to
    # exercise the stacked tables, the cross-shard attribution reduce,
    # and the padding rows of an unbalanced split.
    _MESH_PROBE_ROWS = (
        (frozenset({7}), "READ", "/public/.*"),
        (frozenset(), "HALT", ""),
        (frozenset({9}), "", ""),
    )

    def _mesh_probe_batch(self):
        """Probe batch shared by every ladder parity/materialization
        check: five frames covering remote-gated literal, regex,
        always-match and padding rows."""
        b = max(self.MIN_BUCKET_GREEDY, self._mesh_min_bucket)
        width = self.config.batch_width
        data = np.zeros((b, width), np.uint8)
        lens = np.zeros(b, np.int32)
        rems = np.zeros(b, np.int32)
        cases = [
            (b"READ /public/app\r\n", 7),
            (b"READ /public/app\r\n", 8),
            (b"HALT\r\n", 3),
            (b"WRITE /x\r\n", 9),
            (b"RESET\r\n", 9),
        ]
        for i, (frame, rem) in enumerate(cases):
            row = np.frombuffer(frame, np.uint8)
            data[i, : len(row)] = row
            lens[i] = len(row)
            rems[i] = rem
        return data, lens, rems

    def _mesh_parity_probe(self, mesh) -> bool:
        """Rebuild ONE sharded probe wrapper from scratch against
        ``mesh``, run it beside its single-chip twin over the probe
        batch, and require bit-identical (allow, rule) output — the
        gate EVERY ladder flip (reshape or re-promotion) must pass
        before any engine pointer moves."""
        from ..parallel.mesh import RULE_AXIS
        from ..parallel.rulesharding import (
            ShardedVerdictModel,
            build_sharded_r2d2_from_rows,
            shard_offsets,
        )
        from ..models.r2d2 import build_r2d2_model_from_rows

        rows = list(self._MESH_PROBE_ROWS)
        n_shards = mesh.shape[RULE_AXIS]
        with self._device_ctx():
            probe = ShardedVerdictModel.resident(
                build_sharded_r2d2_from_rows(
                    rows, n_shards, bucket=True
                ),
                shard_offsets(len(rows), n_shards),
                mesh, "r2d2",
                # lint: disable=R23 -- parity-probe twin: built, compared, and discarded in this function — never a resident serving executable, so ledgering it would inflate the compile census with probe noise
                fallback=build_r2d2_model_from_rows(
                    rows, bucket=True
                ),
            )
        data, lens, rems = self._mesh_probe_batch()
        fb = probe.fallback
        with self._device_ctx():
            _, _, a_s, r_s = probe.verdicts_attr(data, lens, rems)
            _, _, a_f, r_f = fb.verdicts_attr(data, lens, rems)
        return bool(
            np.array_equal(np.asarray(a_s), np.asarray(a_f))
            and np.array_equal(np.asarray(r_s), np.asarray(r_f))
        )

    def _reshape_failed(self, reason: str) -> None:
        self.mesh_reshape_failures[reason] = (
            self.mesh_reshape_failures.get(reason, 0) + 1
        )

    def _run_mesh_ladder(self, immediate: bool) -> None:
        """Builder-thread walk of the mesh width ladder: adjudicate
        the dead device set (per-device probes + the attributed
        _mesh_lost), pick the target rung (full when nothing is dead,
        else the widest bucketable mesh over the survivors), parity-
        gate it against the single-chip twin, and flip every engine
        onto it in one pointer pass.  ``immediate`` is the post-fault
        job _demote_mesh queues: it only walks DOWN (a fault with no
        attributable dead device holds the fallback rung for the
        paced walk to adjudicate — transient XLA errors must not
        promote themselves).  A second fault racing the walk aborts
        the flip typed and falls through to the rung ITS demotion
        chose.  Failure anywhere leaves the current rung in place and
        the pacing clock owns the retry."""
        try:
            full = self._mesh
            if full is None:
                return
            with self._lock:
                demoted = self._mesh_demoted
                serving = self._mesh_serving
                prev_lost = set(self._mesh_lost)
            if demoted is None and serving is None:
                return  # full rung — stale job
            # Re-checked on the builder thread: quarantine may have
            # latched between queueing and execution (same hung-device
            # hazard _maybe_mesh_reprobe gates against).
            if self.guard.quarantined:
                return
            from ..parallel.mesh import FLOW_AXIS, RULE_AXIS, reshape_mesh

            # -- adjudicate the dead set -------------------------------
            dead = self._probe_mesh_devices(list(full.devices.flat))
            if immediate:
                dead |= prev_lost
                if not dead:
                    return
            else:
                for dev_id in sorted(prev_lost - dead):
                    self.guard.mark_device_ok(dev_id)
            with self._lock:
                self._mesh_lost = set(dead)
            metrics.MeshLostDevices.set(float(len(dead)))
            if demoted is None and serving is not None and dead == prev_lost:
                return  # reshaped rung already matches the dead set
            # -- pick the target rung ----------------------------------
            d0 = sum(self.mesh_demotions.values())
            target = None
            if not dead:
                target = full
            elif self.config.mesh_reshape:
                survivors = [
                    d for d in full.devices.flat if d.id not in dead
                ]
                with self._device_ctx():
                    target = reshape_mesh(
                        survivors, full.shape[RULE_AXIS],
                        max_flow=full.shape[FLOW_AXIS],
                    )
            if target is None:
                reason = (
                    "below-min-width" if self.config.mesh_reshape
                    else "reshape-disabled"
                )
                self._reshape_failed(reason)
                if demoted is None:
                    # Serving reshaped but the dead set grew past any
                    # bucketable width: drop to the fallback rung via
                    # the typed pointer pass, never a raw state write.
                    self._demote_mesh(reason)
                return
            # -- parity-gate the target --------------------------------
            if not self._mesh_parity_probe(target):
                self._reshape_failed("parity")
                log.warning(
                    "mesh ladder parity mismatch at (flows=%d, "
                    "rules=%d); rung holds",
                    target.shape[FLOW_AXIS], target.shape[RULE_AXIS],
                )
                return
            if target is full and serving is None:
                self._promote_mesh_classic(d0)
                return
            # -- rebuild + flip (reshape down, or reshaped -> full) ----
            with ledger_mod.cause_scope(
                ledger_mod.CAUSE_REPROMOTION if target is full
                else ledger_mod.CAUSE_MESH_RESHAPE,
                epoch=self.policy_epoch,
            ):
                builds = self._rebuild_engines_on(target)
            flipped = 0
            with self._lock:
                if sum(self.mesh_demotions.values()) != d0:
                    # A second fault raced this walk: abort the flip
                    # typed — the new demotion queued its own
                    # immediate job, which re-walks the ladder with
                    # the grown dead set (the next rung down).
                    self._reshape_failed("raced-fault")
                    return
                for key, (eng, built, epoch0, old) in builds.items():
                    cur = self._engines.get(key)
                    if (
                        cur is not eng
                        or getattr(eng, "epoch", 0) != epoch0
                        or eng.model is not old
                    ):
                        continue  # swapped mid-build: the swap built
                        # against _serving_mesh already
                    eng.model = built
                    if target is full:
                        eng._mesh_model = None
                    flipped += 1
                with blackbox.annotate(
                    reason="repromote" if target is full else "reshape"
                ):
                    MESH_LADDER_PROTOCOL.advance(
                        self._mesh_rung(),
                        MESH_FULL if target is full else MESH_RESHAPED,
                    )
                self._mesh_serving = None if target is full else target
                self._mesh_demoted = None
            if target is full:
                self.mesh_repromotions += 1
                metrics.MeshRepromotions.inc()
                log.info(
                    "mesh serving re-promoted to full width after "
                    "off-path parity probe (%d engine(s) rebuilt)",
                    flipped,
                )
            else:
                self.mesh_reshapes += 1
                metrics.MeshReshapes.inc()
                if serving is None and self._mesh_fault_at:
                    self.mesh_reshape_window_ms = (
                        time.monotonic() - self._mesh_fault_at
                    ) * 1e3
                log.warning(
                    "mesh RESHAPED around %d dead device(s) %s: "
                    "serving (flows=%d, rules=%d), %d engine(s) "
                    "flipped", len(dead), sorted(dead),
                    target.shape[FLOW_AXIS], target.shape[RULE_AXIS],
                    flipped,
                )
            metrics.MeshActive.set(1.0)
            self._publish_mesh_capacity()
        except Exception:  # noqa: BLE001 — rung holds, retry paced
            log.exception("mesh ladder walk failed; rung holds")
        finally:
            with self._lock:
                self._mesh_reprobe_inflight = False

    def _promote_mesh_classic(self, d0: int) -> None:
        """Fallback -> full promotion when every device answers: the
        retained sharded wrappers flip back in one pointer pass under
        _lock (typed, counted); engines built DURING the demotion get
        their sharded rebuilds queued (ROADMAP 1c) instead of waiting
        for the next epoch swap."""
        data, lens, rems = self._mesh_probe_batch()
        # Probe one RETAINED wrapper too: its device buffers must
        # still answer (a restarted device may have dropped them —
        # then the flip-back would only re-demote, typed, so this
        # probe keeps that churn off the dispatch path).
        with self._lock:
            retained = [
                getattr(e, "_mesh_model", None)
                for e in self._engines.values()
            ]
        retained = [m for m in retained if m is not None]
        if retained:
            with self._device_ctx():
                out = retained[0](data, lens, rems)
                np.asarray(out[-1])
        promoted = 0
        rebuilds: list = []
        with self._lock:
            if self._mesh_demoted is None:
                return  # raced a concurrent heal
            if sum(self.mesh_demotions.values()) != d0:
                return  # raced a concurrent fault
            for eng in self._engines.values():
                mm = getattr(eng, "_mesh_model", None)
                if mm is not None:
                    eng.model = mm
                    eng._mesh_model = None
                    promoted += 1
            with blackbox.annotate(reason="probe-heal"):
                MESH_LADDER_PROTOCOL.advance(self._mesh_rung(),
                                             MESH_FULL)
            self._mesh_demoted = None
            self._mesh_serving = None
            # ROADMAP 1c: engines BUILT while demoted hold plain
            # single-chip models (no retained wrapper, no
            # fallback attr) — queue their sharded rebuilds so
            # they heal too instead of waiting for the next epoch
            # swap.  (Re-promoted engines above now expose
            # .fallback and drop out of this scan.)
            if not self.config.seam_probe:
                for key, eng in self._engines.items():
                    m = getattr(eng, "model", None)
                    if (
                        key[4] in ("r2d2", "http", "dns")
                        and getattr(eng, "_mesh_model", None) is None
                        and m is not None
                        and not isinstance(m, ConstVerdict)
                        and getattr(m, "fallback", None) is None
                    ):
                        rebuilds.append(
                            (key, getattr(eng, "epoch", 0))
                        )
        for job in rebuilds:
            self._build_queue.put(("mesh_rebuild", job))
        self.mesh_repromotions += 1
        metrics.MeshRepromotions.inc()
        metrics.MeshActive.set(1.0)
        self._publish_mesh_capacity()
        log.info(
            "mesh serving re-promoted after off-path parity probe "
            "(%d engine(s) flipped back)", promoted,
        )

    def _rebuild_engines_on(self, mesh) -> dict:
        """Off-path rebuild of every meshable engine's model against
        ``mesh`` (the reshape fan-out): returns {key: (engine, built,
        epoch0, old_model)} for the flip pass to apply under _lock
        with staleness checks (engine replaced, epoch moved, model
        pointer moved — any of which means an epoch swap already
        rebuilt it against _serving_mesh)."""
        with self._lock:
            snap = [
                (key, eng, getattr(eng, "epoch", 0),
                 getattr(eng, "model", None))
                for key, eng in self._engines.items()
            ]
        builds: dict = {}
        for key, eng, epoch0, old in snap:
            if key[4] not in ("r2d2", "http", "dns"):
                continue
            if old is None or isinstance(old, ConstVerdict):
                continue
            built = self._build_mesh_model_for(key, mesh)
            if built is not None:
                builds[key] = (eng, built, epoch0, old)
        return builds

    def _run_mesh_rebuild(self, key: tuple, epoch0: int) -> None:
        """Builder-thread half of the ROADMAP 1c heal: rebuild ONE
        demotion-era engine's model against the live mesh and flip the
        pointer in — only if the engine is still registered under the
        same key, its epoch has not moved (a swap would have rebuilt
        it sharded already), and the mesh is still promoted.  Verdicts
        are bit-identical across the flip by the sharding parity
        contract (same policy rows, same flattened order), so a
        mid-round flip is as safe as the demotion flip itself."""
        with self._lock:
            eng = self._engines.get(key)
        if (
            eng is None
            or self._mesh_demoted is not None
            or self.guard.quarantined
            or getattr(eng, "epoch", 0) != epoch0
        ):
            return
        model = getattr(eng, "model", None)
        if (
            model is None
            or isinstance(model, ConstVerdict)
            or getattr(model, "fallback", None) is not None
        ):
            return  # already sharded (or nothing to shard)
        # The CURRENT rung's mesh: a rebind while the service runs
        # reshaped must shard onto the survivor mesh, never the full
        # layout a dead chip would fault.
        mesh = self._serving_mesh()
        if mesh is None:
            return
        # A demotion-era engine healing onto the promoted mesh is the
        # tail of the repromotion, so its build books under that cause.
        with ledger_mod.cause_scope(ledger_mod.CAUSE_REPROMOTION,
                                    epoch=self.policy_epoch):
            built = self._build_mesh_model_for(key, mesh)
        if built is None:
            return
        with self._lock:
            if (
                self._engines.get(key) is eng
                and self._mesh_demoted is None
                and getattr(eng, "epoch", 0) == epoch0
                and eng.model is model
            ):
                eng.model = built
                self.mesh_rebind_rebuilds += 1
                metrics.MeshRebindRebuilds.inc()
                log.info(
                    "mesh rebind: demotion-era engine %r re-serving "
                    "sharded", key,
                )

    def _build_mesh_model_for(self, key: tuple, mesh):
        """Off-path build of ONE engine's sharded model against
        ``mesh`` (the single assembly seam shared by the rebind heal
        and the reshape fan-out): resolve the engine's policy through
        the module registry, build the family's sharded wrapper with
        its single-chip twin, and materialize one probe call so a
        broken mesh fails HERE (typed, demotion path) and never on
        dispatch.  None when the policy folded to a constant, the
        module is gone, or the build/probe fails — the engine then
        keeps its current model."""
        module_id, policy_name, ingress, port, proto = key
        if proto not in ("r2d2", "http", "dns"):
            return None
        ins = pl.find_instance(module_id)
        if ins is None:
            return None
        policy = ins.policy_map().get(policy_name)
        t0 = time.perf_counter()
        try:
            with self._device_ctx():
                # lint: disable=R12 -- off-path builder-thread rebuild (the mesh-heal/reshape rung), never the dispatch loop
                if proto == "r2d2":
                    from ..parallel.rulesharding import mesh_r2d2_model

                    built = mesh_r2d2_model(policy, ingress, port, mesh)
                elif proto == "dns":
                    from ..parallel.rulesharding import mesh_dns_model

                    built = mesh_dns_model(policy, ingress, port, mesh)
                else:
                    from ..parallel.rulesharding import mesh_http_model

                    built = mesh_http_model(policy, ingress, port, mesh)
                if getattr(built, "fallback", None) is None:
                    return None  # folded to a constant: nothing to flip
                b = max(self.MIN_BUCKET_GREEDY, self._mesh_min_bucket)
                w = self.config.batch_width
                out = built(
                    np.zeros((b, w), np.uint8),
                    np.zeros(b, np.int32),
                    np.zeros(b, np.int32),
                )
                np.asarray(out[-1])
        except Exception:  # noqa: BLE001 — engine keeps its model
            log.exception("mesh model build failed for %r", key)
            return None
        # Cause rides the caller's scope: mesh-reshape from the ladder
        # walk, repromotion from the full-width flip / 1c heal.
        try:
            self.ledger.record_compile(
                proto, time.perf_counter() - t0,
                shape=self._model_shape_key(built),
                rules=self._rule_bucket_of(built),
                mesh=tuple(sorted(
                    (getattr(mesh, "shape", None) or {}).items()
                )),
                kind="engine-build", epoch=self.policy_epoch,
            )
        except Exception:  # noqa: BLE001 — ledger must not cost the build
            pass
        return built

    def _mesh_guarded(self, model, call):
        """Issue one device dispatch; when a SHARDED dispatch raises
        (lost mesh device, failed collective, transfer error), demote
        the mesh rung typed and reissue on the single-chip fallback so
        the round is answered instead of crashed."""
        try:
            return call(model)
        except Exception as exc:
            fb = getattr(model, "fallback", None)
            if fb is None:
                raise
            log.exception(
                "sharded dispatch failed; demoting to single-chip"
            )
            # The exception text carries the fault attribution (which
            # shard/device raised) — the reshape ladder walks down
            # around exactly those devices.
            self._demote_mesh("device-call", exc=exc)
            return call(fb)

    def _mesh_status(self) -> dict | None:
        """Mesh-rung status surface: None while unresolved (no engine
        built yet) or when multi-chip serving is off."""
        if not self._mesh_resolved or self._mesh is None:
            return None
        from ..parallel.mesh import FLOW_AXIS, RULE_AXIS

        serving = self._mesh_serving
        if self._mesh_demoted is not None:
            rung = "fallback"
        elif serving is not None:
            rung = "reshaped"
        else:
            rung = "full"
        return {
            "devices": int(self._mesh.size),
            "flow_shards": int(self._mesh.shape[FLOW_AXIS]),
            "rule_shards": int(self._mesh.shape[RULE_AXIS]),
            "active": self._mesh_demoted is None,
            "demoted": self._mesh_demoted,
            "demotions": dict(self.mesh_demotions),
            "repromotions": self.mesh_repromotions,
            "rebind_rebuilds": self.mesh_rebind_rebuilds,
            # Width-ladder state: the current rung, the width actually
            # serving, the attributed dead set, and the admission
            # coupling — the operator's one look at "how degraded".
            "rung": rung,
            "serving_devices": (
                1 if rung == "fallback"
                else int((serving or self._mesh).size)
            ),
            "lost_devices": sorted(self._mesh_lost),
            "reshapes": self.mesh_reshapes,
            "reshape_failures": dict(self.mesh_reshape_failures),
            "capacity_frac": self._mesh_capacity,
            "reshape_window_ms": self.mesh_reshape_window_ms,
        }

    def _model_call_attr(self, model, data, lens, remotes):
        """THE launch of a verdict model on the device: one shape-keyed,
        ledgered jit executable per batch (``_jit_for``), behind the
        mesh demotion rung.  Returns (complete, msg_len, allow,
        rule-or-None).  The rule index rides the SAME fused computation
        (an argmax over the hit matrix the verdict reduction already
        builds — no extra device pass; on a mesh, the shard-local
        argmax plus the cross-shard min-index reduction, still one
        device round); when flow observability is off or the model has
        no attributed variant, the plain model launches instead and the
        rule is None."""
        model = self._live_model(model)
        attr = self._flow_observe and hasattr(model, "verdicts_attr")

        def call(m):
            with self._device_ctx():
                if attr:
                    fn = self._jit_for(
                        self._jit_attr, m,
                        lambda d, ln, r: m.verdicts_attr(d, ln, r),
                        arg_fn=_call_model_attr,
                    )
                    return fn(data, lens, remotes)
                fn = self._jit_for(
                    self._jit_cache, m, m.__call__, arg_fn=_call_model,
                )
                return (*fn(data, lens, remotes), None)

        return self._mesh_guarded(model, call)

    def _model_shape_key(self, model):
        """Hashable shape signature for a shape-cacheable model, or
        None — THE one key derivation shared by the shape-keyed jit
        caches and the prewarm-skip check (a second copy could drift
        and silently unpair them).  Memoized on the model: tables are
        immutable after build, and the flatten would otherwise run per
        dispatch."""
        key = getattr(model, "_shape_key_memo", None)
        if key is not None:
            return key
        bare_fn = getattr(model, "dispatch_bare", None)
        if bare_fn is None:
            return None
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(bare_fn())
        key = (
            treedef,
            tuple((tuple(lf.shape), str(lf.dtype)) for lf in leaves),
        )
        try:
            model._shape_key_memo = key
        except Exception:  # noqa: BLE001 — slots/frozen models: skip memo
            pass
        return key

    def _shape_key_cached(self, cache: dict, model) -> bool:
        """True when the model's shape-keyed executables were already
        warmed — a churn rebuild of a same-bucketed table then skips
        the warm launches entirely (the whole point of the bucketed
        shapes: repeat churn costs an array upload, not a trace, and
        not even a warm launch)."""
        key = self._model_shape_key(model)
        return key is not None and key in cache

    def _mark_shape_prewarmed(self, model) -> None:
        key = self._model_shape_key(model)
        if key is None:
            return
        # No private eviction loop here (the PR 20 dedupe): a warmed
        # shape lives in at least one shape-keyed jit cache, and
        # _evict_shape_entries — the ONE eviction path, which also
        # moves the ledger's resident gauge — pops this dict alongside
        # the cache entry, so this book stays bounded by the caches'
        # SHAPE_CACHE_MAX without a second definition of "resident".
        self._prewarmed_shapes[key] = True

    def prewarm(self, engine) -> bool:
        """Compile the engine model for every bucket shape up front so
        the first real batch never pays a compile.  Shape-cached models
        (r2d2) whose executable already exists — churn rebuilding a
        same-bucketed table — skip the warm launches entirely.
        Returns True when any warm launch actually ran (the signal the
        engine-build ledger record is gated on: a fully-warm rebuild
        produced no executable and records nothing).  Warm launches
        record cause ``prewarm`` — off-path warming is its own cause
        regardless of what provoked the build; the provoking cause
        (cold/churn/mesh) rides the engine-build record instead."""
        if isinstance(engine.model, ConstVerdict):
            return False
        with ledger_mod.cause_scope(ledger_mod.CAUSE_PREWARM,
                                    epoch=self.policy_epoch):
            judge = getattr(engine, "judge_shapes", None)
            shapes = judge() if judge is not None else None
            warmed = self._prewarm_model(engine.model, shapes)
            fb = getattr(engine.model, "fallback", None)
            if fb is not None:
                # The demotion rung warms at build too: a device-loss
                # flip must not pay its first single-chip compile on
                # the dispatch path.
                warmed = self._prewarm_model(fb, shapes) or warmed
        return warmed

    def _prewarm_model(self, model, judge_shapes=None) -> bool:
        """Warm every executable real rounds launch: the launcher at
        each bucket (round paths, engine pump and device probe alike)
        plus the gather path, or — for a judge engine — the launcher at
        each of its declared (rows, width) shapes."""
        if self._shape_key_cached(self._prewarmed_shapes, model):
            return False
        width = self.config.batch_width
        shapes = judge_shapes or [(b, width) for b in self._buckets()]
        for b, w in shapes:
            # The attributed variant is the serving-path call when flow
            # observability is on; it degrades to the plain call (rule
            # None) otherwise — either way this warms the executable
            # real rounds will launch.
            out = self._model_call_attr(
                model,
                np.zeros((b, w), np.uint8),
                np.zeros(b, np.int32),
                np.zeros(b, np.int32),
            )
            np.asarray(out[2])
            if judge_shapes is None:
                # The gather (blob-window) path has its own executable
                # per flow bucket — warm it so first real traffic never
                # pays a compile inside a round.
                allow, _rule = self._gathered_call(
                    model,
                    np.zeros(self.BLOB_CHUNK, np.uint8),
                    np.zeros(b, np.int32),
                    np.zeros(b, np.int32),
                    np.zeros(b, np.int32),
                )
                np.asarray(allow)
        self._mark_shape_prewarmed(model)
        return True

    @staticmethod
    def _framing_alignment_mask(snap, eng_idx, cand, aligner):
        """THE per-engine frame-alignment mask of the verdict-cache
        tiers (whole-item and columnar Phase-A share it so the two can
        never drift): for every engine among the candidate rows,
        resolve its framing (CRLF fallback for conns without a
        table-resident engine — the http judge tier and other
        non-vectorized engines keep the historic PR 12 CRLF tail
        gate) and apply ``aligner(framing, row_mask)``."""
        aligned = np.zeros(len(cand), bool)
        for e in np.unique(eng_idx[cand]):
            framing = (
                _engine_framing(snap.objs[int(e)]) if e >= 0 else None
            ) or FRAMINGS[FRAMING_CRLF]
            selm = cand & (eng_idx == e)
            aligned[selm] = aligner(framing, selm)
        return aligned

    def _cache_item_hits(self, it, snap: "_TabSnap"):
        """Per-entry verdict-cache hit mask for one data/mat item, or
        None when nothing hits.  A hit requires: armed row, claim epoch
        == the snapshot's policy epoch (the structural invalidation),
        no residual state (clean dirty bit), request direction, and a
        frame-aligned payload (ends with CRLF) so an invalidation at
        any later point leaves the flow parseable from a boundary."""
        kind, _client, b = it
        n = b.count
        if n == 0:
            return None
        pos = snap.lookup(b.conn_ids)
        hit = (
            (snap.cache[pos] == 1)
            & (snap.cache_epoch[pos] == snap.epoch)
            & (snap.dirty[pos] == 0)
        )
        if not hit.any():
            return None
        if kind != "mat":
            hit &= b.flags == 0
            blob = np.frombuffer(b.blob, np.uint8)
            lengths = b.lengths.astype(np.int64)
            starts = b.offsets[:-1].astype(np.int64)
            if len(blob) != int(lengths.sum()):
                return None
        # Frame alignment per the hitting conns' ENGINE framing: a
        # short-circuit must only ever cover whole frames of that
        # framing (_framing_alignment_mask is the one definition).
        if kind == "mat":
            def aligner(framing, selm):
                return framing.rows_aligned(b.rows[selm], b.lengths[selm])
        else:
            def aligner(framing, selm):
                return framing.segments_aligned(
                    blob, starts[selm], lengths[selm]
                )
        hit &= self._framing_alignment_mask(
            snap, snap.engine[pos], hit, aligner
        )
        return hit if hit.any() else None

    def _count_cache_hits(self, n: int) -> None:
        self.cache_hits += n
        metrics.VerdictCacheHits.inc("service", amount=n)

    def _count_cache_misses(self, n: int) -> None:
        if self._flow_cache_on and n:
            self.cache_misses += n
            metrics.VerdictCacheMisses.inc(amount=n)

    def _flowlog_cached(self, snap: "_TabSnap", conn_ids: np.ndarray,
                        pos: np.ndarray) -> None:
        """Cached-path flow records for one hit group, one add_round
        per engine (the kinds legend the claimed rule rows index)."""
        if self.flowlog is None or not len(conn_ids):
            return
        eng_idx = snap.engine[pos]
        rules = snap.cache_rule[pos]
        for e in np.unique(eng_idx):
            selm = eng_idx == e
            engine = snap.objs[int(e)] if e >= 0 else None
            self._record_cached_round(
                conn_ids[selm].astype(np.int64),
                rules[selm],
                getattr(getattr(engine, "model", None),
                        "match_kinds", ()),
                snap.epoch,
            )

    def _serve_cached_items(self, items: list, snap: "_TabSnap",
                            t_pop: float) -> list:
        """Whole-item tier of the verdict cache: answer every item
        whose entries ALL hit with one `_verdict_body`-shaped all-allow
        frame (bit-identical to a recomputed all-allow vec round) and
        return the rest for the normal paths.  Per-conn FIFO holds: an
        item sharing a conn with a non-cached item in this round keeps
        the normal path, and pipelined-mode sends ride the completion
        FIFO so they can never overtake an in-flight earlier round."""
        t_c0 = time.monotonic()
        masks = [self._cache_item_hits(it, snap) for it in items]
        full = [m is not None and bool(m.all()) for m in masks]
        if not any(full):
            return items
        rest_items = [it for it, f in zip(items, full) if not f]
        rest_conns = None
        if rest_items:
            rest_conns = np.unique(np.concatenate(
                [it[2].conn_ids for it in rest_items]
            ))
        kept: list = []
        served: list = []
        for it, f in zip(items, full):
            if f and (
                rest_conns is None
                or not np.isin(it[2].conn_ids, rest_conns).any()
            ):
                served.append(it)
            else:
                kept.append(it)
        if not served:
            return items
        cache_s = time.monotonic() - t_c0
        swap_s = snap.swap_s
        snap.swap_s = 0.0
        for it in served:
            _kind, client, b = it
            n = b.count
            rt = self.tracer.begin_round(
                PATH_CACHED, n, self._oldest_arrival([it]), t_pop,
                ring_s=self._ring_wait([it]), swap_s=swap_s,
            )
            swap_s = 0.0
            rt.cache_s = cache_s
            cache_s = 0.0  # the mask cost books on the first round only
            rt.formed()
            rt.submitted()
            rt.completed()
            try:
                frame = self._verdict_frame(
                    b.seq, b.conn_ids, b.lengths,
                    np.ones(n, bool),
                )
            except Exception:  # noqa: BLE001 — fail closed, typed
                log.exception("cached verdict frame build failed")
                try:
                    if client.send_verdicts(
                        b.seq,
                        self._typed_entries(
                            b, FilterResult.UNKNOWN_ERROR
                        ),
                        batch=b,
                    ):
                        self.error_entries += n
                except Exception:  # noqa: BLE001
                    log.exception("typed error send failed")
                continue
            rt.drained()
            rtd = (rt, [self._batch_desc(b, client)])
            if self._inline_complete:
                try:
                    client.send(wire.MSG_VERDICT_BATCH, frame,
                                batches=[b])
                except Exception:  # noqa: BLE001 — client may be gone
                    log.exception("cached verdict send failed")
                if not self._round_thread_suppressed():
                    self.tracer.finish_round(rt, [self._batch_desc(b, client)])
            else:
                self._completion_put(("frame", client, frame, b, rtd))
            if not self._round_thread_suppressed():
                self._count_cache_hits(n)
                # LRU recency: one bulk stamp per served item (lock-
                # free like the hit mask itself; a racing table grow
                # only costs a stale stamp, never correctness).
                self._touch_cache_rows(b.conn_ids.astype(np.int64))
                self._flowlog_cached(
                    snap, b.conn_ids.astype(np.int64),
                    snap.lookup(b.conn_ids),
                )
        return kept

    def _run_vec(self, vec_items: list, snap: "_TabSnap",
                 t_pop: float) -> None:
        """The per-item route's vec items: per engine, one device pass
        over the group's concatenated matrix batches (issued and
        answered as _run_mat_group's are) and one over its DATA
        batches, ops emitted columnar straight from the verdict
        arrays."""
        self._count_cache_misses(
            sum(it[2].count for it, _ in vec_items)
        )
        groups: dict[int, list] = {}
        for it, eng in vec_items:
            groups.setdefault(id(eng), []).append((it, eng))
        # The snapshot's swap wait is booked on the round's FIRST trace
        # only (one blocked acquisition, however many path groups).
        swap_s = snap.swap_s
        snap.swap_s = 0.0
        for group in groups.values():
            engine = group[0][1]
            mats = [it for it, _ in group if it[0] == "mat"]
            datas = [it for it, _ in group if it[0] == "data"]
            # Matrix items arrive pre-padded: device chunks are plain
            # row-slices, no gather.  The group is issued and answered
            # like a whole round (one frame per client), with its
            # remotes from the round's snapshot.
            if mats:
                rt = self.tracer.begin_round(
                    PATH_VEC, sum(it[2].count for it in mats),
                    self._oldest_arrival(mats), t_pop,
                    ring_s=self._ring_wait(mats), swap_s=swap_s,
                )
                swap_s = 0.0
                if len(mats) == 1:
                    m_rows = mats[0][2].rows
                    m_lens = mats[0][2].lengths
                    m_ids = mats[0][2].conn_ids
                else:
                    m_rows = np.concatenate([it[2].rows for it in mats])
                    m_lens = np.concatenate([it[2].lengths for it in mats])
                    m_ids = np.concatenate([it[2].conn_ids for it in mats])
                rt.formed()
                self._issue_mat_round(
                    mats, engine, m_ids, m_lens, m_rows,
                    snap.src[snap.lookup(m_ids)], rt,
                )
            if not datas:
                continue
            rt = self.tracer.begin_round(
                PATH_VEC, sum(it[2].count for it in datas),
                self._oldest_arrival(datas), t_pop,
                ring_s=self._ring_wait(datas), swap_s=swap_s,
            )
            swap_s = 0.0
            batches = [it[2] for it in datas]
            conn_ids = np.concatenate([b.conn_ids for b in batches])
            lengths = np.concatenate(
                [b.lengths for b in batches]
            ).astype(np.int32)
            blob = np.frombuffer(
                b"".join(b.blob for b in batches), np.uint8
            )
            n = len(conn_ids)
            offs = np.concatenate(
                ([0], np.cumsum(lengths, dtype=np.int64))
            )[:-1].astype(np.int32)
            rt.formed()
            issued = self._issue_chunks_blob(
                engine, blob, offs, lengths, conn_ids, snap
            )
            rt.submitted()
            sends, start = [], 0
            for _, client, batch in datas:
                sends.append(
                    (client, batch.seq, conn_ids[start : start + batch.count],
                     lengths[start : start + batch.count],
                     start, start + batch.count, batch)
                )
                start += batch.count
            if self._inline_complete:
                self._finish_vec(issued, n, sends, rt, engine)
            else:
                self._completion_put(("vec", issued, n, sends, rt, engine))

    def _issue_chunks(self, engine, rows, lengths, remotes) -> list:
        """Issue device calls over [n, width] rows (int32 lengths, int32
        remote identities) in fixed bucket-shaped chunks WITHOUT
        blocking; returns [(allow_future, rule_future, a, b, cn)] (rule
        None without attribution) for the completion worker to
        materialize."""
        n = len(lengths)
        width = rows.shape[1]
        issued = []
        max_chunk = self.config.batch_flows
        for a in range(0, n, max_chunk):
            b = min(a + max_chunk, n)
            cn = b - a
            f_pad = self._min_bucket
            while f_pad < cn:
                f_pad *= 2
            if cn == f_pad:
                # Exact bucket fit: no pad-copy of the row matrix
                # (saves a ~0.5MB memcpy per full chunk on the hot path).
                data = rows[a:b]
                lens = lengths[a:b]
                rem = remotes[a:b]
            else:
                data = np.zeros((f_pad, width), np.uint8)
                data[:cn] = rows[a:b]
                lens = np.zeros(f_pad, np.int32)
                lens[:cn] = lengths[a:b]
                rem = np.zeros(f_pad, np.int32)
                rem[:cn] = remotes[a:b]
            _, _, chunk_allow, chunk_rule = self._model_call_attr(
                engine.model, data, lens, rem
            )
            if self._inline_complete and hasattr(chunk_allow, "copy_to_host_async"):
                # Co-located/greedy mode materializes chunks
                # sequentially right after issue; starting the
                # device->host copies now lets them overlap.  On a
                # high-latency link this is NOT done: per-array copies
                # would defeat the completion worker's batched readback
                # (one round trip for all pending arrays).
                chunk_allow.copy_to_host_async()
                if chunk_rule is not None:
                    chunk_rule.copy_to_host_async()
            issued.append((chunk_allow, chunk_rule, a, b, cn))
        return issued

    # Fixed device blob window for the gather path: every chunk uploads
    # exactly this many payload bytes, so jit sees ONE blob shape per
    # flow bucket (prewarmable) while the uplink still carries
    # ~payload-sized traffic instead of width-padded rows.
    BLOB_CHUNK = 65536

    def _issue_chunks_blob(self, engine, blob, offs, lengths, conn_ids,
                           snap: "_TabSnap") -> list:
        """Like _issue_chunks, but uploads the EXACT payload bytes and
        builds the [n, width] row view with an on-device gather —
        decisive when the chip is behind a bandwidth-limited link, and
        a cheap HBM gather when co-located.  Chunks are cut by BOTH the
        flow cap and the BLOB_CHUNK byte window."""
        n = len(conn_ids)
        ends = offs.astype(np.int64) + lengths
        issued = []
        max_chunk = self.config.batch_flows
        a = 0
        while a < n:
            b = min(a + max_chunk, n)
            base = int(offs[a])
            if int(ends[b - 1]) - base > self.BLOB_CHUNK:
                b = int(
                    np.searchsorted(ends, base + self.BLOB_CHUNK, side="right")
                )
                b = max(b, a + 1)  # an entry never exceeds the window
            cn = b - a
            f_pad = self._min_bucket
            while f_pad < cn:
                f_pad *= 2
            nb = int(ends[b - 1]) - base
            bp = np.zeros(self.BLOB_CHUNK, np.uint8)
            bp[:nb] = blob[base : base + nb]
            o = np.zeros(f_pad, np.int32)
            o[:cn] = offs[a:b] - base
            lens = np.zeros(f_pad, np.int32)
            lens[:cn] = lengths[a:b]
            remotes = np.zeros(f_pad, np.int32)
            remotes[:cn] = snap.src[snap.lookup(conn_ids[a:b])]
            chunk_allow, chunk_rule = self._gathered_call(
                engine.model, bp, o, lens, remotes
            )
            if self._inline_complete and hasattr(chunk_allow, "copy_to_host_async"):
                chunk_allow.copy_to_host_async()
                if chunk_rule is not None:
                    chunk_rule.copy_to_host_async()
            issued.append((chunk_allow, chunk_rule, a, b, cn))
            a = b
        return issued

    def _gathered_call(self, model, blob_dev, offs, lens, remotes):
        """The launcher's blob-window twin: the on-device row gather
        and the model fused into ONE shape-keyed jit executable per
        flow bucket, so a vec round uploads its payload once and never
        builds the [n, width] rows on the host.  Returns (allow,
        rule-or-None); with flow observability on and an attributed
        model, the rule argmax is fused into the same executable."""
        width = self.config.batch_width
        model = self._live_model(model)
        attr = self._flow_observe and hasattr(model, "verdicts_attr")

        def call(m):
            with self._device_ctx():
                fn = self._jit_for(
                    self._jit_gather,
                    m,
                    lambda bl, o, ln, r: _gather_model(
                        m, bl, o, ln, r, width, attr
                    ),
                    arg_fn=lambda mm, bl, o, ln, r: _gather_model(
                        mm, bl, o, ln, r, width, attr
                    ),
                )
                return fn(blob_dev, offs, lens, remotes)

        # ConstVerdict engines never reach here: vec eligibility
        # excludes them (their verdict needs no payload at all).
        out = self._mesh_guarded(model, call)
        if attr:
            return out[2], out[3]
        return out[-1], None

    def _completion_put(self, rec) -> None:
        """Queue a record into the completion pipeline tagged with the
        issuing thread's dispatcher ROUND id.  The stall watchdog sheds
        a stuck round's whole batch with typed SHED verdicts —
        including groups that round already handed to this pipeline —
        so the send loop must drop those groups' real verdicts or a
        client receives two replies for one seq (and misapplies ops on
        a shim that already consumed it).  The tag is per-round, not
        per-generation: a deposed worker's EARLIER rounds completed
        normally and were never shed, and suppressing their queued
        records would silently lose verdicts."""
        rid = getattr(threading.current_thread(), "_disp_round", None)
        self._completions.put((rid, rec))

    def _finish_vec(self, issued, n, sends, rt, engine) -> None:
        """Inline completion (greedy mode): materialize this round's
        futures and send — runs on the dispatcher thread, so per-conn
        FIFO order is trivially preserved.  The queue/worker variant in
        _completion_loop batches readbacks instead (high-latency link).
        Failures are isolated per chunk/per client like the queue path:
        one dead client or device error must not abort the round."""
        allow, rules = self._readback_chunks(issued, n)
        rt.completed()  # fenced: np.asarray above IS the readback
        self._answer_vec_round(sends, n, rt, engine, allow, rules)

    def _send_vec_frames(self, sends, allow,
                         deny_inject: bytes | None = None) -> None:
        """Emit a vec round's verdicts: one VERDICT_BATCH frame per
        original message, coalesced into one sendall (+ one writer-lock
        trip) per client — the dominant per-item cost in aggregated
        rounds.  Each message's wire batch rides along so send_frames
        marks it answered under the write lock before writing.  Frame
        build and client failures are isolated: one bad entry or dead
        client must not abort the rest of the round."""
        per_client: dict[int, tuple] = {}
        for client, seq, ids, lens, a, b, batch in sends:
            try:
                frame = self._verdict_frame(
                    seq, ids, lens, allow[a:b], deny_inject
                )
            except Exception:  # noqa: BLE001
                log.exception("verdict frame build failed")
                self._answer_error(client, batch)
                continue
            _, frames, bs = per_client.setdefault(
                id(client), (client, [], [])
            )
            frames.append(frame)
            bs.append(batch)
        for client, frames, bs in per_client.values():
            try:
                client.send_frames(
                    wire.MSG_VERDICT_BATCH, frames, batches=bs
                )
            except Exception:  # noqa: BLE001 — client may be gone
                log.exception("verdict send failed")

    def _answer_error(self, client, batch) -> None:
        """Fail closed, never silent, when a wire batch's verdict frame
        could not be built: the shim is owed exactly one reply for the
        seq, and nothing downstream will answer it (the round completes
        normally) — a typed UNKNOWN_ERROR per entry."""
        try:
            sent = client.send_verdicts(
                batch.seq,
                self._typed_entries(batch, FilterResult.UNKNOWN_ERROR),
                batch=batch,
            )
        except Exception:  # noqa: BLE001
            log.exception("error response send failed")
            return
        if sent:  # see _shed_item: no double-booking
            self.error_entries += batch.count

    # Max concurrent device->host readbacks.  Measured on the rounds 1–5
    # chip: one batched jax.device_get costs ~1 link RTT regardless of
    # array count, and 24 CONCURRENT gets still complete in ~1.3 RTT —
    # so G slots cut the "arrived mid-readback" wait from a full RTT
    # (r2's measured p99 was 2.0x RTT for exactly this reason) to
    # ~RTT/G, while the drain-coalescing below keeps the number of
    # outstanding gets bounded when rounds outpace the slots.  Sizing:
    # a get takes ~1.2 RTT end-to-end, so slots must cover
    # 1.2*RTT / round_interval concurrent groups — ~20 for 7ms rounds
    # on a 120ms link; 32 leaves headroom (24+ concurrent gets measured
    # to still complete in ~1.3 RTT).
    READBACK_SLOTS = 32

    def _completion_loop(self) -> None:
        """Stage 1 of the completion pipeline: drains pending records,
        coalesces them into one batched device→host readback per free
        slot (≤READBACK_SLOTS concurrent), and forwards each group with
        its readback future to the send loop in FIFO order."""
        import jax
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(
            max_workers=self.READBACK_SLOTS,
            thread_name_prefix="verdict-readback",
        )
        slots = threading.Semaphore(self.READBACK_SLOTS)

        def readback(futs):
            try:
                return jax.device_get(futs)
            finally:
                slots.release()

        def drain(recs):
            while True:
                try:
                    recs.append(self._completions.get_nowait())
                except queue.Empty:
                    return recs

        while True:
            recs = drain([self._completions.get()])
            # Wait for a readback slot; whatever lands meanwhile is
            # coalesced into this group's single batched get.
            slots.acquire()
            recs = drain(recs)
            stop = any(r[0] == "stop" for _rid, r in recs)
            futs = []
            for _rid, r in recs:
                if r[0] in ("vec", "mat"):
                    # Per chunk: the allow future, then (attribution
                    # on) the rule future — the send loop consumes
                    # them in the same order.
                    for fut, rfut, _, _, _ in r[1]:
                        futs.append(fut)
                        if rfut is not None:
                            futs.append(rfut)
                elif r[0] == "entry2":
                    futs.extend(r[1])
            if futs:
                vals_f = pool.submit(readback, futs)
            else:
                vals_f = None
                slots.release()
            self._sends.put((recs, vals_f, len(futs)))
            if stop:
                self._sends.put(None)
                pool.shutdown(wait=False)
                return

    def _send_loop(self) -> None:
        """Stage 2: waits on each group's readback IN ORDER and emits
        verdict batches — per-connection FIFO is preserved because
        sends happen on this one thread in submission order."""
        while True:
            item = self._sends.get()
            if item is None:
                return
            recs, vals_f, n_futs = item
            try:
                # Bounded wait: a readback stalled past the device
                # deadline quarantines the device and fails THIS group
                # closed (typed deny) instead of wedging the strictly-
                # FIFO send pipeline behind it forever.
                timeout = (
                    self.guard.timeout_s if self.guard.enabled else None
                )
                vals = vals_f.result(timeout) if vals_f is not None else []
            except _FuturesTimeout:
                # (concurrent.futures.TimeoutError is a distinct class
                # from the builtin TimeoutError before py3.11)
                log.error("device readback stalled; quarantining")
                self.guard.record_stall("readback-stall")
                metrics.DeviceStalls.inc()
                if self._mesh is not None and self._mesh_demoted is None:
                    # Same reasoning as the dispatch-stall demotion: a
                    # readback that never lands on a mesh means a
                    # device dropped out of the collective.
                    self._demote_mesh("device-stall")
                vals = [None] * n_futs
            except Exception:  # noqa: BLE001
                log.exception("device readback failed")
                vals = [None] * n_futs
            # One batched get covered every vec group in this drain:
            # stamp their fenced device-complete boundary NOW, before
            # earlier records' sends run, or later groups would book
            # sibling send time as device time.
            for _rid, r in recs:
                if r[0] in ("vec", "mat"):
                    r[4].completed()
            vi = 0
            cur = threading.current_thread()
            for rid, r in recs:
                # Adopt the record's round id for the duration of its
                # sends: a record issued by a round the stall watchdog
                # shed already had its whole batch answered with typed
                # SHED verdicts, so the thread_round_is_shed()
                # suppression in _ClientHandler.send* must cover THIS
                # thread's sends of that record too — or a client
                # receives both a real VERDICT_BATCH and a SHED batch
                # for one seq.  Rounds that completed before their
                # worker was deposed keep their own (un-shed) ids and
                # are emitted normally — never silently lost.
                cur._disp_round = rid
                try:
                    deposed = self.dispatcher.thread_round_is_shed()
                    if r[0] in ("vec", "mat"):
                        issued = r[1]
                        n_futs_round = sum(
                            2 if rfut is not None else 1
                            for _, rfut, _, _, _ in issued
                        )
                        chunk = vals[vi : vi + n_futs_round]
                        vi += n_futs_round  # keep later slices aligned
                        if deposed:
                            continue
                        allow, rules = self._chunk_values(
                            issued, r[2], chunk
                        )
                        if r[0] == "mat":
                            _, _, _, items, rt, engine, ids, lengths = r
                            self._answer_mat_round(
                                items, engine, ids, lengths, allow, rules,
                                rt,
                            )
                        else:
                            _, _, n, sends, rt, engine = r
                            self._answer_vec_round(
                                sends, n, rt, engine, allow, rules
                            )
                    elif r[0] == "entry2":
                        # Runs even when deposed: finish() drains engine
                        # ops/inject and the async-pending refcounts
                        # (skipping it would wedge deferred rounds and
                        # misattribute ops); its sends are suppressed by
                        # the generation adopted above.
                        _, rfuts, finish = r
                        nf = len(rfuts)
                        chunk = vals[vi : vi + nf]
                        vi += nf  # before finish: a throw must not
                        # misalign later records' slices.  deferred_scope:
                        # pump/judge crashes inside a deferred completion
                        # happen on THIS thread, outside any dispatcher
                        # round — recorded sticky so the next round_start
                        # can't erase them before they hold the streak.
                        self.guard.deferred_scope(finish, chunk)
                    elif r[0] == "ready":
                        _, client, batch, entries, rtd = r
                        client.send_verdicts(
                            batch.seq, entries, batch=batch
                        )
                        if rtd is not None and not deposed:
                            rt, descs = rtd
                            self.tracer.finish_round(rt, descs)
                    elif r[0] == "frame":
                        # Verdict-cache whole-item round: the frame was
                        # prebuilt at decision time; it rides this FIFO
                        # so a cached answer can never overtake an
                        # earlier in-flight round's verdicts for the
                        # same conn.
                        _, client, frame, batch, rtd = r
                        client.send(
                            wire.MSG_VERDICT_BATCH, frame,
                            batches=[batch],
                        )
                        if rtd is not None and not deposed:
                            rt, descs = rtd
                            self.tracer.finish_round(rt, descs)
                except Exception:  # noqa: BLE001 — worker must survive
                    log.exception("completion failed")
                finally:
                    cur._disp_round = None

    def _answer_vec_round(self, sends, n, rt, engine, allow,
                          rules) -> None:
        """Answer a round of DATA batches from its verdict arrays: one
        VERDICT_BATCH frame per wire batch."""
        rt.drained()
        self.fast_log.log_batch(
            getattr(engine, "proto", "r2d2"), n, int(n - allow.sum())
        )
        self.vec_batches += 1
        self.vec_entries += n
        metrics.ProxyBatches.inc()
        self._send_vec_frames(
            sends, allow, getattr(engine, "DENY_INJECT", None)
        )
        if not self._round_thread_suppressed():
            self.tracer.finish_round(
                rt, [self._batch_desc(s[6], s[0]) for s in sends]
            )
            if sends:
                self._record_vec_round(
                    engine, np.concatenate([s[2] for s in sends]),
                    allow, rules,
                )

    @staticmethod
    def _chunk_values(issued: list, n: int, vals: list):
        """A round's (allow, rule) arrays from its chunks' read-back
        values: per chunk the allow value, then (with attribution) the
        rule value; a failed allow readback (None) denies and
        unattributes its chunk, a failed rule readback unattributes."""
        allow = np.empty(n, bool)
        rules = np.full(n, -1, np.int32)
        k = 0
        for _, rfut, a, b, cn in issued:
            v = vals[k]
            k += 1
            rv = None
            if rfut is not None:
                rv = vals[k]
                k += 1
            if v is None:
                allow[a:b] = False
            else:
                allow[a:b] = np.asarray(v)[:cn]
                if rv is not None:
                    rules[a:b] = np.asarray(rv)[:cn]
        return allow, rules

    _ERR_ROW = np.frombuffer(b"ERROR\r\n", np.uint8)

    def _verdict_body(self, conn_ids, lengths, allow,
                      deny_inject: bytes | None = None) -> bytes:
        """Columnar op assembly: every entry is (PASS|DROP frame, MORE 1)
        — identical to the streaming oracle's op sequence for one
        complete frame (reference: r2d2parser.go:158-213).
        ``deny_inject`` is the serving engine's per-denied-frame reply
        bytes (None = the historic r2d2 ``ERROR\r\n``; DNS injects
        nothing)."""
        n = len(conn_ids)
        tpl = self._frame_tpl.get(n)
        if tpl is None:
            ops0 = np.zeros(2 * n, wire.FILTER_OP)
            ops0["op"][1::2] = int(MORE)
            ops0["n_bytes"][1::2] = 1
            tpl = (ops0, np.zeros(n, np.uint32), np.full(n, 2, np.uint32))
            if len(self._frame_tpl) < 4096:
                self._frame_tpl[n] = tpl
        ops0, zeros_u32, twos_u32 = tpl
        ops = ops0.copy()
        ops["op"][0::2] = np.where(allow, int(PASS), int(DROP))
        ops["n_bytes"][0::2] = lengths
        err_row = (
            self._ERR_ROW if deny_inject is None
            else np.frombuffer(deny_inject, np.uint8)
        )
        nd = n - int(allow.sum())
        if nd and len(err_row):
            inj_blob = np.broadcast_to(
                err_row, (nd, len(err_row))
            ).tobytes()
            inj_reply = np.where(allow, 0, len(err_row)).astype(np.uint32)
        else:
            inj_blob = b""
            inj_reply = zeros_u32
        return wire.pack_verdict_body(
            conn_ids, zeros_u32, twos_u32, zeros_u32, inj_reply, ops, inj_blob
        )

    def _verdict_frame(self, seq, conn_ids, lengths, allow,
                       deny_inject: bytes | None = None) -> bytes:
        return struct.pack("<QI", seq, len(conn_ids)) + self._verdict_body(
            conn_ids, lengths, allow, deny_inject
        )

    def _catch_up_epoch(self, conn_id: int, sc: "_SidecarConn") -> None:
        """Stale-conn epoch catch-up: a swap left this conn on its
        captured engine because an in-flight round still owed state
        against it.  Once that round drained (no async-pending
        refcount, ops empty), adopt the current epoch's engine and
        migrate the retained buffer — pointer reads only, no
        compile."""
        with self._lock:
            if conn_id in self._async_pending or (
                conn_id < self._tab_size and self._tab_async[conn_id]
            ):
                return  # round still in flight: retry on a later entry
            old_eng = sc.engine
            if old_eng is not None and not self._flow_migratable(
                old_eng, conn_id
            ):
                return
            eng = self._engines.get(
                self._engine_key_for(sc.module_id, sc.conn)
            )
            if eng is not None and old_eng is not None \
                    and eng is not old_eng:
                self._migrate_flow(old_eng, eng, conn_id, sc)
            if eng is not None:
                sc.engine = eng
                sc.fast_ok = sc.conn.parser_name in FAST_PROTOS
            else:
                sc.engine = None
                sc.fast_ok = False
            self._tab_set_engine(
                conn_id, eng if sc.fast_ok else None
            )
            self._stale_conns.discard(conn_id)
            # Caught up to the current epoch: refresh the invariance
            # claim against the adopted engine's table.
            grant = self._arm_flow_cache(conn_id, sc)
        if grant is not None:
            # Dispatch path (per-entry classifier): never send inline
            # — after a swap EVERY stale conn funnels through here in
            # one round, and a blocked shim socket would serialize
            # thousands of sends on the dispatcher.  The builder
            # thread delivers; revalidation makes late delivery safe.
            self._build_queue.put(("grants", [grant]))

    def _classify_entry(self, item, i: int, conns_snapshot: dict,
                        quarantined: bool, responses: dict,
                        fast: list, slow: list,
                        slow_conns: set, cache_hits: list | None = None,
                        ) -> None:
        """Route ONE entry onto the fast/slow/oracle lanes — THE shared
        per-entry classifier of the scalar entrywise path, also used by
        the columnar round for its residual (non-columnar) minority so
        the two rounds can never drift."""
        _, client, batch = item
        key = id(item)
        conn_id, reply, end_stream, data = batch.entry(i)
        sc = conns_snapshot.get(conn_id)
        if sc is None:
            responses[key][i] = (
                conn_id,
                int(FilterResult.UNKNOWN_CONNECTION),
                [],
                b"",
                b"",
            )
            return
        if sc.columnar_dead and not reply:
            # Lane-exit dead latch (columnar overflow with no engine
            # adopter): the overflowed bytes are gone, so the scalar
            # twin of FlowState.overflowed applies — every further
            # request entry answers a typed protocol error, never a
            # mid-stream resume over the dropped bytes.
            responses[key][i] = (
                conn_id, int(FilterResult.OK),
                [(int(ERROR), int(OpError.ERROR_INVALID_FRAME_LENGTH))],
                b"", b"",
            )
            return
        if quarantined:
            # Pure-device engines (no oracle inside) fall back
            # to the in-process oracle; device-assisted engines
            # keep their engine (the device_gate makes their
            # judge step a host policy.matches, bit-identical).
            if sc.engine is not None and not getattr(
                sc.engine, "handles_reply", False
            ):
                self._demote_to_oracle(conn_id, sc)
            self.fallback_entries += 1
            metrics.SidecarFallbackVerdicts.inc()
        elif sc.demoted_mod is not None:
            self._maybe_rebind(conn_id, sc)
        elif conn_id in self._stale_conns:
            # A swap deferred this conn's rebind behind an
            # in-flight round; catch it up to the current
            # epoch before this entry routes.
            self._catch_up_epoch(conn_id, sc)
        if sc.skip[reply]:
            take = min(sc.skip[reply], len(data))
            sc.skip[reply] -= take
            data = data[take:]
            if not data:
                self._tab_mark(conn_id, sc)
                responses[key][i] = (
                    conn_id, int(FilterResult.OK), [], b"", b"",
                )
                return
        eng_flow = (
            sc.engine.flows.get(conn_id) if sc.engine is not None else None
        )
        framing = _engine_framing(sc.engine)
        # Verdict-cache hit, scalar tier (the greedy-mode and minority-
        # entry twin of the columnar Phase-A mask): armed conn, claim
        # epoch current, no residue anywhere, frame-aligned payload.
        # The cache arrays are read lock-free like conns_snapshot —
        # bounded round-grain staleness; a stale read only costs a
        # miss (arming is monotone within an epoch, and disarms flip
        # the state before any residue can exist).
        if (
            cache_hits is not None
            and not reply
            and not end_stream
            and conn_id not in slow_conns
            and framing is not None
            and framing.payload_aligned(data)
            and not sc.bufs[False]
            and conn_id < self._tab_size
            and self._tab_cache[conn_id] == 1
            and self._tab_cache_epoch[conn_id] == self.policy_epoch
            and (
                eng_flow is None
                or not (
                    getattr(eng_flow, "buffer", None)
                    or getattr(eng_flow, "overflowed", False)
                )
            )
        ):
            rule = int(self._tab_cache_rule[conn_id])
            responses[key][i] = (
                conn_id, int(FilterResult.OK),
                [(int(PASS), len(data)), (int(MORE), 1)], b"", b"",
            )
            self._touch_cache_rows(np.array([conn_id], np.int64))
            cache_hits.append((key, i, conn_id, rule, sc.engine))
            return
        if (
            sc.fast_ok
            and not reply
            and conn_id not in slow_conns
            and not sc.bufs[False]
            and (
                eng_flow is None
                or not (eng_flow.buffer or eng_flow.overflowed)
            )
            and not isinstance(sc.engine.model, ConstVerdict)
            and framing is not None
            and framing.payload_single_frame(data)
            and len(data) <= self.config.batch_width
        ):
            fast.append((key, i, sc, conn_id, data))
        else:
            slow_conns.add(conn_id)
            slow.append((key, i, sc, conn_id, reply, end_stream, data))

    def _process_entrywise(self, items: list, t_pop: float = 0.0,
                           swap_s: float = 0.0) -> None:
        # Columnar reassembly lane first (sidecar/reasm.py): the CRLF
        # slow lane as array passes per ROUND.  Quarantined rounds are
        # the host rung; greedy mode keeps the scalar path (1-2 entry
        # rounds lose on the columnar fixed cost).
        if (
            self._reasm is not None
            and not self.guard.quarantined
            and self._process_columnar(items, t_pop, swap_s)
        ):
            return
        # Per-entry path, preserving per-connection order: an entry is
        # fast only if nothing earlier in this round put its connection
        # on the slow path.
        responses: dict[int, list] = {}  # id(item) -> per-entry results
        fast: list[tuple] = []  # (item_key, entry_idx, sc, data)
        slow: list[tuple] = []
        slow_conns: set[int] = set()

        quarantined = self.guard.quarantined
        # Path label for the decomposition: a quarantined round IS the
        # host-fallback rung (oracle demotion / host policy.matches);
        # otherwise the entrywise round is the engine/parser slow path.
        rt = self.tracer.begin_round(
            PATH_HOST if quarantined else PATH_ORACLE,
            sum(it[2].count for it in items),
            self._oldest_arrival(items),
            t_pop or None,
            ring_s=self._ring_wait(items),
            swap_s=swap_s,
        )
        cache_hits: list | None = [] if self._flow_cache_on else None
        for item in items:
            _, client, batch = item
            responses[id(item)] = [None] * batch.count
            with self._lock:
                conns_snapshot = self._conns
            for i in range(batch.count):
                self._classify_entry(item, i, conns_snapshot,
                                     quarantined, responses, fast,
                                     slow, slow_conns,
                                     cache_hits=cache_hits)
        cached_keys: set | None = None
        if cache_hits:
            cached_keys = {(k, i) for k, i, *_ in cache_hits}
            if not self._round_thread_suppressed():
                self._count_cache_hits(len(cache_hits))
                self._record_cached_entries(cache_hits)
        if self._flow_cache_on:
            # Misses are REQUEST-direction entries only (the metric's
            # definition, and the columnar tier's n_elig): replies and
            # end-stream entries are never cache candidates, so they
            # must not deflate a hit rate derived from the counters.
            self._count_cache_misses(
                len(fast)
                + sum(1 for s in slow if not s[4] and not s[5])
            )

        # Async round (completion-pipeline mode): when every slow entry
        # is either CRLF-extractable (engine exposes feed_extract) or
        # host-only work, the whole round issues its device calls
        # without reading back — the completion loop batches the
        # readbacks, overlapping the ~1-RTT device_get with the next
        # round's dispatch exactly like the vec path.  The wave path's
        # one-readback-per-pump (≈1 link RTT each) made mixed rounds
        # RTT-serial: 10k verdicts/s on the rounds 1–5 chip vs the vec
        # path's millions (see BENCH_NOTES round 5).
        if not self._inline_complete and self._slow_async_eligible(slow):
            rt.formed()
            # Attribution captures for the whole round, keyed
            # (item_key, entry_idx) — filled at DECISION time (issue /
            # finish halves) against the engines captured there.
            rules_out: dict = {}
            fast_issued = self._issue_fast(fast) if fast else []
            buckets, plan = self._issue_slow_async(
                slow, responses, rules_out
            )
            rt.submitted()
            # Per group/bucket: the allow future, then (attribution on)
            # the rule future — _finish_fast/_finish_slow_async consume
            # vals in the same order.
            futs = []
            for g in fast_issued:
                futs.append(g[0])
                if g[1] is not None:
                    futs.append(g[1])
            for bk in buckets:
                futs.append(bk[0])
                if bk[1] is not None:
                    futs.append(bk[1])
            n_fast_futs = sum(
                2 if g[1] is not None else 1 for g in fast_issued
            )
            pend = {conn_id for _k, _i, _sc, conn_id, *_ in plan}
            if pend:
                with self._lock:
                    for cid in pend:
                        self._async_pending[cid] = (
                            self._async_pending.get(cid, 0) + 1
                        )

            def finish(vals: list | None) -> None:
                try:
                    # The completion loop's batched device_get (or the
                    # inline np.asarray fallback) fenced this round.
                    rt.completed()
                    self._finish_fast(
                        fast_issued, responses,
                        vals=(
                            vals[:n_fast_futs] if vals is not None
                            else [None] * n_fast_futs
                        ),
                        rules_out=rules_out,
                    )
                    self._finish_slow_async(
                        buckets, plan, responses,
                        vals=(
                            vals[n_fast_futs:] if vals is not None
                            else [None] * (len(futs) - n_fast_futs)
                        ),
                        rules_out=rules_out,
                    )
                    rt.drained()
                    for item in items:
                        _, client, batch = item
                        try:
                            client.send_verdicts(
                                batch.seq, responses[id(item)],
                                batch=batch,
                            )
                        except Exception:  # noqa: BLE001 — client gone
                            log.exception("verdict send failed")
                    if not self._round_thread_suppressed():
                        self.tracer.finish_round(
                            rt,
                            [self._batch_desc(it[2], it[1]) for it in items],
                        )
                        self._record_entrywise(
                            rt.path, items, responses, rules_out,
                            cached=cached_keys,
                        )
                finally:
                    if pend:
                        with self._lock:
                            for cid in pend:
                                n = self._async_pending.get(cid, 1) - 1
                                if n <= 0:
                                    self._async_pending.pop(cid, None)
                                else:
                                    self._async_pending[cid] = n

            self._completion_put(("entry2", futs, finish))
            return

        # Sync fallback.  If any conn in this round has an UNFINISHED
        # async round, its engine state (ops/inject) is still owed to
        # the send thread's finish — running pump/take here would race
        # it and interleave op attribution.  Defer the whole round to
        # the completion queue (futs=[]): it executes on the send
        # thread strictly AFTER the pending finish, preserving both
        # state exclusivity and per-conn response order.
        deferred = False
        if not self._inline_complete and (
            self._async_pending or self._reasm is not None
        ):
            with self._lock:
                pending_now = set(self._async_pending)
            round_conns = {rec[3] for rec in slow}
            round_conns.update(rec[3] for rec in fast)
            if pending_now:
                deferred = bool(round_conns & pending_now)
            if not deferred and self._reasm is not None and round_conns:
                # The reassembler lane tracks its in-flight conns in
                # the _tab_async array (bulk updates): a sync round
                # touching one must queue behind its finish too.
                # (Filtered in Python first: a u64 wire id >= 2^63
                # would overflow np.fromiter's int64.)
                small = [c for c in round_conns
                         if 0 <= c < self._TAB_MAX]
                rc = np.fromiter(small, np.int64, count=len(small))
                with self._lock:
                    rc = rc[rc < self._tab_size]
                    deferred = bool(len(rc)) and bool(
                        self._tab_async[rc].any()
                    )

        def run_sync_and_respond(_vals: list | None = None) -> None:
            rt.formed()
            rules_out: dict = {}
            if fast:
                self._run_fast(fast, responses, rules_out)
            self._run_slow_batched(slow, responses, rules_out)
            # Sync paths read back inside the engine pump/fast finish:
            # submit/complete collapse onto this boundary and the work
            # shows up in the drain stage (still fenced — the pump's
            # np.asarray readbacks have executed by here).
            rt.drained()
            for i_item, item in enumerate(items):
                _, client, batch = item
                if self._inline_complete or deferred:
                    try:
                        client.send_verdicts(
                            batch.seq, responses[id(item)], batch=batch
                        )
                    except Exception:  # noqa: BLE001 — client may be gone
                        log.exception("verdict send failed")
                else:
                    # The LAST item's ready record carries the round
                    # trace (+ every covered batch's descriptor): the
                    # send loop emits records in FIFO order, so the
                    # round closes once every frame is on the wire.
                    last = i_item == len(items) - 1
                    self._completion_put(
                        ("ready", client, batch, responses[id(item)],
                         (rt, [self._batch_desc(it2[2], it2[1]) for it2 in items])
                         if last else None)
                    )
            if self._inline_complete or deferred:
                if not self._round_thread_suppressed():
                    self.tracer.finish_round(
                        rt, [self._batch_desc(it[2], it[1]) for it in items]
                    )
            # Record emission at decision time (the pipelined sends are
            # already queued in FIFO order behind this round).
            if not self._round_thread_suppressed():
                self._record_entrywise(rt.path, items, responses,
                                       rules_out, cached=cached_keys)

        if deferred:
            self._completion_put(("entry2", [], run_sync_and_respond))
        else:
            run_sync_and_respond()

    # -- columnar reassembly lane (sidecar/reasm.py) ----------------------

    def _reasm_fallback(self, reason: str) -> None:
        self.reasm_fallbacks[reason] = (
            self.reasm_fallbacks.get(reason, 0) + 1
        )

    def _reasm_bail(self, conn_ids: np.ndarray,
                    reason: str | None) -> bool:
        """Whole-round fallback to the scalar rung.  Any round conn
        still holding columnar carry state must exit the lane FIRST:
        the scalar classifier reads engine/oracle buffers, not the
        arena, and serving it with the carry invisible would judge
        frames without their carried prefix — wrong op byte counts on
        the wire and bytes stranded in the arena.  Returns False for
        the caller's tail call.  ``reason`` None skips the fallback
        counter (a round with nothing lane-eligible is ordinary scalar
        traffic, not a reassembler fallback)."""
        if reason is not None:
            self._reasm_fallback(reason)
        rc = np.unique(conn_ids)
        for cid in rc[self._reasm.arena.has_slot(rc)]:
            self._reasm_release_to_scalar(int(cid))
        return False

    def _reasm_release_to_scalar(self, conn_id: int) -> None:
        """Pull one conn's carry out of the columnar arena and hand it
        to the scalar side (engine flow buffer via adopt_residue, or
        the oracle mirror when no engine is bound) — the lane-exit
        transition.  Runs on the dispatcher thread BEFORE the conn's
        entries are classified scalar, so the shared residual-dirty
        predicate sees the bytes in their scalar home.

        Every byte (and the dead/overflow latch) released here must
        land in an accountable home — the R14 lane-exit contract: a
        closed conn's slot is dropped EXPLICITLY (never pulled out
        first and leaked), and a dead latch with no engine adopter
        transfers to the conn's own ``columnar_dead`` so further
        entries answer a typed protocol error instead of resuming the
        parse over the dropped bytes (the PR 10 silent-loss class)."""
        sc = self._conns.get(conn_id)
        if sc is None:
            # Conn already closed: no peer awaits these bytes; the
            # explicit drop is close_connection's own arena contract.
            self._reasm.arena.drop(conn_id)
            return
        data, dead = self._reasm.arena.release(conn_id)
        engine = sc.engine
        if engine is not None and hasattr(engine, "adopt_residue"):
            conn = sc.conn
            engine.adopt_residue(
                conn_id, data, dead,
                remote_id=conn.src_id, policy_name=conn.policy_name,
                ingress=conn.ingress, dst_id=conn.dst_id,
                src_addr=conn.src_addr, dst_addr=conn.dst_addr,
            )
        else:
            if dead:
                sc.columnar_dead = True
            if data:
                sc.bufs[False] = bytearray(data) + sc.bufs[False]
        self._tab_mark(conn_id, sc)

    def _process_columnar(self, items: list, t_pop: float,
                          swap_s: float) -> bool:
        """Serve one entrywise round through the columnar reassembler:
        carry append, frame splitting and op/inject/record assembly as
        array passes per ROUND instead of feed/settle Python per ENTRY.

        Phase A is side-effect-free eligibility: anything the lane
        cannot prove safe (reply/end_stream flags, non-CRLF or
        ConstVerdict engines, demoted/stale/transitional conns,
        duplicate conns in one round, too few eligible entries, a
        leftover entry that would force a synchronous engine pump)
        either taints its conn to the scalar minority or bails the
        whole round back to the scalar path — which remains the
        oracle rung, byte-identical by the parity tests.  Phase B
        ingests into the arena, issues ONE model call per
        (engine, width) bucket without reading back, and queues a
        finish that renders verdict frames columnar."""
        reasm = self._reasm
        batches = [it[2] for it in items]
        counts = [b.count for b in batches]
        n_round = int(sum(counts))
        if n_round == 0:
            return False
        # --- Phase A: columnar view + eligibility (no side effects) ---
        if len(batches) == 1:
            b0 = batches[0]
            conn_ids_u = b0.conn_ids
            flags = b0.flags
            lengths = b0.lengths.astype(np.int64)
            blob_b = b0.blob
            ends = b0.offsets[1:].astype(np.int64)
        else:
            conn_ids_u = np.concatenate([b.conn_ids for b in batches])
            flags = np.concatenate([b.flags for b in batches])
            lengths = np.concatenate(
                [b.lengths for b in batches]
            ).astype(np.int64)
            blob_b = b"".join(b.blob for b in batches)
            ends = np.cumsum(lengths)
        starts = ends - lengths
        # Range-check the RAW u64 ids before any int64 view: a wire id
        # >= 2^63 would wrap negative and fancy-index the wrong rows
        # in the conn tables / arena map.
        conn_ids = conn_ids_u.astype(np.int64)
        if len(conn_ids_u) and int(conn_ids_u.max()) >= ByteArena.MAP_MAX:
            return self._reasm_bail(conn_ids, "conn_id_range")
        blob = np.frombuffer(blob_b, np.uint8)
        if len(blob) != int(lengths.sum()):
            return self._reasm_bail(conn_ids, "blob_shape")
        snap = self._tab_snapshot(items)
        pos = snap.lookup(conn_ids)
        eng_idx = snap.engine[pos]
        elig = (flags == 0) & (eng_idx >= 0)
        dirty = snap.dirty[pos].astype(bool)
        has_slot = reasm.arena.has_slot(conn_ids)
        # A dirty conn is lane-eligible only when its residue IS the
        # arena carry (the lane's own state); scalar residue anywhere
        # keeps the conn on the scalar rung until it drains.
        elig &= (~dirty) | has_slot
        if elig.any():
            for e in np.unique(eng_idx[elig]):
                engine = snap.objs[int(e)]
                # Per-framing dispatch (reasm.FRAMINGS): an engine
                # rides the lane iff its declared framing has a
                # registered scanner — CRLF (r2d2 class) and the DNS
                # length prefix today; an engine declaring anything
                # else (cassandra/kafka until their parser state goes
                # arena-portable) must never be scanned with the wrong
                # framing into garbage frames.
                if (
                    engine is None
                    or _engine_framing(engine) is None
                    or isinstance(engine.model, ConstVerdict)
                ):
                    elig &= eng_idx != e
        with self._lock:
            stale = (
                np.fromiter(self._stale_conns, np.int64,
                            count=len(self._stale_conns))
                if self._stale_conns else None
            )
        if stale is not None and len(stale):
            elig &= ~np.isin(conn_ids, stale)
        # Duplicate conns in one round have a sequential carry
        # dependency (entry k+1's stream starts from entry k's
        # residue): route them scalar, whole-conn, preserving order.
        order = np.argsort(conn_ids, kind="stable")
        so = conn_ids[order]
        dup_mask = None
        if len(so) > 1:
            dup = so[1:] == so[:-1]
            if dup.any():
                dup_mask = np.isin(conn_ids, np.unique(so[1:][dup]))
                elig &= ~dup_mask
        # Verdict-cache hit lane (Phase A, still side-effect-free):
        # armed conns whose claim epoch matches the snapshot epoch,
        # with no residue and a frame-aligned payload, are filtered
        # out of the device round in this one vectorized mask — they
        # are answered from the claim in Phase B, before ingest or
        # bucket issue ever sees them.  Duplicate conns stay out: an
        # earlier entry this round may leave residue the hit's clean
        # check cannot see yet.
        hit = None
        t_c0 = time.monotonic()
        if self._flow_cache_on:
            hit = (
                (flags == 0)
                & (snap.cache[pos] == 1)
                & (snap.cache_epoch[pos] == snap.epoch)
                & (~dirty)
            )
            if hit.any():
                hit &= self._framing_alignment_mask(
                    snap, eng_idx, hit,
                    lambda framing, selm: framing.segments_aligned(
                        blob, starts[selm], lengths[selm]
                    ),
                )
            if dup_mask is not None:
                hit &= ~dup_mask
            if hit.any():
                elig &= ~hit
            else:
                hit = None
        cache_s = (time.monotonic() - t_c0) if hit is not None else 0.0
        n_hit = int(hit.sum()) if hit is not None else 0
        n_elig = int(elig.sum())
        if n_elig < max(int(self.config.reasm_min_entries), 1) and not (
            n_hit and n_elig == 0
        ):
            # Too small for the columnar fixed cost (cache hits pay
            # almost none, so an all-hit round proceeds regardless);
            # the scalar rung serves everything, hits included
            # (_classify_entry has the same hit check).
            return self._reasm_bail(
                conn_ids, "round_too_small" if n_elig else None
            )
        # Leftover-minority soundness: the round issues async; any
        # entry that would need a synchronous engine pump (or a
        # transitional rebind/catch-up that could create one) forfeits
        # the lane — the scalar round owns those shapes.
        rest = np.flatnonzero(~elig)
        conns = self._conns
        for k in rest:
            if hit is not None and hit[k]:
                continue  # answered from the claim in Phase B
            cid = int(conn_ids[k])
            fl = int(flags[k])
            sc = conns.get(cid)
            if sc is None:
                continue  # UNKNOWN_CONNECTION: typed inline, async-safe
            if sc.demoted_mod is not None or cid in self._stale_conns:
                return self._reasm_bail(conn_ids, "transitional_conn")
            engine = sc.engine
            if engine is None or isinstance(engine.model, ConstVerdict):
                continue  # host-only work
            if fl & wire.FLAG_END_STREAM:
                return self._reasm_bail(conn_ids, "end_stream")
            if fl & wire.FLAG_REPLY:
                if getattr(engine, "handles_reply", False):
                    return self._reasm_bail(conn_ids, "engine_reply")
                continue  # oracle host-only reply
            if not hasattr(engine, "feed_extract"):
                return self._reasm_bail(conn_ids, "engine_pump")
        # --- Phase B: committed ---------------------------------------
        # Lane-exit for tainted conns still holding arena state: their
        # residue moves to the scalar side before classification (the
        # one release definition — _reasm_bail with no fallback count).
        # Cache hits are not tainted — they hold no carry by the hit
        # mask's clean check.
        lane_exit = rest if hit is None else rest[~hit[rest]]
        if len(lane_exit):
            self._reasm_bail(conn_ids[lane_exit], None)
        rt = self.tracer.begin_round(
            PATH_ORACLE, n_round, self._oldest_arrival(items), t_pop,
            ring_s=self._ring_wait(items), swap_s=swap_s,
        )
        responses: dict[int, list] = {
            id(item): [None] * item[2].count for item in items
        }
        base = np.concatenate(([0], np.cumsum(counts))).astype(np.int64)
        # Cache-hit entries answered from the claim: `_verdict_body`'s
        # (PASS frame, MORE 1) shape, original rule attributed on the
        # `cached` path, device round never issued.
        cached_keys: set | None = None
        if n_hit:
            hit_idx = np.flatnonzero(hit)
            cached_keys = set()
            for k in hit_idx:
                bi = int(np.searchsorted(base, k, side="right")) - 1
                item = items[bi]
                ei = int(k - base[bi])
                responses[id(item)][ei] = (
                    int(conn_ids[k]), int(FilterResult.OK),
                    [(int(PASS), int(lengths[k])), (int(MORE), 1)],
                    b"", b"",
                )
                cached_keys.add((id(item), ei))
            rt.cache_s = cache_s
            if not self._round_thread_suppressed():
                self._count_cache_hits(n_hit)
                self._touch_cache_rows(conn_ids[hit_idx])
                self._flowlog_cached(
                    snap, conn_ids[hit_idx], pos[hit_idx]
                )
        self._count_cache_misses(n_elig)
        fast: list = []
        slow: list = []
        slow_conns: set = set()
        if len(rest):
            with self._lock:
                conns_snapshot = self._conns
            for k in rest:
                if cached_keys is not None and hit[k]:
                    continue  # already answered from the claim
                bi = int(np.searchsorted(base, k, side="right")) - 1
                self._classify_entry(
                    items[bi], int(k - base[bi]), conns_snapshot,
                    False, responses, fast, slow, slow_conns,
                )
        # Ingest + pack (the `reasm` stage of the decomposition).
        t_r0 = time.monotonic()
        e_live = np.flatnonzero(elig)
        groups: list = []
        crash_sel: list = []
        for e in np.unique(eng_idx[e_live]):
            sel = e_live[eng_idx[e_live] == e]
            engine = snap.objs[int(e)]
            try:
                rnd = reasm.ingest(
                    conn_ids[sel], starts[sel], lengths[sel], blob,
                    framing=_engine_framing(engine),
                )
            except Exception:  # noqa: BLE001 — framing hooks are pluggable
                # A raise-capable per-framing hook (reasm.FRAMINGS
                # scan/reader callbacks) crashed for THIS engine's
                # group.  Ingest commits transactionally (the scan
                # runs before any carry mutation), so the arena still
                # holds every group conn's carry intact: the group
                # exits the lane typed and serves through the scalar
                # oracle rung THIS round — real verdicts, zero byte
                # loss — while the other groups keep their columnar
                # service (lint R15's per-entry containment shape;
                # round-level _on_batch_error would instead answer
                # the whole round UNKNOWN_ERROR).
                log.exception("columnar ingest failed; engine group "
                              "falls back to the scalar rung")
                self._reasm_fallback("framing_crash")
                self._record_contained_failure("framing-crash")
                self._reasm_bail(conn_ids[sel], None)
                crash_sel.append(sel)
                continue
            if rnd.over.any():
                # Same accounting as the scalar engine rung's
                # _overflow (the oracle path owns the global metric).
                engine.buffer_overflows += int(rnd.over.sum())
            buckets = reasm.pack_buckets(
                rnd, self.config.batch_width, self._min_bucket,
                snap.src[pos[sel]],
            )
            groups.append([sel, engine, rnd, buckets, None])
        if crash_sel:
            # Crashed groups ride the round's scalar minority: carries
            # were released to the engines above, so the shared
            # classifier routes every entry slow and the finish merge
            # (rest) emits their tuples in entry order.
            crashed = np.concatenate(crash_sel)
            with self._lock:
                conns_crash = self._conns
            for k in crashed:
                bi = int(np.searchsorted(base, k, side="right")) - 1
                self._classify_entry(
                    items[bi], int(k - base[bi]), conns_crash,
                    False, responses, fast, slow, slow_conns,
                )
            rest = (
                np.concatenate((rest, crashed)) if len(rest) else crashed
            )
            e_live = e_live[~np.isin(e_live, crashed)]
        # Dirty flags written NOW, before the next round classifies
        # (same contract as the scalar lane's _tab_mark_many): residue
        # or a dead latch keeps the conn off the vec path.
        with self._lock:
            for sel, engine, rnd, _bk, _is in groups:
                cids = rnd.conn_ids
                ok = cids < self._tab_size
                dirty_new = (
                    (rnd.res_len > 0) | rnd.dead | rnd.over
                ).astype(np.uint8)
                self._tab_dirty[cids[ok]] = dirty_new[ok]
        rt.reasm_s = time.monotonic() - t_r0
        rt.formed()
        # Issue: legacy minority first (host-only work inline, device
        # futures kept), then one model call per columnar bucket.
        rules_out: dict = {}
        fast_issued = self._issue_fast(fast) if fast else []
        sbuckets, plan = self._issue_slow_async(slow, responses,
                                               rules_out)
        for grp in groups:
            _sel, engine, _rnd, buckets, _ = grp
            issued = []
            for fi, data_m, lens_b, rem in buckets:
                # lint: disable=R15 -- device faults ARE typed here: _mesh_guarded demotes and retries single-chip, and a still-raising round reaches the dispatcher's _on_batch_error, which answers every entry UNKNOWN_ERROR (the round-level containment backstop)
                _c, _m, allow, rule = self._model_call_attr(
                    engine.model, data_m, lens_b, rem
                )
                issued.append((fi, allow, rule))
            grp[4] = issued
        rt.submitted()
        futs: list = []
        for g in fast_issued:
            futs.append(g[0])
            if g[1] is not None:
                futs.append(g[1])
        n_fast_futs = len(futs)
        for bk in sbuckets:
            futs.append(bk[0])
            if bk[1] is not None:
                futs.append(bk[1])
        n_legacy_futs = len(futs)
        for _sel, _eng, _rnd, _bk, issued in groups:
            for _fi, allow, rule in issued:
                futs.append(allow)
                if rule is not None:
                    futs.append(rule)
        # In-flight registration: dict refcounts for the legacy plan
        # conns, one bulk array add for the columnar conns.
        pend = {cid for _k, _i, _sc, cid, *_ in plan}
        reasm_cids = conn_ids[e_live]
        with self._lock:
            for cid in pend:
                self._async_pending[cid] = (
                    self._async_pending.get(cid, 0) + 1
                )
            in_rng = reasm_cids[reasm_cids < self._tab_size]
            np.add.at(self._tab_async, in_rng, 1)

        def finish(vals: list | None) -> None:
            try:
                rt.completed()
                self._finish_fast(
                    fast_issued, responses,
                    vals=(
                        vals[:n_fast_futs] if vals is not None
                        else [None] * n_fast_futs
                    ),
                    rules_out=rules_out,
                )
                self._finish_slow_async(
                    sbuckets, plan, responses,
                    vals=(
                        vals[n_fast_futs:n_legacy_futs]
                        if vals is not None
                        else [None] * (n_legacy_futs - n_fast_futs)
                    ),
                    rules_out=rules_out,
                )
                try:
                    self._finish_columnar(
                        items, base, responses, groups, rest,
                        vals[n_legacy_futs:] if vals is not None
                        else [None] * (len(futs) - n_legacy_futs),
                        rt, rules_out, cached=cached_keys,
                    )
                except Exception:  # noqa: BLE001 — fail closed, typed
                    # The shim is owed exactly one reply per seq and
                    # nothing downstream will answer it: a columnar
                    # finish crash answers every covered batch typed
                    # (send() stands down per batch if a racing reply
                    # already landed).
                    log.exception(
                        "columnar finish failed; answering typed"
                    )
                    for item in items:
                        _, cl_, batch = item
                        try:
                            if cl_.send_verdicts(
                                batch.seq,
                                self._typed_entries(
                                    batch, FilterResult.UNKNOWN_ERROR
                                ),
                                batch=batch,
                            ):
                                self.error_entries += batch.count
                        except Exception:  # noqa: BLE001
                            log.exception("typed error send failed")
            finally:
                with self._lock:
                    for cid in pend:
                        n = self._async_pending.get(cid, 1) - 1
                        if n <= 0:
                            self._async_pending.pop(cid, None)
                        else:
                            self._async_pending[cid] = n
                    in_r = reasm_cids[reasm_cids < self._tab_size]
                    dec = self._tab_async[in_r]
                    self._tab_async[in_r] = np.where(dec > 0, dec - 1, 0)

        self._completion_put(("entry2", futs, finish))
        return True

    def _finish_columnar(self, items: list, base: np.ndarray,
                         responses: dict, groups: list, rest,
                         vals: list, rt, rules_out: dict,
                         cached: set | None = None) -> None:
        """Finish half of the columnar round: materialize the bucket
        readbacks, render per-entry ops/injects as array scatters,
        merge the scalar minority's tuples in entry order, and emit one
        verdict frame per wire batch — plus the round's columnar flow
        records with engine-captured epoch/kind attribution."""
        reasm = self._reasm
        n_round = int(base[-1])
        vi = 0
        finished = []  # (sel, engine, rnd, allow_f, rule_f, assembled)
        for sel, engine, rnd, _buckets, issued in groups:
            nf = rnd.frame_count()
            allow_f = np.zeros(nf, bool)
            rule_f = np.full(nf, -1, np.int32)
            for fi, allow_dev, rule_dev in issued:
                v = vals[vi] if vi < len(vals) else None
                vi += 1
                rv = None
                if rule_dev is not None:
                    rv = vals[vi] if vi < len(vals) else None
                    vi += 1
                if v is None:
                    try:
                        a = np.asarray(allow_dev)
                    except Exception:  # noqa: BLE001 — deny on error
                        log.exception("device readback failed")
                        a = None
                else:
                    a = np.asarray(v)
                if a is None:
                    continue  # frames stay denied + unattributed
                allow_f[fi] = a[: len(fi)]
                if rv is not None:
                    rule_f[fi] = np.asarray(rv)[: len(fi)]
                elif rule_dev is not None:
                    try:
                        rule_f[fi] = np.asarray(rule_dev)[: len(fi)]
                    except Exception:  # noqa: BLE001 — unattribute only
                        log.exception("rule readback failed")
            assembled = reasm.assemble(rnd, allow_f)
            finished.append((sel, engine, rnd, allow_f, rule_f,
                             assembled))
            self.fast_log.log_batch(
                getattr(engine, "proto", "r2d2"), nf,
                int(nf - int(allow_f.sum())),
            )
        # Round-wide merge: per-entry counts first, then one scatter
        # pass for ops and injects (scalar minority written per entry).
        oc_full = np.zeros(n_round, np.int64)
        res_full = np.full(n_round, int(FilterResult.OK), np.uint32)
        injo_full = np.zeros(n_round, np.int64)
        injr_full = np.zeros(n_round, np.int64)
        for sel, _eng, _rnd, _af, _rf, (op_counts, _ops, inj_len,
                                        _blob, _nd) in finished:
            oc_full[sel] = op_counts
            injr_full[sel] = inj_len
        rest_resp = []  # (round_idx, response tuple)
        for k in rest:
            bi = int(np.searchsorted(base, k, side="right")) - 1
            item = items[bi]
            r = responses[id(item)][int(k - base[bi])]
            if r is None:  # defensive: a lane bug must fail typed
                r = (int(item[2].conn_ids[int(k - base[bi])]),
                     int(FilterResult.UNKNOWN_ERROR), [], b"", b"")
            rest_resp.append((int(k), r))
            oc_full[k] = len(r[2])
            res_full[k] = r[1]
            injo_full[k] = len(r[3])
            injr_full[k] = len(r[4])
        op_dst = np.concatenate(
            ([0], np.cumsum(oc_full))
        ).astype(np.int64)
        inj_tot = injo_full + injr_full
        inj_dst = np.concatenate(
            ([0], np.cumsum(inj_tot))
        ).astype(np.int64)
        ops_round = np.zeros(int(op_dst[-1]), wire.FILTER_OP)
        inj_round = np.zeros(int(inj_dst[-1]), np.uint8)
        for sel, _eng, _rnd, _af, _rf, (op_counts, ops_g, inj_len,
                                        inj_blob, _nd) in finished:
            g_off = np.concatenate(
                ([0], np.cumsum(op_counts))
            )[:-1].astype(np.int64)
            gather_segments(ops_g, g_off, op_counts, out=ops_round,
                            dst_starts=op_dst[sel])
            gi_off = np.concatenate(
                ([0], np.cumsum(inj_len))
            )[:-1].astype(np.int64)
            gather_segments(inj_blob, gi_off, inj_len, out=inj_round,
                            dst_starts=inj_dst[sel])
        for k, r in rest_resp:
            off = int(op_dst[k])
            for j, (op, nb) in enumerate(r[2]):
                ops_round[off + j] = (int(op), int(nb))
            d = int(inj_dst[k])
            if r[3]:
                io = np.frombuffer(r[3], np.uint8)
                inj_round[d : d + len(io)] = io
                d += len(io)
            if r[4]:
                ir = np.frombuffer(r[4], np.uint8)
                inj_round[d : d + len(ir)] = ir
        rt.drained()
        # One verdict frame per wire batch, sliced from the round
        # arrays; entries whose op list exceeds the ABI capacity route
        # the whole item through the splitting tuple path.
        for bi, item in enumerate(items):
            _, client, batch = item
            a, b = int(base[bi]), int(base[bi + 1])
            try:
                if bool((oc_full[a:b] > wire.MAX_OPS_PER_ENTRY).any()):
                    entries = self._columnar_item_tuples(
                        batch, a, b, oc_full, op_dst, ops_round,
                        injo_full, injr_full, inj_dst, inj_round,
                        res_full, rest_resp,
                    )
                    client.send_verdicts(batch.seq, entries,
                                         batch=batch)
                    continue
                payload = wire.pack_verdict_batch(
                    batch.seq,
                    batch.conn_ids,
                    res_full[a:b],
                    oc_full[a:b].astype(np.uint32),
                    injo_full[a:b].astype(np.uint32),
                    injr_full[a:b].astype(np.uint32),
                    ops_round[op_dst[a] : op_dst[b]],
                    inj_round[inj_dst[a] : inj_dst[b]].tobytes(),
                )
                client.send(wire.MSG_VERDICT_BATCH, payload,
                            batches=[batch])
            except Exception:  # noqa: BLE001 — client may be gone
                log.exception("columnar verdict send failed")
        if self._round_thread_suppressed():
            return
        self.tracer.finish_round(
            rt, [self._batch_desc(it[2], it[1]) for it in items]
        )
        # Scalar-minority records ride the shared entrywise emitter
        # (columnar entries hold None responses and are skipped, and
        # cache-hit entries were already recorded on the `cached` path
        # at decision time); columnar records are one add_round per
        # engine group with the CAPTURED engine's kinds legend + epoch
        # — slot-reuse-safe exactly like the vec rounds.
        self._record_entrywise(rt.path, items, responses, rules_out,
                               cached=cached)
        if self.flowlog is None:
            return
        for _sel, engine, rnd, allow_f, rule_f, (own_oc, _ops, _il,
                                                 _ib, n_den) in finished:
            has_frames = rnd.n_frames > 0
            forwarded = rnd.live & has_frames & (n_den == 0)
            denied = (rnd.live & has_frames & (n_den > 0)) | rnd.over
            errorc = rnd.dead
            rec = forwarded | denied | errorc
            if not rec.any():
                continue
            codes = np.where(
                forwarded, CODE_FORWARDED,
                np.where(errorc, CODE_ERROR, CODE_DENIED),
            ).astype(np.int8)
            rules = np.where(
                forwarded, reasm.last_rules(rnd, rule_f), -1
            ).astype(np.int32)
            self.flowlog.add_round(
                rt.path,
                rnd.conn_ids[rec],
                codes[rec],
                rules[rec],
                kinds=getattr(engine.model, "match_kinds", ()),
                epoch=getattr(engine, "epoch", 0),
            )

    def _columnar_item_tuples(self, batch, a: int, b: int, oc_full,
                              op_dst, ops_round, injo_full, injr_full,
                              inj_dst, inj_round, res_full,
                              rest_resp) -> list:
        """Materialize one item's entries as scalar response tuples —
        the op-capacity-splitting fallback (send_verdicts owns the
        continuation-entry split; >16-op entries are rare)."""
        scalar = {k: r for k, r in rest_resp}
        entries = []
        for k in range(a, b):
            r = scalar.get(k)
            if r is not None:
                entries.append(r)
                continue
            off = int(op_dst[k])
            cnt = int(oc_full[k])
            d = int(inj_dst[k])
            io = int(injo_full[k])
            ir = int(injr_full[k])
            entries.append((
                int(batch.conn_ids[k - a]),
                int(res_full[k]),
                [(int(o["op"]), int(o["n_bytes"]))
                 for o in ops_round[off : off + cnt]],
                inj_round[d : d + io].tobytes(),
                inj_round[d + io : d + io + ir].tobytes(),
            ))
        return entries

    @staticmethod
    def _slow_async_eligible(slow: list) -> bool:
        """True when no slow entry would need a synchronous device
        readback: every entry either goes through feed_extract (CRLF
        engines, request direction), a ConstVerdict engine (host-only
        pump), or the host-only oracle parser."""
        for _key, _i, sc, _conn_id, reply, end_stream, _data in slow:
            engine = sc.engine
            if engine is None:
                continue  # oracle, host-only
            if isinstance(engine.model, ConstVerdict):
                continue  # pump() special-cases ConstVerdict host-side
            if hasattr(engine, "feed_extract") and not reply and not end_stream:
                continue  # extractable
            if reply and not getattr(engine, "handles_reply", False):
                continue  # oracle, host-only
            return False  # engine pump path would read back synchronously
        return True

    def _issue_slow_async(self, slow: list, responses: dict,
                          rules_out: dict | None = None):
        """Issue half of the async slow path: feed every extractable
        entry, collect its completed frames, batch ALL frames into one
        model call per (engine, width) bucket — futures only.  Oracle
        entries (host parsers) are computed right here.  Returns
        (buckets, plan): buckets = [(allow_dev, rule_dev, metas,
        engine)] where metas = [(plan_idx, msg, msg_len)], plan =
        per-entry records for the finish half."""
        plan = []  # (kind, key, i, sc, conn_id, frames | None)
        by_group: dict[tuple, list] = {}  # (id(engine), width) -> metas
        engines: dict[int, object] = {}
        oracle_marks = []
        for key, i, sc, conn_id, reply, end_stream, data in slow:
            engine = sc.engine
            extractable = (
                engine is not None
                and hasattr(engine, "feed_extract")
                and not isinstance(engine.model, ConstVerdict)
                and not reply
                and not end_stream
            )
            if not extractable:
                # ConstVerdict engines, oracle conns, reply, end_stream:
                # all host-only here (see _slow_async_eligible).
                responses[key][i] = self._run_slow_safe(
                    sc, conn_id, reply, end_stream, data
                )
                if rules_out is not None:
                    if engine is not None and (
                        getattr(engine, "handles_reply", False)
                        or not reply
                    ):
                        # Same routing as _run_slow: the engine decided.
                        rules_out[(key, i)] = self._engine_rule_kind(
                            engine, conn_id, sc
                        )
                    else:
                        rules_out[(key, i)] = (
                            int(sc.conn.last_rule_id), "",
                            self.policy_epoch,
                        )
                oracle_marks.append((conn_id, sc))
                continue
            conn = sc.conn
            # lint: disable=R7 -- the scalar oracle/fallback rung beside the columnar lane (reasm-ineligible minorities, greedy mode, parity oracle); the columnar path serves the volume
            frames = engine.feed_extract(
                conn_id, data, remote_id=conn.src_id,
                policy_name=conn.policy_name, ingress=conn.ingress,
                dst_id=conn.dst_id, src_addr=conn.src_addr,
                dst_addr=conn.dst_addr,
            )
            flowdebug.log(
                _flow_log, "flow %d extract: %d frame(s)",
                conn_id, len(frames),
            )
            # The MORE decision belongs to THIS entry's residue — decide
            # it now, not at finish time, when a later round may already
            # have drained or refilled the buffer.
            flow = engine.flows.get(conn_id)
            more = bool(frames) or bool(flow is not None and flow.buffer)
            rec = (key, i, sc, conn_id, engine, more, [])
            plan.append(rec)
            engines[id(engine)] = engine
            for msg, msg_len in frames:
                w = self.config.batch_width
                while msg_len > w:
                    w *= 2
                by_group.setdefault((id(engine), w), []).append(
                    (rec, msg, msg_len)
                )
        buckets = []
        for (eng_id, width), metas in sorted(by_group.items(),
                                             key=lambda kv: kv[0][1]):
            engine = engines[eng_id]
            n = len(metas)
            f_pad = self._min_bucket
            while f_pad < n:
                f_pad *= 2
            data_m = np.zeros((f_pad, width), np.uint8)
            lengths = np.zeros((f_pad,), np.int32)
            remotes = np.zeros((f_pad,), np.int32)
            for j, (rec, msg, msg_len) in enumerate(metas):
                row = np.frombuffer(engine.frame_row(msg), np.uint8)
                data_m[j, : len(row)] = row
                lengths[j] = msg_len
                remotes[j] = rec[2].conn.src_id
            _c, _m, allow, rule = self._model_call_attr(
                engine.model, data_m, lengths, remotes
            )
            # Record each frame's (bucket, slot) so the finish half can
            # emit ops in per-entry stream order.
            bi = len(buckets)
            for j, (rec, msg, msg_len) in enumerate(metas):
                rec[6].append((bi, j, msg, msg_len))
            buckets.append((allow, rule, metas, engine))
        if oracle_marks:
            self._tab_mark_many(oracle_marks)
        # Dirty flags for extract conns are written NOW, on the
        # dispatcher thread, before the next round can be classified:
        # a deferred mark would leave a stale-clean window in which a
        # vec/matrix batch re-admits a conn holding half a frame.
        # (Buffer state is final for this round — finish only drains
        # ops/inject, never buffers.)
        if plan:
            self._tab_mark_many([(rec[3], rec[2]) for rec in plan])
        return buckets, plan

    def _finish_slow_async(self, buckets: list, plan: list,
                           responses: dict, vals: list,
                           rules_out: dict | None = None) -> None:
        """Finish half: one readback per bucket (batched by the
        completion loop via ``vals`` — allow then, with attribution on,
        rule per bucket), then per-entry op emission in arrival order —
        MORE parity and inject draining identical to the wave path's
        pump()/take_ops."""
        allows = []
        ruless = []
        vi = 0
        for allow_dev, rule_dev, metas, _engine in buckets:
            v = vals[vi] if vi < len(vals) else None
            vi += 1
            rv = None
            if rule_dev is not None:
                rv = vals[vi] if vi < len(vals) else None
                vi += 1
            if v is None:
                try:
                    allows.append(np.asarray(allow_dev))
                except Exception:  # noqa: BLE001 — deny on device error
                    log.exception("device readback failed")
                    allows.append(np.zeros(len(metas), bool))
                    ruless.append(np.full(len(metas), -1, np.int32))
                    continue
            else:
                allows.append(np.asarray(v))
            if rv is not None:
                ruless.append(np.asarray(rv))
            elif rule_dev is not None:
                try:
                    ruless.append(np.asarray(rule_dev))
                except Exception:  # noqa: BLE001
                    ruless.append(np.full(len(metas), -1, np.int32))
            else:
                ruless.append(np.full(len(metas), -1, np.int32))
        for key, i, sc, conn_id, engine, more, slots in plan:
            try:
                # lint: disable=R7 -- scalar rung finish half (see _issue_slow_async): per-entry settle survives as the oracle beside the columnar lane
                ops, inject = engine.settle_entry(
                    conn_id,
                    [
                        (msg, msg_len, bool(allows[bi][j]),
                         int(ruless[bi][j]))
                        for bi, j, msg, msg_len in slots
                    ],
                    more,
                )
            except Exception:  # noqa: BLE001 — per-entry containment
                # The flow can be GONE by finish time: a quarantine
                # demotion (_demote_to_oracle pops engine.flows) or a
                # close raced this deferred completion — typically on a
                # deposed round whose seq the SHED reply already
                # answered.  One gone conn must not abort the rest of
                # the round's drain (their ops would leak into the next
                # round's take_ops); this entry fails closed typed.
                log.exception(
                    "async settle failed (conn %d)", conn_id
                )
                self.error_entries += 1
                responses[key][i] = (
                    conn_id, int(FilterResult.UNKNOWN_ERROR), [], b"", b"",
                )
                continue
            responses[key][i] = self._entry_response(
                conn_id, ops, b"", inject
            )
            if rules_out is not None:
                # Captured against the PLAN's engine (snapshotted at
                # issue time), never a re-read sc.engine: this finish
                # may run after a swap already rebound the conn.
                rules_out[(key, i)] = self._engine_rule_kind(
                    engine, conn_id, sc
                )

    def _issue_fast(self, fast: list) -> list:
        """Vectorized single-frame path, issue half: entries grouped
        per engine, one device call per group, futures kept — no
        readback here.  Returns [(allow_dev, rule_dev, recs)] (rule
        None without attribution)."""
        # Capture each record's engine ONCE at grouping: policy_update
        # rebinds sc.engine concurrently, and a re-read after grouping
        # could judge the group with a different engine's model.
        groups: dict[int, tuple] = {}
        for rec in fast:
            eng = rec[2].engine
            groups.setdefault(id(eng), (eng, []))[1].append(rec)
        issued = []
        for engine, recs in groups.values():
            n = len(recs)
            width = self.config.batch_width
            f_pad = self._min_bucket  # bucketed shapes, no jit churn
            while f_pad < n:
                f_pad *= 2
            data = np.zeros((f_pad, width), np.uint8)
            lengths = np.zeros((f_pad,), np.int32)
            remotes = np.zeros((f_pad,), np.int32)
            for i, (_, _, sc, _, payload) in enumerate(recs):
                arr = np.frombuffer(payload, np.uint8)
                data[i, : len(arr)] = arr
                lengths[i] = len(arr)
                remotes[i] = sc.conn.src_id
            complete, msg_len, allow, rule = self._model_call_attr(
                engine.model, data, lengths, remotes
            )
            issued.append((allow, rule, recs, engine))
        return issued

    def _finish_fast(self, issued: list, responses: dict,
                     vals: list | None = None,
                     rules_out: dict | None = None) -> None:
        """Readback + per-entry response build for _issue_fast groups.
        ``vals`` carries pre-fetched values (completion-loop batched
        device_get — allow then, with attribution on, rule per group);
        None entries mean the readback failed → deny.  ``rules_out``
        collects each entry's (deciding rule, match kind) keyed
        (item_key, entry_idx) for flow-record emission — the kind is
        resolved against the engine CAPTURED at judge time, not a
        re-read sc.engine (policy_update rebinds it concurrently and
        the rule row indexes the judging model's tables)."""
        vi = 0
        for allow_dev, rule_dev, recs, engine in issued:
            n = len(recs)
            rules = None
            if vals is not None:
                v = vals[vi]
                vi += 1
                rv = None
                if rule_dev is not None:
                    rv = vals[vi]
                    vi += 1
                allow = (
                    np.zeros(n, bool) if v is None else np.asarray(v)[:n]
                )
                # Unattribute when the ALLOW readback failed: the
                # entries were forced to deny, and stamping them with
                # the device's (allowing) rule would label a deny with
                # the rule that allowed it — mirror _readback_chunks.
                if rv is not None and v is not None:
                    rules = np.asarray(rv)[:n]
            else:
                try:
                    allow = np.asarray(allow_dev)[:n]
                    if rule_dev is not None:
                        rules = np.asarray(rule_dev)[:n]
                except Exception:  # noqa: BLE001 — deny on device error
                    log.exception("device readback failed")
                    allow = np.zeros(n, bool)
                    rules = None
            denied = int(n - allow.sum())
            self.fast_log.log_batch(
                getattr(engine, "proto", "r2d2"), n, denied
            )
            for i, (key, idx, sc, conn_id, payload) in enumerate(recs):
                if allow[i]:
                    ops = [(int(PASS), len(payload)), (int(MORE), 1)]
                    inj = b""
                else:
                    ops = [(int(DROP), len(payload)), (int(MORE), 1)]
                    inj = getattr(engine, "DENY_INJECT", b"ERROR\r\n")
                if rules_out is not None:
                    r_i = int(rules[i]) if rules is not None else -1
                    rules_out[(key, idx)] = (
                        r_i, self._kind_for(engine.model, r_i),
                        getattr(engine, "epoch", 0),
                    )
                responses[key][idx] = (
                    conn_id,
                    int(FilterResult.OK),
                    ops,
                    b"",
                    inj,
                )

    def _run_fast(self, fast: list, responses: dict,
                  rules_out: dict | None = None) -> None:
        """Synchronous fast path (inline mode): issue + finish."""
        self._finish_fast(self._issue_fast(fast), responses,
                          rules_out=rules_out)

    def _run_slow_batched(self, slow: list, responses: dict,
                          rules_out: dict | None = None) -> None:
        """Engine-backed slow entries are processed in WAVES: the nth
        entry of every connection is fed together and each engine is
        pumped ONCE per wave — a round's worth of frames (http/
        cassandra/memcached heads across every flow) is judged in one
        device batch per wave instead of one device call per entry,
        while per-connection order and per-entry op attribution are
        preserved (each conn contributes at most one entry per wave, so
        take_ops drains exactly that entry's ops).

        Oracle-path conns and end_stream entries keep the strict
        per-entry pipeline; once a connection has taken that path in
        this round, its later entries follow it (order)."""
        waves: list[list] = []
        wave_of: dict[int, int] = {}
        tainted: set[int] = set()
        leftovers: list = []
        for rec in slow:
            key, i, sc, conn_id, reply, end_stream, data = rec
            engine = sc.engine
            batchable = (
                engine is not None
                and not end_stream
                and conn_id not in tainted
                and (getattr(engine, "handles_reply", False) or not reply)
            )
            if not batchable:
                tainted.add(conn_id)
                leftovers.append(rec)
                continue
            w = wave_of.get(conn_id, 0)
            wave_of[conn_id] = w + 1
            while len(waves) <= w:
                waves.append([])
            # Engine snapshotted ONCE per record: policy_update rebinds
            # sc.engine concurrently, and feed/take must hit the same one.
            waves[w].append((rec, engine))

        for wave in waves:
            engines: dict[int, object] = {}
            failed: set[int] = set()
            for (key, i, sc, conn_id, reply, end_stream, data), engine in wave:
                self._feed_engine(engine, sc, conn_id, reply, data)
                engines[id(engine)] = engine
            for eid, engine in engines.items():
                try:
                    engine.pump()
                except Exception as exc:  # noqa: BLE001 — contain per engine
                    log.exception("engine pump failed")
                    self._record_contained_failure(
                        f"pump-crash: {type(exc).__name__}"
                    )
                    failed.add(eid)
            for (key, i, sc, conn_id, reply, end_stream, data), engine in wave:
                if id(engine) in failed:
                    self.error_entries += 1
                    responses[key][i] = (
                        conn_id, int(FilterResult.UNKNOWN_ERROR), [], b"", b"",
                    )
                else:
                    responses[key][i] = self._take_engine(
                        engine, conn_id, reply
                    )
                    if rules_out is not None:
                        # Attribution captured NOW, against the engine
                        # that judged the wave: churn may rebind
                        # sc.engine (and reuse its table slot) before
                        # record emission runs.
                        rules_out[(key, i)] = self._engine_rule_kind(
                            engine, conn_id, sc
                        )
                self._tab_mark(conn_id, sc)
        for rec in leftovers:
            key, i, sc, conn_id, reply, end_stream, data = rec
            responses[key][i] = self._run_slow_safe(
                sc, conn_id, reply, end_stream, data
            )
            if rules_out is not None:
                rules_out[(key, i)] = (
                    int(sc.conn.last_rule_id), "", self.policy_epoch,
                )
            self._tab_mark(conn_id, sc)

    @staticmethod
    def _feed_engine(engine, sc: "_SidecarConn", conn_id: int, reply: bool,
                     data: bytes) -> None:
        """One entry into an engine — the single definition of the feed
        kwargs contract, shared by the wave-batched and per-entry paths
        (they must never drift: both serve entries of the same conns)."""
        conn = sc.conn
        if getattr(engine, "handles_reply", False):
            engine.feed(
                conn_id, data, reply=reply, remote_id=conn.src_id,
                policy_name=conn.policy_name, dst_id=conn.dst_id,
                src_addr=conn.src_addr, dst_addr=conn.dst_addr,
            )
        else:
            engine.feed(
                conn_id, data, remote_id=conn.src_id,
                policy_name=conn.policy_name, ingress=conn.ingress,
                dst_id=conn.dst_id, src_addr=conn.src_addr,
                dst_addr=conn.dst_addr,
            )

    @staticmethod
    def _take_engine(engine, conn_id: int, reply: bool):
        """Drain one entry's ops into the response-tuple shape (shared
        by the wave-batched and per-entry paths)."""
        if getattr(engine, "handles_reply", False):
            ops, inj_o, inj_r = engine.take_ops(conn_id, reply)
        else:
            ops, inject = engine.take_ops(conn_id)
            inj_o, inj_r = b"", inject
        return VerdictService._entry_response(conn_id, ops, inj_o, inj_r)

    @staticmethod
    def _entry_response(conn_id: int, ops, inj_o: bytes, inj_r: bytes):
        """THE per-entry response tuple — the one definition shared by
        the wave path (_take_engine) and the async path
        (_finish_slow_async); they must never drift."""
        return (
            conn_id,
            int(FilterResult.OK),
            [(int(op), int(nn)) for op, nn in ops],
            inj_o,
            inj_r,
        )

    def _run_slow_safe(self, sc: _SidecarConn, conn_id: int, reply: bool,
                       end_stream: bool, data: bytes):
        """Per-entry crash containment: one entry's failure yields a
        typed error verdict for THAT entry instead of crashing the whole
        round (the dispatcher's on_batch_error remains the backstop)."""
        try:
            return self._run_slow(sc, conn_id, reply, end_stream, data)
        except Exception:  # noqa: BLE001
            log.exception("entry processing failed (conn %d)", conn_id)
            self.error_entries += 1
            return (conn_id, int(FilterResult.UNKNOWN_ERROR), [], b"", b"")

    def _run_slow(self, sc: _SidecarConn, conn_id: int, reply: bool,
                  end_stream: bool, data: bytes):
        """Stateful path: request direction through the batch engine when
        available, otherwise the in-process oracle parser."""
        # One engine snapshot for the whole entry: policy_update rebinds
        # sc.engine from a reader thread, and a mid-entry swap would
        # feed one engine but take_ops from another (empty) one.
        engine = sc.engine
        if engine is not None and (
            getattr(engine, "handles_reply", False) or not reply
        ):
            self._feed_engine(engine, sc, conn_id, reply, data)
            engine.pump()
            return self._take_engine(engine, conn_id, reply)

        # Oracle path: mirror the datapath buffer, loop while the parser
        # fills the op array (reference: cilium_proxylib.cc:301 do-while).
        buf = sc.bufs[reply]
        cap = self.config.max_flow_buffer
        if cap and len(buf) + len(data) > cap:
            # Bounded retained-data contract: a flow buffering past the
            # cap without a frame boundary gets a typed protocol-error
            # DROP of everything retained + incoming, and dies.  Result
            # stays OK so the shim APPLIES the DROP (consuming its
            # retained bytes) before the ERROR op surfaces PARSER_ERROR.
            dropped = len(buf) + len(data)
            buf.clear()
            metrics.FlowBufferOverflows.inc(sc.conn.parser_name)
            return (
                conn_id,
                int(FilterResult.OK),
                [
                    (int(DROP), dropped),
                    (int(ERROR), int(OpError.ERROR_INVALID_FRAME_LENGTH)),
                ],
                b"",
                b"",
            )
        buf += data
        all_ops: list[tuple[int, int]] = []
        result = FilterResult.OK
        # Loop while the parser fills the op array AND makes progress:
        # a full op array means more complete frames may still be
        # buffered, and a quiescent peer would never trigger another
        # pass, so draining must not be capped at a fixed iteration
        # count (tail frames would stall indefinitely).
        #
        # Each pass hands the parser a bounded WINDOW of the backlog
        # instead of the whole buffer: parsers re-join their input per
        # invocation, so feeding the full backlog every pass is
        # quadratic on large bursts.  A MORE emitted while bytes were
        # withheld by the window is an artifact — the window grows (or
        # the next pass continues after consumption) instead of
        # surfacing it.
        window = 1 << 16
        while True:
            avail = len(buf)
            windowed = avail > window
            chunk = bytes(memoryview(buf)[:window]) if windowed else bytes(buf)
            ops: list = []
            # end_stream only reaches the parser once the window covers
            # the whole backlog — withheld bytes mean the stream has not
            # actually ended from the parser's point of view.
            result = sc.conn.on_data(
                reply, end_stream and not windowed, [chunk], ops
            )
            consumed = 0
            progress = False
            deferred_more = False
            for op, nbytes in ops:
                if op == MORE and windowed:
                    deferred_more = True
                    continue
                all_ops.append((int(op), int(nbytes)))
                if op in (PASS, DROP):
                    take = min(nbytes, avail - consumed)
                    consumed += take
                    sc.skip[reply] += nbytes - take
                    if take:
                        progress = True
            if consumed:
                del buf[:consumed]
            if result != FilterResult.OK:
                break
            if deferred_more:
                if not progress:
                    window *= 2  # frame larger than the window
                continue
            if len(ops) < wire.MAX_OPS_PER_ENTRY:
                break
            if not progress:
                break
        inj_orig = sc.conn.orig_buf.take()
        inj_reply = sc.conn.reply_buf.take()
        return (conn_id, int(result), all_ops, inj_orig, inj_reply)


def _matrix_to_batch(mb: wire.MatrixBatch) -> wire.DataBatch:
    """Fallback conversion for matrix batches that miss the vectorized
    path: unpad rows into a variable-length DataBatch."""
    parts = [
        mb.rows[i, : int(mb.lengths[i])].tobytes() for i in range(mb.count)
    ]
    batch = wire.DataBatch(
        mb.seq,
        mb.conn_ids,
        np.zeros(mb.count, np.uint8),
        mb.lengths,
        b"".join(parts),
    )
    # Alias the answered cell: real-verdict sends mark the conversion,
    # but the dispatcher's _current_batch (what a deposal/crash sweep
    # iterates) still holds the ORIGINAL mat item — a separate flag
    # would let the sweep double-reply a seq the round already served.
    batch._acell = mb._acell
    batch.deadline = mb.deadline
    batch.arrival = mb.arrival
    batch.ring_wait = mb.ring_wait
    return batch


def _death_reason_for(e: OSError) -> str:
    """Typed session-death reason for a failed reply write: a sendall
    bounded by SO_SNDTIMEO surfaces EAGAIN (BlockingIOError) when the
    peer stopped reading, or socket.timeout on some platforms — both
    are the stalled-reader signature; anything else is a broken
    stream.  One definition so every _kill site types identically."""
    return (
        DEATH_SEND_TIMEOUT
        if isinstance(e, (socket.timeout, BlockingIOError))
        else DEATH_WRITE_FAILED
    )


class _ClientHandler:
    """Reader thread + serialized writer for one shim socket."""

    def __init__(self, service: VerdictService, sock: socket.socket):
        self.service = service
        self.sock = sock
        self._wlock = threading.Lock()
        self.module_id = 0
        # Fan-in session state (transport.SessionState): the unit of
        # fault isolation — admission quotas, quarantine latch, and
        # the per-session exactly-once counters all live here.
        self.session = service._new_session()
        # Shared-memory fast path for this session (transport.ShmPeer),
        # attached via MSG_SHM_ATTACH.  Data drains run on this
        # handler's reader thread (SPSC consumer); verdict pushes are
        # serialized under _wlock (SPSC producer).  A detached peer is
        # retained for status: its fallback counters and quarantine
        # reason outlive the rings (operators read them AFTER a fault).
        self.shm: ShmPeer | None = None
        self.shm_detached: ShmPeer | None = None
        # Verdict-cache opt-in (MSG_CACHE_ENABLE): the service never
        # sends MSG_CACHE_GRANT/REVOKE frames to a shim that did not
        # announce support — the native shim's dispatch table stays
        # untouched.
        self.cache_ok = False
        # Kernel send timeout (send only — settimeout would also bound
        # the reader's recv): a shim that stopped READING wedges
        # sendall while this handler's _wlock is held, and every later
        # replier for this client — including the stall watchdog's
        # deposal shed sweep — blocks behind it unboundedly, disabling
        # stall containment service-wide.  With the bound, the wedged
        # write errors out, releases the lock, and the handler is torn
        # down (_kill) — one dead peer costs its own connection, never
        # the watchdog.
        timeout_s = service.guard.timeout_s or 10.0
        try:
            sock.setsockopt(
                socket.SOL_SOCKET, socket.SO_SNDTIMEO,
                struct.pack("ll", int(timeout_s),
                            int((timeout_s % 1.0) * 1e6)),
            )
        except OSError:  # pragma: no cover — platform without SNDTIMEO
            pass

    def _kill(self, reason: str = DEATH_WRITE_FAILED) -> None:
        """Tear the socket down after a failed/timed-out write: the
        frame may be half-written, so the stream is unusable — a peer
        still reading it would desync.  shutdown() wakes the reader
        thread (which owns the close) and makes every later write fail
        fast; the shim sees EOF and fails over/reconnects.  The kill
        is typed on the session (send_timeout = the shim stopped
        reading and SO_SNDTIMEO fired — ONE session's cost, never the
        watchdog's): the reader's teardown path keeps the first
        recorded reason."""
        if self.session.death_reason is None:
            self.session.death_reason = reason
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass

    # -- shm transport (service half) -------------------------------------

    def transport_status(self) -> dict:
        shm = self.shm or self.shm_detached
        base = {
            "session": self.session.id,
            "identity": self.session.identity,
        }
        if shm is None:
            return {**base, "mode": TRANSPORT_SOCKET}
        return {**base, **shm.status()}

    def _transport_reject(self, reason: str) -> None:
        svc = self.service
        svc.transport_rejects[reason] = (
            svc.transport_rejects.get(reason, 0) + 1
        )
        metrics.SidecarTransportFallback.inc(reason)

    def _shm_attach(self, payload: bytes) -> dict:
        """Negotiate the shared-memory fast path: validate generation/
        magic/geometry and map the client's segments.  Every failure is
        a TYPED rejection — the client stays on the socket rung and the
        session keeps serving (fallback serves)."""
        rep = {
            "status": int(FilterResult.OK),
            "generation": 0,
            "error": "",
            # Segment lease: how long the service waits after this
            # session dies WITHOUT an MSG_SHM_DETACH before unlinking
            # the segments itself (the abrupt-death leak guard).
            "lease_s": self.service.config.shm_lease_s,
        }
        if not self.service.config.shm_transport:
            rep["status"] = int(FilterResult.UNKNOWN_ERROR)
            rep["error"] = "shm transport disabled by service config"
            self._transport_reject(REASON_DISABLED)
            return rep
        try:
            req = json.loads(payload.decode())
            peer = ShmPeer.attach({
                "generation": req["generation"],
                "data": req["data"],
                "verdict": req["verdict"],
            })
        except GenerationMismatch as e:
            # Stale/corrupt segment: its embedded generation (or magic/
            # geometry) contradicts the negotiated one.
            rep["status"] = int(FilterResult.UNKNOWN_ERROR)
            rep["error"] = str(e)
            self._transport_reject(REASON_GENERATION)
            return rep
        except RingError as e:
            rep["status"] = int(FilterResult.UNKNOWN_ERROR)
            rep["error"] = str(e)
            self._transport_reject(REASON_ATTACH_REJECTED)
            return rep
        except Exception as e:  # noqa: BLE001 — malformed request
            log.exception("shm attach failed")
            rep["status"] = int(FilterResult.UNKNOWN_ERROR)
            rep["error"] = f"{type(e).__name__}: {e}"
            self._transport_reject(REASON_ATTACH_REJECTED)
            return rep
        old, self.shm = self.shm, peer
        if old is not None:
            old.close()
        rep["generation"] = peer.generation
        # Transport promoted: timeline mark + re-arm the postmortem
        # latch (a successful attach is the heal for shm demotion).
        self.service.recorder.record_mark(
            "shm_attach", session=self.session.id
        )
        log.info(
            "shm transport attached (generation %d, %d data slots)",
            peer.generation, peer.data.slots,
        )
        return rep

    def _shm_detach(self, generation: int) -> None:
        shm = self.shm
        if shm is None or shm.generation != generation:
            return
        self.shm = None
        self.shm_detached = shm
        shm.close()

    def _shm_doorbell(self, payload: bytes, reader) -> None:
        """Drain the data ring through the doorbelled tail (reader
        thread = SPSC consumer), stamp ring-stage timing, and credit
        the freed slots back.  A torn slot quarantines the ring and
        demotes the session — typed, never a hang, never silent."""
        shm = self.shm
        if shm is None:
            # lint: disable=R14 -- a doorbell is a wakeup, not an entry: with no attached session nothing was admitted here, and detach/quarantine sweeps already answered any ring frames typed on the shim side
            return
        generation, data_tail, verdict_head = wire.unpack_shm_doorbell(
            payload
        )
        if generation != shm.generation:
            # lint: disable=R14 -- stale doorbell from a superseded session: its ring is destroyed and the shim's demotion sweep answered every undelivered seq typed before re-attaching; nothing is admitted on this path
            return
        if verdict_head > shm.v_credit_head:
            shm.v_credit_head = verdict_head
        target = data_tail
        while shm.active:
            records = []
            fault = False
            try:
                while shm.head < target:
                    msg_type, frame, t_commit = shm.data.read(shm.head)
                    if msg_type not in (
                        wire.MSG_DATA_BATCH,
                        wire.MSG_DATA_BATCH_DL,
                        wire.MSG_DATA_MATRIX,
                    ):
                        # lint: disable=R15 -- this raise IS the drain's typed exit: the RingError handler latches fault, frames drained before it are still submitted, and _shm_quarantine answers with a quarantined credit (the shim sheds never-admitted frames typed itself)
                        raise RingError(
                            f"unexpected data-ring frame type {msg_type}"
                        )
                    shm.head += 1
                    shm.data.set_head(shm.head)
                    records.append((self._parse_data(msg_type, frame),
                                    t_commit))
            except RingError:
                log.exception("data ring fault; quarantining shm session")
                fault = True
            # Frames drained BEFORE a torn slot are admitted work and
            # must be submitted: the quarantined credit's data_head is
            # this boundary, and the shim skips shedding everything
            # below it on the promise that real verdicts (socket frames
            # after the quarantine) are coming.  Discarding them here
            # would strand their callers against that promise — silent
            # loss by timeout.
            if records:
                self._shm_submit_records(shm, records, reader)
            if fault:
                self._shm_quarantine()
                return
            if not records:
                return
            # Tail-mirror recheck: frames published while this drain
            # (or its inline round) ran are picked up NOW instead of
            # waiting out a credit → re-doorbell round trip (the
            # notification bubble measured ~1ms of p99 at 100k/s).
            # The mirror is stored AFTER each slot's commit word, so
            # everything below it passes the same torn-slot check; a
            # doorbell is then purely a wakeup, never load-bearing.
            fresh = shm.data.tail
            if fresh <= shm.head:
                return
            target = fresh

    def _shm_submit_records(self, shm: ShmPeer, records: list,
                            reader) -> None:
        """Stamp and submit one drained run: ring-stage timing anchored
        at slot commit, one dispatcher admission, and the drain credit
        (suppressed when the round already emitted one — greedy-mode
        cut-through processes inline and its verdict-ring write sends a
        credit carrying the advanced head; the redundant frame measured
        ~60µs of p50 on the per-RPC seam)."""
        shm.counters.doorbell(len(records))
        now = time.monotonic()
        for (_kind, batch), t_commit in records:
            shm.counters.data_frames += 1
            wait = max(now - t_commit, 0.0) if t_commit else 0.0
            batch.ring_wait = wait
            if t_commit:
                # Anchor arrival (and any deadline budget) at slot
                # commit, not at drain: queue-age shedding and the
                # latency decomposition must see the ring wait.
                batch.arrival = t_commit
                if batch.deadline is not None:
                    batch.deadline -= wait
        credits_before = shm.counters.credits
        self.service.submit_ring(
            self, [rec for rec, _t in records],
            reader_backlog=reader.pending,
        )
        if shm.counters.credits == credits_before:
            self._send_credit()

    def _shm_quarantine(self, reason: str = REASON_TORN_SLOT) -> None:
        """Ring fault containment: latch THIS session off the shm rung
        and tell the shim with a quarantined credit.  The shim demotes
        to the socket transport and answers never-admitted ring frames
        typed itself (zero silent loss); this handler and all its
        flows keep serving over the socket — no other session is
        touched.

        Latch AND credit happen under _wlock: a verdict emitter is
        either fully done (its ring write is covered by this credit's
        vtail, so the shim drains it before demoting) or has not
        checked ``active`` yet (and will route to the socket).  A
        latch outside the lock could let a ring write land AFTER the
        quarantined credit — stranded in a ring the shim already
        destroyed, a silently lost verdict."""
        shm = self.shm
        if shm is None:
            return
        with self._wlock:
            if not shm.quarantine(reason):
                return
            self.service.recorder.record_mark(
                "shm_demotion", reason=reason, session=self.session.id
            )
            try:
                # lint: disable=R2 -- the quarantined credit must serialize with verdict-ring writes under this handler's write lock (see docstring); SO_SNDTIMEO bounds a wedge
                self._send_credit_locked(CREDIT_FLAG_QUARANTINED)
            except OSError as e:
                self._kill(_death_reason_for(e))

    def _send_credit(self, flags: int = 0) -> None:
        with self._wlock:
            if self.shm is None:
                return
            try:
                # lint: disable=R2 -- credit frames must serialize with verdict-ring writes under this handler's write lock (same contract as send()); SO_SNDTIMEO bounds a wedged peer
                self._send_credit_locked(flags)
            except OSError as e:
                self._kill(_death_reason_for(e))

    def _send_credit_locked(self, flags: int = 0) -> None:
        shm = self.shm
        shm.counters.credits += 1
        wire.send_msg(
            self.sock,
            wire.MSG_SHM_CREDIT,
            wire.pack_shm_credit(
                shm.generation, flags, shm.head, shm.verdict.tail
            ),
        )

    def _emit_frames_locked(self, msg_type: int,
                            payloads: list[bytes]) -> None:
        """Write frames to the client (write lock held; caller owns
        OSError containment).  Verdict frames ride the shm verdict
        ring — ONE credit frame wakes the shim for the whole round —
        when a session is attached and has room; anything else, and
        every ring-refused frame, goes out as a socket frame."""
        shm = self.shm
        rest = payloads
        if (
            shm is not None
            and shm.active
            and msg_type in (wire.MSG_VERDICT_BATCH,
                             wire.MSG_VERDICT_MULTI)
        ):
            rest = []
            pushed = 0
            for p in payloads:
                if not shm.verdict.fits(len(p)):
                    shm.counters.fallback(REASON_OVERSIZE)
                    shm.oversize_run += 1
                    rest.append(p)
                elif shm.verdict.try_push(msg_type, p,
                                          shm.v_credit_head):
                    pushed += 1
                    shm.oversize_run = 0
                else:
                    shm.counters.fallback(REASON_VERDICT_RING_FULL)
                    rest.append(p)
            if pushed:
                shm.counters.verdict_frames += pushed
                self._send_credit_locked()
            spree = self.service.config.shm_oversize_spree
            if spree and shm.oversize_run >= spree and shm.active:
                # Every frame this session produces misses the ring:
                # the per-frame fit check is pure overhead.  Demote
                # THIS session's shm rung typed (we already hold
                # _wlock — same latch-and-credit ordering contract as
                # _shm_quarantine).
                if shm.quarantine(REASON_OVERSIZE_SPREE):
                    self.service.recorder.record_mark(
                        "shm_demotion",
                        reason=REASON_OVERSIZE_SPREE,
                        session=self.session.id,
                    )
                    try:
                        # lint: disable=R2 -- quarantined credit under the held handler write lock, same contract as _shm_quarantine
                        self._send_credit_locked(CREDIT_FLAG_QUARANTINED)
                    except OSError as e:
                        self._kill(_death_reason_for(e))
        if rest:
            self.sock.sendall(
                b"".join(
                    wire.HEADER.pack(wire.MAGIC, msg_type, len(p)) + p
                    for p in rest
                )
            )

    def _suppressed(self) -> bool:
        """True on a thread whose round the stall watchdog shed (the
        stuck worker/cut-through reader itself, or the send loop
        emitting a record that round queued) and on a deposed worker —
        the batch already received typed shed verdicts, so a late send
        (after the stall clears) would duplicate/interleave replies."""
        disp = self.service.dispatcher
        return disp.thread_is_deposed() or disp.thread_round_is_shed()

    def send(self, msg_type: int, payload: bytes, batches=None) -> bool:
        """Returns True only when THIS call answered the covered
        seq(s) — it marked the batches and attempted the write (an
        OSError to a gone client still counts: there is no one left to
        shed to).  False means the call stood down without writing:
        round/generation-suppressed, or a racing reply already
        answered.  Fail-closed repliers key their shed/error COUNTERS
        on this — counting a stood-down reply would double-book an
        entry as both served and shed.  ``batches``: the wire batches
        this payload answers.  They are marked ``answered`` ATOMICALLY
        under the write lock BEFORE the write, so a fail-closed
        replier (shed/crash containment) racing a real-verdict send —
        including one currently wedged inside this very sendall, which
        is exactly what trips the stall watchdog — can never add a
        second reply for a seq the shim will consume.  ANY batch
        already answered stands the whole payload down: a packed
        multi-seq payload cannot be split, and a deposal sweep that
        got to one of its batches first will (or did) answer the
        siblings typed too — writing anyway would double-reply the
        answered seq."""
        if self._suppressed():
            return False
        with self._wlock:
            if batches:
                if any(b.answered for b in batches):
                    return False  # a racing reply already answered
                for b in batches:
                    b.answered = True
                # THE per-session answered count: the marking site is
                # the single point every typed reply (verdict, SHED,
                # error; ring or socket) passes exactly once, so the
                # fan-in exactly-once surface (submitted == answered
                # after quiesce) is counted where it is enforced.
                self.session.answered += sum(
                    getattr(b, "count", 0) for b in batches
                )
            try:
                # lint: disable=R2 -- _wlock IS the sendall serializer (the answered-flag dance requires it); a wedged write trips the stall watchdog and _kill breaks the socket
                self._emit_frames_locked(msg_type, [payload])
            except OSError as e:
                self._kill(_death_reason_for(e))
        return True

    def send_frames(self, msg_type: int, payloads: list[bytes],
                    batches=None) -> bool:
        """One sendall for a round's worth of frames to this client;
        ``batches`` parallels ``payloads``.  Same contract as send(),
        per frame: a frame whose batch was already answered is dropped
        under the write lock, the rest are marked answered before the
        write; True only when this call answered at least one frame."""
        if self._suppressed():
            return False
        with self._wlock:
            if batches is not None:
                keep = [
                    i for i, b in enumerate(batches) if not b.answered
                ]
                if not keep:
                    return False  # every frame lost its race: stand down
                for i in keep:
                    batches[i].answered = True
                # Same per-session answered count as send(): only the
                # frames THIS call actually answered.
                self.session.answered += sum(
                    getattr(batches[i], "count", 0) for i in keep
                )
                if len(keep) != len(payloads):
                    payloads = [payloads[i] for i in keep]
            try:
                # lint: disable=R2 -- same contract as send(): _wlock serializes the one-sendall round write; watchdog+_kill bound a wedge
                self._emit_frames_locked(msg_type, payloads)
            except OSError as e:
                self._kill(_death_reason_for(e))
        return True

    def send_verdicts(self, seq: int, entries: list, batch=None) -> bool:
        """entries: (conn_id, result, ops, inject_orig, inject_reply) —
        op lists longer than the ABI capacity split into continuation
        entries (reference: 16-op OnIO array, cilium_proxylib.cc:199).
        Same contract as send(); ``batch`` is the wire batch this
        reply answers."""
        conn_ids, results, op_counts = [], [], []
        inj_o, inj_r = [], []
        flat_ops: list[tuple[int, int]] = []
        blob = bytearray()
        for conn_id, result, ops, io, ir in entries:
            chunks = [
                ops[k : k + wire.MAX_OPS_PER_ENTRY]
                for k in range(0, len(ops), wire.MAX_OPS_PER_ENTRY)
            ] or [[]]
            for ci, chunk in enumerate(chunks):
                last = ci == len(chunks) - 1
                conn_ids.append(conn_id)
                results.append(result)
                op_counts.append(len(chunk))
                flat_ops.extend(chunk)
                if last:
                    inj_o.append(len(io))
                    inj_r.append(len(ir))
                    blob += io
                    blob += ir
                else:
                    inj_o.append(0)
                    inj_r.append(0)
        ops_arr = np.zeros((len(flat_ops),), wire.FILTER_OP)
        if flat_ops:
            ops_arr["op"] = [o for o, _ in flat_ops]
            ops_arr["n_bytes"] = [n for _, n in flat_ops]
        return self.send(
            wire.MSG_VERDICT_BATCH,
            wire.pack_verdict_batch(
                seq, conn_ids, results, op_counts, inj_o, inj_r,
                ops_arr, bytes(blob),
            ),
            batches=None if batch is None else [batch],
        )

    @staticmethod
    def _parse_data(msg_type: int, payload: bytes):
        if msg_type == wire.MSG_DATA_BATCH:
            return ("data", wire.unpack_data_batch(payload))
        if msg_type == wire.MSG_DATA_BATCH_DL:
            budget_s, batch = wire.unpack_data_batch_dl(payload)
            # Anchor the relative budget to this host's monotonic clock
            # at receive: entries still queued past it are shed typed.
            batch.deadline = time.monotonic() + budget_s
            return ("data", batch)
        return ("mat", wire.unpack_data_matrix(payload))

    def read_loop(self) -> None:
        reader = wire.BufferedReader(self.sock)
        svc = self.service
        try:
            while True:
                msg_type, payload = reader.recv_msg()
                if msg_type in (
                    wire.MSG_DATA_BATCH,
                    wire.MSG_DATA_BATCH_DL,
                    wire.MSG_DATA_MATRIX,
                ):
                    kind, batch = self._parse_data(msg_type, payload)
                    # Backlog probe: bytes already buffered behind this
                    # frame mean the reader is behind — route to the
                    # dispatcher so the worker aggregates the backlog
                    # into one device round.  An idle stream cuts
                    # through (processed right here, no handoff).
                    backlogged = reader.pending
                    if kind == "data":
                        svc.submit_data(self, batch, backlogged=backlogged)
                    else:
                        svc.submit_matrix(self, batch, backlogged=backlogged)
                elif msg_type == wire.MSG_SHM_DOORBELL:
                    self._shm_doorbell(payload, reader)
                elif msg_type == wire.MSG_SHM_ATTACH:
                    self.send(
                        wire.MSG_SHM_ATTACH_REPLY,
                        json.dumps(self._shm_attach(payload)).encode(),
                    )
                elif msg_type == wire.MSG_SHM_DETACH:
                    gen, dflags = wire.unpack_shm_detach(payload)
                    self._shm_detach(gen)
                    if not dflags & wire.DETACH_FLAG_NO_ACK:
                        self.send(
                            wire.MSG_ACK,
                            wire.pack_ack(int(FilterResult.OK)),
                        )
                elif msg_type == wire.MSG_SESSION_HELLO:
                    # Fire-and-forget identity announcement: names the
                    # session for quotas/metrics and runs crash-loop
                    # (reconnect-storm) detection.
                    svc._session_hello(
                        self.session, wire.unpack_session_hello(payload)
                    )
                elif msg_type == wire.MSG_CACHE_ENABLE:
                    # Fire-and-forget opt-in; grants start flowing for
                    # conns registered from here on.
                    self.cache_ok = True
                elif msg_type == wire.MSG_CLOSE:
                    self.service.submit_close(wire.unpack_close(payload))
                elif msg_type == wire.MSG_NEW_CONNECTION:
                    args = wire.unpack_new_connection(payload)
                    res, grant, cflags = self.service.new_connection(
                        *args, client=self
                    )
                    # Trailing result-flags word (RESIDUE_ADOPTED):
                    # old shims stop reading after the u4 result.
                    self.send(
                        wire.MSG_CONN_RESULT,
                        np.array([args[1]], "<u8").tobytes()
                        + np.array([res], "<u4").tobytes()
                        + np.array([cflags], "<u4").tobytes(),
                    )
                    if grant is not None:
                        # After the reply: the shim's post-RPC stale-
                        # grant drop is ordered BEFORE this frame.
                        self.service._send_cache_grants([grant])
                elif msg_type == wire.MSG_OPEN_MODULE:
                    params, debug = wire.unpack_open_module(payload)
                    self.module_id = self.service.open_module(params, debug)
                    self.send(
                        wire.MSG_MODULE_ID,
                        np.array([self.module_id], "<u8").tobytes(),
                    )
                elif msg_type == wire.MSG_POLICY_UPDATE:
                    module_id, pj = wire.unpack_policy_update(payload)
                    status, epoch = self.service.policy_update(
                        module_id, pj
                    )
                    self.send(
                        wire.MSG_ACK, wire.pack_ack_epoch(status, epoch)
                    )
                elif msg_type == wire.MSG_HANDOFF:
                    # Successor side channel: the claimant dialed our
                    # socket path.  Surrender runs on THIS reader
                    # thread (quiesce, snapshot, fence, release the
                    # path); a refusal is typed in the reply so the
                    # claimant cold-boots instead of hanging.
                    gen, deadline_s = wire.unpack_handoff(payload)
                    if gen < 0:
                        snap, err = None, "malformed handoff request"
                    else:
                        snap, err = svc.handoff_surrender(
                            gen, deadline_s
                        )
                    self.send(
                        wire.MSG_HANDOFF_REPLY,
                        wire.pack_handoff_reply(snap, err),
                    )
                elif msg_type == wire.MSG_STATUS:
                    self.send(
                        wire.MSG_STATUS_REPLY,
                        json.dumps(self.service.status()).encode(),
                    )
                elif msg_type == wire.MSG_TRACE:
                    # A malformed diagnostic request must never kill
                    # this read loop (it would tear down every flow on
                    # the shim connection): any parse/shape problem
                    # degrades to the defaults.
                    try:
                        req = json.loads(payload.decode()) if payload else {}
                        n = int(req.get("n", 100))
                        kind = req.get("kind")
                        if kind is not None:
                            kind = str(kind)
                        session = req.get("session")
                        if session is not None:
                            session = int(session)
                    except (ValueError, TypeError, AttributeError,
                            UnicodeDecodeError):
                        n, kind, session = 100, None, None
                    self.send(
                        wire.MSG_TRACE_REPLY,
                        json.dumps(
                            self.service.trace_dump(
                                n, kind, session=session
                            )
                        ).encode(),
                    )
                elif msg_type == wire.MSG_TIMELINE:
                    # Same containment as MSG_TRACE: a malformed
                    # diagnostic request degrades to defaults, never
                    # kills the shim connection's read loop.
                    try:
                        req = json.loads(payload.decode()) if payload else {}
                        n = int(req.get("n", 100))
                        since = int(req.get("since", 0))
                        table = req.get("table")
                        if table is not None:
                            table = str(table)
                    except (ValueError, TypeError, AttributeError,
                            UnicodeDecodeError):
                        n, since, table = 100, 0, None
                    self.send(
                        wire.MSG_TIMELINE_REPLY,
                        json.dumps(
                            self.service.timeline_dump(
                                n=n, since=since, table=table
                            )
                        ).encode(),
                    )
                elif msg_type == wire.MSG_LEDGER:
                    # Same containment as MSG_TRACE: a malformed
                    # diagnostic request degrades to defaults, never
                    # kills the shim connection's read loop.
                    try:
                        req = json.loads(payload.decode()) if payload else {}
                        n = int(req.get("n", 100))
                        since = int(req.get("since", 0))
                        cause = req.get("cause")
                        if cause is not None:
                            cause = str(cause)
                    except (ValueError, TypeError, AttributeError,
                            UnicodeDecodeError):
                        n, since, cause = 100, 0, None
                    self.send(
                        wire.MSG_LEDGER_REPLY,
                        json.dumps(
                            self.service.ledger_dump(
                                n=n, since=since, cause=cause
                            )
                        ).encode(),
                    )
                elif msg_type == wire.MSG_OBSERVE:
                    # Same containment as MSG_TRACE: a malformed
                    # diagnostic request degrades to defaults, never
                    # kills the shim connection's read loop.
                    try:
                        req = json.loads(payload.decode()) if payload else {}
                        if not isinstance(req, dict):
                            req = {}
                    except (ValueError, UnicodeDecodeError):
                        req = {}
                    try:
                        out = self.service.observe_dump(req)
                    except (TypeError, ValueError):
                        out = self.service.observe_dump({})
                    self.send(
                        wire.MSG_OBSERVE_REPLY, json.dumps(out).encode()
                    )
                else:
                    log.warning("unknown message type %d", msg_type)
        except wire.ConnectionClosed:
            pass
        except OSError:
            pass
        finally:
            # The reader owns the close (see _kill); shutdown first so
            # a send-loop thread mid-sendall on this socket fails fast
            # instead of deferring the fd teardown.
            shutdown_close(self.sock)
            # Peer death releases the ring mappings (the creator owns
            # the segments; our views just unmap).  A session that died
            # holding an ACTIVE shm rung is counted — the operator-
            # visible difference between orderly detach and a vanished
            # shim — and its segments are leased for reclaim: the dead
            # creator will never unlink them, so the survivor must
            # (after lease expiry) or /dev/shm leaks one ring pair per
            # crash.  In-flight rounds for this session need no sweep:
            # their sends hit the dead socket and are counted answered
            # (there is no one left to shed to), and the answered-cell
            # marking still runs under _wlock so a late replier races
            # exactly once.
            abrupt = False
            shm = self.shm
            if shm is not None:
                self.shm = None
                if shm.active:
                    abrupt = True
                    shm.counters.fallback(REASON_PEER_DEATH)
                shm.close()
                # No MSG_SHM_DETACH ever arrived for these rings —
                # orderly clients detach (or demote, which detaches)
                # before dying.  Schedule the survivor-side unlink.
                self.service._schedule_shm_reclaim(shm)
            # Retire the session typed: a kill path (_kill) recorded
            # its reason first; otherwise EOF with a live shm rung is
            # the abrupt-death signature and a plain EOF is orderly.
            self.service._session_dead(
                self.session,
                DEATH_ABRUPT if abrupt else DEATH_CLOSED,
            )
            # Prune this handler so reconnecting shims don't accumulate
            # dead entries for the service's lifetime.
            with self.service._lock:
                try:
                    self.service._clients.remove(self)
                except ValueError:
                    pass

"""Rule-axis model sharding: split a rule set across devices.

The reference scales per-endpoint policy by compiling per-identity rule
tables inside each Envoy worker (reference: envoy/cilium_network_policy.h:
50-76 — every worker holds the whole table).  On TPU the equivalent scale
limit is HBM: a policy's packed NFA transition tables (delta is O(S²·C))
and per-rule compare tensors grow with the rule count, and past a point
one chip cannot hold them.  Rule-axis sharding splits the RULES of one
policy across the mesh's ``RULE_AXIS``:

  - every shard compiles ITS OWN rule subset into its own tables (an NFA
    over fewer patterns has fewer states, so delta shrinks
    quadratically — sharding 2x cuts per-device table HBM ~4x);
  - shards are padded to a common (states, classes, patterns) shape and
    stacked along a leading shard dim, laid out with
    ``PartitionSpec(RULE_AXIS)`` so each device holds exactly one
    shard's tables;
  - evaluation runs under ``shard_map``: flows shard over FLOW_AXIS,
    every device evaluates its local rule subset, and per-rule-subset
    partial verdicts merge with an OR-reduce (``psum > 0``) over
    RULE_AXIS — one small [F] collective per batch, riding ICI.

The OR-reduce is exact, not approximate: every model's verdict is
``any(rule allows)`` over disjoint rule subsets (for Kafka the ORable
partials are (simple, cover); the ∀-topics combine happens after the
reduce — see models/kafka.py kafka_rule_hits/kafka_combine).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import MAX_REMOTES, ConstVerdict, pack_remote_sets
from ..models.http import (
    HttpBatchModel,
    build_http_model,
    http_verdicts,
    http_verdicts_attr,
)
from ..models.kafka import (
    KafkaBatchModel,
    build_kafka_model,
    kafka_combine,
    kafka_rule_hits,
)
from ..models.dns import (
    DnsBatchModel,
    build_dns_model_from_rows,
    collect_dns_policy_rows,
    dns_row_arrays,
    dns_verdicts,
    dns_verdicts_attr,
)
from ..models.r2d2 import (
    MAX_CMD,
    R2d2BatchModel,
    _rule_bucket,
    build_r2d2_model_from_rows,
    collect_policy_rows,
    r2d2_verdicts,
    r2d2_verdicts_attr,
)
from ..ops.nfa import DeviceNfa, device_nfa
from ..regex import compile_patterns
from ..regex.tables import NfaTables
from .mesh import FLOW_AXIS, RULE_AXIS

P = jax.sharding.PartitionSpec

# Sentinel beating every real rule row in the cross-shard min-index
# reduction (rule counts are int32 row indices, far below this).
_NO_MATCH = np.iinfo(np.int32).max


def split_balanced(seq: list, k: int) -> list[list]:
    """Split seq into k contiguous, size-balanced chunks (first chunks
    one longer when len % k != 0).  Chunks may be empty when k > len."""
    n = len(seq)
    base, extra = divmod(n, k)
    out, i = [], 0
    for j in range(k):
        step = base + (1 if j < extra else 0)
        out.append(seq[i : i + step])
        i += step
    return out


def shard_offsets(n_rows: int, n_shards: int) -> jax.Array:
    """[n_shards] int32 global row index of each shard's FIRST rule row
    under split_balanced — the per-shard bias that turns a shard-local
    first-match argmax into a global row id (attribution contract:
    global index == the unsharded model's flattened row order == the
    host oracle's walk order)."""
    sizes = np.asarray(
        [len(s) for s in split_balanced(list(range(n_rows)), n_shards)],
        np.int32,
    )
    return jnp.asarray(
        np.concatenate(([0], np.cumsum(sizes)))[:-1].astype(np.int32)
    )


# --- table padding --------------------------------------------------------

def pad_tables(t: NfaTables, s: int, c: int, r: int) -> NfaTables:
    """Pad an NfaTables to (s states, c classes, r patterns).  Padding
    states have no transitions and are never set; padding classes are
    never produced by classmap; padding patterns never accept."""
    assert s >= t.n_states and c >= t.n_classes and r >= t.n_patterns
    delta = np.zeros((c, s, s), np.uint8)
    delta[: t.n_classes, : t.n_states, : t.n_states] = t.delta
    start = np.zeros((s,), bool)
    start[: t.n_states] = t.start
    accept = np.zeros((r, s), bool)
    accept[: t.n_patterns, : t.n_states] = t.accept
    accept_final = np.zeros((r, s), bool)
    accept_final[: t.n_patterns, : t.n_states] = t.accept_final
    matches_empty = np.zeros((r,), bool)
    matches_empty[: t.n_patterns] = t.matches_empty
    return NfaTables(
        n_states=s,
        n_classes=c,
        n_patterns=r,
        classmap=t.classmap,
        delta=delta,
        start=start,
        accept=accept,
        accept_final=accept_final,
        matches_empty=matches_empty,
        patterns=list(t.patterns),
    )


def _never_match_tables(n_patterns: int) -> NfaTables:
    """Tables with n_patterns patterns that accept nothing (used to give
    head-pattern-less shards a uniformly shaped head NFA)."""
    t = compile_patterns(["x"])
    t.accept[:] = False
    t.accept_final[:] = False
    t.matches_empty[:] = False
    return pad_tables(t, t.n_states, t.n_classes, max(n_patterns, 1))


def stack_nfas(tables: list[NfaTables]) -> DeviceNfa:
    """Pad a list of per-shard tables to a common shape and stack their
    device forms along a leading shard axis."""
    s = max(t.n_states for t in tables)
    c = max(t.n_classes for t in tables)
    r = max(t.n_patterns for t in tables)
    nfas = [device_nfa(pad_tables(t, s, c, r)) for t in tables]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *nfas)


def _stack_models(models: list):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *models)


# --- r2d2 -----------------------------------------------------------------

def build_sharded_r2d2_model(
    policy, ingress: bool, port: int, n_shards: int, bucket: bool = False
) -> ConstVerdict | R2d2BatchModel:
    """Compile the policy's rows into ``n_shards`` stacked shard models:
    every leaf gains a leading [n_shards] dim to lay out with
    PartitionSpec(RULE_AXIS).  Aux dims (states/classes/patterns) are
    padded to the max across shards so the stacked treedef is uniform.
    Padded rule rows are dead via never-accepting NFA pattern rows
    (file_ok is always False for them, independent of input bytes).
    ``bucket=True`` pads the per-shard rule axis to the power-of-two
    bucket (models/r2d2.MIN_RULE_BUCKET) so policy churn that stays in
    the bucket reuses the compiled mesh executable — the sharded twin
    of the single-chip shape-bucketed dispatch cache, keyed by
    (shard count, bucket) through the stacked leaf shapes."""
    rows = collect_policy_rows(policy, ingress, port)
    if isinstance(rows, ConstVerdict):
        return rows
    return build_sharded_r2d2_from_rows(rows, n_shards, bucket=bucket)


def build_sharded_r2d2_from_rows(
    rows: list, n_shards: int, bucket: bool = False
) -> R2d2BatchModel:
    """Rows-based half of build_sharded_r2d2_model (exposed for giant
    synthetic tables — the 100k-rule bench slice — where a full
    proxylib policy compile of the same rows would dominate)."""
    shards = split_balanced(rows, n_shards)
    r_max = max(len(s) for s in shards)
    if bucket:
        r_max = _rule_bucket(r_max)
    shard_tables = [
        compile_patterns([r[2] for r in s]) if s else _never_match_tables(1)
        for s in shards
    ]
    s_max = max(t.n_states for t in shard_tables)
    c_max = max(t.n_classes for t in shard_tables)
    models = []
    for s, t in zip(shards, shard_tables):
        packed = np.zeros((r_max, MAX_REMOTES), np.int32)
        any_remote = np.zeros((r_max,), bool)
        cmd_needle = np.zeros((r_max, MAX_CMD), np.uint8)
        cmd_len = np.zeros((r_max,), np.int32)
        cmd_any = np.zeros((r_max,), bool)
        if s:
            ids, anyr = pack_remote_sets([r[0] for r in s])
            packed[: len(s)] = ids
            any_remote[: len(s)] = anyr
            for i, (_, cmd, _f) in enumerate(s):
                b = cmd.encode()
                cmd_needle[i, : len(b)] = np.frombuffer(b, np.uint8)
                cmd_len[i] = len(b)
                cmd_any[i] = len(b) == 0
        models.append(
            R2d2BatchModel(
                nfa=device_nfa(pad_tables(t, s_max, c_max, r_max)),
                cmd_needle=jnp.asarray(cmd_needle),
                cmd_len=jnp.asarray(cmd_len),
                cmd_any=jnp.asarray(cmd_any),
                remote_ids=jnp.asarray(packed),
                any_remote=jnp.asarray(any_remote),
            )
        )
    return _stack_models(models)


# --- dns ------------------------------------------------------------------

def build_sharded_dns_from_rows(
    rows: list, n_shards: int, bucket: bool = False
) -> DnsBatchModel:
    """Shard (remote_set, DnsRule|None) rows across n_shards stacked
    models.  Aux dims unify across shards (needle width, NFA
    states/classes/patterns) so the stacked treedef is uniform;
    padding rows are dead (needle_len -1, never-accepting automaton
    slots, remote set {-1}) exactly like the single-chip padding."""
    shards = split_balanced(list(rows), n_shards)
    r_max = max(len(s) for s in shards)
    if bucket:
        r_max = _rule_bucket(r_max)
    # One needle width across shards so stacked leaves share shapes.
    width = max(
        (len(r.name.encode("latin-1", "replace"))
         for s in shards for _, r in s
         if r is not None and r.name),
        default=0,
    )
    width = max(8, (width + 7) // 8 * 8)
    per_shard = [
        dns_row_arrays(s, r_max, width=width) for s in shards
    ]
    tables = [
        compile_patterns(arr[6]) if any(arr[6]) else
        _never_match_tables(max(len(arr[6]), 1))
        for arr in per_shard
    ]
    s_max = max(t.n_states for t in tables)
    c_max = max(t.n_classes for t in tables)
    p_max = max(t.n_patterns for t in tables)
    models = []
    for arr, t in zip(per_shard, tables):
        needle, n_len, n_any, use_rx, packed, any_remote, _pats = arr
        models.append(
            DnsBatchModel(
                nfa=device_nfa(pad_tables(t, s_max, c_max, p_max)),
                name_needle=jnp.asarray(needle),
                name_len=jnp.asarray(n_len),
                name_any=jnp.asarray(n_any),
                use_rx=jnp.asarray(use_rx),
                remote_ids=jnp.asarray(packed),
                any_remote=jnp.asarray(any_remote),
            )
        )
    return _stack_models(models)


def mesh_dns_model(policy, ingress: bool, port: int, mesh):
    """Mesh-resident DNS name-policy model for the live serving path —
    the sharded twin of models/dns.build_dns_model: same port cascade,
    same flattened row order, single-chip fallback compiled alongside
    (the device-loss rung), ``match_kinds``/``invariant_rows`` from the
    fallback so attribution and the verdict-cache claim are identical
    on both rungs."""
    rows = collect_dns_policy_rows(policy, ingress, port)
    if isinstance(rows, ConstVerdict):
        return rows
    n_shards = mesh.shape[RULE_AXIS]
    fallback = build_dns_model_from_rows(rows, bucket=True)
    stacked = build_sharded_dns_from_rows(rows, n_shards, bucket=True)
    return ShardedVerdictModel.resident(
        stacked, shard_offsets(len(rows), n_shards), mesh, "dns",
        fallback=fallback, match_kinds=fallback.match_kinds,
    )


# --- http -----------------------------------------------------------------

def build_sharded_http_model(
    rules_with_remotes: list, n_shards: int
) -> ConstVerdict | HttpBatchModel:
    """Shard (remote_set, PortRuleHTTP) rows across n_shards stacked
    models.  Every tier pads to cross-shard maxima: literal rows via the
    live mask, regex/head patterns via never-accepting table rows, rule
    dims via dead rules (no wildcard flag + no rows = method_ok False)."""
    from ..models.http import analyze_rules, lit_arrays

    if not rules_with_remotes:
        return ConstVerdict(False)
    shards = split_balanced(list(rules_with_remotes), n_shards)
    r_max = max(len(s) for s in shards)
    analyzed = [analyze_rules(s) for s in shards]

    def line_tab(patterns):
        return (
            compile_patterns(patterns) if patterns else _never_match_tables(1)
        )

    line_ts = [line_tab(a[2]) for a in analyzed]
    any_head = any(a[7] for a in analyzed)
    head_ts = [line_tab(a[7]) if any_head else None for a in analyzed]

    nm = max(max(len(a[0]) for a in analyzed), 1)
    npath = max(max(len(a[1]) for a in analyzed), 1)
    # Needle widths unified across shards so stacked models share shapes.
    lit_w = max(
        (
            len(lit)
            for a in analyzed
            for rows in (a[0], a[1])
            for lit, _, _ in rows
        ),
        default=0,
    )
    lit_w = max(8, (lit_w + 7) // 8 * 8)
    # Slot-usage flags are aux (static) — must agree across shards
    # (a[4] is each shard's line_slot list).
    has_m_rx = any(s == 0 for a in analyzed for s in a[4])
    has_p_rx = any(s == 1 for a in analyzed for s in a[4])
    pl_max = max(t.n_patterns for t in line_ts)
    ls = max(t.n_states for t in line_ts)
    lc = max(t.n_classes for t in line_ts)
    if any_head:
        p_max = max(t.n_patterns for t in head_ts)
        hs = max(t.n_states for t in head_ts)
        hc = max(t.n_classes for t in head_ts)

    models = []
    for shard, a, lt, ht in zip(shards, analyzed, line_ts, head_ts):
        (m_rows, p_rows, _line_pats, line_rule, line_slot, method_any,
         path_any, _head_pats, head_rule, head_count) = a
        n = len(shard)
        mn, ml, mp, mr, mlive = lit_arrays(m_rows, nm, width=lit_w)
        pn, pl_, pp, pr, plive = lit_arrays(p_rows, npath, width=lit_w)
        packed_ids = np.zeros((r_max, MAX_REMOTES), np.int32)
        any_remote = np.zeros((r_max,), bool)
        ma = np.zeros((r_max,), bool)
        pa = np.zeros((r_max,), bool)
        hcnt = np.zeros((r_max,), np.int32)
        if n:
            ids, anyr = pack_remote_sets([rs for rs, _ in shard])
            packed_ids[:n] = ids
            any_remote[:n] = anyr
            ma[:n] = method_any
            pa[:n] = path_any
            hcnt[:n] = np.asarray(head_count, np.int32)
        lr = np.zeros((pl_max,), np.int32)
        lsl = np.zeros((pl_max,), np.int32)
        lr[: len(line_rule)] = np.asarray(line_rule, np.int32)
        lsl[: len(line_slot)] = np.asarray(line_slot, np.int32)
        hr = np.zeros((max(p_max, 1) if any_head else 1,), np.int32)
        if any_head:
            hr[: len(head_rule)] = np.asarray(head_rule, np.int32)
        models.append(
            HttpBatchModel(
                m_needle=jnp.asarray(mn),
                m_len=jnp.asarray(ml),
                m_prefix=jnp.asarray(mp),
                m_rule=jnp.asarray(mr),
                m_live=jnp.asarray(mlive),
                p_needle=jnp.asarray(pn),
                p_len=jnp.asarray(pl_),
                p_prefix=jnp.asarray(pp),
                p_rule=jnp.asarray(pr),
                p_live=jnp.asarray(plive),
                method_any=jnp.asarray(ma),
                path_any=jnp.asarray(pa),
                line_nfa=device_nfa(pad_tables(lt, ls, lc, pl_max)),
                line_rule=jnp.asarray(lr),
                line_slot=jnp.asarray(lsl),
                head_nfa=(
                    device_nfa(pad_tables(ht, hs, hc, p_max))
                    if any_head
                    else None
                ),
                head_rule=jnp.asarray(hr),
                head_count=jnp.asarray(hcnt),
                remote_ids=jnp.asarray(packed_ids),
                any_remote=jnp.asarray(any_remote),
                n_rules=r_max,
                has_method_rx=has_m_rx,
                has_path_rx=has_p_rx,
            )
        )
    return _stack_models(models)


# --- kafka ----------------------------------------------------------------

def _pad_kafka_model(m: KafkaBatchModel, r: int) -> KafkaBatchModel:
    """Pad rule rows to r with dead rules (api_key_mask all-False fails
    key_ok; any_remote False with no ids fails remote_ok)."""
    cur = m.version.shape[0]
    if cur == r:
        return m

    def pad(x, fill=0):
        widths = [(0, r - cur)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, widths, constant_values=fill)

    return KafkaBatchModel(
        api_key_mask=pad(m.api_key_mask, False),
        version=pad(m.version),
        version_any=pad(m.version_any, False),
        client=pad(m.client),
        client_len=pad(m.client_len),
        client_any=pad(m.client_any, False),
        topic=pad(m.topic),
        topic_len=pad(m.topic_len),
        topic_any=pad(m.topic_any, False),
        is_topic_key=m.is_topic_key,
        remote_ids=pad(m.remote_ids),
        any_remote=pad(m.any_remote, False),
    )


def build_sharded_kafka_model(
    rules_with_remotes: list, n_shards: int
) -> ConstVerdict | KafkaBatchModel:
    if not rules_with_remotes:
        return ConstVerdict(False)
    shards = split_balanced(list(rules_with_remotes), n_shards)
    r_max = max(len(s) for s in shards)
    models = []
    for s in shards:
        if s:
            m = build_kafka_model(s)
        else:
            m = build_kafka_model(rules_with_remotes[:1])
            m = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), m)
        models.append(_pad_kafka_model(m, r_max))
    return _stack_models(models)


# --- sharded evaluation ---------------------------------------------------

def _on_mesh(tree, mesh):
    """Place stacked per-shard leaves with their leading dim over
    RULE_AXIS (the layout every sharded step's in_specs expect)."""
    return jax.device_put(
        tree, jax.sharding.NamedSharding(mesh, P(RULE_AXIS))
    )


def _local(model):
    """Drop the singleton shard dim a device sees under shard_map, and
    mark every leaf varying over FLOW_AXIS for the vma checker: model
    state mixes with flow-varying data inside lax.scan carries, whose
    input/output varying-axis sets must agree."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.pcast(x[0], FLOW_AXIS, to="varying"), model
    )


def sharded_verdict_step(mesh, verdict_fn):
    """Jitted (stacked_model, data, lengths, remotes) -> (complete,
    msg_len, allow) over a (FLOW_AXIS, RULE_AXIS) mesh for models whose
    verdict is any-rule-allows (r2d2, http): flows shard, rules shard,
    allow OR-reduces over RULE_AXIS."""

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(RULE_AXIS), P(FLOW_AXIS), P(FLOW_AXIS), P(FLOW_AXIS)),
        out_specs=(P(FLOW_AXIS), P(FLOW_AXIS), P(FLOW_AXIS)),
    )
    def step(model, data, lengths, remotes):
        complete, msg_len, allow = verdict_fn(
            _local(model), data, lengths, remotes
        )
        allow = (
            jax.lax.psum(allow.astype(jnp.int32), RULE_AXIS) > 0
        )
        return complete, msg_len, allow

    return step


def sharded_kafka_step(mesh):
    """Jitted (stacked_model, batch, remotes) -> allow [F] bool.  The
    ORable partials (simple, cover) psum over RULE_AXIS; the ∀-topics
    combine runs on the merged partials (it does not distribute over
    rule subsets)."""

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(RULE_AXIS), P(FLOW_AXIS), P(FLOW_AXIS)),
        out_specs=P(FLOW_AXIS),
    )
    def step(model, batch, remotes):
        simple, cover = kafka_rule_hits(_local(model), batch, remotes)
        simple = jax.lax.psum(simple.astype(jnp.int32), RULE_AXIS) > 0
        cover = jax.lax.psum(cover.astype(jnp.int32), RULE_AXIS) > 0
        return kafka_combine(
            simple, cover, batch.topic_count, batch.overflow
        )

    return step


def sharded_verdict_step_attr(mesh, attr_fn):
    """Jitted (stacked_model, offsets, data, lengths, remotes) ->
    (complete, msg_len, allow, rule) over a (FLOW_AXIS, RULE_AXIS)
    mesh, with rule ids resolved GLOBALLY across rule shards in the
    same device round: each shard's ``attr_fn`` yields its local
    first-match argmax, the local index is biased by the shard's
    global row offset, and a cross-shard min-index reduction (pmin
    over RULE_AXIS) picks the host oracle's first match — no second
    hit-matrix pass, no extra readback."""

    @jax.jit
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P(RULE_AXIS), P(RULE_AXIS),
            P(FLOW_AXIS), P(FLOW_AXIS), P(FLOW_AXIS),
        ),
        out_specs=(
            P(FLOW_AXIS), P(FLOW_AXIS), P(FLOW_AXIS), P(FLOW_AXIS),
        ),
    )
    def step(model, offsets, data, lengths, remotes):
        local, off = _local((model, offsets))
        complete, msg_len, allow_l, rule_l = attr_fn(
            local, data, lengths, remotes
        )
        cand = jnp.where(
            rule_l >= 0, rule_l + off, jnp.int32(_NO_MATCH)
        )
        cand = jax.lax.pmin(cand, RULE_AXIS)
        allow = jax.lax.psum(allow_l.astype(jnp.int32), RULE_AXIS) > 0
        rule = jnp.where(allow, cand, jnp.int32(-1))
        return complete, msg_len, allow, rule

    return step


# --- mesh-resident serving models -----------------------------------------
#
# Drop-in replacements for the single-chip batch models on the live
# dispatch path: same (data, lengths, remotes) -> (complete, msg_len,
# allow[, rule]) contract, tables resident sharded across the mesh.
# One jitted step per (mesh, family, attr) lives for the process: jit's
# own shape cache then keys executables by the stacked model's leaf
# shapes — i.e. by (shard count, rule bucket) — so policy churn whose
# rebuilt tables land in the same buckets re-uploads arrays without
# retracing a mesh executable.

_FAMILY_FNS = {
    "r2d2": (r2d2_verdicts, r2d2_verdicts_attr),
    "http": (http_verdicts, http_verdicts_attr),
    "dns": (dns_verdicts, dns_verdicts_attr),
}
_STEP_CACHE: dict = {}


def _mesh_step(mesh, family: str, attr: bool):
    key = (mesh, family, attr)
    step = _STEP_CACHE.get(key)
    if step is None:
        plain_fn, attr_fn = _FAMILY_FNS[family]
        step = (
            sharded_verdict_step_attr(mesh, attr_fn)
            if attr
            else sharded_verdict_step(mesh, plain_fn)
        )
        _STEP_CACHE[key] = step
    return step


def _pad_flow_axis(n: int, n_flow: int, *arrays):
    """Pad leading (flow) axes up to a multiple of the mesh's flow
    extent — shard_map requires exact divisibility.  The service's
    power-of-two buckets always divide, so this is a no-op on the
    dispatch path; ad-hoc callers (probes, tests) pay one jnp.pad."""
    pad = (-n) % n_flow
    if not pad:
        return 0, arrays
    out = tuple(
        jax.tree_util.tree_map(
            lambda x: jnp.pad(
                x, [(0, pad)] + [(0, 0)] * (jnp.ndim(x) - 1)
            ),
            a,
        )
        for a in arrays
    )
    return pad, out


@jax.tree_util.register_pytree_node_class
class ShardedVerdictModel:
    """A (flows, rules)-mesh-resident verdict model.

    ``stacked`` is the per-shard model pytree (leading [n_shards] dim,
    laid out with PartitionSpec(RULE_AXIS)); ``offsets`` the per-shard
    global row offsets the attributed step biases local argmaxes with.
    ``fallback`` is the SINGLE-CHIP executable compiled from the same
    rows — the degradation rung the service demotes to when a mesh
    device is lost (typed + counted; verdicts are bit-identical by the
    sharding parity contract).  ``fallback`` and ``match_kinds`` are
    host-side metadata, deliberately OUTSIDE the pytree (like
    R2d2BatchModel.match_kinds): the traced computation never reads
    them, and keeping them out of aux keeps churn relabels on the
    compiled executable."""

    def __init__(self, stacked, offsets, mesh, family: str,
                 fallback=None, match_kinds: tuple = ()):
        self.stacked = stacked
        self.offsets = offsets
        self.mesh = mesh
        self.family = family
        self.fallback = fallback
        self.match_kinds = match_kinds

    @classmethod
    def resident(cls, stacked, offsets, mesh, family: str, **kw):
        """The wrapper with its tables laid out on ``mesh`` once, at
        build: rule shards over RULE_AXIS, replicated over FLOW_AXIS.
        Left on the default device, every sharded call would move them
        from there again."""
        stacked, offsets = _on_mesh((stacked, offsets), mesh)
        return cls(stacked, offsets, mesh, family, **kw)

    @property
    def n_shards(self) -> int:
        return int(self.offsets.shape[0])

    @property
    def remote_ids(self):
        """Stacked per-shard remote tables (epoch parity probes ravel
        these to draw candidate identities)."""
        return self.stacked.remote_ids

    def tree_flatten(self):
        return (self.stacked, self.offsets), (self.mesh, self.family)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], leaves[1], aux[0], aux[1])

    def dispatch_bare(self) -> "ShardedVerdictModel":
        """Shape-keyed dispatch-cache marker (see R2d2BatchModel): the
        service jits with the wrapper as an ARGUMENT, so same-bucketed
        churn rebuilds share one compiled mesh executable keyed by
        (shard count, rule bucket) through the stacked leaf shapes."""
        return self

    def __call__(self, data, lengths, remotes):
        n = data.shape[0]
        pad, (data, lengths, remotes) = _pad_flow_axis(
            n, self.mesh.shape[FLOW_AXIS], data, lengths, remotes
        )
        out = _mesh_step(self.mesh, self.family, attr=False)(
            self.stacked, data, lengths, remotes
        )
        return tuple(o[:n] for o in out) if pad else out

    def verdicts_attr(self, data, lengths, remotes):
        n = data.shape[0]
        pad, (data, lengths, remotes) = _pad_flow_axis(
            n, self.mesh.shape[FLOW_AXIS], data, lengths, remotes
        )
        out = _mesh_step(self.mesh, self.family, attr=True)(
            self.stacked, self.offsets, data, lengths, remotes
        )
        return tuple(o[:n] for o in out) if pad else out


@jax.tree_util.register_pytree_node_class
class ShardedKafkaModel:
    """Mesh twin of KafkaBatchModel's (batch, remotes) -> allow
    contract: the ORable (simple, cover) partials psum over RULE_AXIS,
    the ∀-topics combine runs on the merged partials."""

    def __init__(self, stacked, mesh, fallback=None):
        self.stacked = stacked
        self.mesh = mesh
        self.fallback = fallback

    def tree_flatten(self):
        return (self.stacked,), (self.mesh,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], aux[0])

    def __call__(self, batch, remotes):
        key = (self.mesh, "kafka")
        step = _STEP_CACHE.get(key)
        if step is None:
            step = _STEP_CACHE[key] = sharded_kafka_step(self.mesh)
        n = remotes.shape[0]
        n_flow = self.mesh.shape[FLOW_AXIS]
        pad, padded = _pad_flow_axis(n, n_flow, batch, remotes)
        if pad:
            batch, remotes = padded
        allow = step(self.stacked, batch, remotes)
        return allow[:n] if pad else allow


# --- mesh-aware model builds (the live serving path's entry) --------------

def mesh_r2d2_model(policy, ingress: bool, port: int, mesh):
    """Mesh-resident r2d2 model for the live serving path: rule rows
    split-balanced and padded across the mesh's RULE_AXIS (bucketed so
    churn reuses compiled mesh executables), plus the single-chip
    fallback executable the service demotes to on device loss.
    Constant-verdict rule sets fold exactly as in the unsharded build.
    ``match_kinds`` comes from the fallback compile so the attribution
    legend is identical on both rungs."""
    rows = collect_policy_rows(policy, ingress, port)
    if isinstance(rows, ConstVerdict):
        return rows
    n_shards = mesh.shape[RULE_AXIS]
    fallback = build_r2d2_model_from_rows(rows, bucket=True)
    stacked = build_sharded_r2d2_model(
        policy, ingress, port, n_shards, bucket=True
    )
    return ShardedVerdictModel.resident(
        stacked, shard_offsets(len(rows), n_shards), mesh, "r2d2",
        fallback=fallback, match_kinds=fallback.match_kinds,
    )


def mesh_http_model_from_rows(rows: list, mesh):
    """THE one assembly of a mesh-resident HTTP model from flattened
    (remote_set, PortRuleHTTP) rows — shared by the policy-cascade
    build below and models/builder.build_model_for_filter so the two
    wrapper constructions can never drift."""
    fallback = build_http_model(rows)
    if isinstance(fallback, ConstVerdict):
        return fallback
    n_shards = mesh.shape[RULE_AXIS]
    stacked = build_sharded_http_model(rows, n_shards)
    return ShardedVerdictModel.resident(
        stacked, shard_offsets(len(rows), n_shards), mesh, "http",
        fallback=fallback,
        match_kinds=getattr(fallback, "match_kinds", ()),
    )


def mesh_http_model(policy, ingress: bool, port: int, mesh):
    """Mesh-resident HTTP model for (policy, direction, port) — the
    sharded twin of models/http.build_http_model_for_port, same port
    cascade and flattened row order."""
    from ..models.http import collect_http_rows

    rows = collect_http_rows(policy, ingress, port)
    if isinstance(rows, ConstVerdict):
        return rows
    return mesh_http_model_from_rows(rows, mesh)


def mesh_model_from_family_rows(family: str, rows: list, mesh):
    """Build a ShardedVerdictModel for ``family`` ("r2d2" | "dns" |
    "http") from already-flattened rule rows against an ARBITRARY mesh
    — the width-ladder's one assembly seam: the service's off-path
    reshape (and its parity probe) and the devicecheck reshape audit
    both rebuild through here, so a degraded-width rebuild can never
    drift from the full-width construction (same ``split_balanced``
    re-balance, same re-derived ``shard_offsets``, same pow2 rule
    buckets so the shape-keyed executable cache still hits)."""
    n_shards = mesh.shape[RULE_AXIS]
    if family == "r2d2":
        fallback = build_r2d2_model_from_rows(rows, bucket=True)
        stacked = build_sharded_r2d2_from_rows(rows, n_shards,
                                               bucket=True)
    elif family == "dns":
        fallback = build_dns_model_from_rows(rows, bucket=True)
        stacked = build_sharded_dns_from_rows(rows, n_shards,
                                              bucket=True)
    elif family == "http":
        fallback = build_http_model(rows)
        if isinstance(fallback, ConstVerdict):
            return fallback
        stacked = build_sharded_http_model(rows, n_shards)
    else:
        raise ValueError(f"unknown sharded family {family!r}")
    if isinstance(fallback, ConstVerdict):
        return fallback
    return ShardedVerdictModel.resident(
        stacked, shard_offsets(len(rows), n_shards), mesh, family,
        fallback=fallback,
        match_kinds=getattr(fallback, "match_kinds", ()),
    )


def mesh_kafka_model(rules_with_remotes: list, mesh):
    """Mesh-resident kafka topic-ACL model from (remote_set, rule)
    rows."""
    fallback = build_kafka_model(rules_with_remotes)
    if isinstance(fallback, ConstVerdict):
        return fallback
    stacked = build_sharded_kafka_model(
        rules_with_remotes, mesh.shape[RULE_AXIS]
    )
    return ShardedKafkaModel(_on_mesh(stacked, mesh), mesh,
                             fallback=fallback)

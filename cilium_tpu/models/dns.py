"""DNS name-policy batch verdict model — the first length-prefixed
protocol family on the batched-verdict hot path.

Replaces the reference's per-request dnsproxy name walk (reference:
pkg/fqdn + the proxylib-style per-rule regex loop) with one fused
device pass over a [flows, bytes] batch of DNS-over-TCP query frames
(2-byte length prefix + 12-byte header + QNAME label sequence +
QTYPE/QCLASS):

  1. frame:    msg_len from the length prefix; complete = frame fits
  2. name:     a bounded label walk (MAX_LABELS fori_loop steps) finds
               the QNAME span, validates it (no compression pointers,
               labels <= 63, question section complete), and rewrites
               the row in place to the DOTTED, 0x20-folded name —
               interior length bytes become '.', A-Z fold to a-z
  3. match:    exact-name needle compare + wildcard/regex rows on the
               shared DFA/NFA automaton tier + remote-ID set, reduced
               across the flattened (rule, matcher) rows

Build is a pure function ``PolicyInstance -> device arrays``; rule rows
pad to the power-of-two churn bucket like r2d2; evaluation is jitted
and shards on the flow axis (parallel/rulesharding.mesh_dns_model is
the mesh-resident twin).  Bit-identical to the streaming oracle
(proxylib/parsers/dns.py) — tests/test_dns_model.py fuzzes both; the
structural bounds (MAX_LABELS etc.) are shared constants so the two
rungs cannot drift.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.bytescan import spans_equal_prefix
from ..ops.rxsearch import (
    DeviceDfa,
    DeviceNfa,
    automaton_search_spans,
    compile_automaton,
)
from ..proxylib.parsers.dns import (
    DNS_HEADER_LEN,
    DNS_PREFIX_LEN,
    MAX_LABEL,
    MAX_LABELS,
    DnsRule,
)
from ..proxylib.policy import CompiledPortRules, PolicyInstance
from .base import ConstVerdict, VerdictModel, first_match, pack_remote_sets, remote_ok
from .r2d2 import _rule_bucket

# Smallest well-formed query frame: prefix + header + root name + Q.
DNS_MIN_FRAME = DNS_PREFIX_LEN + DNS_HEADER_LEN + 1 + 4
_QNAME_OFF = DNS_PREFIX_LEN + DNS_HEADER_LEN  # first length byte


@jax.tree_util.register_pytree_node_class
@dataclass
class DnsBatchModel(VerdictModel):
    nfa: "DeviceDfa | DeviceNfa"  # pattern/regex automaton, one row each
    name_needle: jax.Array  # [R, W] uint8 — exact names, dotted+folded
    name_len: jax.Array  # [R] int32 (-1 = row matches via automaton/any)
    name_any: jax.Array  # [R] bool — byte-free always-match rows
    use_rx: jax.Array  # [R] bool — row decided by the automaton tier
    remote_ids: jax.Array  # [R, MAX_REMOTES] int32
    any_remote: jax.Array  # [R] bool
    # Host-side aux, deliberately OUTSIDE the pytree (see
    # R2d2BatchModel.match_kinds): the trace never reads them, and
    # keeping them out of aux keys churn relabels onto the compiled
    # executable.
    match_kinds: tuple = ()
    invariant_rows: tuple = ()

    def tree_flatten(self):
        return (
            (self.nfa, self.name_needle, self.name_len, self.name_any,
             self.use_rx, self.remote_ids, self.any_remote),
            (),
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def __call__(self, data, lengths, remotes):
        return dns_verdicts(self, data, lengths, remotes)

    def verdicts_attr(self, data, lengths, remotes):
        return dns_verdicts_attr(self, data, lengths, remotes)

    def dispatch_bare(self) -> "DnsBatchModel":
        """Shape-keyed dispatch-cache marker (see R2d2BatchModel):
        same-bucketed churn rebuilds share one compiled executable."""
        return self


def _collect_rows(rules: CompiledPortRules):
    rows = []  # (remote_set, DnsRule | None)
    for rule in rules.rules:
        matchers = rule.l7_matchers or [None]
        for m in matchers:
            if m is not None:
                assert isinstance(m, DnsRule), f"not a dns rule: {m!r}"
            rows.append((rule.allowed_remotes, m))
    return rows


def collect_dns_policy_rows(
    policy: PolicyInstance | None, ingress: bool, port: int
) -> ConstVerdict | list:
    """Effective (remote_set, DnsRule|None) rows for (policy,
    direction, port) under the reference port cascade — the same
    flattened first-match row order the host ``matches_at`` walks
    (models/r2d2.collect_policy_rows is the template)."""
    if policy is None:
        return ConstVerdict(False)
    side = policy.ingress if ingress else policy.egress
    rows = []
    for key in (port, 0):
        rules = side.by_port.get(key)
        if rules is None:
            continue
        if not rules.have_l7_rules or not rules.rules:
            return ConstVerdict(True)
        rows.extend(_collect_rows(rules))
    if not rows:
        return ConstVerdict(False)
    return rows


def build_dns_model(
    policy: PolicyInstance | None, ingress: bool, port: int
) -> ConstVerdict | DnsBatchModel:
    rows = collect_dns_policy_rows(policy, ingress, port)
    if isinstance(rows, ConstVerdict):
        return rows
    return build_dns_model_from_rows(rows, bucket=True)


def dns_row_arrays(rows: list, n_pad: int, width: int | None = None):
    """Host arrays for (remote_set, DnsRule|None) rows padded to
    ``n_pad`` (padding rows are dead: remote set {-1}, needle_len -1,
    never-accepting automaton slot).  Shared by the single-chip build
    and the rule-axis sharded build so the two cannot drift.  Returns
    (needle, n_len, n_any, use_rx, packed_ids, any_remote, patterns)."""
    exact = [
        (r.name.encode("latin-1", "replace") if r is not None else b"")
        for _, r in rows
    ]
    if width is None:
        # The needle must hold the WHOLE longest exact name (bounded by
        # the MAX_LABELS walk at ~2.5KB): truncating here would make
        # the exact compare a prefix compare — a device over-allow the
        # host oracle never produces.
        width = max((len(b) for b in exact), default=0)
        width = max(8, (width + 7) // 8 * 8)
    needle = np.zeros((n_pad, width), np.uint8)
    n_len = np.full((n_pad,), -1, np.int32)
    n_any = np.zeros((n_pad,), bool)
    use_rx = np.zeros((n_pad,), bool)
    patterns = []
    for i, (_, rule) in enumerate(rows):
        if rule is None or not (rule.name or rule.pattern or rule.regex):
            n_any[i] = True
            patterns.append("")
            continue
        if rule.name:
            b = exact[i]
            assert len(b) <= width, "needle width must cover every name"
            needle[i, : len(b)] = np.frombuffer(b, np.uint8)
            n_len[i] = len(b)
            patterns.append("")
            continue
        use_rx[i] = True
        patterns.append(rule.device_pattern())
    packed_ids, any_remote = pack_remote_sets([r[0] for r in rows])
    n = len(rows)
    if n_pad > n:
        ids = np.full((n_pad, packed_ids.shape[1]), -1, np.int32)
        ids[:n] = packed_ids
        packed_ids = ids
        ar = np.zeros((n_pad,), bool)
        ar[:n] = any_remote
        any_remote = ar
    patterns += [""] * (n_pad - n)
    return needle, n_len, n_any, use_rx, packed_ids, any_remote, patterns


def build_dns_model_from_rows(
    rows: list, bucket: bool = False
) -> DnsBatchModel:
    """Compile (remote_set, DnsRule|None) rows into device arrays;
    ``bucket=True`` pads the row axis to the power-of-two churn bucket
    (models/r2d2.MIN_RULE_BUCKET semantics)."""
    n = len(rows)
    n_pad = _rule_bucket(n) if bucket else n
    (needle, n_len, n_any, use_rx, packed_ids, any_remote,
     patterns) = dns_row_arrays(rows, n_pad)
    nfa = compile_automaton(patterns)
    kinds = tuple(
        "literal" if not (r is not None and (r.pattern or r.regex))
        else ("nfa" if isinstance(nfa, DeviceNfa) else "regex")
        for _, r in rows
    )
    from ..policy.invariance import reduce_dns_rows

    return DnsBatchModel(
        nfa=nfa,
        name_needle=jnp.asarray(needle),
        name_len=jnp.asarray(n_len),
        name_any=jnp.asarray(n_any),
        use_rx=jnp.asarray(use_rx),
        remote_ids=jnp.asarray(packed_ids),
        any_remote=jnp.asarray(any_remote),
        match_kinds=kinds,
        invariant_rows=reduce_dns_rows(rows),
    )


def _dns_name_span(data: jax.Array, lengths: jax.Array):
    """Frame + QNAME structure of each row's FIRST prefixed frame.

    Returns (complete [F] bool, msg_len [F] i32, valid [F] bool,
    span_start [F] i32, span_end [F] i32, dotted [F, L] u8) where
    ``dotted`` is the row rewritten in place to the dotted 0x20-folded
    name over [span_start, span_end) — interior label-length bytes
    become '.', the leading length byte and terminal zero sit outside
    the span.  The label walk is ONE lax.scan over the byte columns
    (each flow's single label chain advances when the scan reaches its
    current label-length position — O(F·L) total, column slices only,
    no gathers); every structural bound mirrors
    proxylib.parsers.dns.parse_dns_query exactly, so a query invalid
    on one rung is invalid on both."""
    f, l = data.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    if l < DNS_MIN_FRAME:
        z = jnp.zeros((f,), jnp.int32)
        return (
            jnp.zeros((f,), bool), z, jnp.zeros((f,), bool), z, z, data,
        )
    plen = (
        data[:, 0].astype(jnp.int32) << 8
    ) | data[:, 1].astype(jnp.int32)
    msg_len = plen + DNS_PREFIX_LEN
    complete = (lengths >= DNS_PREFIX_LEN) & (msg_len <= lengths)
    qd = (data[:, 6].astype(jnp.int32) << 8) | data[:, 7].astype(jnp.int32)
    limit = jnp.minimum(msg_len, l)  # the walk never leaves the frame
    invalid0 = ~complete | (msg_len < DNS_MIN_FRAME) | (qd < 1)

    def body(carry, col):
        pos, done, invalid, nlab = carry
        c, lb = col
        lb = lb.astype(jnp.int32)
        at = (pos == c) & ~done & ~invalid
        readable = c < limit
        invalid = invalid | (at & ~readable)
        act = at & readable
        terminal = act & (lb == 0)
        done = done | terminal
        step = act & ~terminal
        # Compression pointer / oversized label / too many labels.
        bad = (lb > MAX_LABEL) | (nlab >= MAX_LABELS)
        invalid = invalid | (step & bad)
        step = step & ~bad
        pos = jnp.where(step, pos + 1 + lb, pos)
        nlab = nlab + step.astype(jnp.int32)
        return (pos, done, invalid, nlab), step

    # The initial carry is derived from ``data`` (the *_like forms keep
    # its varying axes) so that under shard_map it varies over the flow
    # axis exactly as the body's output does.
    (pos, done, invalid, _), sep_cols = jax.lax.scan(
        body,
        (jnp.full_like(msg_len, _QNAME_OFF), jnp.zeros_like(complete),
         invalid0, jnp.zeros_like(msg_len)),
        (jnp.arange(l, dtype=jnp.int32), data.T),
    )
    is_sep = sep_cols.T  # [F, L]: True at label-length byte positions
    # Never terminated (chain left the row / too deep) or a question
    # section that cannot hold QTYPE+QCLASS: invalid.
    invalid = invalid | ~done | (pos + 5 > msg_len)
    valid = ~invalid
    span_start = jnp.full((f,), _QNAME_OFF + 1, jnp.int32)
    span_end = jnp.where(valid, pos, span_start)
    upper = (data >= jnp.uint8(0x41)) & (data <= jnp.uint8(0x5A))
    folded = jnp.where(upper, data + jnp.uint8(0x20), data)
    dotted = jnp.where(is_sep, jnp.uint8(0x2E), folded)
    return complete, msg_len, valid, span_start, span_end, dotted


def _dns_rule_hits(
    model: DnsBatchModel,
    data: jax.Array,  # [F, L] uint8 — buffered stream per flow
    lengths: jax.Array,  # [F] int32
    remotes: jax.Array,  # [F] int32 — source security identity
):
    """Shared frame/name/match pass; returns (complete, msg_len,
    hits [F, R] bool) — consumed by both reductions (any-allow and
    first-match attribution), like models/r2d2._r2d2_rule_hits."""
    complete, msg_len, valid, s, e, dotted = _dns_name_span(data, lengths)
    exact_ok = spans_equal_prefix(
        dotted, s, e, model.name_needle, model.name_len
    )  # [F, R]
    rx_ok = automaton_search_spans(model.nfa, dotted, s, e)  # [F, R]
    # The QNAME validity gate masks name-CONSTRAINED rows only: a
    # malformed question can never satisfy a name rule, but a
    # byte-free "allow these peers' DNS" row admits any complete
    # frame — the invariance contract the verdict cache's byte-free
    # claim rests on (policy/invariance.reduce_dns_rows).
    name_ok = model.name_any[None, :] | (
        (exact_ok | (model.use_rx[None, :] & rx_ok)) & valid[:, None]
    )
    rem_ok = remote_ok(remotes, model.remote_ids, model.any_remote)
    return complete, msg_len, name_ok & rem_ok


@jax.jit
def dns_verdicts(
    model: DnsBatchModel,
    data: jax.Array,
    lengths: jax.Array,
    remotes: jax.Array,
):
    """(complete [F] bool, msg_len [F] i32, allow [F] bool) — msg_len
    is the whole prefixed frame; allow meaningful only where
    complete.  A structurally invalid query matches no rule."""
    complete, msg_len, hits = _dns_rule_hits(model, data, lengths, remotes)
    return complete, msg_len, jnp.any(hits, axis=1)


@jax.jit
def dns_verdicts_attr(
    model: DnsBatchModel,
    data: jax.Array,
    lengths: jax.Array,
    remotes: jax.Array,
):
    """dns_verdicts plus the deciding rule row (first-match argmax over
    the same fused hit matrix — the host matches_at walk order)."""
    complete, msg_len, hits = _dns_rule_hits(model, data, lengths, remotes)
    allow = jnp.any(hits, axis=1)
    return complete, msg_len, allow, first_match(hits, allow)

"""HTTP batch verdict model: request-line + header policy on device.

Replaces the reference's per-request std::regex walk in the Envoy filter
(reference: envoy/cilium_l7policy.cc:51 + cilium_network_policy.h:50-76
HttpNetworkPolicyRule: anchored regex on path/method/host, exact header
presence) and the agent-side rule model (reference:
pkg/policy/api/http.go:28 PortRuleHTTP) with one device pass:

  1. tokenize the request line ([F, L] uint8): method span = [0, sp1),
     path span = (sp1, sp2) — pure bytescan, no host round-trip
  2. TIERED method/path matching:
       - tier 0 (free): omitted fields allow everything (http.go skips
         the check entirely) — a per-rule flag, no byte work
       - tier 1 (literal): patterns that are literals ("GET"),
         alternations of literals ("GET|HEAD"), or literal prefixes
         ("/api/v1/.*") — the overwhelming majority of real policies —
         match with vectorized byte compares, NO automaton at all
       - tier 2 (regex): everything else goes through the NFA (matmul,
         small sets) or per-pattern DFA (block-diagonal, large sets)
  3. host regex + exact header lines matched as CRLF-delimited patterns
     searched over the whole request head
  4. a rule allows iff all its present components match; request allowed
     iff any rule with a matching remote allows.

The tiers are bit-identical to the pure-regex path: literal analysis is
done on the parsed AST (so escapes and alternation mirror the compiler),
and literal-prefix rows carry the regex ``.*``-excludes-newline guard.

Deny maps to a 403 response injected by the runtime engine
(reference: cilium_l7policy.cc 403 body injection).
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.bytescan import first_occurrence, first_subsequence2, spans_equal_prefix, spans_start_with
from ..ops.dfa import DeviceDfa, dfa_search_spans
from ..ops.nfa import DeviceNfa, device_nfa, nfa_search_spans
from ..policy.api import PortRuleHTTP
from ..regex import compile_patterns
from ..regex.parse import DOT_BYTES, ParseError, parse
from .base import ConstVerdict, first_match, pack_remote_sets, remote_ok

_RE_META = set("\\^$.[]|()*+?{}")

LIT_W = 64  # max literal needle bytes; longer literals fall to regex


def re_escape(s: str) -> str:
    """Escape a literal for the POSIX-extended regex compiler."""
    return "".join("\\" + c if c in _RE_META else c for c in s)


def _ci_literal(s: str) -> str:
    """Case-insensitive regex for a literal (header field names are
    case-insensitive, RFC 9110)."""
    out = []
    for c in s:
        if c.isalpha():
            out.append(f"[{c.upper()}{c.lower()}]")
        elif c in _RE_META:
            out.append("\\" + c)
        else:
            out.append(c)
    return "".join(out)


def _header_pattern(header: str) -> str:
    """'Name: value' -> CRLF-framed pattern with case-insensitive name and
    optional OWS around the value (matching the Host handling and the
    reference's case-insensitive header lookup)."""
    name, sep, value = header.partition(":")
    if not sep:
        return "\r\n" + re_escape(header) + "\r\n"
    return (
        "\r\n" + _ci_literal(name) + ":[ \t]*"
        + re_escape(value.strip()) + "[ \t]*\r\n"
    )


# --- literal-tier analysis ------------------------------------------------

def _ast_literal(node) -> bytes | None:
    """Bytes of a pure single-byte-literal concatenation, else None."""
    kind = node[0]
    if kind == "empty":
        return b""
    if kind == "lit":
        s = node[1]
        return bytes([next(iter(s))]) if len(s) == 1 else None
    if kind == "cat":
        parts = [_ast_literal(x) for x in node[1]]
        if any(p is None for p in parts):
            return None
        return b"".join(parts)
    return None


def _ast_dotstar(node) -> bool:
    return node[0] == "star" and node[1][0] == "lit" and node[1][1] == DOT_BYTES


def analyze_literal(pattern: str):
    """Classify a rule field pattern for the literal tier.

    Returns ("any", None) — omitted field, no constraint;
            ("lits", [bytes, ...]) — full match any of the literals;
            ("prefix", bytes) — literal then ``.*`` (newline-guarded);
            None — general regex (tier 2).
    The analysis runs on the parsed AST so escaping/alternation exactly
    mirror the regex compiler's reading of the pattern."""
    if pattern == "":
        return ("any", None)
    try:
        ast = parse(pattern)
    except ParseError:
        return None  # surface the error via the regex compiler
    lit = _ast_literal(ast)
    if lit is not None:
        return ("lits", [lit]) if len(lit) <= LIT_W else None
    if _ast_dotstar(ast):
        return ("prefix", b"")
    if ast[0] == "cat" and len(ast[1]) >= 2 and _ast_dotstar(ast[1][-1]):
        head = (
            ast[1][0] if len(ast[1]) == 2 else ("cat", ast[1][:-1])
        )
        lit = _ast_literal(head)
        if lit is not None and len(lit) <= LIT_W:
            return ("prefix", lit)
    if ast[0] == "alt":
        outs = [_ast_literal(b) for b in ast[1]]
        if all(o is not None and len(o) <= LIT_W for o in outs):
            return ("lits", outs)
    return None


def analyze_rules(
    rules_with_remotes: list, tiers_on: bool = True
) -> tuple:
    """Classify every rule's method/path into the literal or regex tier
    and collect host/header patterns.  Shared by build_http_model and
    the rule-axis sharded builder (parallel/rulesharding.py)."""
    r = len(rules_with_remotes)
    m_rows: list[tuple[bytes, bool, int]] = []  # (needle, prefix, rule)
    p_rows: list[tuple[bytes, bool, int]] = []
    line_patterns: list[str] = []
    line_rule: list[int] = []
    line_slot: list[int] = []
    method_any = np.zeros((r,), bool)
    path_any = np.zeros((r,), bool)
    head_patterns: list[str] = []
    head_rule: list[int] = []
    head_count: list[int] = []

    for i, (_, h) in enumerate(rules_with_remotes):
        for slot, field in ((0, h.method), (1, h.path)):
            kind = analyze_literal(field) if tiers_on else (
                ("any", None) if field == "" else None
            )
            if kind is None:
                # Anchored full matches (Envoy regex_match semantics,
                # cilium_network_policy.h:50).
                line_patterns.append(f"^({field})$" if field else "^.*$")
                line_rule.append(i)
                line_slot.append(slot)
            elif kind[0] == "any":
                (method_any if slot == 0 else path_any)[i] = True
            elif kind[0] == "lits":
                rows = m_rows if slot == 0 else p_rows
                for lit in kind[1]:
                    rows.append((lit, False, i))
            else:  # prefix
                rows = m_rows if slot == 0 else p_rows
                rows.append((kind[1], True, i))
        n_head = 0
        if h.host:
            # Field names are case-insensitive and OWS after ':' is
            # optional (RFC 9110); match any casing and whitespace run.
            head_patterns.append(
                f"\r\n[Hh][Oo][Ss][Tt]:[ \t]*({h.host})[ \t]*\r\n"
            )
            head_rule.append(i)
            n_head += 1
        for header in h.headers:
            head_patterns.append(_header_pattern(header))
            head_rule.append(i)
            n_head += 1
        head_count.append(n_head)
    return (m_rows, p_rows, line_patterns, line_rule, line_slot,
            method_any, path_any, head_patterns, head_rule, head_count)


def lit_arrays(rows: list, n_pad: int | None = None,
               width: int | None = None):
    """Pack (needle, prefix, rule) literal rows into device-ready numpy
    arrays, padded to ``n_pad`` rows (dead rows have live=False).  The
    needle width is trimmed to the longest actual needle (rounded up to
    8, min 8) — the span-compare window build scales with it; pass
    ``width`` to unify shapes across shards."""
    n = max(len(rows), 1) if n_pad is None else n_pad
    if width is None:
        max_len = max((len(lit) for lit, _, _ in rows), default=0)
        width = min(LIT_W, max(8, (max_len + 7) // 8 * 8))
    w = width
    needle = np.zeros((n, w), np.uint8)
    nlen = np.zeros((n,), np.int32)
    prefix = np.zeros((n,), bool)
    rule = np.zeros((n,), np.int32)
    live = np.zeros((n,), bool)
    for k, (lit, pfx, ri) in enumerate(rows):
        needle[k, : len(lit)] = np.frombuffer(lit, np.uint8)
        nlen[k] = len(lit)
        prefix[k] = pfx
        rule[k] = ri
        live[k] = True
    return needle, nlen, prefix, rule, live


@jax.tree_util.register_pytree_node_class
@dataclass
class HttpBatchModel:
    # tier 1: literal method (slot m) / path (slot p) rows
    m_needle: jax.Array  # [Nm, LIT_W] uint8
    m_len: jax.Array  # [Nm] int32
    m_prefix: jax.Array  # [Nm] bool
    m_rule: jax.Array  # [Nm] int32
    m_live: jax.Array  # [Nm] bool (False = padding row)
    p_needle: jax.Array  # [Np, LIT_W] uint8
    p_len: jax.Array  # [Np] int32
    p_prefix: jax.Array  # [Np] bool
    p_rule: jax.Array  # [Np] int32
    p_live: jax.Array  # [Np] bool
    method_any: jax.Array  # [R] bool — field omitted
    path_any: jax.Array  # [R] bool
    # tier 2: general regex line patterns (anchored), slot-tagged
    line_nfa: "DeviceNfa | DeviceDfa | None"
    line_rule: jax.Array  # [PL] int32
    line_slot: jax.Array  # [PL] int32 — 0 method, 1 path
    # host/header patterns over the request head
    head_nfa: "DeviceNfa | DeviceDfa | None"
    head_rule: jax.Array  # [P] int32 — owning rule row
    head_count: jax.Array  # [R] int32 — head patterns per rule
    remote_ids: jax.Array  # [R, MAX_REMOTES] int32
    any_remote: jax.Array  # [R] bool
    n_rules: int = 0
    # Static slot usage (trace-time): which spans the regex tier must
    # actually search — an all-path pattern set skips the method-span
    # automaton pass entirely (half the regex-tier cost).
    has_method_rx: bool = False
    has_path_rx: bool = False
    # Per-rule compiled match kind (literal|regex|nfa) — static aux for
    # rule attribution labels, never device data.
    match_kinds: tuple = ()
    # Per-rule (remote_set_or_None, byte_free) reduction for the verdict
    # cache's byte-invariance analysis (policy/invariance.py) — host
    # aux like match_kinds, never device data, never a pytree leaf.
    invariant_rows: tuple = ()

    def tree_flatten(self):
        return (
            (self.m_needle, self.m_len, self.m_prefix, self.m_rule,
             self.m_live, self.p_needle, self.p_len, self.p_prefix,
             self.p_rule, self.p_live, self.method_any, self.path_any,
             self.line_nfa, self.line_rule, self.line_slot,
             self.head_nfa, self.head_rule, self.head_count,
             self.remote_ids, self.any_remote),
            (self.n_rules, self.has_method_rx, self.has_path_rx,
             self.match_kinds),
        )

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(
            *leaves, n_rules=aux[0],
            has_method_rx=aux[1], has_path_rx=aux[2],
            match_kinds=aux[3] if len(aux) > 3 else (),
        )

    def __call__(self, data, lengths, remotes):
        return http_verdicts(self, data, lengths, remotes)

    def verdicts_attr(self, data, lengths, remotes):
        return http_verdicts_attr(self, data, lengths, remotes)

    def dispatch_bare(self) -> "HttpBatchModel":
        """Shape-keyed dispatch marker (see R2d2BatchModel): the
        executable takes the tables as arguments, so every policy whose
        tables share shapes shares one compiled judge.  The host-only
        labels leave the pytree aux, so they do not key it."""
        return replace(self, match_kinds=(), invariant_rows=())


def _reduce_http_rows(rules_with_remotes) -> tuple:
    from ..policy.invariance import reduce_http_rows

    return reduce_http_rows(rules_with_remotes)


def build_http_model(
    rules_with_remotes: list[tuple[frozenset, PortRuleHTTP]],
    backend: str = "auto",
) -> HttpBatchModel | ConstVerdict:
    """Compile (allowed_remote_set, PortRuleHTTP) rows into device tables.

    Empty fields wildcard (reference: http.go — omitted fields allow all).
    ``backend`` governs the REGEX tier only: "nfa" (dense matmul),
    "dfa" (per-pattern gatherless blocks), "auto" (DFA-first at any
    size, NFA fallback on determinization blowup), or
    "regex-only" (disable the literal tier — every pattern through the
    automaton; used by parity tests)."""
    if not rules_with_remotes:
        return ConstVerdict(False)

    r = len(rules_with_remotes)
    rx_backend = "auto" if backend == "regex-only" else backend
    rows = analyze_rules(rules_with_remotes, tiers_on=backend != "regex-only")
    (m_rows, p_rows, line_patterns, line_rule, line_slot, method_any,
     path_any, head_patterns, head_rule, head_count) = rows

    packed_ids, any_remote = pack_remote_sets(
        [rs for rs, _ in rules_with_remotes]
    )

    mn, ml, mp, mr, mlive = lit_arrays(m_rows)
    pn, pl_, pp, pr, plive = lit_arrays(p_rows)

    line_tab = _compile_line_tables(line_patterns, rx_backend)
    head_tab = _compile_line_tables(head_patterns, rx_backend)

    def _tier(tab) -> str:
        from ..ops.nfa import DeviceNfa as _Nfa

        return "nfa" if isinstance(tab, _Nfa) else "regex"

    # Per-rule match kind for attribution: a rule is "literal" when its
    # method/path resolved to tier 0/1 and it carries no head patterns;
    # any automaton involvement labels it by that automaton's backend
    # ("nfa" dense matmul / "regex" per-pattern DFA), nfa winning when
    # a rule touches both tables.
    kinds = ["literal"] * r
    for i in line_rule:
        kinds[i] = _tier(line_tab)
    for i in head_rule:
        if kinds[i] != "nfa":
            kinds[i] = _tier(head_tab)

    return HttpBatchModel(
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
        method_any=jnp.asarray(method_any),
        path_any=jnp.asarray(path_any),
        line_nfa=line_tab,
        line_rule=jnp.asarray(np.asarray(line_rule, np.int32)),
        line_slot=jnp.asarray(np.asarray(line_slot, np.int32)),
        head_nfa=head_tab,
        head_rule=jnp.asarray(np.asarray(head_rule, np.int32).reshape(-1)),
        head_count=jnp.asarray(np.asarray(head_count, np.int32)),
        remote_ids=jnp.asarray(packed_ids),
        any_remote=jnp.asarray(any_remote),
        n_rules=r,
        has_method_rx=any(s == 0 for s in line_slot),
        has_path_rx=any(s == 1 for s in line_slot),
        match_kinds=tuple(kinds),
        invariant_rows=_reduce_http_rows(rules_with_remotes),
    )


def _compile_line_tables(patterns: list[str], backend: str):
    """Compile regex-tier patterns with the requested backend; None when
    the tier is empty.  DFA-first at every size since the integer-id
    step rewrite (ops/dfa.py) made the DFA ~12× the dense NFA."""
    from ..ops.rxsearch import compile_automaton

    if backend == "nfa":
        return device_nfa(compile_patterns(patterns)) if patterns else None
    return compile_automaton(patterns, backend)


def _first_occurrence_after(data, start, end, byte):
    """First ``byte`` at position > start and < end, else end."""
    f, l = data.shape
    pos = jnp.arange(l, dtype=jnp.int32)[None, :]
    valid = (pos > start[:, None]) & (pos < end[:, None])
    hit = (data == jnp.uint8(byte)) & valid
    return jnp.min(jnp.where(hit, pos, end[:, None]), axis=1)


def _last_in_span(data, start, end, byte):
    """Last ``byte`` at position >= start and < end, else -1."""
    f, l = data.shape
    pos = jnp.arange(l, dtype=jnp.int32)[None, :]
    valid = (pos >= start[:, None]) & (pos < end[:, None])
    hit = (data == jnp.uint8(byte)) & valid
    return jnp.max(jnp.where(hit, pos, jnp.int32(-1)), axis=1)


def _lit_hits(data, start, end, needle, nlen, prefix, live):
    """[F, N] literal-row hits on the span: exact rows need span == lit,
    prefix rows need span startswith lit AND no newline in the ``.*``
    remainder (regex ``.`` excludes \\n).  "No newline in the remainder"
    is exactly "the LAST span newline, if any, lies inside the needle
    bytes" — needle-internal newlines were matched literally."""
    exact = spans_equal_prefix(data, start, end, needle, nlen)
    starts = spans_start_with(data, start, end, needle, nlen)
    last_nl = _last_in_span(data, start, end, 0x0A)  # [F]
    no_nl_after = last_nl[:, None] < start[:, None] + nlen[None, :]
    hit = jnp.where(prefix[None, :], starts & no_nl_after, exact)
    return hit & live[None, :]


def _scatter_or(hits, rule_idx, n_rules):
    """[F, N] bool hits keyed by rule -> [F, R] bool any-hit."""
    f = hits.shape[0]
    counts = jnp.zeros((f, n_rules), jnp.int32)
    counts = counts.at[:, rule_idx].add(hits.astype(jnp.int32))
    return counts > 0


def _http_rule_hits(
    model: HttpBatchModel,
    data: jax.Array,  # [F, L] uint8 — complete request heads
    lengths: jax.Array,  # [F] int32 — head length incl. final CRLFCRLF
    remotes: jax.Array,  # [F] int32
):
    """Shared tokenize/tier pass; returns (complete [F] bool, head_len
    [F] int32, hits [F, R] bool) — the per-rule-row hit matrix both
    reductions (any-allow and first-match attribution) consume."""
    lengths = jnp.asarray(lengths, jnp.int32)
    remotes = jnp.asarray(remotes, jnp.int32)
    r = model.n_rules
    f = data.shape[0]

    # Head completeness: first CRLFCRLF.
    crlf2 = _first_crlfcrlf(data, lengths)
    complete = crlf2 < lengths
    head_len = crlf2 + 4

    # Request line tokenize.
    line_end = first_subsequence2(data, lengths, 0x0D, 0x0A)  # [F]
    sp1 = first_occurrence(data, line_end, 0x20)
    sp2 = _first_occurrence_after(data, sp1, line_end, 0x20)
    m_start, m_end = jnp.zeros_like(sp1), sp1
    p_start, p_end = sp1 + 1, sp2

    # Tier 0/1: wildcard flags + literal rows.
    method_ok = model.method_any[None, :] | _scatter_or(
        _lit_hits(data, m_start, m_end, model.m_needle, model.m_len,
                  model.m_prefix, model.m_live),
        model.m_rule, r,
    )
    path_ok = model.path_any[None, :] | _scatter_or(
        _lit_hits(data, p_start, p_end, model.p_needle, model.p_len,
                  model.p_prefix, model.p_live),
        model.p_rule, r,
    )

    # Tier 2: leftover regex patterns, evaluated on both spans and
    # routed by slot.  (Resolved at trace time; absent for pure-literal
    # rule sets — the common case.)
    if model.line_nfa is not None:
        search = (
            dfa_search_spans
            if isinstance(model.line_nfa, DeviceDfa)
            else nfa_search_spans
        )
        is_m = model.line_slot == 0
        if model.has_method_rx:
            rx_m = search(model.line_nfa, data, m_start, m_end)  # [F, PL]
            method_ok = method_ok | _scatter_or(
                rx_m & is_m[None, :], model.line_rule, r
            )
        if model.has_path_rx:
            rx_p = search(model.line_nfa, data, p_start, p_end)
            path_ok = path_ok | _scatter_or(
                rx_p & ~is_m[None, :], model.line_rule, r
            )

    # Host/header patterns searched over the head region starting at the
    # request line's CRLF (so every header line is CRLF-framed).
    if model.head_nfa is not None:
        head_search = (
            dfa_search_spans
            if isinstance(model.head_nfa, DeviceDfa)
            else nfa_search_spans
        )
        h_hits = head_search(
            model.head_nfa, data, line_end, head_len - 2
        )  # [F, P]
        # all-of per rule: count matches per rule == head_count
        per_rule = jnp.zeros((h_hits.shape[0], r), jnp.int32)
        per_rule = per_rule.at[:, model.head_rule].add(
            h_hits.astype(jnp.int32)
        )
        head_ok = per_rule >= model.head_count[None, :]
    else:
        head_ok = jnp.ones((f, r), bool)

    rok = remote_ok(remotes, model.remote_ids, model.any_remote)
    return complete, head_len, method_ok & path_ok & head_ok & rok


@jax.jit
def http_verdicts(
    model: HttpBatchModel,
    data: jax.Array,  # [F, L] uint8 — complete request heads
    lengths: jax.Array,  # [F] int32 — head length incl. final CRLFCRLF
    remotes: jax.Array,  # [F] int32
):
    """Returns (complete [F] bool, head_len [F] int32, allow [F] bool)."""
    complete, head_len, hits = _http_rule_hits(model, data, lengths, remotes)
    allow = jnp.any(hits, axis=1)
    return complete, head_len, allow & complete


@jax.jit
def http_verdicts_attr(
    model: HttpBatchModel,
    data: jax.Array,
    lengths: jax.Array,
    remotes: jax.Array,
):
    """http_verdicts plus the deciding rule row: (complete, head_len,
    allow, rule [F] int32).  ``rule`` is the FIRST matching rule row in
    the host oracle's walk order (exact-port rules then wildcard, one
    row per (rule, matcher) — build_http_model_for_port's flattening),
    or -1 where not allowed; an argmax over the same hit matrix in the
    same fused pass."""
    complete, head_len, hits = _http_rule_hits(model, data, lengths, remotes)
    allow = jnp.any(hits, axis=1) & complete
    return complete, head_len, allow, first_match(hits, allow)


def _first_crlfcrlf(data: jax.Array, lengths: jax.Array) -> jax.Array:
    f, l = data.shape
    pos = jnp.arange(l, dtype=jnp.int32)[None, :]

    def shifted(k):
        return jnp.concatenate(
            [data[:, k:], jnp.zeros((f, k), dtype=data.dtype)], axis=1
        )

    hit = (
        (data == 0x0D)
        & (shifted(1) == 0x0A)
        & (shifted(2) == 0x0D)
        & (shifted(3) == 0x0A)
        & ((pos + 3) < lengths[:, None])
    )
    return jnp.min(jnp.where(hit, pos, lengths[:, None]), axis=1)


def collect_http_rows(policy, ingress: bool, port: int):
    """Resolve the effective (remote_set, PortRuleHTTP) rows for
    (policy, direction, port), applying the reference's port cascade
    (exact port OR wildcard 0) — the HTTP twin of
    models/r2d2.collect_policy_rows.  Returns a ConstVerdict for the
    degenerate cases; exposed so rule-axis sharding can split the rows
    in the same flattened walk order the attribution contract names."""
    from ..proxylib.parsers.http import HttpRule

    if policy is None:
        return ConstVerdict(False)
    side = policy.ingress if ingress else policy.egress
    rows: list[tuple[frozenset, PortRuleHTTP]] = []
    for key in (port, 0):
        rules = side.by_port.get(key)
        if rules is None:
            continue
        if not rules.have_l7_rules or not rules.rules:
            return ConstVerdict(True)
        for rule in rules.rules:
            matchers = rule.l7_matchers or [None]
            for m in matchers:
                if m is None:
                    rows.append((rule.allowed_remotes, PortRuleHTTP()))
                else:
                    assert isinstance(m, HttpRule), f"not an http rule: {m!r}"
                    rows.append(
                        (
                            rule.allowed_remotes,
                            PortRuleHTTP(
                                method=m.method_src, path=m.path_src,
                                host=m.host_src, headers=list(m.headers),
                            ),
                        )
                    )
    if not rows:
        return ConstVerdict(False)
    return rows


def build_http_model_for_port(policy, ingress: bool, port: int,
                              backend: str = "auto"):
    """Compile the effective HTTP rule rows for (policy, direction,
    port) from a proxylib PolicyInstance — used by the sidecar's
    engine bind (see collect_http_rows for the cascade semantics)."""
    rows = collect_http_rows(policy, ingress, port)
    if isinstance(rows, ConstVerdict):
        return rows
    return build_http_model(rows, backend=backend)

"""Device-contract verification by ABSTRACT tracing (R8-R11, no device).

The AST half of R8-R11 (``rules_device.py``) pattern-matches hazards;
this half proves the contracts on the REAL verdict models by tracing
them abstractly — ``jax.eval_shape`` / ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` inputs, which runs under ``JAX_PLATFORMS=cpu``,
allocates no buffers, executes no model, and needs no TPU:

- **R8** — the model traces at all on abstract values (any Python
  branch on traced data would raise ConcretizationTypeError), the
  jaxpr is IDENTICAL across two traces (no wall-clock/rng/iteration-
  order dependence — the recompile-storm seed), and no output aval is
  weak-typed (weak types key per-caller-dtype executables downstream).
- **R9** — the traced jaxpr contains no host-callback or transfer
  primitives anywhere in its (recursive) equation tree: a ``.item()``
  or np coercion on a traced value would have failed the trace, and a
  smuggled ``pure_callback``/``device_put`` is a host round-trip the
  dispatch round would pay per batch.
- **R10** — every sharded step in ``parallel/rulesharding.py`` traces
  under 1x1, 1x2, 2x1 and 2x2 (flows, rules) CPU meshes: shard_map
  validates in_specs/out_specs against the function's actual arity and
  rank at trace time, so a drifted spec fails HERE instead of at first
  trace on a real multi-chip mesh.  The gate also pins stacked-leaf
  shard arity (an unbalanced/unpadded shard stack), forbids transfer
  primitives inside the stepped bodies, and requires trace determinism
  per mesh plus a shard-count-independent primitive set.
- **R11** — ``verdicts_attr``'s jaxpr is the verdict jaxpr plus a
  bounded attribution epilogue: output arity 4 with an int32 rule
  row, and an equation count within ``ATTR_EXTRA_EQNS`` of the plain
  twin — a second hit-matrix pass would ~double it.

Import of jax (and the models) happens inside the entry point so the
plain AST lint never pays for it; ``bin/cilium-lint
--device-contracts`` and tests/test_device_contracts.py are the
consumers.
"""

from __future__ import annotations

from .core import Finding

# An attribution epilogue is argmax + where + a handful of selects;
# a SECOND hit-matrix pass is dozens-to-hundreds of equations on these
# models.  The bound is deliberately loose enough for op-by-op jax
# version drift and tight enough that a doubled pass cannot hide.
ATTR_EXTRA_EQNS = 12

# Primitives that mean "host round-trip" when they appear inside a
# traced verdict computation.
_FORBIDDEN_PRIM_SUBSTRINGS = ("callback", "device_put", "infeed",
                              "outfeed")

_BATCH = 8
_WIDTH = 128


def _iter_eqns(jaxpr):
    """Every equation in a (closed) jaxpr, recursing into sub-jaxprs
    (pjit/closed_call/scan/cond carry theirs in params)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(v):
    import jax.extend.core as jcore

    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def _model_cases():
    """Tiny-but-representative models per engine family, each touching
    every tier the builders have (literal, prefix, regex, header)."""
    from ..models.base import SeamProbe
    from ..models.dns import build_dns_model_from_rows
    from ..models.http import build_http_model
    from ..models.r2d2 import build_r2d2_model_from_rows
    from ..policy.api import PortRuleHTTP
    from ..proxylib.parsers.dns import DnsRule

    http = build_http_model([
        (frozenset(), PortRuleHTTP(method="GET", path="/api/v1/.*")),
        (frozenset({7}), PortRuleHTTP(method="GET|HEAD",
                                      path="/x/[a-z]+",
                                      host="example[.]com")),
        (frozenset({3}), PortRuleHTTP()),
    ])
    r2d2 = build_r2d2_model_from_rows([
        (frozenset(), "OPEN", "/etc/.*"),
        (frozenset({3}), "", "docs/[a-z]+[.]txt"),
        (frozenset({3, 9}), "RETR", ""),
    ])
    dns = build_dns_model_from_rows([
        (frozenset(), DnsRule(name="www.example.com")),
        (frozenset({3}), DnsRule(pattern="*.svc.cluster.local")),
        (frozenset({3, 9}), DnsRule(regex="internal[.](a|b)")),
        (frozenset({7}), None),
    ])
    return [
        ("http", "cilium_tpu/models/http.py", http),
        ("r2d2", "cilium_tpu/models/r2d2.py", r2d2),
        ("dns", "cilium_tpu/models/dns.py", dns),
        ("seam_probe", "cilium_tpu/models/base.py", SeamProbe()),
    ]


def _abstract_args():
    import jax
    import jax.numpy as jnp

    return (
        jax.ShapeDtypeStruct((_BATCH, _WIDTH), jnp.uint8),
        jax.ShapeDtypeStruct((_BATCH,), jnp.int32),
        jax.ShapeDtypeStruct((_BATCH,), jnp.int32),
    )


def _check_model(name, path, model):
    import jax

    data, lengths, remotes = _abstract_args()
    findings = []

    def fail(rule, msg):
        findings.append(Finding(
            rule, path, 0, 0, f"[device-contract:{name}] {msg}",
            symbol=name,
        ))

    # R8: abstract trace succeeds, twice, identically.
    try:
        jx1 = jax.make_jaxpr(model.__call__)(data, lengths, remotes)
        jx2 = jax.make_jaxpr(model.__call__)(data, lengths, remotes)
    except Exception as e:  # noqa: BLE001 — any trace failure is the finding
        fail("R8", f"verdict model failed to trace abstractly "
                   f"(Python branching on traced data?): {e!r}")
        return findings
    if str(jx1) != str(jx2):
        fail("R8", "two traces of the verdict model produced "
                   "DIFFERENT jaxprs — trace-time nondeterminism "
                   "(wall clock / rng / iteration order) and a "
                   "recompile per dispatch on the hot path")
    for i, aval in enumerate(jx1.out_avals):
        if getattr(aval, "weak_type", False):
            fail("R8", f"verdict output {i} has weak_type=True: a "
                       f"Python-scalar constant leaked into the "
                       f"output dtype lattice — downstream consumers "
                       f"key a separate executable per caller dtype "
                       f"mix")

    # R9: no host-callback / transfer primitives in the whole tree.
    for eqn in _iter_eqns(jx1.jaxpr):
        pname = eqn.primitive.name
        if any(s in pname for s in _FORBIDDEN_PRIM_SUBSTRINGS):
            fail("R9", f"traced verdict computation contains host "
                       f"round-trip primitive {pname!r} — a device->"
                       f"host sync inside the dispatch round")

    # R11: fused attribution — arity-4, int32 rule row, bounded
    # equation delta vs the plain twin.
    if not hasattr(model, "verdicts_attr"):
        return findings
    try:
        jxa = jax.make_jaxpr(model.verdicts_attr)(data, lengths, remotes)
    except Exception as e:  # noqa: BLE001
        fail("R11", f"verdicts_attr failed to trace abstractly: {e!r}")
        return findings
    if len(jxa.out_avals) != 4:
        fail("R11", f"verdicts_attr returns {len(jxa.out_avals)} "
                    f"outputs, contract is 4 (complete, len, allow, "
                    f"rule)")
    else:
        rule_aval = jxa.out_avals[3]
        if str(rule_aval.dtype) != "int32":
            fail("R11", f"attribution rule row dtype is "
                        f"{rule_aval.dtype}, contract is int32 (the "
                        f"wire packs <i4)")
    n_plain = sum(1 for _ in _iter_eqns(jx1.jaxpr))
    n_attr = sum(1 for _ in _iter_eqns(jxa.jaxpr))
    if n_attr > n_plain + ATTR_EXTRA_EQNS:
        fail("R11", f"verdicts_attr traces to {n_attr} equations vs "
                    f"{n_plain} for the plain verdict (+{ATTR_EXTRA_EQNS} "
                    f"allowed): attribution is recomputing the hit "
                    f"matrix — a SECOND device pass the parity tests "
                    f"cannot see")
    for eqn in _iter_eqns(jxa.jaxpr):
        pname = eqn.primitive.name
        if any(s in pname for s in _FORBIDDEN_PRIM_SUBSTRINGS):
            fail("R9", f"attributed verdict computation contains "
                       f"host round-trip primitive {pname!r}")
    return findings


# Mesh aspect ratios the R10 gate traces every sharded step under —
# both axes exercised alone and together so a spec that only works
# when an axis is trivial cannot pass.  The 4-wide rows cover the
# flow widths the reshape ladder lands on (4 -> 2 -> 1) and the
# >2-wide extents ROADMAP 5b's uncapped flow sharding serves; rows
# the local device count cannot fill are skipped as before.
_SHARD_MESHES = ((1, 1), (1, 2), (2, 1), (2, 2), (4, 1), (4, 2))

_SHARD_PATH = "cilium_tpu/parallel/rulesharding.py"


def check_stacked_model(stacked, mesh) -> list[str]:
    """R10 structural half: every leaf of a stacked shard model must
    lead with a shard dim equal to the mesh's RULE_AXIS extent — the
    split-balanced + pad_tables contract.  A builder that skipped the
    cross-shard padding (or stacked for the wrong shard count) shows
    up here before shard_map ever traces.  Returns problem strings."""
    import jax

    from ..parallel.mesh import RULE_AXIS

    n = mesh.shape[RULE_AXIS]
    probs = []
    for i, leaf in enumerate(jax.tree_util.tree_leaves(stacked)):
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape or shape[0] != n:
            probs.append(
                f"stacked leaf {i} shape {shape} does not lead with "
                f"the RULE_AXIS shard dim {n} (unbalanced/unpadded "
                f"shard stack)"
            )
    return probs


def _step_jaxpr_findings(name: str, jx, fail) -> None:
    """Shared per-step jaxpr checks: no host-transfer primitives
    anywhere inside the stepped body, and a second trace must be
    byte-identical (trace-time nondeterminism would recompile per
    shard-count/mesh in production)."""
    for eqn in _iter_eqns(jx.jaxpr):
        pname = eqn.primitive.name
        if any(s in pname for s in _FORBIDDEN_PRIM_SUBSTRINGS):
            fail(f"[device-contract:{name}] stepped body contains "
                 f"host round-trip primitive {pname!r} — a device->"
                 f"host sync inside the mesh round")


def _check_sharded():
    """R10: every sharded step in ``parallel/rulesharding.py`` traces
    under every ``_SHARD_MESHES`` (flows, rules) CPU mesh — shard_map
    validates in_specs/out_specs against the step functions' actual
    arity and rank at trace time, so a drifted spec fails HERE instead
    of at first trace on a real multi-chip mesh.  On top of the trace:
    stacked-leaf shard arity (the unbalanced-pad pin), no transfer
    primitives inside the stepped bodies, repeat-trace jaxpr
    determinism per mesh, and a shard-count-independent primitive set
    (the computation's SHAPE may change with the mesh; its structure
    must not).  Meshes the local device count cannot fill are skipped
    (the 1x1 floor always runs)."""
    import jax
    import numpy as np

    from ..kafka.request import RequestMessage
    from ..models.dns import (
        build_dns_model_from_rows,
        dns_verdicts,
        dns_verdicts_attr,
    )
    from ..models.kafka import build_kafka_model, encode_requests
    from ..models.r2d2 import (
        build_r2d2_model_from_rows,
        r2d2_verdicts,
        r2d2_verdicts_attr,
    )
    from ..proxylib.parsers.dns import DnsRule
    from ..parallel import rulesharding
    from ..parallel.mesh import flow_mesh
    from ..policy.api import PortRuleKafka

    findings = []

    def fail(msg):
        findings.append(Finding("R10", _SHARD_PATH, 0, 0, msg))

    model = build_r2d2_model_from_rows([
        (frozenset(), "OPEN", "/etc/.*"),
        (frozenset({3}), "", "docs/[a-z]+"),
    ])
    dmodel = build_dns_model_from_rows([
        (frozenset(), DnsRule(name="www.example.com")),
        (frozenset({3}), DnsRule(pattern="*.example.com")),
    ])
    kr = PortRuleKafka(topic="orders")
    kr.sanitize()
    kmodel = build_kafka_model([(frozenset(), kr)])
    kbatch = encode_requests(
        [RequestMessage(0, 2, 1, "c", ["orders"], parsed=True)] * _BATCH
    )
    data, lengths, remotes = _abstract_args()
    devices = jax.devices()
    prim_sets: dict[str, dict] = {}
    traced_any = False
    for n_flow, n_rule in _SHARD_MESHES:
        if n_flow * n_rule > len(devices):
            continue
        try:
            mesh = flow_mesh(n_flow=n_flow, n_rule=n_rule,
                             devices=devices[: n_flow * n_rule])
        except Exception as e:  # noqa: BLE001
            fail(f"[device-contract:mesh] cannot build the "
                 f"{n_flow}x{n_rule} CPU mesh: {e!r}")
            continue
        traced_any = True
        stacked = rulesharding._stack_models([model] * n_rule)
        for prob in check_stacked_model(stacked, mesh):
            fail(f"[device-contract:stacked@{n_flow}x{n_rule}] {prob}")
        dstacked = rulesharding._stack_models([dmodel] * n_rule)
        for prob in check_stacked_model(dstacked, mesh):
            fail(f"[device-contract:dns-stacked@{n_flow}x{n_rule}] "
                 f"{prob}")
        offsets = rulesharding.shard_offsets(2, n_rule)
        cases = (
            ("sharded_verdict_step",
             rulesharding.sharded_verdict_step(mesh, r2d2_verdicts),
             (stacked, data, lengths, remotes), 3),
            ("sharded_verdict_step_attr",
             rulesharding.sharded_verdict_step_attr(
                 mesh, r2d2_verdicts_attr),
             (stacked, offsets, data, lengths, remotes), 4),
            ("sharded_dns_step",
             rulesharding.sharded_verdict_step(mesh, dns_verdicts),
             (dstacked, data, lengths, remotes), 3),
            ("sharded_dns_step_attr",
             rulesharding.sharded_verdict_step_attr(
                 mesh, dns_verdicts_attr),
             (dstacked, offsets, data, lengths, remotes), 4),
            ("sharded_kafka_step",
             rulesharding.sharded_kafka_step(mesh),
             (rulesharding._stack_models([kmodel] * n_rule),
              kbatch, np.ones(_BATCH, np.int32)), 1),
        )
        for name, step, args, n_out in cases:
            tag = f"{name}@{n_flow}x{n_rule}"
            try:
                jx1 = jax.make_jaxpr(step)(*args)
                jx2 = jax.make_jaxpr(step)(*args)
            except Exception as e:  # noqa: BLE001
                fail(f"[device-contract:{tag}] failed to trace — "
                     f"in_specs/out_specs drifted from the step "
                     f"function's signature or shard arity: {e!r}")
                continue
            outs = jx1.out_avals
            if len(outs) != n_out:
                fail(f"[device-contract:{tag}] expected {n_out} "
                     f"outputs, got {len(outs)}")
            if name == "sharded_verdict_step_attr" and len(outs) == 4 \
                    and str(outs[3].dtype) != "int32":
                fail(f"[device-contract:{tag}] global first-match "
                     f"rule row dtype is {outs[3].dtype}, contract "
                     f"is int32")
            if str(jx1) != str(jx2):
                fail(f"[device-contract:{tag}] two traces produced "
                     f"DIFFERENT jaxprs — trace-time nondeterminism "
                     f"recompiles per mesh in production")
            _step_jaxpr_findings(tag, jx1, fail)
            prims = frozenset(
                eqn.primitive.name for eqn in _iter_eqns(jx1.jaxpr)
            )
            prev = prim_sets.setdefault(name, {})
            for other, oprims in prev.items():
                if prims != oprims:
                    fail(f"[device-contract:{name}] primitive set "
                         f"differs between meshes {other} and "
                         f"{n_flow}x{n_rule}: "
                         f"{sorted(prims ^ oprims)} — the stepped "
                         f"computation's structure must not depend "
                         f"on the shard count")
            prev[f"{n_flow}x{n_rule}"] = prims
    if not traced_any:
        fail("[device-contract:mesh] no (flows, rules) mesh could be "
             "built from the available devices")
    return findings


def check_reshape_ladder(build=None) -> list[Finding]:
    """R10 reshape half: every DEGRADED rung the width ladder can land
    on (lose a chip, reshape over the survivors — flow extent 4 -> 2
    -> 1 at a preserved-or-halved rule extent) assembles through
    ``mesh_model_from_family_rows`` and traces with the SAME structure
    as full width: stacked-leaf shard arity against the rung's
    RULE_AXIS, a retained single-chip fallback twin (the next
    demotion's landing rung), repeat-trace jaxpr determinism, no
    host-transfer primitives, and a width-INDEPENDENT primitive set —
    a reshape may change shapes, never the stepped computation.
    ``build`` is the assembly seam under audit, injectable so the
    sensitivity unit can pin that a broken reshape model fails here.
    Rungs the local device count cannot fill are skipped; a single
    device has no mesh rungs at all (empty findings)."""
    import jax

    from ..parallel import rulesharding
    from ..parallel.mesh import (
        FLOW_AXIS,
        RULE_AXIS,
        flow_mesh,
        reshape_mesh,
    )
    from ..proxylib.parsers.dns import DnsRule

    if build is None:
        build = rulesharding.mesh_model_from_family_rows

    findings: list[Finding] = []

    def fail(msg):
        findings.append(Finding("R10", _SHARD_PATH, 0, 0, msg))

    family_rows = {
        "r2d2": [
            (frozenset(), "OPEN", "/etc/.*"),
            (frozenset({3}), "", "docs/[a-z]+"),
            (frozenset({7}), "READ", "/pub/.*"),
        ],
        "dns": [
            (frozenset(), DnsRule(name="www.example.com")),
            (frozenset({3}), DnsRule(pattern="*.example.com")),
        ],
    }
    devices = list(jax.devices())
    # Full-width origin: the widest layout the local devices fill
    # (8 CPU devices -> 4x2, 4 -> 2x2, 2 -> 2x1); rule extent 2 when
    # possible so the rule-preserving half of reshape_mesh is on the
    # audited path.
    n_rule = 2 if len(devices) >= 4 else 1
    n_flow = len(devices) // n_rule
    if n_flow < 1 or n_flow * n_rule < 2:
        return findings
    n_flow = min(1 << (n_flow.bit_length() - 1), 4)
    full = flow_mesh(n_flow=n_flow, n_rule=n_rule,
                     devices=devices[: n_flow * n_rule])
    # Walk the ladder: drop the tail chip one at a time and reshape
    # over what remains, collecting each DISTINCT rung width.
    rungs = [("full", full)]
    seen = {(n_flow, n_rule)}
    survivors = devices[: n_flow * n_rule]
    while len(survivors) > 1:
        survivors = survivors[:-1]
        rung = reshape_mesh(survivors, n_rule,
                            max_flow=full.shape[FLOW_AXIS])
        if rung is None:
            break
        key = (rung.shape[FLOW_AXIS], rung.shape[RULE_AXIS])
        if key in seen:
            continue
        seen.add(key)
        rungs.append((f"{key[0]}x{key[1]}", rung))
    args = _abstract_args()
    prim_sets: dict[str, dict] = {}
    for rung_name, mesh in rungs:
        for family, rows in family_rows.items():
            tag = f"reshape:{family}@{rung_name}"
            try:
                model = build(family, rows, mesh)
            except Exception as e:  # noqa: BLE001
                fail(f"[device-contract:{tag}] reshaped assembly "
                     f"raised: {e!r}")
                continue
            if not isinstance(model, rulesharding.ShardedVerdictModel):
                fail(f"[device-contract:{tag}] assembly folded to "
                     f"{type(model).__name__} — these rows must build "
                     f"a mesh-resident model at every rung")
                continue
            for prob in check_stacked_model(model.stacked, mesh):
                fail(f"[device-contract:{tag}] {prob}")
            if model.n_shards != mesh.shape[RULE_AXIS]:
                fail(f"[device-contract:{tag}] shard_offsets arity "
                     f"{model.n_shards} != rung RULE_AXIS extent "
                     f"{mesh.shape[RULE_AXIS]} (stale full-width "
                     f"offsets would mis-attribute global rule rows)")
            if model.fallback is None:
                fail(f"[device-contract:{tag}] reshaped model carries "
                     f"no single-chip fallback twin — the NEXT device "
                     f"loss on this rung would have nothing to demote "
                     f"to")
            try:
                jx1 = jax.make_jaxpr(model.verdicts_attr)(*args)
                jx2 = jax.make_jaxpr(model.verdicts_attr)(*args)
            except Exception as e:  # noqa: BLE001
                fail(f"[device-contract:{tag}] failed to trace the "
                     f"reshaped attributed step: {e!r}")
                continue
            if str(jx1) != str(jx2):
                fail(f"[device-contract:{tag}] two traces produced "
                     f"DIFFERENT jaxprs — a nondeterministic reshape "
                     f"rebuild recompiles per fault in production")
            _step_jaxpr_findings(tag, jx1, fail)
            prims = frozenset(
                eqn.primitive.name for eqn in _iter_eqns(jx1.jaxpr)
            )
            prev = prim_sets.setdefault(family, {})
            for other, oprims in prev.items():
                if prims != oprims:
                    fail(f"[device-contract:reshape:{family}] "
                         f"primitive set differs between rungs "
                         f"{other} and {rung_name}: "
                         f"{sorted(prims ^ oprims)} — a degraded "
                         f"width must change shapes, not the stepped "
                         f"computation")
            prev[rung_name] = prims
    return findings


def check_device_contracts() -> list[Finding]:
    """Run every abstract device-contract check; returns findings
    (empty = all contracts hold).  Safe without a TPU: everything runs
    as abstract evaluation on the CPU backend."""
    import os

    import jax

    try:
        # Force the CPU backend BEFORE any model import touches a
        # device: abstract tracing needs no chip, and on a TPU host
        # (or this container, where libtpu init blocks for minutes)
        # grabbing the real backend for an eval_shape pass is pure
        # waste.  No-op/raises harmlessly when a backend is already
        # initialized (pytest's conftest pins cpu anyway).
        jax.config.update("jax_platforms", "cpu")
        if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""
        ):
            # The R10 gate traces real 2x2 meshes: ask the (not yet
            # initialized) CPU backend for 4 virtual devices.  Read at
            # backend init — harmless if the backend is already up
            # (the multi-device meshes are then skipped, the 1x1
            # floor still runs).
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            )
    except Exception:  # noqa: BLE001 — backend already up; proceed
        pass
    findings: list[Finding] = []
    for name, path, model in _model_cases():
        findings.extend(_check_model(name, path, model))
    findings.extend(_check_sharded())
    findings.extend(check_reshape_ladder())
    findings.extend(check_shape_closure())
    return findings


# --- R16: shape-closure audit ---------------------------------------------
#
# "No new jit shapes" was prose until now.  This half makes it a gate:
# enumerate the DECLARED executable-shape universe (the service's
# MIN_BUCKET pow2 ladder, pack_buckets' width ladder, the
# MIN_RULE_BUCKET churn buckets, the mesh shard extents, bounded by
# SHAPE_CACHE_MAX), trace the full serving surface abstractly
# (eval_shape — no device, no execution), and assert the traced
# executable set is CLOSED under that universe.  A future engine that
# ships an unbucketed axis — one raw batch size, one unpadded rule
# table — fails HERE, as a tier-1 gate, instead of silently
# re-tracing per shape on the hot path.

_AXIS_CAP = 1 << 22


def _pow2_set(floor: int, cap: int = _AXIS_CAP) -> frozenset:
    out = set()
    v = int(floor)
    while v <= cap:
        out.add(v)
        v *= 2
    return frozenset(out)


def enumerate_shape_universe() -> dict:
    """The statically-declared executable-shape universe, resolved
    from the SAME constants the serving path derives its shapes from
    (a second copy could drift and silently unpair the gate)."""
    from ..models.r2d2 import MIN_RULE_BUCKET
    from ..sidecar.service import VerdictService
    from ..utils import defaults

    return {
        # Dispatch batch (flow) axis: pow2 from the greedy floor; the
        # remote floor (MIN_BUCKET) and every pack_buckets f_pad are
        # members by construction.
        "flows": _pow2_set(VerdictService.MIN_BUCKET_GREEDY),
        # Row width axis: pack_buckets widens base_width << k.
        "widths": _pow2_set(defaults.BATCH_WIDTH),
        # Rule-table churn buckets (models/r2d2.MIN_RULE_BUCKET).
        "rules": _pow2_set(MIN_RULE_BUCKET),
        # Mesh shard extents: pow2, flow extent capped at the
        # smallest dispatch bucket so every bucket divides it.
        "mesh": _pow2_set(1, VerdictService.MIN_BUCKET_GREEDY),
        "cache_max": VerdictService.SHAPE_CACHE_MAX,
    }


_R16_PATH = "cilium_tpu/sidecar/service.py"


def audit_traced_shapes(traced, universe) -> list[Finding]:
    """R16 closure primitive: every traced executable's (flows, width)
    axes must be members of the enumerated universe.  ``traced`` is an
    iterable of (tag, path, n_flows_or_None, width_or_None)."""
    findings = []
    for tag, path, n_flows, width in traced:
        if n_flows is not None and n_flows not in universe["flows"]:
            findings.append(Finding(
                "R16", path, 0, 0,
                f"[shape-closure:{tag}] traced executable batch axis "
                f"{n_flows} is OUTSIDE the declared bucket universe "
                f"(pow2 ladder from MIN_BUCKET_GREEDY): this shape "
                f"re-traces every time it recurs on the hot path",
                symbol=tag,
            ))
        if width is not None and width not in universe["widths"]:
            findings.append(Finding(
                "R16", path, 0, 0,
                f"[shape-closure:{tag}] traced executable row width "
                f"{width} is OUTSIDE the declared width ladder "
                f"(batch_width << k): an unbucketed width axis keys a "
                f"new executable per frame size",
                symbol=tag,
            ))
    return findings


def _bare_shape_key(model):
    """The churn cache's key derivation, locally: treedef + leaf
    shapes/dtypes of the model's bare dispatch pytree (None when the
    model is not shape-keyed)."""
    import jax

    bare_fn = getattr(model, "dispatch_bare", None)
    if bare_fn is None:
        return None
    leaves, treedef = jax.tree_util.tree_flatten(bare_fn())
    return (
        str(treedef),
        tuple((tuple(lf.shape), str(lf.dtype)) for lf in leaves),
    )


def audit_rule_axis(tag: str, path: str, build) -> list[Finding]:
    """Rule-axis churn closure: same-bucket rebuilds must key the SAME
    executable.  ``build(n)`` compiles an n-rule model; 2 and 3 rules
    share the MIN_RULE_BUCKET bucket, so their shape keys must be
    identical — an unbucketed builder keys a new executable per rule
    count, i.e. a full re-trace on every policy churn."""
    k2 = _bare_shape_key(build(2))
    k3 = _bare_shape_key(build(3))
    if k2 is None or k3 is None:
        return [Finding(
            "R16", path, 0, 0,
            f"[shape-closure:{tag}] model exposes no dispatch_bare "
            f"shape key — the shape-keyed churn cache cannot cover it",
            symbol=tag,
        )]
    if k2 != k3:
        return [Finding(
            "R16", path, 0, 0,
            f"[shape-closure:{tag}] rule axis is UNBUCKETED: a 2-rule "
            f"and a 3-rule table key DIFFERENT executables — every "
            f"policy churn re-traces instead of hitting the "
            f"shape-keyed cache; pad the row axis to the "
            f"MIN_RULE_BUCKET power-of-two ladder",
            symbol=tag,
        )]
    return []


def check_shape_closure() -> list[Finding]:
    """R16 abstract-trace half: trace the full serving surface — all
    four engine families (r2d2/http/kafka/dns), single-chip + sharded,
    attr + plain — via eval_shape, plus the real pack_buckets packer
    over adversarial frame lengths, and assert every traced executable
    shape is a member of the enumerated universe, the distinct-
    executable count fits SHAPE_CACHE_MAX, and the shape-keyed rule
    axes are churn-closed."""
    import jax
    import numpy as np

    from ..kafka.request import RequestMessage
    from ..models.dns import (
        build_dns_model_from_rows,
        dns_verdicts,
        dns_verdicts_attr,
    )
    from ..models.http import build_http_model
    from ..models.kafka import (
        build_kafka_model,
        encode_requests,
        kafka_verdicts,
    )
    from ..models.r2d2 import (
        build_r2d2_model_from_rows,
        r2d2_verdicts,
        r2d2_verdicts_attr,
    )
    from ..parallel import rulesharding
    from ..parallel.mesh import FLOW_AXIS, RULE_AXIS, flow_mesh
    from ..policy.api import PortRuleHTTP, PortRuleKafka
    from ..proxylib.parsers.dns import DnsRule
    from ..sidecar.reasm import Reassembler
    from ..sidecar.service import VerdictService
    from ..utils import defaults

    universe = enumerate_shape_universe()
    findings: list[Finding] = []
    traced: list[tuple] = []
    exes: set = set()

    # Rule-axis probes hold the regex VOCABULARY fixed across n: the
    # automaton state/class axes legitimately scale with the compiled
    # pattern set (bucketing them is the open ROADMAP churn-cache
    # extension), so only the row axis may vary here — that is the
    # axis MIN_RULE_BUCKET declares closed.
    def rows_r2d2(n):
        return [(frozenset({i}), "", "/p/.*") for i in range(n)]

    def rows_dns(n):
        return [
            (frozenset({i}), DnsRule(name="w.example.com"))
            for i in range(n)
        ]

    r2 = build_r2d2_model_from_rows(rows_r2d2(2), bucket=True)
    dn = build_dns_model_from_rows(rows_dns(2), bucket=True)
    ht = build_http_model([
        (frozenset(), PortRuleHTTP(method="GET", path="/api/.*")),
        (frozenset({3}), PortRuleHTTP()),
    ])
    kr = PortRuleKafka(topic="orders")
    kr.sanitize()
    km = build_kafka_model([(frozenset(), kr)])
    mods = {
        "r2d2": "cilium_tpu/models/r2d2.py",
        "dns": "cilium_tpu/models/dns.py",
        "http": "cilium_tpu/models/http.py",
        "kafka": "cilium_tpu/models/kafka.py",
    }

    def trace(tag, path, fn, args, flows, width):
        try:
            jax.eval_shape(fn, *args)
        except Exception as e:  # noqa: BLE001 — any trace failure gates
            findings.append(Finding(
                "R16", path, 0, 0,
                f"[shape-closure:{tag}] serving-surface trace "
                f"failed: {e!r}",
                symbol=tag,
            ))
            return
        traced.append((tag, path, flows, width))
        exes.add(tag)

    # Single-chip surface, attr + plain, over the two smallest flow
    # buckets x two widths (membership, not exhaustiveness: the
    # universe is infinite pow2; the serving path can only DERIVE
    # members, which the AST half of R16 pins).
    b0 = VerdictService.MIN_BUCKET_GREEDY
    w0 = defaults.BATCH_WIDTH
    for b in (b0, 2 * b0):
        for w in (w0, 2 * w0):
            args = (
                jax.ShapeDtypeStruct((b, w), np.uint8),
                jax.ShapeDtypeStruct((b,), np.int32),
                jax.ShapeDtypeStruct((b,), np.int32),
            )
            for name, model in (("r2d2", r2), ("dns", dn),
                                ("http", ht)):
                trace(f"{name}.plain@{b}x{w}", mods[name],
                      model.__call__, args, b, w)
                attr = getattr(model, "verdicts_attr", None)
                if attr is not None:
                    trace(f"{name}.attr@{b}x{w}", mods[name],
                          attr, args, b, w)
    kbatch = encode_requests(
        [RequestMessage(0, 2, 1, "c", ["orders"], parsed=True)] * b0
    )
    trace(f"kafka.plain@{b0}", mods["kafka"], kafka_verdicts,
          (km, kbatch, np.ones(b0, np.int32)), b0, None)

    # Sharded surface: every mesh the local device count can fill;
    # shard extents must be universe members, and the stepped
    # executables trace at a bucketed global shape.
    devices = jax.devices()
    for n_flow, n_rule in ((1, 2), (2, 1), (2, 2)):
        if n_flow * n_rule > len(devices):
            continue
        mesh = flow_mesh(n_flow=n_flow, n_rule=n_rule,
                         devices=devices[: n_flow * n_rule])
        for axis, extent in (("flows", mesh.shape[FLOW_AXIS]),
                             ("rules", mesh.shape[RULE_AXIS])):
            if extent not in universe["mesh"]:
                findings.append(Finding(
                    "R16", _SHARD_PATH, 0, 0,
                    f"[shape-closure:mesh@{n_flow}x{n_rule}] {axis} "
                    f"shard extent {extent} is outside the declared "
                    f"mesh universe (pow2, flow extent <= the "
                    f"smallest dispatch bucket)",
                ))
        args = (
            jax.ShapeDtypeStruct((b0, w0), np.uint8),
            jax.ShapeDtypeStruct((b0,), np.int32),
            jax.ShapeDtypeStruct((b0,), np.int32),
        )
        offsets = rulesharding.shard_offsets(2, n_rule)
        for name, model, vfn, afn in (
            ("r2d2", r2, r2d2_verdicts, r2d2_verdicts_attr),
            ("dns", dn, dns_verdicts, dns_verdicts_attr),
        ):
            stacked = rulesharding._stack_models([model] * n_rule)
            trace(f"{name}.sharded@{n_flow}x{n_rule}", _SHARD_PATH,
                  rulesharding.sharded_verdict_step(mesh, vfn),
                  (stacked,) + args, b0, w0)
            trace(f"{name}.sharded_attr@{n_flow}x{n_rule}",
                  _SHARD_PATH,
                  rulesharding.sharded_verdict_step_attr(mesh, afn),
                  (stacked, offsets) + args, b0, w0)
        trace(f"kafka.sharded@{n_flow}x{n_rule}", _SHARD_PATH,
              rulesharding.sharded_kafka_step(mesh),
              (rulesharding._stack_models([km] * n_rule), kbatch,
               np.ones(b0, np.int32)), b0, None)

    # The real packer's output shapes over adversarial frame lengths
    # (minimal, exact-width, width+1, a multi-bucket jump) must land
    # in the same universe the dispatch caches enumerate.
    reasm = Reassembler()
    frame_lens = [2, w0, w0 + 1, 4 * w0 + 5, 17]
    payloads = [b"x" * (fl - 2) + b"\r\n" for fl in frame_lens]
    lens = np.array([len(p) for p in payloads], np.int64)
    ends = np.cumsum(lens)
    rnd = reasm.ingest(
        np.arange(1, len(payloads) + 1, dtype=np.int64),
        ends - lens, lens,
        np.frombuffer(b"".join(payloads), np.uint8),
    )
    for _fi, data, _lengths, _rem in reasm.pack_buckets(
        rnd, w0, b0, np.zeros(len(payloads), np.int32)
    ):
        f_pad, wv = data.shape
        traced.append((f"pack_buckets@{f_pad}x{wv}",
                       "cilium_tpu/sidecar/reasm.py", int(f_pad),
                       int(wv)))

    findings.extend(audit_traced_shapes(traced, universe))
    if len(exes) > universe["cache_max"]:
        findings.append(Finding(
            "R16", _R16_PATH, 0, 0,
            f"[shape-closure] {len(exes)} distinct serving-surface "
            f"executables exceed SHAPE_CACHE_MAX="
            f"{universe['cache_max']} — the executable cache would "
            f"thrash-evict on the hot path",
        ))
    findings.extend(audit_rule_axis(
        "r2d2.rule-axis", mods["r2d2"],
        lambda n: build_r2d2_model_from_rows(rows_r2d2(n),
                                             bucket=True),
    ))
    findings.extend(audit_rule_axis(
        "dns.rule-axis", mods["dns"],
        lambda n: build_dns_model_from_rows(rows_dns(n), bucket=True),
    ))
    return findings

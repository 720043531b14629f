"""Compile the served verdict steps for a described TPU v5e, no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: what it refuses (a program that does not fit,
a kernel that cannot be partitioned) fails here at no chip time.  Each
step compiles at the served shape: 2048 flows (``batch_flows``) by 256
bytes (``batch_width``), and 2048 by 512 for the HTTP judge, whose
base width is 512.  Nothing runs, so nothing here is a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

FLOWS, WIDTH = 2048, 256  # DaemonConfig batch_flows x batch_width
HTTP_WIDTH = 512  # HttpSidecarEngine.MIN_WIDTH
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    prev = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    from cilium_tpu.parallel.mesh import FLOW_AXIS, RULE_AXIS

    devs = np.asarray(topo.devices[:4]).reshape(2, 2)
    return Mesh(devs, (FLOW_AXIS, RULE_AXIS))


@pytest.fixture
def tpu_trace(monkeypatch):
    """Trace the TPU formulations: the ops pick theirs from
    ``jax.default_backend()``, which is the CPU here.  The compile
    cache is off (a TPU executable cannot be read back without a chip)
    and the trace caches are cleared on both sides, so no CPU trace
    leaks into these compiles and no TPU trace into later tests."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    monkeypatch.undo()
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _args(sharding, flows=FLOWS, width=WIDTH):
    return (
        jax.ShapeDtypeStruct((flows, width), jnp.uint8, sharding=sharding),
        jax.ShapeDtypeStruct((flows,), jnp.int32, sharding=sharding),
        jax.ShapeDtypeStruct((flows,), jnp.int32, sharding=sharding),
    )


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
    )
    assert 0 < used < V5E_HBM_BYTES, mem
    return used


def _stress_http_rows(backend_split: bool):
    """One bench.py stress policy: 12 literal, 6 DFA-tier and 2 NFA-tier
    rules."""
    import bench
    from cilium_tpu.policy.api import PortRuleHTTP

    lit = [(frozenset(), PortRuleHTTP(method="GET",
                                      path=f"/svc000/r{j:02d}/.*"))
           for j in range(12)]
    rx = [(frozenset(), PortRuleHTTP(method="GET",
                                     path=bench._stress_regex_path(j)))
          for j in range(bench.STRESS_HTTP_REGEX_RULES)]
    nfa = [(frozenset(), PortRuleHTTP(method="GET",
                                      path=bench._stress_nfa_path(j)))
           for j in range(bench.STRESS_HTTP_NFA_RULES)]
    return (lit, rx, nfa) if backend_split else lit + rx + nfa


def _stress_dns_rows():
    import bench
    from cilium_tpu.proxylib.parsers.dns import DnsRule

    return (
        [(frozenset(), DnsRule(name=bench._stress_dns_name(0, j)))
         for j in range(bench.STRESS_DNS_EXACT_RULES)]
        + [(frozenset(), DnsRule(pattern=bench._stress_dns_pattern(j)))
           for j in range(bench.STRESS_DNS_PATTERN_RULES)]
    )


def _r2d2_model():
    from cilium_tpu.models.r2d2 import build_r2d2_model_from_rows

    return build_r2d2_model_from_rows([
        (frozenset(), "READ", "/public/.*"),
        (frozenset(), "HALT", ""),
    ], bucket=True)


def test_r2d2_verdicts_attr_compiles(topo, one_chip, tpu_trace):
    from cilium_tpu.models.r2d2 import r2d2_verdicts_attr

    model = _r2d2_model()
    compiled = jax.jit(r2d2_verdicts_attr.__wrapped__).lower(
        _abstract(model, one_chip), *_args(one_chip)
    ).compile()
    _fits(compiled)


def test_http_served_policy_compiles(topo, one_chip, tpu_trace):
    """The model the sidecar builds for one stress policy (auto backend:
    the literal tier plus one NFA carrying all eight regex rules)."""
    from cilium_tpu.models.http import build_http_model, http_verdicts_attr
    from cilium_tpu.ops.nfa import DeviceNfa

    model = build_http_model(_stress_http_rows(backend_split=False))
    assert isinstance(model.line_nfa, DeviceNfa)
    assert "literal" in model.match_kinds
    compiled = jax.jit(http_verdicts_attr.__wrapped__).lower(
        _abstract(model, one_chip), *_args(one_chip, width=HTTP_WIDTH)
    ).compile()
    _fits(compiled)


@pytest.mark.parametrize("tier", ["literal", "dfa", "nfa"])
def test_http_tier_compiles(topo, one_chip, tpu_trace, tier):
    """Each automaton tier on its own, as bench.py's stress set splits
    them: literal rows, DFA blocks for the six regex rules, the dense
    NFA for the two DFA-blowup rules."""
    from cilium_tpu.models.http import build_http_model, http_verdicts_attr
    from cilium_tpu.ops.dfa import DeviceDfa
    from cilium_tpu.ops.nfa import DeviceNfa

    lit, rx, nfa = _stress_http_rows(backend_split=True)
    model = {
        "literal": lambda: build_http_model(lit),
        "dfa": lambda: build_http_model(rx, backend="dfa"),
        "nfa": lambda: build_http_model(nfa, backend="auto"),
    }[tier]()
    want = {"literal": type(None), "dfa": DeviceDfa, "nfa": DeviceNfa}[tier]
    assert isinstance(model.line_nfa, want)
    compiled = jax.jit(http_verdicts_attr.__wrapped__).lower(
        _abstract(model, one_chip), *_args(one_chip, width=HTTP_WIDTH)
    ).compile()
    _fits(compiled)


def test_dns_verdicts_attr_compiles(topo, one_chip, tpu_trace):
    from cilium_tpu.models.dns import build_dns_model_from_rows, dns_verdicts_attr

    model = build_dns_model_from_rows(_stress_dns_rows(), bucket=True)
    compiled = jax.jit(dns_verdicts_attr.__wrapped__).lower(
        _abstract(model, one_chip), *_args(one_chip)
    ).compile()
    _fits(compiled)


def _sharded_args(mesh, stacked, width=WIDTH):
    from cilium_tpu.parallel.mesh import FLOW_AXIS, RULE_AXIS

    rules = NamedSharding(mesh, P(RULE_AXIS))
    flows = NamedSharding(mesh, P(FLOW_AXIS))
    return (_abstract(stacked, rules), *_args(flows, width=width))


@pytest.mark.parametrize("family", ["r2d2", "dns", "http"])
def test_sharded_step_compiles_on_2x2_mesh(topo, mesh, tpu_trace, family):
    """The (flows, rules) = (2, 2) serving mesh: rule tables shard over
    the rule axis, batches over the flow axis, the verdict OR-reduces
    across rule shards."""
    from cilium_tpu.models.dns import dns_verdicts
    from cilium_tpu.models.http import http_verdicts
    from cilium_tpu.models.r2d2 import r2d2_verdicts
    from cilium_tpu.parallel.rulesharding import (
        build_sharded_dns_from_rows,
        build_sharded_http_model,
        build_sharded_r2d2_from_rows,
        sharded_verdict_step,
    )

    width = WIDTH
    if family == "r2d2":
        stacked = build_sharded_r2d2_from_rows([
            (frozenset(), "READ", "/public/.*"),
            (frozenset(), "HALT", ""),
            (frozenset(), "WRITE", "/tmp/.*"),
        ], 2, bucket=True)
        step = sharded_verdict_step(mesh, r2d2_verdicts)
    elif family == "dns":
        stacked = build_sharded_dns_from_rows(_stress_dns_rows(), 2,
                                              bucket=True)
        step = sharded_verdict_step(mesh, dns_verdicts)
    else:
        stacked = build_sharded_http_model(
            _stress_http_rows(backend_split=False), 2
        )
        step = sharded_verdict_step(mesh, http_verdicts)
        width = HTTP_WIDTH
    compiled = step.lower(*_sharded_args(mesh, stacked, width)).compile()
    per_device = _fits(compiled)
    assert per_device > 0
    text = compiled.as_text()
    assert "all-reduce" in text  # the OR across rule shards

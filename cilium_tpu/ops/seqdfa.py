"""Sequence-axis parallel DFA search: shard the BYTES, not the flows.

The long-context strategy for this framework.  The reference's closest
analog is streaming frame reassembly (proxylib's MORE contract,
SURVEY §5 long-context) — but on TPU a single very wide frame
(a 32KB HTTP head is the worst case the in-process engine tiers for)
forces ``ops/dfa.py`` through tens of thousands of SEQUENTIAL scan
steps on one device.  Sequence parallelism fixes the wall-clock the
same way ring attention fixes attention over long sequences: split the
byte axis across the mesh and replace the sequential dependency with an
associative combine.

The construction (the classic parallel-prefix automaton):

1. **Absorbing accepts.**  Sticky acceptance ("accepted if ANY prefix
   hit an accept state") is folded into the automaton by making accept
   states absorbing — then acceptance is a property of the FINAL state
   only, and the whole span becomes one function composition.
2. **Chunk folding.**  A byte ``b`` is a state map δ_b: S→S; a chunk of
   bytes composes to one map.  Each device folds its local slice with
   the same one-hot-matmul step the serial scan uses, but carries the
   full [S, S] permutation-like matrix instead of one state row:
   ``P' = P @ D_c`` (batched over [F, R], MXU-friendly, no gathers).
   Inactive positions (outside a flow's span) multiply by identity.
3. **Associative combine.**  The per-chunk maps (tiny: [F, R, S, S]
   int8) are matmul-composed across the sequence axis — log-depth in
   theory; with n_devices ≤ 8 chunks a serial fold of the gathered
   summaries costs nanoseconds and keeps the collective to ONE
   all_gather over ICI.

Per-device work is O(F·R·S³/D) per byte-slice versus the serial scan's
O(F·R·S²·C) over ALL bytes — with the per-pattern S ≈ 16 ≈ C these are
the same cost class, so wall-clock scales ~1/D with device count.

Bit-exactness: composed-map acceptance equals the serial sticky scan by
construction (absorbing accepts ⊆ accept_final); fuzz-checked against
ops/dfa.py in tests/test_seqdfa.py on an 8-device mesh.
"""

from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..regex.dfa import DfaTables
from .dfa import DeviceDfa, byte_class_onehot, device_dfa

SEQ_AXIS = "seq"


def make_absorbing(tables: DfaTables) -> DfaTables:
    """Accept states become self-loops on every byte class, so sticky
    acceptance reduces to final-state acceptance (step 1 above)."""
    delta = tables.delta.copy()
    ri, si = np.nonzero(tables.accept)
    delta[ri, si, :] = si[:, None]
    return replace(tables, delta=delta)


def device_dfa_absorbing(tables: DfaTables) -> DeviceDfa:
    return device_dfa(make_absorbing(tables))


def _fold_chunk(dfa: DeviceDfa, data, t0, span_start, span_end,
                vary_axis: str | None = None):
    """Fold data[f, :] (positions t0..t0+Lc) into state maps
    [F, R, S, S] one-hot: map[f, r, s0, :] = state reached from s0."""
    f, lc = data.shape
    r, s, c = dfa.n_patterns, dfa.n_states, dfa.n_classes
    eye = jnp.eye(s, dtype=jnp.int8)
    p0 = jnp.broadcast_to(eye[None, None, :, :], (f, r, s, s)).astype(jnp.int8)
    if vary_axis is not None:
        # Inside shard_map the scan carry becomes device-varying (each
        # device folds its own byte slice); the initial carry must be
        # marked varying too or jax's manual-axes check rejects the scan.
        p0 = jax.lax.pcast(p0, (vary_axis,), to="varying")
    # delta as [R, C, S, S]: for class c, D[r, c, s, t] = 1 iff δ(s,c)=t,
    # derived from the integer-id table (padded states map to 0 but are
    # never selected: composition starts from the identity and final
    # application selects real start states only).
    delta_sc = (
        dfa.delta_id.transpose(0, 2, 1)[:, :, :, None]
        == jnp.arange(s, dtype=jnp.int32)[None, None, None, :]
    ).astype(jnp.int8)

    def step(p, inputs):
        byte_col, t = inputs  # [F], scalar-per-flow position
        cls1h = byte_class_onehot(dfa, byte_col)  # [F, C]
        # Per-flow transition matrix for this byte: [F, R, S, S]
        d_t = jnp.einsum(
            "fc,rcst->frst", cls1h, delta_sc,
            preferred_element_type=jnp.int32,
        ).astype(jnp.int8)
        nxt = jnp.einsum(
            "frsu,frut->frst", p, d_t, preferred_element_type=jnp.int32
        )
        nxt = (nxt > 0).astype(jnp.int8)
        active = (t >= span_start) & (t < span_end)  # [F]
        return jnp.where(active[:, None, None, None], nxt, p), None

    ts = t0 + jnp.arange(lc, dtype=jnp.int32)
    p, _ = jax.lax.scan(step, p0, (data.T, ts), unroll=8)
    return p


def _compose(p1, p2):
    """(p2 ∘ p1): apply p1 first.  [..., S, S] one-hot matmul."""
    out = jnp.einsum(
        "...su,...ut->...st", p1, p2, preferred_element_type=jnp.int32
    )
    return (out > 0).astype(jnp.int8)


def _apply_start_accept(dfa: DeviceDfa, pmap):
    """Start state through the composed map; accept_final membership
    (absorbing accepts make sticky == final)."""
    final_state = jnp.einsum(
        "rs,frst->frt", dfa.start_1h, pmap,
        preferred_element_type=jnp.int32,
    ).astype(jnp.int8)
    return (
        jnp.einsum(
            "frt,rt->fr", final_state, dfa.accept_final_mask,
            preferred_element_type=jnp.int32,
        )
        > 0
    )


def seqdfa_search_batch(
    dfa_abs: DeviceDfa, data, lengths, n_chunks: int = 1
):
    """Single-device reference of the chunked formulation: fold
    n_chunks sub-spans independently, compose, accept.  Exists so the
    sharded path's math is testable without a mesh."""
    f, width = data.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    assert width % n_chunks == 0
    lc = width // n_chunks
    pmap = None
    for k in range(n_chunks):
        pk = _fold_chunk(
            dfa_abs, data[:, k * lc : (k + 1) * lc],
            jnp.int32(k * lc), jnp.zeros_like(lengths), lengths,
        )
        pmap = pk if pmap is None else _compose(pmap, pk)
    return _apply_start_accept(dfa_abs, pmap)


def seqdfa_search_sharded(dfa_abs: DeviceDfa, data, lengths, mesh: Mesh):
    """Sequence-sharded search over ``mesh``'s SEQ_AXIS: each device
    folds its byte slice, one all_gather moves the [S, S] summaries
    over ICI, and every device composes + accepts (replicated result).

    ``data`` is [F, W] with W divisible by the seq axis size; flows may
    simultaneously shard on a flow axis if the mesh has one."""
    from jax import shard_map

    n_seq = mesh.shape[SEQ_AXIS]
    f, width = data.shape
    if width % n_seq != 0:
        raise ValueError(f"width {width} not divisible by seq axis {n_seq}")
    lc = width // n_seq
    lengths = jnp.asarray(lengths, jnp.int32)

    if f % n_seq != 0:
        raise ValueError(f"flow count {f} not divisible by seq axis {n_seq}")
    fb = f // n_seq

    def local(data_slice, lengths_full):
        # Which chunk this device holds follows from its axis index.
        k = jax.lax.axis_index(SEQ_AXIS)
        p = _fold_chunk(
            dfa_abs, data_slice, k * lc,
            jnp.zeros_like(lengths_full), lengths_full,
            vary_axis=SEQ_AXIS,
        )
        # [D, F, R, S, S] — tiny; ONE collective over the seq axis.
        all_p = jax.lax.all_gather(p, SEQ_AXIS)

        def body(i, acc):
            return _compose(acc, all_p[i])

        pmap = jax.lax.fori_loop(1, n_seq, body, all_p[0])
        out = _apply_start_accept(dfa_abs, pmap)  # [F, R], full batch
        # Every device holds the full composed map; emit only this
        # device's flow block so the output spec shards cleanly over
        # the same axis (concatenation rebuilds [F, R]).
        return jax.lax.dynamic_slice_in_dim(out, k * fb, fb, axis=0)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, SEQ_AXIS), P(None)),
        out_specs=P(SEQ_AXIS, None),
    )(data, lengths)

"""Batched per-pattern DFA evaluation on TPU.

The scale-out sibling of ops/nfa.py.  The dense union NFA advances a
[F, S_total] state set with an O(S_total²·C) matmul per byte; at
hundred-rule scale S_total is thousands and the delta is HBM-hostile.
But the union automaton is block-diagonal — patterns never share states
— and each pattern determinizes to a TINY DFA (regex/dfa.py), whose
next state is a SCALAR.  The per-byte step therefore needs no S×S
transition algebra at all:

  state:   [F, R, S] one-hot int8 (deterministic => exactly one bit)
  cls1h:   [F, C]    range compares (classes are unions of byte runs)
  row:     [F, R, C] = state @ delta_id[R, S, C]   (row select, MXU)
  nxt_id:  [F, R]    = Σ_c row·cls1h               (class select, VPU)
  state':  [F, R, S] = (nxt_id == iota_S)          (one-hot rebuild)

Work per byte is O(F·R·S·C) — S× less than the one-hot-delta matmul
this replaced and S_total/S·S× less than the dense NFA — with tables a
few KB.  No gathers anywhere: TPU gathers do not vectorize (a
gather-based scan measured ~10k flows/s; take_along_axis variants cost
~0.4s per 500k-flow pass).

Acceptance is a mask reduction (state ⋅ accept_mask), sticky across
steps like the NFA op.  API mirrors ops/nfa.py; bit-identical by
construction from the same CompiledPattern NFAs (tests/test_dfa_op.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..regex.dfa import DfaTables


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceDfa:
    """Packed per-pattern DFA tables resident on device.

    ``delta_id`` holds the transition TARGET ID (not a one-hot): a
    deterministic automaton's next state is a scalar, so the step
    contracts the one-hot state against an integer-valued table —
    O(S·C) MACs per (flow, pattern, byte) instead of the one-hot
    delta's O(S²·C), a 48× compute and ~50× HBM-traffic saving at
    S=48/C=19 (measured 3× wall on the 500k-flow stress replay)."""

    # Byte classes as unions of ranges: cls c contains byte b iff
    # lo[c,k] <= b <= hi[c,k] for some k.  The range compare form costs
    # ~C*K byte-ops per flow-byte instead of materializing a [F, 256]
    # one-hot (16MB per scan step at F=64k) for the classmap matmul.
    cls_lo: jax.Array  # [C, K] int32 (padded rows have lo > hi)
    cls_hi: jax.Array  # [C, K] int32
    delta_id: jax.Array  # [R, S, C] int8 — next-state id per (state, class)
    start_1h: jax.Array  # [R, S] int8
    accept_mask: jax.Array  # [R, S] int8 — sticky accept states
    accept_final_mask: jax.Array  # [R, S] int8 — accept | accept-via-END
    n_states: int
    n_classes: int
    n_patterns: int

    def tree_flatten(self):
        leaves = (
            self.cls_lo,
            self.cls_hi,
            self.delta_id,
            self.start_1h,
            self.accept_mask,
            self.accept_final_mask,
        )
        return leaves, (self.n_states, self.n_classes, self.n_patterns)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)


def device_dfa(tables: DfaTables) -> DeviceDfa:
    """Upload packed host tables to the device."""
    from ..regex.dfa import DfaBlowupError

    r, s, c = tables.n_patterns, tables.n_states, tables.n_classes
    if s > 128:  # ids 0..s-1 must fit int8
        # DfaBlowupError (not ValueError) so compile_automaton's 'auto'
        # path falls back to the dense NFA instead of failing the build.
        raise DfaBlowupError(
            f"DFA state id must fit int8 (got {s} states)"
        )
    # Byte classes as maximal runs of the 256-entry classmap.
    runs: list[list[tuple[int, int]]] = [[] for _ in range(c)]
    start_b = 0
    for b in range(1, 257):
        if b == 256 or tables.classmap[b] != tables.classmap[start_b]:
            runs[int(tables.classmap[start_b])].append((start_b, b - 1))
            start_b = b
    k = max(1, max(len(rr) for rr in runs))
    cls_lo = np.full((c, k), 1, np.int32)  # lo>hi: empty padding
    cls_hi = np.zeros((c, k), np.int32)
    for ci, rr in enumerate(runs):
        for ki, (lo, hi) in enumerate(rr):
            cls_lo[ci, ki] = lo
            cls_hi[ci, ki] = hi
    # Padded states/patterns keep delta_id=0: the one-hot state vector
    # never activates them, so their targets are never selected.
    delta_id = tables.delta.astype(np.int8)  # [R, S, C]
    start_1h = np.zeros((r, s), np.int8)
    start_1h[np.arange(r), tables.start] = 1
    return DeviceDfa(
        cls_lo=jnp.asarray(cls_lo),
        cls_hi=jnp.asarray(cls_hi),
        delta_id=jnp.asarray(delta_id),
        start_1h=jnp.asarray(start_1h),
        accept_mask=jnp.asarray(tables.accept.astype(np.int8)),
        accept_final_mask=jnp.asarray(tables.accept_final.astype(np.int8)),
        n_states=s,
        n_classes=c,
        n_patterns=r,
    )


def byte_class_onehot(dfa: DeviceDfa, byte_col: jax.Array) -> jax.Array:
    """[F] bytes -> [F, C] one-hot byte classes (shared by the serial
    scan and the sequence-sharded fold so the two paths cannot drift).
    Range-compare form: classes are unions of byte runs, so membership
    is a handful of [F] compares instead of a [F, 256] one-hot matmul
    (which cost 16MB of traffic per scan step at F=64k — measured 3.5x
    slower end to end on the r2d2 search)."""
    b = jnp.asarray(byte_col, jnp.int32)[:, None, None]  # [F, 1, 1]
    in_run = (b >= dfa.cls_lo[None, :, :]) & (b <= dfa.cls_hi[None, :, :])
    return jnp.any(in_run, axis=2).astype(jnp.int8)  # [F, C]


def _accepts(state: jax.Array, mask: jax.Array) -> jax.Array:
    """[F, R] bool: the one-hot state is in the mask."""
    return (
        jnp.einsum(
            "frs,rs->fr", state, mask, preferred_element_type=jnp.int32
        )
        > 0
    )


_BLOCK = 8  # byte positions per trip of the scan loop


def _dfa_scan(dfa: DeviceDfa, data, span_start, span_end):
    f, length = data.shape
    r, s, c = dfa.n_patterns, dfa.n_states, dfa.n_classes

    state0 = jnp.broadcast_to(dfa.start_1h[None, :, :], (f, r, s)).astype(
        jnp.int8
    )
    accepted0 = _accepts(state0, dfa.accept_mask)

    # Positions past every span change no state and re-OR an accept bit
    # already ORed, so the loop stops after the last block any span
    # reaches: 3 blocks for a round of 24-byte frames in 256-byte rows.
    # The bound is a traced scalar, so one executable serves every round.
    span_end = jnp.minimum(jnp.asarray(span_end, jnp.int32), length)
    hi = jnp.max(span_end, initial=0)
    n_blk = (hi + _BLOCK - 1) // _BLOCK
    pad = -length % _BLOCK  # zero rows at t >= length are never active
    data_t = jnp.pad(data.T, ((0, pad), (0, 0)))  # [L + pad, F]

    iota_s = jnp.arange(s, dtype=jnp.int32)

    def step(state, accepted, byte_col, t):
        cls1h = byte_class_onehot(dfa, byte_col)  # [F, C]
        # Row select: row[f, r, c] = delta_id[r, cur_state(f,r), c]
        # — one-hot state × integer table, O(S·C) MACs per (f, r).
        row = jax.lax.dot_general(
            state,
            dfa.delta_id,
            (((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.int32,
        ).transpose(1, 0, 2)  # [F, R, C]
        # Class select (VPU): nxt_id[f, r] = row[f, r, cls(byte_f)].
        nxt_id = (row * cls1h[:, None, :].astype(jnp.int32)).sum(
            axis=2
        )  # [F, R]
        nxt = (nxt_id[:, :, None] == iota_s).astype(jnp.int8)  # [F, R, S]
        active = (t >= span_start) & (t < span_end)  # [F]
        state = jnp.where(active[:, None, None], nxt, state)
        accepted = accepted | _accepts(state, dfa.accept_mask)
        return state, accepted

    def block(k, carry):
        # Each step is a handful of SMALL kernels (the per-policy tables
        # are tiny), so one step a trip is launch-latency-bound; the
        # unrolled block lets XLA fuse across byte positions.
        t0 = k * _BLOCK
        cols = jax.lax.dynamic_slice_in_dim(data_t, t0, _BLOCK)
        for i in range(_BLOCK):
            carry = step(*carry, cols[i], t0 + i)
        return carry

    state, accepted = jax.lax.fori_loop(
        0, n_blk, block, (state0, accepted0)
    )
    final_acc = _accepts(state, dfa.accept_final_mask)
    return accepted | final_acc  # [F, R] bool


@jax.jit
def dfa_search_spans(
    dfa: DeviceDfa, data: jax.Array, span_start: jax.Array, span_end: jax.Array
) -> jax.Array:
    """Search each pattern within ``data[f, span_start[f]:span_end[f]]``;
    same contract as ops.nfa.nfa_search_spans."""
    return _dfa_scan(dfa, data, span_start, span_end)


@jax.jit
def dfa_search_batch(
    dfa: DeviceDfa, data: jax.Array, lengths: jax.Array
) -> jax.Array:
    """Search each pattern in ``data[f, :lengths[f]]``; [F, R] bool."""
    zeros = jnp.zeros_like(lengths)
    return _dfa_scan(dfa, data, zeros, lengths)

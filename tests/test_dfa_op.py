"""Per-pattern DFA path vs dense NFA path: bit-identical search results.

The DFA tables (regex/dfa.py) and gather op (ops/dfa.py) are the
scale-out alternative to the matmul NFA; both compile from the same
CompiledPattern NFAs, so every (pattern, subject, span) must agree.
"""

import random

import numpy as np
import pytest

from cilium_tpu.ops.dfa import device_dfa, dfa_search_batch, dfa_search_spans
from cilium_tpu.ops.nfa import device_nfa, nfa_search_batch, nfa_search_spans
from cilium_tpu.regex import compile_patterns
from cilium_tpu.regex.dfa import (
    DfaBlowupError,
    compile_pattern_dfas,
    pattern_dfa,
)
from cilium_tpu.regex.nfa import compile_pattern

PATTERNS = [
    r"abc",
    r"^abc",
    r"abc$",
    r"^abc$",
    r"^$",
    r"a.c",
    r"a.*c",
    r"a.+c",
    r"ab?c",
    r"a|b|c",
    r"(ab|cd)+",
    r"[a-z0-9_]+",
    r"[^abc]",
    r"\d+",
    r"a{2,4}",
    r"/public/.*",
    r"^/public/.*$",
    r"/api/v[0-9]+/users/[0-9]+",
    r"^(GET|HEAD)$",
    r".*\.example\.com",
    r"",
]

SUBJECTS = [
    b"",
    b"abc",
    b"xabcy",
    b"ab",
    b"aXc",
    b"ac",
    b"abab",
    b"cd",
    b"a_09z",
    b"123",
    b"aaa",
    b"aaaaa",
    b"/public/file1",
    b"x/public/",
    b"/api/v12/users/7",
    b"/api/vx/users/7",
    b"GET",
    b"GET ",
    b"HEAD",
    b"img.example.com",
    b"example.com",
    b"READ /public/a.txt\r\n",
]


def _pad(subjects, width=32):
    data = np.zeros((len(subjects), width), np.uint8)
    lengths = np.zeros((len(subjects),), np.int32)
    for i, s in enumerate(subjects):
        data[i, : len(s)] = np.frombuffer(s, np.uint8)
        lengths[i] = len(s)
    return data, lengths


def test_dfa_matches_nfa_batch():
    nfa = device_nfa(compile_patterns(PATTERNS))
    dfa = device_dfa(compile_pattern_dfas(PATTERNS))
    data, lengths = _pad(SUBJECTS)
    want = np.asarray(nfa_search_batch(nfa, data, lengths))
    got = np.asarray(dfa_search_batch(dfa, data, lengths))
    for i, s in enumerate(SUBJECTS):
        assert (got[i] == want[i]).all(), (
            f"{s!r}: dfa={got[i].tolist()} nfa={want[i].tolist()}"
        )


def test_dfa_matches_nfa_spans():
    """Random sub-spans (including empty) must agree too."""
    rng = random.Random(5)
    nfa = device_nfa(compile_patterns(PATTERNS))
    dfa = device_dfa(compile_pattern_dfas(PATTERNS))
    data, lengths = _pad(SUBJECTS)
    f = len(SUBJECTS)
    start = np.zeros((f,), np.int32)
    end = np.zeros((f,), np.int32)
    for i in range(f):
        a = rng.randrange(0, int(lengths[i]) + 1)
        b = rng.randrange(0, int(lengths[i]) + 1)
        start[i], end[i] = a, b
    want = np.asarray(nfa_search_spans(nfa, data, start, end))
    got = np.asarray(dfa_search_spans(dfa, data, start, end))
    np.testing.assert_array_equal(got, want)


def _span_case(case):
    """(data, span_start, span_end) for one span shape of the bounded
    scan: the loop stops after the last 8-byte block any span reaches."""
    rng = random.Random(case)
    fill = b"abcdxyz/._0123456789GETPOSTHEAD@ \r\n"
    width = {"width_20": 20, "width_37": 37}.get(case, 256)
    if case.startswith("width_"):
        # Rows filled to the width, so the last (partial) block is live.
        subjects = [
            bytes(rng.choice(fill) for _ in range(rng.randrange(0, width + 1)))
            for _ in range(40)
        ] + [bytes(rng.choice(fill) for _ in range(width))] * 2
    else:
        subjects = list(SUBJECTS)
    data, lengths = _pad(subjects, width)
    f = len(subjects)
    start = np.zeros((f,), np.int32)
    end = np.zeros((f,), np.int32)
    for i in range(f):
        a = rng.randrange(0, int(lengths[i]) + 1)
        b = rng.randrange(0, int(lengths[i]) + 1)
        start[i], end[i] = min(a, b), max(a, b)
    if case == "all_empty":
        end[:] = start
    elif case == "start_after_end":
        start, end = np.maximum(end, 1), np.minimum(start, end) - 1
    elif case == "one_reaches_width":
        row = bytes(rng.choice(fill) for _ in range(width))
        data[-1] = np.frombuffer(row, np.uint8)
        start[-1], end[-1] = 0, width
    elif case == "width_37":
        start[-1], end[-1] = 33, width  # only the last, 5-byte block
    return data, start, end


@pytest.mark.parametrize(
    "case",
    [
        "all_empty",
        "one_reaches_width",
        "width_20",
        "width_37",
        "start_after_end",
        "short_frames_at_256",
    ],
)
def test_bounded_scan_matches_nfa_spans(case):
    """The DFA scan runs only the blocks the round's longest span
    reaches; the dense NFA scans every byte.  They must agree."""
    nfa = device_nfa(compile_patterns(PATTERNS))
    dfa = device_dfa(compile_pattern_dfas(PATTERNS))
    data, start, end = _span_case(case)
    want = np.asarray(nfa_search_spans(nfa, data, start, end))
    got = np.asarray(dfa_search_spans(dfa, data, start, end))
    np.testing.assert_array_equal(got, want)


def test_bounded_scan_one_executable():
    """Rounds whose longest span ends at byte 3, 22 and 256 of one
    (F, 256) shape share one executable: the bound is traced, not a
    shape, so no round compiles."""
    nfa = device_nfa(compile_patterns(PATTERNS))
    dfa = device_dfa(compile_pattern_dfas(PATTERNS))
    data, lengths = _pad(SUBJECTS * 2, 256)
    data[-1] = ord("a")
    start = np.zeros_like(lengths)
    sizes = []
    for hi in (3, 22, 256):
        end = np.minimum(lengths, hi)
        end[-1] = hi
        got = np.asarray(dfa_search_spans(dfa, data, start, end))
        sizes.append(dfa_search_spans._cache_size())
        want = np.asarray(nfa_search_spans(nfa, data, start, end))
        np.testing.assert_array_equal(got, want)
    assert sizes[0] == sizes[1] == sizes[2], sizes


def test_dfa_fuzz_random_bytes():
    rng = random.Random(9)
    subjects = []
    alphabet = b"abcdxyz/._0123456789GETPOSTHEAD@ \r\n"
    for _ in range(200):
        n = rng.randrange(0, 24)
        subjects.append(bytes(rng.choice(alphabet) for _ in range(n)))
    nfa = device_nfa(compile_patterns(PATTERNS))
    dfa = device_dfa(compile_pattern_dfas(PATTERNS))
    data, lengths = _pad(subjects)
    want = np.asarray(nfa_search_batch(nfa, data, lengths))
    got = np.asarray(dfa_search_batch(dfa, data, lengths))
    mism = np.flatnonzero((got != want).any(axis=1))
    assert mism.size == 0, (
        f"{mism.size} subjects diverge; first: {subjects[mism[0]]!r} "
        f"dfa={got[mism[0]].tolist()} nfa={want[mism[0]].tolist()}"
    )


def test_dfa_accept_threshold_ordering():
    """Accepting states must occupy the top ids (the sticky-accept
    threshold trick)."""
    d = pattern_dfa(compile_pattern("/public/.*"))
    # start must not be accepting for this pattern
    assert d.start < d.accept_thresh
    assert d.n_states > d.accept_thresh  # has accepting states


def test_pad_dfa_tables_parity():
    """Cross-set padding (shared jit shapes across policies) must not
    change any verdict: padded states are unreachable and padded classes
    never produced."""
    from cilium_tpu.regex.dfa import pad_dfa_tables

    small = compile_pattern_dfas(["abc", "^x$"])
    big = compile_pattern_dfas(PATTERNS)
    s = max(small.n_states, big.n_states) + 3
    c = max(small.n_classes, big.n_classes) + 2
    data, lengths = _pad(SUBJECTS)
    for t in (small, big):
        want = np.asarray(dfa_search_batch(device_dfa(t), data, lengths))
        padded = pad_dfa_tables(t, s, c)
        got = np.asarray(dfa_search_batch(device_dfa(padded), data, lengths))
        np.testing.assert_array_equal(got, want)


def test_dfa_blowup_guard():
    # Unanchored "a.{k}" forces the DFA to track which of the last k+1
    # positions held an 'a' — 2^(k+1) subset states.
    with pytest.raises(DfaBlowupError):
        pattern_dfa(compile_pattern("a.{8}"), max_states=64)

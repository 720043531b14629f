"""The traffic generator: seeded, shaped as its file says, found by name."""

import json
import os
from collections import Counter

import numpy as np
import pytest

from benchmark import deploy
from benchmark.cell import Cell
from benchmark.mixgen import CATEGORIES, Traffic
from benchmark.tests.conftest import load


def _pushes(tr, n=64, k=5):
    return [tr.push(i, j) for i in range(0, tr.n, max(tr.n // n, 1))
            for j in range(k)]


@pytest.mark.parametrize("name", ["mixed-6k-rules.mixed-closed",
                                  "r2d2-line.whole-poisson",
                                  "r2d2-line.mixed-closed",
                                  "mixed-6k-rules.poisson"])
def test_same_seed_same_traffic(name):
    cfg, params = load(*name.split("."))
    big = 3_000_000_000  # seeds past 32 bits
    a = Traffic(params, cfg, big, 256)
    b = Traffic(params, cfg, big, 256)
    c = Traffic(params, cfg, big + 1, 256)
    assert _pushes(a) == _pushes(b)
    assert a.policy == b.policy and (a.order == b.order).all()
    assert _pushes(a) != _pushes(c)
    # Another seed: the same numbers of each kind of connection.
    def kinds(t):
        return Counter(zip(t.proto.tolist(), t.category.tolist(),
                           t.lane.tolist()))
    assert kinds(a) == kinds(c)


def test_mixed_closed_shape():
    cfg, params = load("mixed-6k-rules", "mixed-closed")
    tr = Traffic(params, cfg, 7, 256)
    assert tr.n == 8192
    assert Counter(tr.proto.tolist()) == {"http": 4096, "dns": 2048,
                                          "r2d2": 2048}
    assert tr.lane.sum() == 96
    # Verdicts per push by category give the 80/10/5/5 frame split.
    per = {"complete": 1.0, "partial": 0.5, "pipelined": 2.0, "reply": 1.0}
    cat = Counter(tr.category.tolist())
    frames = {c: cat[j] * per[c] for j, c in enumerate(CATEGORIES)}
    total = sum(frames.values())
    for c, share in zip(CATEGORIES, (0.80, 0.10, 0.05, 0.05)):
        assert abs(frames[c] / total - share) < 0.002
    assert set(tr.policy) == {p["name"] for p in deploy.policies(cfg)}


def test_messages_carry_the_pushes():
    cfg, params = load("mixed-6k-rules", "mixed-closed")
    tr = Traffic(params, cfg, 5, 256)
    idx = np.arange(0, 600, 3)
    ks = np.arange(len(idx)) % 4
    seen = 0
    for kind, sel, args in tr.messages(idx, ks):
        assert len({tr.policy[i] for i in idx[sel]}) == 1
        if kind == "matrix":
            ids, lens, rows = args
            rows = np.frombuffer(rows, np.uint8).reshape(len(ids), 256)
            got = [rows[j, :lens[j]].tobytes() for j in range(len(ids))]
        else:
            ids, flags, lens, blob = args
            offs = np.concatenate(([0], np.cumsum(lens.astype(int))))
            got = [blob[offs[j]:offs[j + 1]] for j in range(len(ids))]
            assert flags.tolist() == [int(tr.push(i, k)[0]) for i, k in
                                      zip(idx[sel], ks[sel])]
        want = [tr.push(i, k)[1] for i, k in zip(idx[sel], ks[sel])]
        assert got == want
        assert (ids == tr.cid[idx[sel]]).all()
        seen += len(sel)
    assert seen == len(idx)


def test_partial_pushes_rebuild_the_frame():
    cell = Cell("r2d2-line.mixed-closed")
    tr = Traffic(cell.traffic, cell.config, 9, 256)
    i = int(np.flatnonzero(tr.category == 1)[0])
    first, second = tr.push(i, 0), tr.push(i, 1)
    assert tr.verdicts(np.array([i, i]), np.array([0, 1])).tolist() == [0, 1]
    assert (first[1] + second[1]).endswith(b"\r\n")
    assert b"\r\n" not in first[1]


def test_new_traffic_file_is_found_by_name(tiny_root):
    with open(os.path.join(tiny_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mix = {"loop": "poisson", "conns": 64, "frame_shares": {"complete": 1},
           "on_io_conns": 0, "rate": 123, "client_batch": 16,
           "client_hold_ms": 0.2, "warmup_s": 1, "variants": 4,
           "check_pushes": 100}
    with open(os.path.join(tiny_root, "benchmark", "traffic",
                           "burst-new.json"), "w") as f:
        json.dump(mix, f)
    bench["workloads"].append({"name": "r2d2-line.burst-new",
                               "config": "r2d2-line",
                               "traffic": "burst-new", "chips": 1,
                               "why": "test"})
    root2 = os.path.join(tiny_root, "..", "with-new")
    os.makedirs(root2, exist_ok=True)
    for sub in ("configs", "traffic", "metrics"):
        src = os.path.join(tiny_root, "benchmark", sub)
        dst = os.path.join(root2, "benchmark", sub)
        if not os.path.exists(dst):
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            os.symlink(src, dst)
    with open(os.path.join(root2, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = Cell("r2d2-line.burst-new", root=root2)
    assert cell.traffic == mix
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    tr = Traffic(cell.traffic, cell.config, 1, 256)
    assert tr.n == 64 and tr.whole.all()

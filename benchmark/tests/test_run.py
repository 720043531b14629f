"""A whole run on the CPU at a tiny size, past the look for a chip: the
result line keeps to its contract, and a fault planted in the served
path turns ``correct`` false."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run
from benchmark.cell import Cell
from benchmark.tests.conftest import ROOT


def _no_result(out: str) -> bool:
    return not any('"metrics"' in line for line in out.splitlines())


def test_refuses_without_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "r2d2-line.whole-poisson", "--seed", "3000000001", "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
    last = proc.stderr.strip().splitlines()[-1]
    assert "platform=cpu" in last and "device_kind=" in last \
        and "count=" in last


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "r2d2-line.whole-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def _check_contract(out: dict, cell: Cell, trace: bool) -> None:
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "check"
    assert set(keys) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "check"}
    json.loads(json.dumps(out))
    want = cell.per_layer if trace else cell.end_to_end
    units = {m["name"]: m["unit"] for m in want}
    assert set(out["metrics"]) <= set(units)
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name]
        assert isinstance(m["value"], float) and m["value"] > 0
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        assert k in out["device"]
    for name, c in out["check"].items():
        assert set(c) == {"value", "limit"}


def test_result_line_open_loop(tiny_root):
    cell = Cell("r2d2-line.whole-poisson", root=tiny_root)
    out = run.run_cell(cell, 3_000_000_007, 2.0, False)
    _check_contract(out, cell, False)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"verdict_p50_ms", "goodput_per_s",
                                  "setup_s"}
    assert out["attempted"] > 0


def test_result_line_closed_traced(tiny_root):
    cell = Cell("r2d2-line.mixed-closed", root=tiny_root)
    out = run.run_cell(cell, 11, 2.0, True)
    _check_contract(out, cell, True)
    assert out["correct"] is True
    # No TPU plane in a CPU trace: the idle share is left out, the
    # readers of the service's spans and counters are not.
    assert "device_idle_share.closed" not in out["metrics"]
    assert {"queue_ms.closed", "entries_per_round.closed",
            "host_round_ms.closed", "device_stage_ms.closed"} <= set(
                out["metrics"])


def _flip_first_pass(orig):
    """An answer altered where it is produced: the first PASS op of
    every verdict body the service packs becomes a DROP."""
    from cilium_tpu.sidecar import wire

    def packed(conn_ids, results, op_counts, io_l, ir_l, ops, blob):
        ops = np.array(ops, wire.FILTER_OP, copy=True)
        hit = np.flatnonzero(ops["op"] == 1)
        if len(hit):
            ops["op"][hit[0]] = 2
        return orig(conn_ids, results, op_counts, io_l, ir_l, ops, blob)

    return packed


def _drop_half_batch(orig):
    """Half of each batch left out: the service sees only the even
    entries of every data batch, so the rest are never answered."""
    from cilium_tpu.sidecar import wire

    def unpacked(payload):
        b = orig(payload)
        keep = np.arange(b.count) % 2 == 0
        offs = b.offsets
        blob = b"".join(b.blob[offs[i]:offs[i + 1]]
                        for i in np.flatnonzero(keep))
        return wire.DataBatch(b.seq, b.conn_ids[keep], b.flags[keep],
                              b.lengths[keep], blob, arrival=b.arrival)

    return unpacked


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_planted_fault_fails(tiny_root, monkeypatch, fault):
    from cilium_tpu.sidecar import wire

    if fault == "answer_altered":
        monkeypatch.setattr(wire, "pack_verdict_body",
                            _flip_first_pass(wire.pack_verdict_body))
    else:
        monkeypatch.setattr(wire, "unpack_data_batch",
                            _drop_half_batch(wire.unpack_data_batch))
    cell = Cell("r2d2-line.mixed-closed", root=tiny_root)
    out = run.run_cell(cell, 13, 2.0, False)
    assert out["correct"] is False
    key = "mismatches" if fault == "answer_altered" else "unanswered"
    assert out["check"][key]["value"] > out["check"][key]["limit"]

"""chip_smoke.py on the CPU: it refuses to report without a TPU, and
its served path — VerdictService + SidecarClient against the proxylib
oracle, one chip and the 2x2 mesh — holds at a tiny scale."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from cilium_tpu.utils import jaxcache

TINY = chip_smoke.Scale(conns=96, frames=300, http_policies=3,
                        dns_policies=2, shim_conns=6)


def _no_result(out: str) -> bool:
    return not any('"ok"' in line for line in out.splitlines())


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert _no_result(captured.out)
    assert "no TPU" in captured.err


def test_fails_alone_in_a_directory(tmp_path):
    shutil.copy(Path(chip_smoke.__file__), tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert _no_result(proc.stdout)


def test_traffic_mix_and_rounds():
    conns, rounds = chip_smoke.make_traffic(0, chip_smoke.Scale())
    assert len(conns) == 8192
    by_cat = {c: sum(x.category == c for x in conns)
              for c in chip_smoke.CATEGORIES}
    frames = {
        "complete": by_cat["complete"] * rounds,
        "partial": by_cat["partial"] * rounds // 2,
        "pipelined": 2 * by_cat["pipelined"] * rounds,
        "reply": by_cat["reply"] * rounds,
    }
    total = sum(frames.values())
    assert total >= 200_000
    for cat, share in (("complete", 0.80), ("partial", 0.10),
                       ("pipelined", 0.05), ("reply", 0.05)):
        assert abs(frames[cat] / total - share) < 0.005, (cat, frames)
    assert {c.proto for c in conns} == {"http", "dns", "r2d2"}
    assert sum(c.shim for c in conns) == 96


@pytest.mark.parametrize("phase", ["one_chip", "mesh"])
def test_served_path_matches_oracle_on_cpu(tmp_path, capsys, phase):
    watch = chip_smoke.CompileWatch()
    run = getattr(chip_smoke, f"run_{phase}")
    run(0, TINY, str(tmp_path), watch)
    out = capsys.readouterr().out
    assert "answers_checked=" in out
    assert "compile while serving" not in out


def test_compile_cache_env_wins(monkeypatch):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert jaxcache.configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        got = jaxcache.configure_compile_cache()
        root = Path(chip_smoke.__file__).resolve().parent
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # Placed once: a later call keeps it.
        assert jaxcache.configure_compile_cache() == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


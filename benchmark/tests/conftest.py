"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# A cell small enough for the CPU: every configuration and traffic mix
# of the benchmark, with fewer policies, connections and seconds.
TINY_CONFIG = {"http_policies": 3, "dns_policies": 2}
TINY_TRAFFIC = {"conns": 96, "warmup_s": 1, "check_pushes": 3000}


def make_tiny_root(dest: str) -> str:
    """A checkout-shaped directory whose BENCHMARK.json names tiny copies
    of every configuration and traffic file; the metric readers are the
    benchmark's own."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(os.path.join(dest, "benchmark", "configs"))
    os.makedirs(os.path.join(dest, "benchmark", "traffic"))
    shutil.copytree(os.path.join(ROOT, "benchmark", "metrics"),
                    os.path.join(dest, "benchmark", "metrics"))
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        for k, v in TINY_CONFIG.items():
            if k in cfg:
                cfg[k] = v
        with open(os.path.join(dest, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in bench["workloads"]:
        name = w["traffic"]
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               f"{name}.json")) as f:
            tr = json.load(f)
        tr.update(TINY_TRAFFIC)
        if tr.get("on_io_conns"):
            tr["on_io_conns"] = 6
        if "outstanding" in tr:
            tr["outstanding"] = 48
        if tr["loop"] == "poisson":
            tr["rate"], tr["client_batch"] = 200, 32
        with open(os.path.join(dest, "benchmark", "traffic",
                               f"{name}.json"), "w") as f:
            json.dump(tr, f)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def load(config: str, traffic: str) -> tuple[dict, dict]:
    """A configuration and a traffic mix by file name (the files of the
    mixed-6k-rules cells stay in the tree while no cell names them)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           f"{traffic}.json")) as f:
        return cfg, json.load(f)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("tiny")))

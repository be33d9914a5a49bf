"""The benchmark's command line: no accelerator, no program, no result;
weights from a seed are the same in every process; importing the
benchmark starts no backend."""
import json
import os
import shutil
import subprocess
import sys

from bench import spec

ROOT = str(spec.ROOT)
CELL = spec.load_benchmark()["workloads"][0]["name"]


def _env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    env.update(kw)
    return env


def _run(cwd, *args):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=_env(), capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_run_refuses_the_cpu_and_prints_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no accelerator" in r.stderr
    assert _no_result(r.stdout)


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec.load_benchmark()["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "src/repro" in r.stderr
    assert _no_result(r.stdout)


WEIGHTS = """
import hashlib, sys
import numpy as np
from bench import weights
from bench.models import decode_lm
cfg = dict(vocab_size=64, hidden_size=32, num_attention_heads=2,
           num_key_value_heads=2, head_dim=16, intermediate_size=64,
           num_hidden_layers=2, max_position_embeddings=32,
           serving={"use_kernel": False})
params = weights.init_params(weights.param_specs(decode_lm.build_graph(cfg)),
                             int(sys.argv[1]))
h = hashlib.sha256()
for name in sorted(params):
    for leaf in __import__("jax").tree_util.tree_leaves(params[name]):
        h.update(name.encode()); h.update(np.asarray(leaf).tobytes())
print(h.hexdigest())
"""


def test_one_seed_gives_the_same_weights_in_two_processes():
    def digest(seed):
        r = subprocess.run([sys.executable, "-c", WEIGHTS, str(seed)],
                           env=_env(), capture_output=True, text=True,
                           timeout=300, check=True)
        return r.stdout.strip().splitlines()[-1]

    seed = 2**33 + 7
    first, second = digest(seed), digest(seed)
    assert first == second
    assert digest(seed + 2**32) != first       # the high word counts too


IMPORTS = """
import importlib, pkgutil
import bench
names = [m.name for m in pkgutil.walk_packages(bench.__path__, "bench.")]
for name in names:
    importlib.import_module(name)
from jax._src import xla_bridge
print(len(names), xla_bridge.backends_are_initialized())
"""


def test_importing_the_benchmark_starts_no_backend():
    """No module of bench/ touches a device (or libtpu) when imported."""
    env = _env()
    env.pop("JAX_PLATFORMS")
    r = subprocess.run([sys.executable, "-c", IMPORTS], env=env,
                       capture_output=True, text=True, timeout=300,
                       check=True)
    count, initialised = r.stdout.split()
    assert int(count) > 20
    assert initialised == "False"

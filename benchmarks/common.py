"""Shared benchmark utilities.

The paper's experiments ran on 136 dual-Xeon nodes; this container is one
CPU core.  Each figure keeps the paper's *sweep structure and instance
orders* but scales iteration budgets by ``SCALE`` (documented in
EXPERIMENTS.md; absolute times are not comparable, relative behaviour is).
Set REPRO_BENCH_SCALE=1.0 on a real machine for full budgets.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import annealing, composite, genetic, instances, qap  # noqa: E402

SCALE = float(os.environ.get("REPRO_BENCH_SCALE", "0.02"))
RUNS = int(os.environ.get("REPRO_BENCH_RUNS", "3"))   # paper: 10


def scaled(n: int, lo: int = 2) -> int:
    return max(int(round(n * SCALE)), lo)


def get(n: int):
    inst = instances.get_instance(n)
    return jnp.asarray(inst.C), jnp.asarray(inst.M), inst


def random_instance(n: int, seed: int):
    """Symmetric random (C, M) numpy pair with zero diagonals — the shared
    instance recipe of the service benchmarks (mapper_throughput,
    solver_hotloop)."""
    rng = np.random.default_rng(seed)
    C = rng.integers(0, 10, (n, n)).astype(np.float32)
    M = rng.integers(1, 10, (n, n)).astype(np.float32)
    C, M = C + C.T, M + M.T
    np.fill_diagonal(C, 0)
    np.fill_diagonal(M, 0)
    return C, M


def time_fn(fn: Callable, *args) -> Tuple[float, object]:
    # jit warmup run is included deliberately excluded: time steady-state
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def accuracy(f: float, f0: float) -> float:
    """Paper's A1 = 100 * (F - F0) / F0."""
    return 100.0 * (f - f0) / f0


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


def sa_budget(num_exchanges: int = 50, ipe: int = 100, neighbors: int = 50,
              solvers: int = 25) -> annealing.SAConfig:
    return annealing.SAConfig(
        max_neighbors=neighbors,
        iters_per_exchange=max(int(ipe * SCALE ** 0.5), 2),
        num_exchanges=max(int(num_exchanges * SCALE ** 0.5), 2),
        solvers=solvers)


def ga_budget(generations: int = 200, pop: int = 0) -> genetic.GAConfig:
    return genetic.GAConfig(generations=scaled(generations, 5), pop_size=pop)


def write_bench_json(path: str, section: str, payload: Dict) -> None:
    """Merge one benchmark's results into a machine-readable JSON file.

    Each benchmark owns a top-level ``section`` key; existing sections
    written by other benchmarks are preserved, so CI can run several
    benchmarks and upload one artifact (``BENCH_mapper.json``) whose
    history tracks the perf trajectory.
    """
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError):
            data = {}                     # corrupt/partial file: start over
    data[section] = payload
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")

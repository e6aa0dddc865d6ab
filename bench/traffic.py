"""Job streams for the benchmark, generated from a configuration, a mix and a seed.

A configuration (``bench/configs/<name>.json``) fixes what the machine's
users submit: the job-size model, the runtime model and the flow recipe.
A traffic mix (``bench/mixes/<name>.json``) fixes how they submit it: how
many jobs wait in the queue and how set-up drives the stream before the
window opens.  One generator reads both, so a new mix or a new
configuration is a data file.

Every seed sees the same jobs in the same order.  The configuration's
``block_seed`` draws one block of (size, runtime) pairs from the model;
the stream is that block, in the order drawn, over and over.  The run's
seed draws only each job's flows and its solver seed.

The flow recipe is a copy of the resource manager's ``default_flows``
(heavy ring plus sparse random background), kept here so that a change
to the program cannot change the yardstick.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Tuple

import numpy as np

SEED_SPACE = 2**31 - 1          # job seeds stay inside a signed 32-bit key


@dataclasses.dataclass(frozen=True)
class Job:
    index: int
    size: int
    run_s: float
    seed: int                   # the solver's PRNG seed for this job
    flow_seed: int


def ring_background_flows(n: int, seed, ring: float = 100.0,
                          density: float = 0.1,
                          max_background: int = 9) -> np.ndarray:
    """Heavy ring traffic over the n processes plus sparse random
    background flows, symmetric, float32, zero diagonal.  With the default
    parameters this equals ``repro.serve.rm.default_flows(n, seed)``."""
    if n < 1:
        raise ValueError("n must be >= 1")
    rng = np.random.default_rng([n, seed])
    C = np.zeros((n, n), np.float32)
    k = np.arange(n)
    C[k, (k + 1) % n] = ring
    C[(k + 1) % n, k] = ring
    extra = rng.random((n, n)) < density
    C += np.triu(extra * rng.integers(1, max_background + 1, (n, n)),
                 1).astype(np.float32)
    return np.triu(C, 1) + np.triu(C, 1).T


def _draw_sizes(rng, count: int, m: dict) -> np.ndarray:
    """Sizes from the two-stage log-uniform, rounded to a power of two with
    probability ``pow2_prob``, conditioned on [min_size, max_size]."""
    out: List[int] = []
    while len(out) < count:
        k = 4 * count
        hi = rng.random(k) >= m["uprob"]
        x = np.where(hi, rng.uniform(m["umed"], m["uhi"], k),
                     rng.uniform(m["ulow"], m["umed"], k))
        x = np.where(rng.random(k) < m["pow2_prob"], np.round(x), x)
        s = np.round(2.0 ** x).astype(np.int64)
        s = s[(s >= m["min_size"]) & (s <= m["max_size"])]
        out.extend(int(v) for v in s[:count - len(out)])
    return np.asarray(out, np.int64)


def _runtimes(rng, sizes: np.ndarray, r: dict) -> np.ndarray:
    """Hyper-gamma of log runtime: the first gamma with probability
    p = clip(pa * size + pb, 0, 1), else the second."""
    p = np.clip(r["pa"] * sizes + r["pb"], 0.0, 1.0)
    first = rng.random(sizes.shape[0]) < p
    g = np.where(first, rng.gamma(r["a1"], r["b1"], sizes.shape[0]),
                 rng.gamma(r["a2"], r["b2"], sizes.shape[0]))
    return np.exp(g)


def block(config: dict) -> Tuple[np.ndarray, np.ndarray]:
    """The configuration's block of (size, runtime) pairs, from its
    ``block_seed`` alone."""
    jobs = config["jobs"]
    rng = np.random.default_rng(jobs["block_seed"])
    sizes = _draw_sizes(rng, jobs["block_jobs"], jobs["size_model"])
    return sizes, _runtimes(rng, sizes, jobs["runtime"])


class Stream:
    """The endless job stream of one (configuration, mix, seed)."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix = config, mix
        self.flow_params = config["flows"]
        self.sizes, self.runtimes = block(config)
        self.rng = np.random.default_rng(seed)
        self._iter = self._jobs()

    def flows(self, job: Job) -> np.ndarray:
        return ring_background_flows(job.size, job.flow_seed,
                                     **self.flow_params)

    def _jobs(self) -> Iterator[Job]:
        index = 0
        while True:
            for size, run_s in zip(self.sizes.tolist(), self.runtimes.tolist()):
                yield Job(index=index, size=int(size), run_s=float(run_s),
                          seed=int(self.rng.integers(SEED_SPACE)),
                          flow_seed=int(self.rng.integers(SEED_SPACE)))
                index += 1

    def __next__(self) -> Job:
        return next(self._iter)

    def __iter__(self):
        return self

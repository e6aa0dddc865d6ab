"""The benchmark's tests: its modules live in ``bench/`` and import one
another by name, as ``bench/run.py`` runs them."""
import copy
import json
import sys
from pathlib import Path

import pytest

from repro.core.annealing import SAConfig

BENCH = Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def tiny() -> dict:
    """The capacity configuration cut to a 64-node machine, jobs of 2 to
    16, one bucket and a token solver budget: the harness's whole path on
    the CPU in seconds."""
    cfg = json.loads((BENCH / "configs" / "capacity4096-psa.json").read_text())
    cfg["machine"]["dims"] = [4, 4, 4]
    cfg["jobs"]["size_model"].update(min_size=2, max_size=16)
    cfg["jobs"]["block_jobs"] = 24
    cfg["engine"] = {"buckets": [16], "large_buckets": [512],
                     "polish_rounds": 8, "warm_start": True,
                     "sa_cfg": SAConfig(max_neighbors=4, iters_per_exchange=2,
                                        num_exchanges=2, solvers=2)}
    return cfg


@pytest.fixture
def tiny_config():
    return tiny()


@pytest.fixture
def tiny_mix():
    mix = json.loads((BENCH / "mixes" / "steady.json").read_text())
    mix["backlog"] = 4
    return copy.deepcopy(mix)

"""The benchmark's job streams: deterministic per seed, the same jobs in
the same order for every seed, and the flow recipe of the program."""
import json

import numpy as np

import traffic
from conftest import BENCH

SEEDS = (0, 7, 2**31 + 5, 2**40 + 3)


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def mix(name="steady"):
    return json.loads((BENCH / "mixes" / f"{name}.json").read_text())


def take(stream, n):
    return [next(stream) for _ in range(n)]


def test_stream_is_deterministic_per_seed():
    cfg = config("capacity4096-psa")
    for seed in SEEDS:
        a = take(traffic.Stream(cfg, mix(), seed), 300)
        b = take(traffic.Stream(cfg, mix(), seed), 300)
        assert a == b
    assert (take(traffic.Stream(cfg, mix(), 1), 50)
            != take(traffic.Stream(cfg, mix(), 2), 50))


def test_every_seed_gets_the_same_jobs_in_the_same_order():
    """The seed draws each job's flows and solver seed, never its size,
    runtime or place in the stream."""
    cfg = config("capacity4096-psa")
    nb = cfg["jobs"]["block_jobs"]
    order = [[(j.index, j.size, j.run_s)
              for j in take(traffic.Stream(cfg, mix(), seed), 2 * nb)]
             for seed in SEEDS]
    assert all(o == order[0] for o in order)
    assert ([(s, r) for _, s, r in order[0][:nb]]
            == [(s, r) for _, s, r in order[0][nb:]])
    a = take(traffic.Stream(cfg, mix(), SEEDS[0]), nb)
    b = take(traffic.Stream(cfg, mix(), SEEDS[1]), nb)
    assert [j.flow_seed for j in a] != [j.flow_seed for j in b]
    assert [j.seed for j in a] != [j.seed for j in b]


def test_capacity_sizes_follow_the_model_within_the_partition():
    sizes, runtimes = traffic.block(config("capacity4096-psa"))
    assert sizes.min() >= 2 and sizes.max() <= 128
    pow2 = np.mean((sizes & (sizes - 1)) == 0)
    assert 0.6 < pow2 < 0.95            # pow2_prob 0.75, plus small sizes
    # log2 sizes spread over the whole range, not piled at a limit
    counts = np.bincount(np.round(np.log2(sizes)).astype(int), minlength=8)
    assert (counts[1:8] > 0).all() and counts.max() < 0.25 * sizes.size
    assert (runtimes > 0).all()


def test_flow_recipe_equals_the_programs_default_flows():
    from repro.serve.rm import default_flows
    for n in (1, 2, 5, 64, 300):
        for seed in (0, 3, 2**31 - 2):
            assert np.array_equal(traffic.ring_background_flows(n, seed),
                                  default_flows(n, seed))

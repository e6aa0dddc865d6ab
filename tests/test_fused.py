"""Fused SA/GA megakernel steps: bitwise equality vs the unfused loops.

``SAConfig(loop="fused")`` runs a whole temperature step — and
``GAConfig(eval="fused")`` a whole generation — as one Pallas launch,
replaying the identical on-chip counter-RNG stream as the unfused
``loop="event", rng="counter"`` / ``eval="wide", rng="counter"`` paths
(docs/DESIGN.md §13).  On CPU the fused dispatch routes to the lock-step
references in ``kernels/ref.py``, so every comparison below is bitwise;
the interpret-mode Pallas kernels are validated against those same
references on integer-valued instances where f32 sums are exact in any
order.
"""
from dataclasses import replace

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import annealing, composite, genetic, qap
from repro.kernels import ops, prng, ref

from _fixtures import GA_SMALL, PCA_SMALL, SA_SMALL, instance, padded_batch


def _bitwise(a, b, msg=""):
    for x, y in zip(a, b):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), msg


# ------------------------------------------------------------- solver level
@pytest.mark.parametrize("n", [8, 32, 64])
def test_psa_fused_matches_unfused_counter_loops(n):
    """run_psa: fused == event == scan on the shared counter stream."""
    C, M = instance(n, n)
    key = jax.random.PRNGKey(1)
    outs = {}
    for name, cfg in (("fused", replace(SA_SMALL, loop="fused")),
                      ("event", replace(SA_SMALL, loop="event",
                                        rng="counter")),
                      ("scan", replace(SA_SMALL, loop="scan",
                                       rng="counter"))):
        outs[name] = annealing.run_psa(C, M, key, cfg, 2)
    _bitwise(outs["fused"], outs["event"], "fused != event")
    _bitwise(outs["fused"], outs["scan"], "fused != scan")
    assert qap.is_permutation(outs["fused"][0])


def test_psa_fused_invariant_to_event_width():
    """The event window width is a scheduling knob, not a semantic one:
    fused results are identical for width 1, 3, and full."""
    C, M = instance(24, 3)
    key = jax.random.PRNGKey(2)
    outs = [annealing.run_psa(C, M, key,
                              replace(SA_SMALL, loop="fused", event_width=w),
                              2)
            for w in (1, 3, None)]
    _bitwise(outs[0], outs[1], "width 1 != width 3")
    _bitwise(outs[0], outs[2], "width 1 != full width")


def test_psa_fused_padded_batch_warm_and_cold():
    """run_psa_batch on a bucket-padded batch with mixed warm/cold
    starts: fused == event-counter bitwise, pad tails stay identity."""
    sizes, bucket = (8, 12, 16), 16
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket)
    ip = np.full((len(sizes), bucket), -1, np.int32)
    # warm-start rows 0 and 2 from reversed-prefix permutations
    for b in (0, 2):
        n = sizes[b]
        ip[b, :n] = np.arange(n)[::-1]
        ip[b, n:] = np.arange(n, bucket)
    ip = jnp.asarray(ip)
    got = annealing.run_psa_batch(Cs, Ms, keys,
                                  replace(SA_SMALL, loop="fused"), 2,
                                  n_valid=nvs, init_perm=ip)
    want = annealing.run_psa_batch(Cs, Ms, keys,
                                   replace(SA_SMALL, loop="event",
                                           rng="counter"), 2,
                                   n_valid=nvs, init_perm=ip)
    _bitwise(got, want, "fused != event on the padded batch")
    perms = np.asarray(got[0])
    for b, n in enumerate(sizes):
        assert sorted(perms[b, :n]) == list(range(n))
        np.testing.assert_array_equal(perms[b, n:], np.arange(n, bucket))


@pytest.mark.parametrize("n", [8, 32])
def test_pga_fused_matches_wide_counter(n):
    """run_pga: fused == wide on the shared counter stream, including the
    per-generation history."""
    C, M = instance(n, n + 1)
    key = jax.random.PRNGKey(3)
    got = genetic.run_pga(C, M, key, replace(GA_SMALL, eval="fused"), 2)
    want = genetic.run_pga(C, M, key,
                           replace(GA_SMALL, eval="wide", rng="counter"), 2)
    _bitwise(got, want, "fused != wide")
    assert qap.is_permutation(got[0])


def test_pga_fused_padded_batch_warm_and_cold():
    sizes, bucket = (8, 12, 16), 16
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket, seed0=5)
    ip = np.full((len(sizes), bucket), -1, np.int32)
    ip[1, :sizes[1]] = np.arange(sizes[1])[::-1]
    ip[1, sizes[1]:] = np.arange(sizes[1], bucket)
    ip = jnp.asarray(ip)
    got = genetic.run_pga_batch(Cs, Ms, keys,
                                replace(GA_SMALL, eval="fused"), 2,
                                n_valid=nvs, init_perm=ip)
    want = genetic.run_pga_batch(Cs, Ms, keys,
                                 replace(GA_SMALL, eval="wide",
                                         rng="counter"), 2,
                                 n_valid=nvs, init_perm=ip)
    _bitwise(got, want, "fused != wide on the padded batch")


def test_pca_fused_composite():
    """The composite rebuilds its SA stage config, so loop='fused' and
    eval='fused' must propagate through run_pca unchanged."""
    C, M = instance(16, 9)
    key = jax.random.PRNGKey(4)
    fused = replace(PCA_SMALL,
                    sa=replace(PCA_SMALL.sa, loop="fused"),
                    ga=replace(PCA_SMALL.ga, eval="fused"))
    unfused = replace(PCA_SMALL,
                      sa=replace(PCA_SMALL.sa, loop="event", rng="counter"),
                      ga=replace(PCA_SMALL.ga, eval="wide", rng="counter"))
    got = composite.run_pca(C, M, key, fused, 2)
    want = composite.run_pca(C, M, key, unfused, 2)
    _bitwise(got, want, "fused composite != unfused counter composite")


# ------------------------------------------------------------- kernel level
def _sa_states(n, B, seed):
    C, M = instance(n, seed)
    ps = qap.random_permutations(jax.random.PRNGKey(seed), B, n)
    fs = ref.qap_objective_ref(jnp.asarray(C), jnp.asarray(M), ps)
    temps = jnp.linspace(5.0, 50.0, B).astype(jnp.float32)
    keys = prng.key_data(
        jax.random.split(jax.random.PRNGKey(seed + 1), B)).astype(jnp.uint32)
    nvs = jnp.full((B,), n, jnp.int32)
    return jnp.asarray(C), jnp.asarray(M), ps, fs, temps, keys, nvs


@pytest.mark.parametrize("n", [8, 32])
def test_sa_step_kernel_interpret_matches_ref(n):
    """Interpret-mode fused SA kernel == the lock-step reference, bitwise
    (integer-valued instances: f32 sums are exact in any order)."""
    C, M, ps, fs, temps, keys, nvs = _sa_states(n, 5, n + 20)
    got = ops.qap_sa_step(C, M, ps, fs, ps, fs, temps, keys, nvs,
                          max_neighbors=10, max_success=3,
                          force_pallas=True, interpret=True)
    want = ref.qap_sa_step_ref(C, M, ps, fs, ps, fs, temps, keys, nvs,
                               max_neighbors=10, max_success=3)
    _bitwise(got, want, "fused SA kernel != ref")


def test_sa_step_kernel_interpret_masked():
    """A padded instance (n_valid < N, zero-padded C/M, identity pad tail)
    gives the same step as the reference."""
    n, nv, B = 16, 11, 4
    C, M, ps, fs, temps, keys, _ = _sa_states(nv, B, 33)
    Cp = jnp.zeros((n, n), jnp.float32).at[:nv, :nv].set(C)
    Mp = jnp.zeros((n, n), jnp.float32).at[:nv, :nv].set(M)
    tail = jnp.broadcast_to(jnp.arange(nv, n, dtype=jnp.int32), (B, n - nv))
    pp = jnp.concatenate([ps, tail], axis=1)
    nvs = jnp.full((B,), nv, jnp.int32)
    got = ops.qap_sa_step(Cp, Mp, pp, fs, pp, fs, temps, keys, nvs,
                          max_neighbors=10, max_success=3,
                          force_pallas=True, interpret=True)
    want = ref.qap_sa_step_ref(Cp, Mp, pp, fs, pp, fs, temps, keys, nvs,
                               max_neighbors=10, max_success=3)
    _bitwise(got, want, "masked fused SA kernel != ref")
    np.testing.assert_array_equal(np.asarray(got[0])[:, nv:],
                                  np.asarray(tail))


@pytest.mark.parametrize("crossover", ["ox", "oxs"])
def test_ga_step_kernel_interpret_matches_ref(crossover):
    """Interpret-mode fused GA kernel == the lock-step reference, bitwise."""
    n, islands, pop = 16, 3, 8
    C, M = instance(n, 41)
    C, M = jnp.asarray(C), jnp.asarray(M)
    pops = jnp.stack([qap.random_permutations(jax.random.PRNGKey(50 + i),
                                              pop, n)
                      for i in range(islands)])
    fits = jax.vmap(lambda p: ref.qap_objective_ref(C, M, p))(pops)
    keys = prng.key_data(
        jax.random.split(jax.random.PRNGKey(42), islands)).astype(jnp.uint32)
    nvs = jnp.full((islands,), n, jnp.int32)
    kw = dict(n_off=4, tournament=3, p_crossover=0.9, p_mutation=0.3,
              crossover=crossover)
    got = ops.qap_ga_step(C, M, pops, fits, keys, nvs,
                          force_pallas=True, interpret=True, **kw)
    want = ref.qap_ga_step_ref(C, M, pops, fits, keys, nvs, **kw)
    _bitwise(got, want, "fused GA kernel != ref")


# -------------------------------------------------------- routing + config
def test_resolved_loop_vmem_routing():
    """'fused' silently degrades to the unfused golden loops whenever the
    kernel cannot hold the instance: sparse flows or beyond the VMEM cap."""
    cfg = replace(SA_SMALL, loop="fused")
    assert annealing.resolved_loop(cfg, 64) == "fused"
    assert annealing.resolved_loop(cfg, None) == "fused"
    assert ops.fused_step_fits(64)
    assert not ops.fused_step_fits(4096)
    assert annealing.resolved_loop(cfg, 4096) == "event"
    assert annealing.resolved_loop(replace(cfg, flows="sparse"), 64) == \
        "event"
    assert annealing.resolved_loop(replace(SA_SMALL, loop="scan"), 64) == \
        "scan"

    gcfg = replace(GA_SMALL, eval="fused")
    assert genetic.resolved_eval(gcfg, 64) == "fused"
    assert genetic.resolved_eval(gcfg, 4096) == "wide"
    assert genetic.resolved_eval(replace(gcfg, flows="sparse"), 64) == "wide"
    assert genetic.resolved_eval(replace(GA_SMALL, eval="island"), 64) == \
        "island"


def test_fused_refused_on_tpu(monkeypatch):
    """On a TPU backend, where the fused kernels do not compile, a config
    that would run fused raises a clear error instead of falling back to
    the unfused loops; the VMEM-cap and sparse fallbacks are unchanged."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    with pytest.raises(NotImplementedError, match="do not compile for TPU"):
        annealing.resolved_loop(replace(SA_SMALL, loop="fused"), 64)
    with pytest.raises(NotImplementedError, match="do not compile for TPU"):
        genetic.resolved_eval(replace(GA_SMALL, eval="fused"), 64)
    assert annealing.resolved_loop(replace(SA_SMALL, loop="fused"),
                                   4096) == "event"
    assert genetic.resolved_eval(replace(GA_SMALL, eval="fused",
                                         flows="sparse"), 64) == "wide"
    assert annealing.resolved_loop(SA_SMALL, 64) == "event"


def test_config_validation():
    C, M = instance(8, 77)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="loop"):
        annealing.resolved_loop(replace(SA_SMALL, loop="bogus"))
    with pytest.raises(ValueError, match="rng"):
        annealing.run_psa(C, M, key, replace(SA_SMALL, rng="bogus"), 2)
    with pytest.raises(ValueError, match="event_width"):
        annealing.resolved_event_width(replace(SA_SMALL, event_width=0))
    with pytest.raises(ValueError, match="event_width"):
        annealing.resolved_event_width(replace(SA_SMALL,
                                               event_width="bogus"))
    with pytest.raises(ValueError, match="counter"):
        genetic.run_pga(C, M, key,
                        replace(GA_SMALL, eval="island", rng="counter"), 2)


def test_event_width_auto():
    """event_width='auto' resolves deterministically without a measured
    cache entry, and autotune_event_width fills the per-(backend, n)
    cache it then reads."""
    cfg = replace(SA_SMALL, event_width="auto")
    assert "auto" in repr(cfg)          # config digests see the mode
    backend = jax.default_backend()
    saved = dict(annealing._EVENT_WIDTH_CACHE)
    try:
        annealing._EVENT_WIDTH_CACHE.clear()
        fallback = annealing.resolved_event_width(cfg, 16)
        assert fallback == annealing._default_event_width(cfg.max_neighbors)
        w = annealing.autotune_event_width(16,
                                           max_neighbors=cfg.max_neighbors,
                                           repeats=1)
        assert annealing._EVENT_WIDTH_CACHE[(backend, 16)] == w
        assert 1 <= annealing.resolved_event_width(cfg, 16) \
            <= cfg.max_neighbors
        # a second call reuses the cache (no re-measurement)
        assert annealing.autotune_event_width(16) == w
    finally:
        annealing._EVENT_WIDTH_CACHE.clear()
        annealing._EVENT_WIDTH_CACHE.update(saved)


def test_event_width_auto_solver_results_unchanged():
    """The autotuned width is a scheduling choice only: run_psa results
    are bitwise-identical to the deterministic default width."""
    C, M = instance(16, 88)
    key = jax.random.PRNGKey(6)
    base = annealing.run_psa(C, M, key, SA_SMALL, 2)
    auto = annealing.run_psa(C, M, key,
                             replace(SA_SMALL, event_width="auto"), 2)
    _bitwise(base, auto, "event_width='auto' changed solver results")

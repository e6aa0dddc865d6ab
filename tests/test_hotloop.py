"""Golden equality: acceptance-event SA hot loop == sequential candidate scan.

The acceptance-event loop (``SAConfig(loop="event")``, the default) scores
all remaining candidates of a temperature level in one wide batched
``kernels.ops.qap_delta`` dispatch and applies the first Metropolis-accepted
one per round.  It consumes the same candidate stream and the same
acceptance uniforms as the retained sequential scan (``loop="scan"``), and
rejected candidates never mutate state — so on the CPU reference path whole
solves must be **bitwise identical**: objectives, permutations, and exchange
histories, for cold, warm-started (``init_perm``), and padded (``n_valid``)
PSA and PCA solves.
"""
import functools
from dataclasses import replace

import numpy as np
import jax
import pytest
import jax.numpy as jnp

from repro.core import annealing, composite, mapping, qap
from repro.kernels import ops

from _fixtures import SA_SMALL, PCA_SMALL, instance, padded_batch

SA_SCAN = replace(SA_SMALL, loop="scan")
PCA_SCAN = replace(PCA_SMALL, sa=replace(PCA_SMALL.sa, loop="scan"))


def _assert_bitwise(event, scan):
    ep, ef, eh = event
    sp, sf, sh = scan
    assert np.asarray(ef).tobytes() == np.asarray(sf).tobytes()
    np.testing.assert_array_equal(np.asarray(ep), np.asarray(sp))
    np.testing.assert_array_equal(np.asarray(eh), np.asarray(sh))


def _warm_rows(sizes, bucket):
    """init_perm batch warm on rows 0 and 2 (rotations), cold elsewhere."""
    ips = np.full((len(sizes), bucket), -1, np.int32)
    for i in (0, 2):
        n = sizes[i]
        ips[i, :n] = np.roll(np.arange(n), 1)
        ips[i, n:] = np.arange(n, bucket)
    return jnp.asarray(ips)


# ------------------------------------------------------------ step level
def test_temperature_step_event_matches_scan_golden():
    """Direct step-level equality over a run of temperature levels."""
    C, M = map(jnp.asarray, instance(16, 0))
    beta = annealing.make_beta(C, M, jax.random.PRNGKey(1), SA_SMALL)
    se = ss = annealing.init_chain(C, M, jax.random.PRNGKey(2), SA_SMALL)
    for t in range(12):
        k = jax.random.PRNGKey(100 + t)
        se = annealing.temperature_step(C, M, se, k, SA_SMALL, beta)
        ss = annealing.temperature_step(C, M, ss, k, SA_SCAN, beta)
        for a, b in zip(se, ss):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), t


def test_acceptance_cap_zero_freezes_state():
    """max_success=0 must accept nothing in either realisation."""
    C, M = map(jnp.asarray, instance(12, 3))
    for cfg in (replace(SA_SMALL, max_success=0),
                replace(SA_SCAN, max_success=0)):
        beta = annealing.make_beta(C, M, jax.random.PRNGKey(1), cfg)
        s0 = annealing.init_chain(C, M, jax.random.PRNGKey(2), cfg)
        s1 = annealing.temperature_step(C, M, s0, jax.random.PRNGKey(3),
                                        cfg, beta)
        np.testing.assert_array_equal(np.asarray(s1.p), np.asarray(s0.p))
        assert float(s1.f) == float(s0.f)


# ----------------------------------------------------------- solve level
def test_psa_cold_bitwise():
    C, M = map(jnp.asarray, instance(12, 0))
    key = jax.random.PRNGKey(0)
    _assert_bitwise(annealing.run_psa(C, M, key, SA_SMALL, num_processes=2),
                    annealing.run_psa(C, M, key, SA_SCAN, num_processes=2))


def test_psa_identity_seeded_bitwise():
    C, M = map(jnp.asarray, instance(12, 5))
    key = jax.random.PRNGKey(4)
    cfg_e = replace(SA_SMALL, seed_with="identity")
    cfg_s = replace(SA_SCAN, seed_with="identity")
    _assert_bitwise(annealing.run_psa(C, M, key, cfg_e, num_processes=2),
                    annealing.run_psa(C, M, key, cfg_s, num_processes=2))


def test_psa_batch_padded_and_warm_bitwise():
    """The instance-batched path: n_valid padding + mixed warm/cold rows."""
    sizes = [8, 12, 16, 16]
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket=16)
    ips = _warm_rows(sizes, bucket=16)
    _assert_bitwise(
        annealing.run_psa_batch(Cs, Ms, keys, SA_SMALL, num_processes=2,
                                n_valid=nvs, init_perm=ips),
        annealing.run_psa_batch(Cs, Ms, keys, SA_SCAN, num_processes=2,
                                n_valid=nvs, init_perm=ips))


def test_event_width_never_changes_results():
    """The round window bounds evaluation, not decisions: every width —
    degenerate 1, an uneven 3, and the full candidate set — must be
    bitwise-equal to the sequential scan."""
    C, M = map(jnp.asarray, instance(12, 9))
    key = jax.random.PRNGKey(6)
    golden = annealing.run_psa(C, M, key, SA_SCAN, num_processes=2)
    for w in (1, 3, SA_SMALL.max_neighbors):
        cfg = replace(SA_SMALL, event_width=w)
        _assert_bitwise(annealing.run_psa(C, M, key, cfg, num_processes=2),
                        golden)


def test_event_width_validation():
    import pytest
    assert annealing.resolved_event_width(SA_SMALL) >= 1
    assert annealing.resolved_event_width(
        replace(SA_SMALL, event_width=999)) == SA_SMALL.max_neighbors
    with pytest.raises(ValueError, match="event_width"):
        annealing.resolved_event_width(replace(SA_SMALL, event_width=0))


def test_pca_cold_bitwise():
    C, M = map(jnp.asarray, instance(12, 7))
    key = jax.random.PRNGKey(2)
    _assert_bitwise(composite.run_pca(C, M, key, PCA_SMALL, num_processes=2),
                    composite.run_pca(C, M, key, PCA_SCAN, num_processes=2))


def test_pca_batch_padded_and_warm_bitwise():
    sizes = [8, 12, 16, 16]
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket=16)
    ips = _warm_rows(sizes, bucket=16)
    _assert_bitwise(
        composite.run_pca_batch(Cs, Ms, keys, PCA_SMALL, num_processes=2,
                                n_valid=nvs, init_perm=ips),
        composite.run_pca_batch(Cs, Ms, keys, PCA_SCAN, num_processes=2,
                                n_valid=nvs, init_perm=ips))


def test_event_solutions_remain_feasible_under_padding():
    """Sanity on top of equality: event-loop solves keep the feasibility
    invariant (valid prefix is a permutation of the real nodes, padded
    tail is identity)."""
    sizes = [6, 9]
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket=16, seed0=50)
    bp, _, _ = annealing.run_psa_batch(Cs, Ms, keys, SA_SMALL,
                                       num_processes=2, n_valid=nvs)
    for i, n in enumerate(sizes):
        perm = np.asarray(bp)[i]
        assert sorted(perm[:n].tolist()) == list(range(n))
        np.testing.assert_array_equal(perm[n:], np.arange(n, 16))
        assert bool(qap.is_permutation(jnp.asarray(perm)))


def test_unknown_loop_rejected():
    import pytest
    C, M = map(jnp.asarray, instance(8, 1))
    cfg = replace(SA_SMALL, loop="nope")
    with pytest.raises(ValueError, match="hot-loop"):
        annealing.run_psa(C, M, jax.random.PRNGKey(0), cfg, num_processes=2)


# ------------------------------------------------------- loop counts
def test_loop_counts_leave_psa_batch_bitwise_unchanged():
    """counts=True adds the event loop's per-lane, per-level counts and
    changes nothing else: perms, F and history are bitwise those of the
    program without them."""
    sizes = [8, 12, 16, 16]
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket=16)
    ips = _warm_rows(sizes, bucket=16)
    kw = dict(num_processes=2, n_valid=nvs, init_perm=ips)
    off = annealing.run_psa_batch(Cs, Ms, keys, SA_SMALL, **kw)
    *on, counts = annealing.run_psa_batch(Cs, Ms, keys, SA_SMALL, counts=True,
                                          **kw)
    _assert_bitwise(on, off)
    levels = SA_SMALL.num_exchanges * SA_SMALL.iters_per_exchange
    shape = (len(sizes), 2, SA_SMALL.solvers, levels)
    assert counts.rounds.shape == counts.accepts.shape == shape
    rounds, accepts = np.asarray(counts.rounds), np.asarray(counts.accepts)
    assert (rounds >= 1).all() and (accepts >= 0).all()
    assert (accepts <= np.minimum(rounds, SA_SMALL.max_success)).all()


def _level_draws(t, n, cfg):
    kpair, kacc = jax.random.split(jax.random.PRNGKey(200 + t))
    return (qap.random_swap_pairs(kpair, cfg.max_neighbors, n, None),
            jax.random.uniform(kacc, (cfg.max_neighbors,)))


@pytest.mark.parametrize("width", [1, 3, None, SA_SMALL.max_neighbors])
def test_event_loop_accepts_equal_the_scan_oracle(width):
    """Level by level, on the same draws, the event loop accepts as many
    moves as the sequential scan, and runs no more rounds than one per
    acceptance plus one per window of the candidate list."""
    cfg = replace(SA_SMALL, event_width=width)
    k, w = cfg.max_neighbors, annealing.resolved_event_width(cfg, 12)
    bound = min(cfg.max_success, k) + -(-k // w)
    event = jax.jit(functools.partial(annealing._acceptance_event_loop,
                                      cfg=cfg, counts=True))
    scan = jax.jit(functools.partial(annealing._candidate_scan, cfg=SA_SCAN))
    C, M = map(jnp.asarray, instance(12, 11))
    state = annealing.init_chain(C, M, jax.random.PRNGKey(3), cfg)
    t0, seen = state.temp, set()
    for t in range(16):
        state = state._replace(temp=t0 * 0.5 ** t)   # hot to frozen
        pairs, us = _level_draws(t, 12, cfg)
        *ev, rounds, accepts = event(C, M, state, pairs, us)
        *sc, scan_accepts = scan(C, M, state, pairs, us)
        assert int(accepts) == int(scan_accepts), t
        assert 1 <= int(rounds) <= bound, t
        for a, b in zip(ev, sc):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), t
        seen.add(int(accepts))
        state = state._replace(p=ev[0], f=ev[1], best_p=ev[2], best_f=ev[3])
    assert 0 in seen and cfg.max_success in seen    # frozen and capped levels


def test_loop_counts_only_from_the_event_loop():
    C, M = map(jnp.asarray, instance(8, 1))
    beta = annealing.make_beta(C, M, jax.random.PRNGKey(1), SA_SCAN)
    s0 = annealing.init_chain(C, M, jax.random.PRNGKey(2), SA_SCAN)
    with pytest.raises(ValueError, match="no counts"):
        annealing.temperature_step(C, M, s0, jax.random.PRNGKey(3), SA_SCAN,
                                   beta, counts=True)


# ------------------------------------------------------ delta kernel forms
def _kernel_wave(Cs, Ms, keys, nvs, ips):
    """One bucket PSA wave with loop counts, then the engine's polish."""
    perms, fs, _, counts = annealing.run_psa_batch(
        Cs, Ms, keys, SA_SMALL, num_processes=2, n_valid=nvs, init_perm=ips,
        counts=True)
    pkeys = jnp.stack([jax.random.fold_in(k, 7) for k in keys])
    pp, pf = mapping.polish_batch(Cs, Ms, perms, pkeys, 4, nvs)
    return perms, fs, counts, pp, pf


def test_delta_kernel_forms_give_the_same_wave(monkeypatch):
    """A bucket PSA wave and its polish, with every dense delta run by the
    row-form kernel and then by the per-candidate form (interpret mode),
    give bitwise-equal perms, F and loop counts, and equal those of the
    reference path: the same accept decisions, candidate by candidate."""
    sizes = [8, 12, 16, 16]
    Cs, Ms, nvs, keys = padded_batch(sizes, bucket=16)
    ips = _warm_rows(sizes, bucket=16)
    forms = {"reference": jax.tree_util.tree_leaves(
        _kernel_wave(Cs, Ms, keys, nvs, ips))}
    kernel_delta = functools.partial(ops.qap_delta, force_pallas=True,
                                     interpret=True)
    monkeypatch.setattr(ops, "qap_delta", kernel_delta)
    # Trace caches key on signatures only: the kernel-path traces must
    # neither come from nor leak into the reference-path tests.
    jax.clear_caches()
    try:
        for form in ("row", "candidate"):
            monkeypatch.setattr(ops, "_kernel_form", lambda n, f=form: f)
            forms[form] = jax.tree_util.tree_leaves(
                _kernel_wave(Cs, Ms, keys, nvs, ips))
            jax.clear_caches()
    finally:
        jax.clear_caches()
    for form in ("row", "candidate"):
        for got, want in zip(forms[form], forms["reference"]):
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes()

"""Theorem-level integration tests: each convergence guarantee of the paper
is checked empirically on the ridge problem in the regime it covers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DCGDShift,
    DianaShift,
    FixedShift,
    GDCI,
    Identity,
    RandDianaShift,
    RandK,
    StarShift,
    TopK,
    VRGDCI,
    rand_diana_default_p,
    stepsize_dcgd_fixed,
    stepsize_dcgd_star,
    stepsize_diana,
    stepsize_gdci,
    stepsize_rand_diana,
    stepsize_vr_gdci,
)
from repro.core.simulate import run_dcgd_shift, run_gdci
from repro.data.problems import make_logreg, make_ridge


@pytest.fixture(scope="module")
def prob():
    # Conditioned for decisive theorem measurements (the paper-exact
    # instance lives in test_theorems):
    #  * noise=10 — non-interpolating regime; with noise=0, grad_i(x*)
    #    is lam-residual-only and the DCGD variance neighborhood that
    #    Theorem 1 measures collapses to the 1e-7 float32 knife edge.
    #  * lam=0.3 — at lam=1/m the self-noise coupling
    #    gamma*omega*L_bar^2/(2*mu*n) is ~0.57 at Theorem 1's max
    #    stepsize, so the neighborhood radius scales ~2x (not ~4x) when
    #    gamma drops 4x; a modestly larger mu restores the
    #    linear-in-gamma radius the gamma/4 assertion checks while
    #    keeping kappa ~150 (much larger lam over-conditions the
    #    problem and the exactness tests bottom out at the f32 floor
    #    before their "still contracting" windows sample).
    return make_ridge(lam=0.3, noise=10.0)


@pytest.fixture(scope="module")
def q():
    return RandK(0.25)


def test_uncompressed_gd_is_exact(prob):
    """Sanity: Q = Identity, zero shift == plain distributed GD."""
    tr = run_dcgd_shift(
        prob, DCGDShift(Identity(), FixedShift()), 1.0 / prob.L, 2000
    )
    assert tr.rel_err[-1] < 1e-9


def test_theorem1_dcgd_neighborhood(prob, q):
    """Thm 1: DCGD converges to a gamma-proportional neighborhood."""
    om = q.omega(prob.d)
    g = stepsize_dcgd_fixed(prob.L, prob.L_max, om, prob.n_workers)
    tr_full = run_dcgd_shift(prob, DCGDShift(q, FixedShift()), g, 4000, seed=1)
    tr_half = run_dcgd_shift(prob, DCGDShift(q, FixedShift()), g / 4, 16000, seed=1)
    tail_full = float(np.median(tr_full.rel_err[-500:]))
    tail_half = float(np.median(tr_half.rel_err[-500:]))
    assert tail_full > 1e-7  # genuinely stuck in a neighborhood
    # Thm 1: radius scales ~ gamma => gamma/4 shrinks it ~4x (allow slack 2x)
    assert tail_half < tail_full / 2.0


def test_theorem2_dcgd_star_exact(prob, q):
    """Thm 2: oracle shifts give exact linear convergence."""
    om = q.omega(prob.d)
    g = stepsize_dcgd_star(prob.L, prob.L_max, om, 0.0, prob.n_workers)
    tr = run_dcgd_shift(
        prob, DCGDShift(q, StarShift()), g, 6000, use_star=True, seed=2
    )
    assert tr.rel_err[-1] < 5e-5
    # linearity: the windowed error decreases every window until it
    # reaches the float32 floor (~1e-13, by about window 8), where it
    # only jitters
    w = tr.rel_err[::500]
    floor = 1e-12
    above = [i for i in range(len(w) - 1) if w[i] > floor]
    assert len(above) >= 6
    assert all(w[i + 1] < w[i] for i in above)


def test_theorem2_star_with_biased_c(prob, q):
    """Thm 2 with contractive C_i (Top-K) in the shift update still exact."""
    om = q.omega(prob.d)
    c = TopK(0.5)
    g = stepsize_dcgd_star(prob.L, prob.L_max, om, c.delta(prob.d), prob.n_workers)
    tr = run_dcgd_shift(
        prob, DCGDShift(q, StarShift(c=c)), g, 6000, use_star=True, seed=3
    )
    assert tr.rel_err[-1] < 5e-4


def test_theorem3_diana_exact(prob, q):
    om = q.omega(prob.d)
    alpha, g = stepsize_diana(prob.L_max, om, 0.0, prob.n_workers)
    tr = run_dcgd_shift(prob, DCGDShift(q, DianaShift(alpha)), g, 12000, seed=4)
    assert tr.rel_err[-1] < 1e-4


def test_theorem3_generalized_diana_with_topk(prob, q):
    """Generalized DIANA: biased C_i in the shift update (eq. 10)."""
    om = q.omega(prob.d)
    c = TopK(0.5)
    alpha, g = stepsize_diana(prob.L_max, om, c.delta(prob.d), prob.n_workers)
    tr = run_dcgd_shift(
        prob, DCGDShift(q, DianaShift(alpha, c=c)), g, 12000, seed=5
    )
    assert tr.rel_err[-1] < 1e-4


def test_theorem4_rand_diana_exact(prob, q):
    om = q.omega(prob.d)
    p = rand_diana_default_p(om)
    _, g = stepsize_rand_diana(prob.L_max, om, prob.n_workers, p)
    tr = run_dcgd_shift(prob, DCGDShift(q, RandDianaShift(p)), g, 12000, seed=6)
    assert tr.rel_err[-1] < 1e-3
    # exactness: keeps contracting through late training (no variance floor)
    assert float(np.median(tr.rel_err[-1000:])) < float(
        np.median(tr.rel_err[5000:6000])
    )


def test_theorem5_gdci_neighborhood(prob, q):
    om = q.omega(prob.d)
    eta, gamma = stepsize_gdci(prob.L, prob.L_max, prob.mu, om, prob.n_workers)
    m = GDCI(q, gamma=gamma, eta=eta)
    tr = run_gdci(prob, m, 6000, seed=7)
    tail = float(np.median(tr.rel_err[-500:]))
    assert tail < 1e-1       # converged to the neighborhood...
    assert tail > 1e-9       # ...but not exactly (non-interpolation regime)


def test_theorem6_vr_gdci_exact(prob, q):
    om = q.omega(prob.d)
    alpha, eta, gamma = stepsize_vr_gdci(
        prob.L, prob.L_max, prob.mu, om, prob.n_workers
    )
    m = VRGDCI(q, gamma=gamma, eta=eta, alpha=alpha)
    tr = run_gdci(prob, m, 20000, seed=8)
    assert tr.rel_err[-1] < 1e-4
    # VR eliminates the GDCI neighborhood:
    eta_g, gamma_g = stepsize_gdci(prob.L, prob.L_max, prob.mu, om, prob.n_workers)
    tr_g = run_gdci(prob, GDCI(q, gamma=gamma_g, eta=eta_g), 20000, seed=8)
    assert tr.rel_err[-1] < float(np.median(tr_g.rel_err[-500:]))


def test_diana_beats_dcgd_in_bits():
    """The headline practical claim: shift learning reaches tighter
    tolerances than plain DCGD, which stalls at its variance radius.
    Uses a noisy (non-interpolating) problem and aggressive compression
    (Rand-K, q=0.05) so the DCGD radius is well above the float32 floor."""
    prob = make_ridge(noise=10.0, seed=5)
    q = RandK(0.05)
    om = q.omega(prob.d)
    alpha, g_d = stepsize_diana(prob.L_max, om, 0.0, prob.n_workers)
    g_f = stepsize_dcgd_fixed(prob.L, prob.L_max, om, prob.n_workers)
    tr_diana = run_dcgd_shift(prob, DCGDShift(q, DianaShift(alpha)), g_d, 20000)
    tr_dcgd = run_dcgd_shift(prob, DCGDShift(q, FixedShift()), g_f, 20000)
    dcgd_tail = float(np.median(tr_dcgd.rel_err[-2000:]))
    diana_tail = float(np.median(tr_diana.rel_err[-2000:]))
    assert dcgd_tail > 1e-7        # DCGD stuck in its neighborhood
    assert diana_tail < dcgd_tail  # DIANA breaks through it


def test_logreg_problem_wellformed():
    prob = make_logreg(m=200, d=40)
    g = prob.full_grad(prob.x_star)
    assert float(jnp.linalg.norm(g)) < 1e-5
    assert abs(prob.kappa - 100.0) < 5.0
    wg = prob.worker_grads(prob.x_star)
    assert wg.shape == (10, 40)
    np.testing.assert_allclose(
        np.asarray(jnp.mean(wg, axis=0)), np.asarray(g), atol=1e-5
    )


def test_rand_diana_on_logreg():
    prob = make_logreg(m=200, d=40)
    q = RandK(0.25)
    om = q.omega(prob.d)
    p = rand_diana_default_p(om)
    _, g = stepsize_rand_diana(prob.L_max, om, prob.n_workers, p)
    tr = run_dcgd_shift(prob, DCGDShift(q, RandDianaShift(p)), g, 15000, seed=9)
    assert tr.rel_err[-1] < 1e-2

"""Variable-order variable-timestep BDF integrator (CVODE reimplemented in JAX).

This is the paper's fully-implicit solver (§2.3, Eq. 2): the fixed-leading-
coefficient BDF(1..5) of CVODE [Cohen & Hindmarsh 1996] in Nordsieck form,
with

  * the l / tq coefficient recurrences of SUNDIALS' cvSetBDF / cvSetTqBDF,
  * a modified-Newton corrector whose linear solves use the Hines-structured
    approximate Jacobian M = I - gamma*J~ (NEURON's default preconditioner),
  * WRMS-norm local error test, eta_{q-1}/eta_q/eta_{q+1} order selection
    (cvPrepareNextStep / cvAdjust{Increase,Decrease}BDF),
  * tstop semantics: a step never crosses ``t_limit`` — this is what makes the
    FAP execution model *non-speculative* (no backstepping ever needed),
  * IVP-reset on synaptic discontinuities (order -> 1, fresh h, history
    discarded) — the cost the paper's event-grouping variants amortise,
  * a CVODE-grade Jacobian-freshness policy (``jac_policy="reuse"``, the
    default): the Newton matrix M = I - gamma*J~ is assembled and factored
    ONCE per setup (``CellModel.newton_setup``, CVODE's lsetup) and the
    stored factors are reused across Newton iterations *and* accepted
    steps; a rebuild happens only on gamma drift (|gamma/gamma_saved - 1|
    > DGMAX), a periodic MSBP step counter, convergence-rate decay
    (crate > CRDOWN after a multi-iteration solve), or after a Newton
    convergence failure.  A convergence failure with *stale* factors
    first retries the same step with a fresh setup (CVODE's
    CV_FAIL_BAD_J path) before shrinking h.  Stale factors change the
    Newton iteration count, never the accepted state beyond tolerance —
    the corrector still converges to the exact implicit solution.
    ``jac_policy="iteration"`` is the legacy knob: re-assemble/factor on
    every Newton iteration, lowered computation identical to the
    historical path, and
  * ``method="ndf"``: the Klopfenstein/Shampine NDF error constants of
    MATLAB's ode15s wired into the BDF tq coefficients — same corrector,
    kappa-modified error weighting, h larger by |C_ndf/C_bdf|^(-1/(q+1))
    at equal tolerance (up to ~26% more step at q=2).

Every function is pure and ``vmap``-compatible: a network of neurons is a
vmapped pytree of ``BDFState`` with *independent* (t, h, q) per neuron — the
essence of the paper's per-neuron variable stepping.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

QMAX = 5
LMAX = QMAX + 1           # zn rows: 0..QMAX

# CVODE constants
ETAMX1 = 1.0e4            # etamax after the very first step
ETAMX = 10.0              # etamax otherwise
ETAMIN_EF = 0.1           # min eta after an error-test failure
ETAMXF = 0.2              # max eta after an error-test failure
ETACF = 0.25              # eta after a Newton-convergence failure
THRESH = 1.5              # order/step change threshold
BIAS1, BIAS2, BIAS3 = 6.0, 6.0, 10.0
ADDON = 1.0e-6
NLS_COEF = 0.1
CRDOWN = 0.3
RDIV = 2.0
MAX_NEWTON = 4
MAX_NCF = 10
MAX_NEF = 7
HMIN = 1.0e-9             # ms
MAX_ATTEMPTS = 40

# Jacobian-freshness policy (cvLSetup decision in cvNlsNewton)
MSBP = 20                 # max steps between setups
DGMAX = 0.3               # |gamma/gamma_saved - 1| beyond which factors rebuild
MAX_NCF_RESTART = 4       # consecutive conv failures before the q->1 restart

# NDF (Shampine & Reichelt, ode15s): kappa-modified BDF error constants.
# ratio[k] = |1 + (k+1) kappa_k gamma_k| is the NDF/BDF error-constant
# ratio at order k (gamma_k = sum_{j<=k} 1/j); scaling the tq error-test
# coefficients by it accepts steps larger by ratio^(-1/(k+1)) at equal
# tolerance.  kappa_5 = 0: BDF5 unchanged (NDF5 would not be stable).
_NDF_KAPPA = np.array([0.0, -0.1850, -1.0 / 9.0, -0.0823, -0.0415, 0.0])
_NDF_RATIO = np.ones((LMAX + 1,))
for _k in range(1, QMAX + 1):
    _gamk = sum(1.0 / _j for _j in range(1, _k + 1))
    _NDF_RATIO[_k] = abs(1.0 + (_k + 1) * _NDF_KAPPA[_k] * _gamk)
del _k, _gamk


class BDFState(NamedTuple):
    t: jnp.ndarray            # f64[]
    h: jnp.ndarray            # f64[] current (scaled-into-zn) step size
    q: jnp.ndarray            # i32[]
    zn: jnp.ndarray           # f64[LMAX, n] Nordsieck array
    tau: jnp.ndarray          # f64[LMAX+1]  tau[1..q(+1)] recent step sizes
    qwait: jnp.ndarray        # i32[]
    etamax: jnp.ndarray       # f64[]
    acor_save: jnp.ndarray    # f64[n] correction of previous step
    nst: jnp.ndarray          # i32[] accepted steps
    nfe: jnp.ndarray          # i32[] rhs evaluations
    nni: jnp.ndarray          # i32[] newton iterations
    netf: jnp.ndarray         # i32[] error-test failures
    nncf: jnp.ndarray         # i32[] newton-convergence failures
    nreset: jnp.ndarray       # i32[] IVP resets (event deliveries)
    failed: jnp.ndarray       # bool[]
    # ---- Jacobian-freshness policy (jac_policy="reuse") ----------------
    gamma_saved: jnp.ndarray  # f64[] gamma the stored factors were built at
    nstlp: jnp.ndarray        # i32[] nst at the last setup (MSBP counter)
    nsetups: jnp.ndarray      # i32[] Newton-matrix assemblies+factorizations
    jbad: jnp.ndarray         # bool[] factors flagged stale: setup next attempt
    factors: jnp.ndarray      # f64[n_factors] flat newton_setup factor vector


class BDFOptions(NamedTuple):
    atol: float = 1.0e-3
    rtol: float = 0.0
    hmax: float = 1.0e9
    h0: float = -1.0          # <=0: use heuristic
    precond: str = "neuron"   # "neuron" (paper default) | "schur" (exact HH block)
    method: str = "bdf"       # "bdf" | "ndf" (kappa-modified error constants)
    jac_policy: str = "reuse" # "reuse" (CVODE freshness policy) |
    #                           "iteration" (legacy: setup every Newton iter)


def _wrms(x, y, opts: BDFOptions):
    w = 1.0 / (opts.rtol * jnp.abs(y) + opts.atol)
    return jnp.sqrt(jnp.mean((x * w) ** 2))


def reinit(model, t, y, iinj, opts: BDFOptions, counters=None,
           f=None) -> BDFState:
    """(Re-)initialise the IVP at (t, y): order 1, heuristic h0.

    ``f`` may carry a precomputed rhs evaluation at (t, y) — the fused
    deliver/step path (``step_or_deliver``) shares the rhs stream of the
    Newton corrector with the reset heuristic instead of paying a second
    evaluation.

    The factor cache starts empty (``jbad=True``): the first step attempt
    runs a setup, so a reset costs no factorization of its own."""
    if opts.method not in ("bdf", "ndf"):
        raise ValueError(f"unknown method {opts.method!r}")
    if opts.jac_policy not in ("reuse", "iteration"):
        raise ValueError(f"unknown jac_policy {opts.jac_policy!r}")
    if f is None:
        f = model.rhs(t, y, iinj)
    fn = _wrms(f, y, opts)
    h_heur = 0.5 / (fn + 1.0e-10)
    h = jnp.where(opts.h0 > 0, opts.h0, jnp.clip(h_heur, 1.0e-6, 1.0))
    h = jnp.minimum(h, opts.hmax)
    n = y.shape[0]
    zn = jnp.zeros((LMAX, n), y.dtype).at[0].set(y).at[1].set(h * f)
    tau = jnp.zeros((LMAX + 1,), y.dtype).at[1].set(h)
    z = jnp.zeros((), jnp.int32)
    c = counters or (z, z + 1, z, z, z, z, z)
    return BDFState(t=jnp.asarray(t, y.dtype), h=h, q=jnp.ones((), jnp.int32),
                    zn=zn, tau=tau, qwait=jnp.full((), 2, jnp.int32),
                    etamax=jnp.asarray(ETAMX1, y.dtype), acor_save=jnp.zeros_like(y),
                    nst=c[0], nfe=c[1], nni=c[2], netf=c[3], nncf=c[4],
                    nreset=c[5], failed=jnp.zeros((), bool),
                    gamma_saved=jnp.ones((), y.dtype),
                    nstlp=jnp.asarray(c[0], jnp.int32), nsetups=c[6],
                    jbad=jnp.ones((), bool),
                    factors=jnp.zeros((model.n_factors(opts.precond),),
                                      y.dtype))


# --------------------------------------------------------------------------
# coefficient machinery (cvSetBDF / cvSetTqBDF), masked static loops to QMAX
# --------------------------------------------------------------------------
def _set_bdf_coeffs(q, h, tau, method: str = "bdf"):
    qf = q.astype(h.dtype)
    l = jnp.zeros((LMAX,), h.dtype).at[0].set(1.0).at[1].set(1.0)
    alpha0 = jnp.asarray(-1.0, h.dtype)
    hsum = h
    xi_inv = jnp.asarray(1.0, h.dtype)
    xistar_inv = jnp.asarray(1.0, h.dtype)

    # for (j=2; j < q; j++)
    for j in range(2, QMAX):
        active = j < q
        hsum_n = hsum + tau[j - 1]
        xi_inv_n = h / hsum_n
        alpha0_n = alpha0 - 1.0 / j
        l_n = l.at[1:].add(l[:-1] * xi_inv_n)
        hsum = jnp.where(active, hsum_n, hsum)
        alpha0 = jnp.where(active, alpha0_n, alpha0)
        l = jnp.where(active, l_n, l)

    # j = q  (only when q > 1)
    active = q > 1
    alpha0_n = alpha0 - 1.0 / jnp.maximum(qf, 1.0)
    xistar_inv_n = -l[1] - alpha0_n
    hsum_n = hsum + tau[jnp.maximum(q - 1, 1)]
    xi_inv_n = h / hsum_n
    alpha0_hat_n = -l[1] - xi_inv_n
    l_n = l.at[1:].add(l[:-1] * xistar_inv_n)
    alpha0 = jnp.where(active, alpha0_n, alpha0)
    xistar_inv = jnp.where(active, xistar_inv_n, xistar_inv)
    hsum = jnp.where(active, hsum_n, hsum)
    xi_inv = jnp.where(active, xi_inv_n, xi_inv)
    alpha0_hat = jnp.where(active, alpha0_hat_n, alpha0)
    l = jnp.where(active, l_n, l)

    # tq coefficients (cvSetTqBDF)
    A1 = 1.0 - alpha0_hat + alpha0
    A2 = 1.0 + qf * A1
    tq2 = jnp.abs(A1 / (alpha0 * A2))
    lq = l[jnp.clip(q, 0, QMAX)]
    tq5 = jnp.abs(A2 * xistar_inv / (lq * xi_inv))
    # order q-1 coefficient
    Cc = xistar_inv / lq
    A3 = alpha0 + 1.0 / qf
    A4 = alpha0_hat + xi_inv
    Cpinv = (1.0 - A4 + A3) / A3
    tq1 = jnp.where(q > 1, jnp.abs(Cc * Cpinv), 1.0)
    # order q+1 coefficient
    hsum_p = hsum + tau[jnp.clip(q, 1, LMAX)]
    xi_inv_p = h / hsum_p
    A5 = alpha0 - 1.0 / (qf + 1.0)
    A6 = alpha0_hat - xi_inv_p
    Cppinv = (1.0 - A6 + A5) / A2
    tq3 = jnp.abs(Cppinv / (xi_inv_p * (qf + 2.0) * A5))
    if method == "ndf":
        # quasi-NDF: BDF corrector, NDF error weighting.  tq1/tq2/tq3
        # multiply the correction norms into the scaled local-error
        # estimates at orders q-1/q/q+1, so scaling them by the (< 1)
        # NDF/BDF error-constant ratio presents the smaller NDF
        # truncation constants to the error test and step selection.
        r = jnp.asarray(_NDF_RATIO, h.dtype)
        tq1 = tq1 * r[jnp.clip(q - 1, 0, LMAX)]
        tq2 = tq2 * r[jnp.clip(q, 0, LMAX)]
        tq3 = tq3 * r[jnp.clip(q + 1, 0, LMAX)]
    tq4 = NLS_COEF / tq2
    gamma = h / l[1]
    return l, (tq1, tq2, tq3, tq4, tq5), gamma


def _predict(zn, q):
    """zn <- Pascal(q) zn  (cvPredict)."""
    for k in range(1, QMAX + 1):
        for j in range(QMAX, k - 1, -1):
            upd = zn.at[j - 1].add(zn[j])
            zn = jnp.where(jnp.logical_and(k <= q, j <= q), upd, zn)
    return zn


def _unpredict(zn, q):
    """Inverse of _predict (cvRestore)."""
    for k in range(QMAX, 0, -1):
        for j in range(k, QMAX + 1):
            upd = zn.at[j - 1].add(-zn[j])
            zn = jnp.where(jnp.logical_and(k <= q, j <= q), upd, zn)
    return zn


def _rescale(zn, tau, h, q, eta):
    fac = eta
    for j in range(1, LMAX):
        upd = zn.at[j].multiply(fac)
        zn = jnp.where(j <= q, upd, zn)
        fac = fac * eta
    return zn, h * eta


def _increase_order(zn, tau, h, q, acor_save):
    """cvIncreaseBDF: add one order using the saved correction."""
    dt = zn.dtype
    l = jnp.zeros((LMAX,), dt).at[2].set(1.0)
    alpha0 = jnp.asarray(-1.0, dt)
    alpha1 = jnp.asarray(1.0, dt)
    prod = jnp.asarray(1.0, dt)
    xiold = jnp.asarray(1.0, dt)
    hsum = h
    for j in range(1, QMAX):                     # j = 1 .. q-1
        active = j <= q - 1
        hsum_n = hsum + tau[j + 1]
        xi = hsum_n / h
        prod_n = prod * xi
        alpha0_n = alpha0 - 1.0 / (j + 1)
        alpha1_n = alpha1 + 1.0 / xi
        l_n = l
        for i in range(QMAX, 1, -1):             # i = j+2 .. 2 descending
            upd = l_n.at[i].set(l_n[i] * xiold + l_n[i - 1])
            l_n = jnp.where(i <= j + 2, upd, l_n)
        hsum = jnp.where(active, hsum_n, hsum)
        prod = jnp.where(active, prod_n, prod)
        alpha0 = jnp.where(active, alpha0_n, alpha0)
        alpha1 = jnp.where(active, alpha1_n, alpha1)
        l = jnp.where(active, l_n, l)
        xiold = jnp.where(active, xi, xiold)
    A1 = (-alpha0 - alpha1) / prod
    Lrow = jnp.clip(q + 1, 2, QMAX)
    zn = zn.at[Lrow].set(A1 * acor_save)
    for j in range(2, QMAX):
        upd = zn.at[j].add(l[j] * zn[Lrow])
        zn = jnp.where(jnp.logical_and(j >= 2, j <= q), upd, zn)
    return zn


def _decrease_order(zn, tau, h, q):
    """cvDecreaseBDF: drop one order."""
    dt = zn.dtype
    l = jnp.zeros((LMAX,), dt).at[2].set(1.0)
    hsum = jnp.zeros((), dt)
    for j in range(1, QMAX - 1):                 # j = 1 .. q-2
        active = j <= q - 2
        hsum_n = hsum + tau[j]
        xi = hsum_n / h
        l_n = l
        for i in range(QMAX, 1, -1):
            upd = l_n.at[i].set(l_n[i] * xi + l_n[i - 1])
            l_n = jnp.where(i <= j + 2, upd, l_n)
        hsum = jnp.where(active, hsum_n, hsum)
        l = jnp.where(active, l_n, l)
    qrow = jnp.clip(q, 2, QMAX)
    for j in range(2, QMAX):
        upd = zn.at[j].add(-l[j] * zn[qrow])
        zn = jnp.where(jnp.logical_and(j >= 2, j < q), upd, zn)
    return zn


# --------------------------------------------------------------------------
# one integration step with retries (cvStep)
# --------------------------------------------------------------------------
def step(model, st: BDFState, t_limit, iinj, opts: BDFOptions) -> BDFState:
    """Advance one accepted BDF step, never crossing t_limit (tstop mode)."""
    st, _ = _step_impl(model, st, t_limit, iinj, opts)
    return st


def _step_impl(model, st: BDFState, t_limit, iinj, opts: BDFOptions,
               deliver=None, y_ev=None):
    """One accepted BDF step (cvStep).  Returns (state, f_first) where
    ``f_first`` is the rhs evaluation of the first Newton iteration.

    ``deliver`` (optional bool[]) rides event-delivery lanes through the
    same Newton machinery: their first rhs is evaluated at the *current*
    time on the post-event state ``y_ev`` instead of (t+h, ypred), and the
    lane converges/accepts immediately — the caller rebuilds the order-1
    reset from ``f_first`` while step lanes proceed unchanged.  With
    ``deliver=None`` the lowered computation is identical to the
    historical ``step``.
    """
    dtype = st.zn.dtype
    y_ref = st.zn[0]
    t0 = st.t
    reuse = opts.jac_policy == "reuse"

    def wrms(x, y):
        return _wrms(x, y, opts)

    def attempt_body(carry):
        st, ncf, nef, attempts, done, f_first = carry

        # ---- tstop / hmax clamp --------------------------------------------
        room = t_limit - st.t
        h_goal = jnp.minimum(st.h, jnp.minimum(room, opts.hmax))
        h_goal = jnp.maximum(h_goal, HMIN)
        eta0 = h_goal / st.h
        zn, h = _rescale(st.zn, st.tau, st.h, st.q, eta0)
        st = st._replace(zn=zn, h=h)

        l, tq, gamma = _set_bdf_coeffs(st.q, st.h, st.tau, method=opts.method)
        tq1, tq2, tq3, tq4, tq5 = tq

        zn_pred = _predict(st.zn, st.q)
        ypred = zn_pred[0]
        zdot_term = zn_pred[1] / l[1]            # gamma * ydot_pred
        t_new = st.t + st.h

        # ---- Jacobian freshness (cvNlsNewton's callSetup decision) ---------
        # Setup is hoisted OUT of the Newton loop: one assembly+factorization
        # per attempt at most (vs one per iteration on the legacy path), and
        # usually zero — the stored factors survive across accepted steps
        # until gamma drifts, MSBP steps pass, or convergence degrades.
        if reuse:
            gamrat = gamma / st.gamma_saved
            need = jnp.logical_or(
                st.jbad,
                jnp.logical_or(st.nst - st.nstlp >= MSBP,
                               jnp.abs(gamrat - 1.0) > DGMAX))
            with jax.named_scope("bdf.setup"):
                factors = jax.lax.cond(
                    need,
                    lambda: model.newton_setup(ypred, gamma,
                                               mode=opts.precond),
                    lambda: st.factors)
            st = st._replace(
                factors=factors,
                gamma_saved=jnp.where(need, gamma, st.gamma_saved),
                nstlp=jnp.where(need, st.nst, st.nstlp),
                nsetups=st.nsetups + need.astype(jnp.int32),
                jbad=jnp.zeros((), bool))
            jcur = need                          # factors current for this y?
        else:
            jcur = jnp.ones((), bool)            # rebuilt every iteration

        # ---- modified Newton (cvNlsNewton) ---------------------------------
        def newton_body(c):
            y, acor, delp, crate, m, conv, div, nni, nfe, f_keep = c
            if deliver is None:
                f = model.rhs(t_new, y, iinj)
            else:
                # deliver lanes share this evaluation: rhs at the current
                # time on the post-event state (exactly reinit's f)
                t_eval = jnp.where(deliver, t0, t_new)
                y_eval = jnp.where(deliver, y_ev, y)
                f = model.rhs(t_eval, y_eval, iinj)
            f_keep = jnp.where(m == 0, f, f_keep)
            G = acor + zdot_term - gamma * f
            if reuse:
                delta = model.newton_solve(st.factors, -G, mode=opts.precond)
            else:
                delta = model.solve_newton_mat(y, gamma, -G, mode=opts.precond)
            dnrm = wrms(delta, y_ref)
            y = y + delta
            acor = acor + delta
            crate_n = jnp.where(m > 0, jnp.maximum(CRDOWN * crate,
                                                   dnrm / jnp.maximum(delp, 1e-300)),
                                crate)
            dcon = dnrm * jnp.minimum(1.0, crate_n) / tq4
            conv = dcon < 1.0
            if deliver is not None:
                conv = jnp.logical_or(conv, deliver)
            div = jnp.logical_and(m >= 1, dnrm > RDIV * jnp.maximum(delp, 1e-300))
            return (y, acor, dnrm, crate_n, m + 1, conv, div, nni + 1, nfe + 1,
                    f_keep)

        def newton_cond(c):
            m, conv, div = c[4], c[5], c[6]
            return jnp.logical_and(m < MAX_NEWTON,
                                   jnp.logical_and(~conv, ~div))

        init = (ypred, jnp.zeros_like(ypred), jnp.zeros((), dtype),
                jnp.ones((), dtype), jnp.zeros((), jnp.int32),
                jnp.zeros((), bool), jnp.zeros((), bool), st.nni, st.nfe,
                f_first)
        with jax.named_scope("bdf.newton"):
            y, acor, _, crate, m_it, conv, _, nni, nfe, f_first = \
                jax.lax.while_loop(newton_cond, newton_body, init)
        nsetups = st.nsetups if reuse else st.nsetups + (nni - st.nni)
        st = st._replace(nni=nni, nfe=nfe, nsetups=nsetups)

        acnrm = wrms(acor, y_ref)
        dsm = acnrm * tq2

        err_ok = dsm <= 1.0
        accepted = jnp.logical_and(conv, err_ok)
        if deliver is not None:
            # deliver lanes terminate after one attempt; their step state
            # is discarded by the caller in favour of the order-1 reset
            accepted = jnp.logical_or(accepted, deliver)
        # stale-factor convergence failure (CVODE's CV_FAIL_BAD_J): retry
        # the SAME step with a forced fresh setup before any h reduction
        stale = (jnp.logical_and(~conv, ~jcur) if reuse
                 else jnp.zeros((), bool))

        # shared BDF1-restart evaluation: both failure ladders rebuild
        # zn[1] = h * f(t, zn[0]) on their force paths.  zn[0] and t are
        # only touched on accept so the evaluation is attempt-invariant,
        # and it almost never fires: one gated cond serves both ladders
        # instead of hoisting a rhs into every attempt
        force_ef = jnp.logical_and(jnp.logical_and(conv, ~accepted),
                                   nef + 1 >= MAX_NEF)
        force_cf = (jnp.logical_and(jnp.logical_and(~conv, jcur),
                                    ncf + 1 >= MAX_NCF_RESTART)
                    if reuse else jnp.zeros((), bool))
        f_restart = jax.lax.cond(
            jnp.logical_or(force_ef, force_cf),
            lambda: model.rhs(t0, y_ref, iinj),
            lambda: jnp.zeros_like(y_ref))

        # ---- outcomes -------------------------------------------------------
        def on_conv_fail(st, ncf, nef):
            zn = st.zn                            # zn was never predicted in-place
            if reuse:
                # stale-factor retries pass through untouched (eta = 1, no
                # counter charges) — only jbad is raised.  With fresh
                # factors the ladder shrinks by ETACF, and because it only
                # ever sees fresh factors at the *predictor*, several
                # shrinks that still cannot land the corrector restart the
                # BDF1 history outright (the netf force's twin) instead of
                # riding the shrink to MAX_NCF — the per-iteration legacy
                # rebuild recovers from garbage predictions on its own,
                # this path needs the restart
                force = jnp.logical_and(~stale, ncf + 1 >= MAX_NCF_RESTART)
                eta = jnp.where(stale, jnp.asarray(1.0, dtype),
                                jnp.asarray(ETACF, dtype))
                q = jnp.where(force, jnp.ones((), jnp.int32), st.q)
                zn, h = _rescale(zn, st.tau, st.h, q, eta)
                zn = jnp.where(force, zn.at[1].set(h * f_restart), zn)
                st = st._replace(zn=zn, h=h, q=q,
                                 etamax=jnp.where(stale, st.etamax,
                                                  jnp.asarray(1.0, dtype)),
                                 nncf=st.nncf + jnp.where(stale, 0, 1),
                                 jbad=jnp.ones((), bool),
                                 nfe=st.nfe + jnp.where(force, 1, 0))
                inc = jnp.where(stale, 0, 1)
                return st, ncf + inc, nef
            zn, h = _rescale(zn, st.tau, st.h, st.q,
                             jnp.asarray(ETACF, dtype))
            st = st._replace(zn=zn, h=h, etamax=jnp.asarray(1.0, dtype),
                             nncf=st.nncf + 1, jbad=jnp.ones((), bool))
            return st, ncf + 1, nef

        def on_err_fail(st, ncf, nef):
            Lq = (st.q + 1).astype(dtype)
            eta = 1.0 / (jnp.power(BIAS2 * dsm, 1.0 / Lq) + ADDON)
            eta = jnp.clip(eta, ETAMIN_EF, ETAMXF)
            # after many failures drop to order 1 with small steps
            force = nef + 1 >= MAX_NEF
            q = jnp.where(force, jnp.ones((), jnp.int32), st.q)
            eta = jnp.where(force, jnp.asarray(ETAMIN_EF, dtype), eta)
            zn, h = _rescale(st.zn, st.tau, st.h, q, eta)
            # when forcing q=1, zn[1] = h * f_restart (CVODE's small-NEF
            # restart): after MAX_NEF rescales the history row is no longer
            # a valid first-derivative term, so the retry would keep
            # solving a corrupted BDF1 equation
            zn = jnp.where(force, zn.at[1].set(h * f_restart), zn)
            st = st._replace(zn=zn, h=h, q=q, etamax=jnp.asarray(1.0, dtype),
                             netf=st.netf + 1,
                             nfe=st.nfe + jnp.where(force, 1, 0))
            return st, ncf, nef + 1

        def on_accept(st, ncf, nef):
            # cvCompleteStep
            q, h = st.q, st.h
            nst = st.nst + 1
            tau = st.tau
            for i in range(LMAX, 1, -1):         # cvCompleteStep: i = q .. 2
                upd = tau.at[i].set(tau[i - 1])
                tau = jnp.where(i <= q, upd, tau)
            tau = jnp.where(jnp.logical_and(q == 1, nst > 1),
                            tau.at[2].set(tau[1]), tau)
            tau = tau.at[1].set(h)
            zn = zn_pred
            for j in range(LMAX):
                upd = zn.at[j].add(l[j] * acor)
                zn = jnp.where(j <= q, upd, zn)
            qwait = st.qwait - 1

            # ---- order & step selection (cvPrepareNextStep) ----------------
            Lq = (q + 1).astype(dtype)
            etaq = 1.0 / (jnp.power(BIAS2 * dsm, 1.0 / Lq) + ADDON)

            do_sel = qwait == 0
            # eta for order q-1
            ddn = wrms(zn[jnp.clip(q, 1, QMAX)], y_ref) * tq1
            etaqm1 = jnp.where(q > 1,
                               1.0 / (jnp.power(BIAS1 * ddn, 1.0 / q.astype(dtype)) + ADDON),
                               0.0)
            # eta for order q+1
            dup = wrms(acor - st.acor_save, y_ref) * tq3
            etaqp1 = jnp.where(q < QMAX,
                               1.0 / (jnp.power(BIAS3 * dup, 1.0 / (Lq + 1.0)) + ADDON),
                               0.0)
            etam = jnp.maximum(etaqm1, jnp.maximum(etaq, etaqp1))
            qprime_sel = jnp.where(etam == etaqm1, q - 1,
                                   jnp.where(etam == etaq, q, q + 1))
            eta_sel = jnp.where(etam < THRESH, 1.0, etam)
            qprime_sel = jnp.where(etam < THRESH, q, qprime_sel)

            eta_noq = jnp.where(etaq < THRESH, 1.0, etaq)
            eta = jnp.where(do_sel, eta_sel, eta_noq)
            qprime = jnp.where(do_sel, qprime_sel, q)
            eta = jnp.minimum(eta, st.etamax)
            # never exceed hmax
            eta = eta / jnp.maximum(1.0, eta * h / opts.hmax)

            # apply order change on zn
            zn_inc = _increase_order(zn, tau, h, q, acor)
            zn_dec = _decrease_order(zn, tau, h, q)
            zn = jnp.where(qprime > q, zn_inc, jnp.where(qprime < q, zn_dec, zn))
            qnew = jnp.clip(qprime, 1, QMAX)
            zn, hnew = _rescale(zn, tau, h, qnew, eta)
            qwait = jnp.where(do_sel, qnew + 1, qwait)

            # convergence-rate decay: a multi-iteration Newton whose
            # contraction rate exceeds the CRDOWN slack flags the factors
            # stale so the next step rebuilds (single-iteration solves keep
            # the init crate=1 and carry no rate information)
            jbad_next = jnp.logical_and(m_it >= 2, crate > CRDOWN)
            st = st._replace(
                t=st.t + h, h=hnew, q=qnew, zn=zn, tau=tau, qwait=qwait,
                etamax=jnp.asarray(ETAMX, dtype), acor_save=acor, nst=nst,
                jbad=jnp.logical_or(st.jbad, jbad_next))
            return st, ncf, nef

        st_cf, ncf_cf, nef_cf = on_conv_fail(st, ncf, nef)
        st_ef, ncf_ef, nef_ef = on_err_fail(st, ncf, nef)
        st_ok, ncf_ok, nef_ok = on_accept(st, ncf, nef)

        st = jax.tree_util.tree_map(
            lambda a, b, c: jnp.where(accepted, a, jnp.where(conv, b, c)),
            st_ok, st_ef, st_cf)
        ncf = jnp.where(accepted, ncf_ok, jnp.where(conv, ncf_ef, ncf_cf))
        nef = jnp.where(accepted, nef_ok, jnp.where(conv, nef_ef, nef_cf))

        give_up = jnp.logical_or(ncf >= MAX_NCF,
                                 jnp.logical_or(nef >= MAX_NEF + 3,
                                                attempts + 1 >= MAX_ATTEMPTS))
        if deliver is not None:
            give_up = jnp.logical_and(give_up, ~deliver)
        st = st._replace(failed=jnp.logical_or(st.failed, give_up))
        done = jnp.logical_or(accepted, give_up)
        return st, ncf, nef, attempts + 1, done, f_first

    def attempt_cond(carry):
        return ~carry[4]

    z32 = jnp.zeros((), jnp.int32)
    st, _, _, _, _, f_first = jax.lax.while_loop(
        attempt_cond, attempt_body,
        (st, z32, z32, z32, jnp.zeros((), bool), jnp.zeros_like(y_ref)))
    # snap to t_limit when within rounding distance
    snap = (t_limit - st.t) < 1e-10
    st = st._replace(t=jnp.where(snap, t_limit, st.t))
    return st, f_first


def step_or_deliver(model, st: BDFState, t_limit, w_ampa, w_gaba, deliver,
                    iinj, opts: BDFOptions) -> BDFState:
    """Fused branch of the vardt advance loop: one rhs + Hines-solve stream
    serves both the event-delivery reset and the BDF step.

    ``deliver`` (bool[]) selects per lane: True -> apply the synaptic
    discontinuity at the current time and reset the IVP (order 1, fresh h,
    history discarded — ``deliver_event`` semantics, bit-identical);
    False -> one accepted BDF step clamped at ``t_limit`` (``step``
    semantics, bit-identical).  The deliver lanes ride the step's Newton
    machinery so the reset's rhs evaluation is the corrector's first —
    under vmap the advance loop pays ONE evaluation stream per iteration
    instead of one per branch.
    """
    y_ev = model.apply_event(st.zn[0], w_ampa, w_gaba)
    st_stepped, f_ev = _step_impl(model, st, t_limit, iinj, opts,
                                  deliver=deliver, y_ev=y_ev)
    counters = (st.nst, st.nfe + 1, st.nni, st.netf, st.nncf, st.nreset + 1,
                st.nsetups)
    st_del = reinit(model, st.t, y_ev, iinj, opts, counters=counters, f=f_ev)
    st_del = st_del._replace(failed=st.failed)
    return jax.tree_util.tree_map(
        lambda d, s: jnp.where(deliver, d, s), st_del, st_stepped)


def advance_to(model, st: BDFState, t_target, iinj, opts: BDFOptions,
               max_steps: int = 100000) -> BDFState:
    """Step until st.t >= t_target (or failure)."""

    def cond(c):
        st, k = c
        return jnp.logical_and(jnp.logical_and(st.t < t_target - 1e-12, ~st.failed),
                               k < max_steps)

    def body(c):
        st, k = c
        return step(model, st, t_target, iinj, opts), k + 1

    st, _ = jax.lax.while_loop(cond, body, (st, jnp.zeros((), jnp.int32)))
    return st


def interpolate(st: BDFState, t_eval):
    """Evaluate the Nordsieck polynomial at t_eval in [t-h_used, t]."""
    s = (t_eval - st.t) / st.h
    y = jnp.zeros_like(st.zn[0])
    sj = jnp.ones(())
    for j in range(LMAX):
        y = y + jnp.where(j <= st.q, sj, 0.0) * st.zn[j]
        sj = sj * s
    return y


def deliver_event(model, st: BDFState, w_ampa, w_gaba, iinj,
                  opts: BDFOptions) -> BDFState:
    """Apply a synaptic discontinuity at the current time and reset the IVP
    (paper §2.3: discontinuities lead to a reset of the IVP problem and
    interpolator state history)."""
    y = model.apply_event(st.zn[0], w_ampa, w_gaba)
    counters = (st.nst, st.nfe + 1, st.nni, st.netf, st.nncf, st.nreset + 1,
                st.nsetups)
    new = reinit(model, st.t, y, iinj, opts, counters=counters)
    new = new._replace(failed=st.failed)
    return new

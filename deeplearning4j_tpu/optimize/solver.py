"""Solver dispatch + the optimizer programs.

Parity: reference `optimize/Solver.java:54-70` (algorithm dispatch),
`BaseOptimizer.java:129-206` (iterate: gradientAndScore -> adjust -> line
search -> listeners -> termination), `ConjugateGradient.java:47-122`
(Polak-Ribiere), `LBFGS.java:152-266` (two-loop recursion, m=4),
`GradientAscent.java` (line-searched descent),
`IterationGradientDescent.java` (plain stepped descent), terminations
(`EpsTermination`/`Norm2Termination`/`ZeroDirection`), and
`StochasticHessianFree.java:44-262` (Martens HF: Gauss-Newton products via
the R-operator + damped inner CG — the reference pairs it with
`MultiLayerNetwork.computeDeltasR/feedForwardR` at
`MultiLayerNetwork.java:554-627,1407-1479`).

TPU-native design: each solver is ONE jit-compiled `lax.scan` over a fixed
iteration count with a carried `done` flag implementing the reference's
data-dependent termination conditions (XLA needs static trip counts; a
tripped termination masks further updates).  Flat-vector algebra via
`ravel_pytree`; inner Armijo line search via `linesearch.backtrack`.
Hessian-free replaces the reference's hand-written R-op machinery with
jvp-over-grad (exact HVP) or jvp->loss-Hessian->vjp (Gauss-Newton, when the
objective factors as predict+loss), plus Levenberg-Marquardt damping.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from deeplearning4j_tpu.nn.conf import OptimizationAlgorithm
from deeplearning4j_tpu.optimize.linesearch import backtrack
from deeplearning4j_tpu.optimize.updater import (adjust_gradient,
                                                 init_updater, tree_norm)

EPS_TERMINATION = 1e-6   # |score - old_score| tolerance (EpsTermination parity)
NORM2_TERMINATION = 1e-8  # gradient-norm tolerance (Norm2Termination parity)
# consecutive sub-eps (or failed-line-search) iterations before terminating.
# An f32 score's ulp near a large loss value dwarfs EPS_TERMINATION, so a
# single exactly-equal score pair is a rounding coin-flip, not convergence —
# solvers crossing a flat valley would otherwise freeze or survive it
# depending on 1-ulp differences in how their loss happened to be lowered.
STALL_PATIENCE = 2


class Objective(NamedTuple):
    """What a solver optimizes — the `Model.gradientAndScore` contract.

    grad_and_score(params, key) -> (grads_pytree, scalar_score)
    score(params, key) -> scalar_score
    gnvp (optional): (params, v_pytree, key) -> pytree — Gauss-Newton
        curvature-vector product for Hessian-free; when absent HF uses the
        exact Hessian-vector product (jvp of the gradient).
    grad_score_aux (optional): (params, key) -> (grads, score, aux_pytree)
        — a side channel for byproducts of the gradient forward (e.g.
        BatchNorm batch moments) that the caller wants back without paying
        a second forward pass.  Solvers carry the aux of the LAST live
        iteration through their scan (frozen once terminated) and
        `optimize_with_aux` returns it alongside the result.
    """

    grad_and_score: Callable
    score: Callable
    gnvp: Optional[Callable] = None
    grad_score_aux: Optional[Callable] = None


class BatchedObjective(NamedTuple):
    """An Objective whose callables take the batch explicitly —
    `(params, x, y, key)` instead of closing over the batch arrays.

    This is the contract the compiled train-step cache
    (`optimize/step_cache.py`) needs: with (x, y) as jit ARGUMENTS the
    solver program compiles once per (conf, shapes) and is reused for
    every batch, instead of baking each batch in as constants and
    re-tracing the whole `lax.scan` per `fit` call.
    """

    grad_and_score: Callable                 # (params, x, y, key) -> (g, s)
    score: Callable                          # (params, x, y, key) -> s
    gnvp: Optional[Callable] = None          # (params, v, x, y, key) -> pytree
    grad_score_aux: Optional[Callable] = None  # (params, x, y, key) -> (g, s, aux)

    def bind(self, x, y) -> "Objective":
        """Close over one batch (concrete arrays or jit tracers)."""
        return Objective(
            grad_and_score=lambda p, k: self.grad_and_score(p, x, y, k),
            score=lambda p, k: self.score(p, x, y, k),
            gnvp=(None if self.gnvp is None
                  else lambda p, v, k: self.gnvp(p, v, x, y, k)),
            grad_score_aux=(None if self.grad_score_aux is None
                            else lambda p, k: self.grad_score_aux(p, x, y, k)))


def batched_from_loss(loss_fn: Callable) -> BatchedObjective:
    """BatchedObjective from a pure loss `(params, x, y, key) -> scalar`."""

    def gs(params, x, y, key):
        s, g = jax.value_and_grad(loss_fn)(params, x, y, key)
        return g, s

    return BatchedObjective(grad_and_score=gs, score=loss_fn)


def from_loss(loss_fn: Callable) -> Objective:
    """Build an Objective from a pure loss `(params, key) -> scalar`."""

    def gs(params, key):
        s, g = jax.value_and_grad(loss_fn)(params, key)
        return g, s

    return Objective(grad_and_score=gs, score=loss_fn)


def from_predict_loss(predict: Callable, loss_of_out: Callable) -> Objective:
    """Objective from `predict(params, key) -> outputs` and
    `loss_of_out(outputs) -> scalar`, with a Gauss-Newton product
    G v = J^T (H_loss (J v)) — the TPU replacement for the reference's
    R-operator machinery (`StochasticHessianFree.java:89-262`)."""

    def loss_fn(params, key):
        return loss_of_out(predict(params, key))

    def gs(params, key):
        s, g = jax.value_and_grad(loss_fn)(params, key)
        return g, s

    def gnvp(params, v, key):
        z, jz = jax.jvp(lambda p: predict(p, key), (params,), (v,))
        hl_jz = jax.jvp(jax.grad(loss_of_out), (z,), (jz,))[1]
        return jax.vjp(lambda p: predict(p, key), params)[1](hl_jz)[0]

    return Objective(grad_and_score=gs, score=loss_fn, gnvp=gnvp)


def weighted_predict_loss(predict, rowwise_loss: Callable, labels,
                          row_weights) -> Objective:
    """`from_predict_loss` with a pad-row weight mask threaded through the
    Gauss-Newton product (ROADMAP: cached Hessian-free).

    loss_of_out is the row-weighted mean of `rowwise_loss(labels, z)` as a
    gemm contraction (`dot(rows, w)`), the same bit-exact-under-padding
    form `make_finetune_loss` uses: a pad row's weight is exactly 0, so
    its contribution to the loss Hessian — and therefore to the curvature
    cotangent entering the predict vjp — is an exact float zero, and a
    zero-padded bucket batch produces the same Gauss-Newton products as
    the unpadded batch."""

    def loss_of_out(z):
        rows = rowwise_loss(labels, z)
        return jnp.dot(rows, row_weights) / jnp.maximum(
            jnp.dot(row_weights, jnp.ones_like(row_weights)), 1.0)

    return from_predict_loss(predict, loss_of_out)


def make_termination(conf):
    """Build the termination predicate from conf (pluggable parity with
    `optimize/terminations/*`: EpsTermination, Norm2Termination,
    ZeroDirection).  An empty `termination_conditions` tuple never
    terminates early (all iterations run)."""
    conds = tuple(getattr(conf, "termination_conditions", ("eps", "norm2"))
                  or ())
    eps = getattr(conf, "termination_eps", EPS_TERMINATION)
    n2 = getattr(conf, "termination_norm2", NORM2_TERMINATION)

    def terminated(score, old_score, gnorm, dnorm=None):
        """(stall, hard): `stall` is the eps plateau condition — callers
        terminate only after STALL_PATIENCE consecutive stalls; `hard`
        conditions (norm2 / zero_direction) terminate immediately."""
        stall = jnp.asarray(False)
        hard = jnp.asarray(False)
        if "eps" in conds:
            stall = jnp.logical_or(stall, jnp.abs(score - old_score) < eps)
        if "norm2" in conds:
            hard = jnp.logical_or(hard, gnorm < n2)
        if "zero_direction" in conds and dnorm is not None:
            hard = jnp.logical_or(hard, dnorm < 1e-12)
        return stall, hard

    return terminated


def apply_step(conf, x, d, alpha):
    """Pluggable step application (parity: `optimize/stepfunctions/*`) —
    default: x + alpha*d; gradient: x + d; negative variants flip the sign."""
    sf = (getattr(conf, "step_function", "default") or "default").lower()
    if sf == "gradient":
        return x + d
    if sf == "negative_gradient":
        return x - d
    if sf == "negative_default":
        return x - alpha * d
    return x + alpha * d


def _terminated(score, old_score, gnorm):
    """Module-default predicate (eps + norm2) — kept for callers without a
    conf in scope."""
    return jnp.logical_or(
        jnp.abs(score - old_score) < EPS_TERMINATION,
        gnorm < NORM2_TERMINATION,
    )


def _aux_zeros(objective: Objective, params0, key):
    """Initial aux carry: a zero pytree shaped like the objective's aux
    output (abstract eval only — no FLOPs spent)."""
    if objective.grad_score_aux is None:
        return ()
    shapes = jax.eval_shape(objective.grad_score_aux, params0, key)[2]
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _grad_score_aux(objective: Objective, params, key):
    """(grads, score, aux) whichever channel the objective provides."""
    if objective.grad_score_aux is not None:
        return objective.grad_score_aux(params, key)
    g, s = objective.grad_and_score(params, key)
    return g, s, ()


def _sgd(objective: Objective, params0, conf, key):
    """ITERATION_GRADIENT_DESCENT: updater-chain steps, no line search.

    The scan carries the params and the updater's two trees as they are;
    `adjust_gradient` maps over them leaf by leaf (`conf.fused_updater` is
    accepted and inert: there is no other layout to carry).
    """
    upd0 = init_updater(params0)
    terminated = make_termination(conf)
    aux0 = _aux_zeros(objective, params0, key)

    def step(carry, it):
        params, upd, k, done, old_score, stall_n, aux = carry
        k, sub = jax.random.split(k)
        grads, score, aux_new = _grad_score_aux(objective, params, sub)
        adj, upd_new = adjust_gradient(conf, it, grads, params, upd)
        gnorm = tree_norm(grads)
        dnorm = tree_norm(adj)
        # direction is -adj (a descent step), alpha fixed at 1 — the
        # configured step function still applies (stepfunctions parity)
        new_params = jax.tree_util.tree_map(
            lambda p, a: apply_step(conf, p, -a.astype(p.dtype), 1.0),
            params, adj)
        # masked update once terminated
        params = jax.tree_util.tree_map(
            lambda old, new: jnp.where(done, old, new), params, new_params)
        upd = jax.tree_util.tree_map(
            lambda old, new: jnp.where(done, old, new), upd, upd_new)
        aux = jax.tree_util.tree_map(
            lambda old, new: jnp.where(done, old, new), aux, aux_new)
        stall, hard = terminated(score, old_score, gnorm, dnorm)
        stall_n = jnp.where(done, stall_n,
                            jnp.where(stall, stall_n + 1, 0))
        done = jnp.logical_or(done, jnp.logical_or(
            hard, stall_n >= STALL_PATIENCE))
        return (params, upd, k, done, score, stall_n, aux), score

    init = (params0, upd0, key, jnp.asarray(False), jnp.inf,
            jnp.asarray(0), aux0)
    (params, _, _, _, _, _, aux), scores = jax.lax.scan(
        step, init, jnp.arange(conf.num_iterations))
    return params, scores, aux


def _line_searched(objective: Objective, params0, conf, key, algo):
    """GD / CG / LBFGS over the flat parameter vector with Armijo search."""
    x0, unravel = ravel_pytree(params0)
    n = x0.shape[0]
    m = conf.lbfgs_memory

    def score_flat(x, k):
        return objective.score(unravel(x), k)

    def grad_flat(x, k):
        g, s, aux = _grad_score_aux(objective, unravel(x), k)
        return ravel_pytree(g)[0], s, aux

    is_cg = algo == OptimizationAlgorithm.CONJUGATE_GRADIENT
    is_lbfgs = algo == OptimizationAlgorithm.LBFGS
    terminated = make_termination(conf)
    aux0 = _aux_zeros(objective, params0, key)

    def step(carry, it):
        (x, x_prev, g_prev, d_prev, s_hist, y_hist, hist_n, k, done,
         old_score, prev_alpha, stall_n, aux) = carry
        k, kg = jax.random.split(k)
        g, score, aux_new = grad_flat(x, kg)
        aux = jax.tree_util.tree_map(
            lambda old, new: jnp.where(done, old, new), aux, aux_new)
        gnorm = jnp.linalg.norm(g)

        if is_lbfgs:
            # push the completed curvature pair (s,y) = (x_t - x_{t-1},
            # g_t - g_{t-1}) before computing this iteration's direction
            s_vec = x - x_prev
            y_vec = g - g_prev
            have_pair = jnp.logical_and(it > 0, jnp.vdot(s_vec, y_vec) > 1e-10)
            s_hist = jnp.where(have_pair,
                               jnp.roll(s_hist, -1, axis=0).at[m - 1].set(s_vec),
                               s_hist)
            y_hist = jnp.where(have_pair,
                               jnp.roll(y_hist, -1, axis=0).at[m - 1].set(y_vec),
                               y_hist)
            hist_n = jnp.where(have_pair, jnp.minimum(hist_n + 1, m), hist_n)

        if is_cg:
            # Polak-Ribiere: beta = max(0, g.(g - g_prev) / g_prev.g_prev)
            denom = jnp.vdot(g_prev, g_prev)
            beta = jnp.where(denom > 0,
                             jnp.maximum(0.0, jnp.vdot(g, g - g_prev) / denom),
                             0.0)
            d = -g + beta * d_prev
            # restart on non-descent directions
            d = jnp.where(jnp.vdot(d, g) < 0, d, -g)
        elif is_lbfgs:
            # two-loop recursion; valid pairs live at indices m-hist_n..m-1,
            # newest at m-1 (rolling append)
            q = g
            alphas = []
            for i in range(m - 1, -1, -1):  # newest -> oldest
                valid = i >= m - hist_n
                rho = jnp.where(valid,
                                1.0 / (jnp.vdot(y_hist[i], s_hist[i]) + 1e-10),
                                0.0)
                a_i = rho * jnp.vdot(s_hist[i], q)
                q = q - jnp.where(valid, a_i, 0.0) * y_hist[i]
                alphas.append((i, a_i, rho, valid))
            # initial Hessian scaling gamma = s.y / y.y of the newest pair
            sy = jnp.vdot(s_hist[m - 1], y_hist[m - 1])
            yy = jnp.vdot(y_hist[m - 1], y_hist[m - 1])
            gamma = jnp.where(jnp.logical_and(hist_n > 0, yy > 0), sy / yy, 1.0)
            r = gamma * q
            for i, a_i, rho, valid in reversed(alphas):  # oldest -> newest
                b_i = rho * jnp.vdot(y_hist[i], r)
                r = r + jnp.where(valid, a_i - b_i, 0.0) * s_hist[i]
            d = -r
            d = jnp.where(jnp.vdot(d, g) < 0, d, -g)
        else:  # plain line-searched gradient descent
            d = -g

        # adaptive initial trial: grow from the last accepted step (the
        # reference's BaseOptimizer similarly carries `step` across
        # iterations) so flat regions don't pin progress to tiny steps
        # probes reuse kg: f0 and f(x + alpha*d) must see the SAME noise
        # realization (dropout mask / corruption) or Armijo compares noise,
        # not step quality, and stochastic objectives spuriously terminate
        trial = jnp.clip(prev_alpha * 2.0, 1e-3, 1e6)
        alpha, new_score = backtrack(
            lambda xx: score_flat(xx, kg), x, d, g, score,
            max_iters=conf.num_line_search_iterations,
            initial_step=trial)
        x_new = apply_step(conf, x, d, alpha)

        progressed = alpha > 0
        stall, hard = terminated(new_score, old_score, gnorm,
                                 jnp.linalg.norm(d))
        # a failed line search is a soft stall too: the next iteration
        # retries with a fresh direction (CG restarts to -g) before the
        # run is declared converged
        stall = jnp.logical_or(stall, ~progressed)
        stall_n = jnp.where(done, stall_n,
                            jnp.where(stall, stall_n + 1, 0))
        done_new = jnp.logical_or(done, jnp.logical_or(
            hard, stall_n >= STALL_PATIENCE))

        x_prev_out = jnp.where(done, x_prev, x)
        x_out = jnp.where(done, x, x_new)
        g_prev = jnp.where(done, g_prev, g)
        d_prev = jnp.where(done, d_prev, d)
        out_score = jnp.where(done, old_score, new_score)
        prev_alpha = jnp.where(jnp.logical_or(done, alpha == 0.0),
                               prev_alpha, alpha)
        return (x_out, x_prev_out, g_prev, d_prev, s_hist, y_hist, hist_n, k,
                done_new, out_score, prev_alpha, stall_n, aux), out_score

    init = (x0, x0, jnp.zeros_like(x0), jnp.zeros_like(x0),
            jnp.zeros((m, n), x0.dtype), jnp.zeros((m, n), x0.dtype),
            jnp.asarray(0), key, jnp.asarray(False), jnp.inf,
            jnp.asarray(0.5, x0.dtype), jnp.asarray(0), aux0)
    carry, scores = jax.lax.scan(step, init, jnp.arange(conf.num_iterations))
    return unravel(carry[0]), scores, carry[-1]


def _hessian_free(objective: Objective, params0, conf, key):
    """Martens Hessian-free: damped inner CG on curvature-vector products.

    Parity: `StochasticHessianFree.java:44-262` — Gauss-Newton products
    (via `Objective.gnvp` when available, else exact HVP by jvp-over-grad),
    CG warm-started from the previous solution (decayed), and
    Levenberg-Marquardt lambda adaptation from the reduction ratio rho.
    """
    x0, unravel = ravel_pytree(params0)
    terminated = make_termination(conf)
    aux0 = _aux_zeros(objective, params0, key)

    def grad_flat(x, k):
        g, s, _ = _grad_score_aux(objective, unravel(x), k)
        return ravel_pytree(g)[0], s

    def grad_flat_aux(x, k):
        g, s, aux = _grad_score_aux(objective, unravel(x), k)
        return ravel_pytree(g)[0], s, aux

    def score_flat(x, k):
        return objective.score(unravel(x), k)

    def bvp(x, v, lam, k):
        """Damped curvature-vector product (B + lam I) v."""
        if objective.gnvp is not None:
            hv = ravel_pytree(objective.gnvp(unravel(x), unravel(v), k))[0]
        else:
            hv = jax.jvp(lambda xx: grad_flat(xx, k)[0], (x,), (v,))[1]
        return hv + lam * v

    cg_iters = conf.hf_cg_iterations

    def cg_solve(x, g, lam, d0, k):
        """CG on (B + lam I) d = -g, warm start d0; fixed trip count with a
        converged mask (static shapes for XLA)."""

        def mv(v):
            return bvp(x, v, lam, k)

        r0 = -g - mv(d0)
        rs0 = jnp.vdot(r0, r0)

        def body(carry, _):
            d, r, p, rs = carry
            ap = mv(p)
            denom = jnp.vdot(p, ap)
            live = jnp.logical_and(rs > 1e-16, denom > 1e-20)
            alpha = jnp.where(live, rs / jnp.where(denom == 0, 1.0, denom), 0.0)
            d = d + alpha * p
            r = r - alpha * ap
            rs_new = jnp.vdot(r, r)
            beta = jnp.where(live, rs_new / jnp.where(rs == 0, 1.0, rs), 0.0)
            p = jnp.where(live, r + beta * p, p)
            return (d, r, p, jnp.where(live, rs_new, rs)), None

        (d, *_), _ = jax.lax.scan(body, (d0, r0, r0, rs0), None,
                                  length=cg_iters)
        return d

    def step(carry, it):
        x, d_prev, lam, k, done, old_score, stall_n, aux = carry
        k, kg = jax.random.split(k)
        g, score, aux_new = grad_flat_aux(x, kg)
        aux = jax.tree_util.tree_map(
            lambda old, new: jnp.where(done, old, new), aux, aux_new)
        gnorm = jnp.linalg.norm(g)
        d = cg_solve(x, g, lam, 0.95 * d_prev, kg)
        # quadratic-model reduction for the LM rho test
        qm = jnp.vdot(g, d) + 0.5 * jnp.vdot(d, bvp(x, d, lam, kg))
        proposal = apply_step(conf, x, d, 1.0)  # stepfunctions parity
        new_score = score_flat(proposal, kg)
        rho = (new_score - score) / jnp.where(qm >= 0, -1e-10, qm)
        lam = jnp.where(rho > 0.75, lam * (2.0 / 3.0),
                        jnp.where(rho < 0.25, lam * 1.5, lam))
        accept = new_score < score
        x_new = jnp.where(jnp.logical_or(done, ~accept), x, proposal)
        d_prev = jnp.where(done, d_prev, d)
        # rejected iterations report the evaluated score at x (not
        # old_score, which starts at +inf and would leak into the trace)
        out_score = jnp.where(done, old_score,
                              jnp.where(accept, new_score, score))
        stall, hard = terminated(new_score, old_score, gnorm,
                                 jnp.linalg.norm(d))
        stall_n = jnp.where(done, stall_n,
                            jnp.where(stall, stall_n + 1, 0))
        done = jnp.logical_or(done, jnp.logical_or(
            hard, stall_n >= STALL_PATIENCE))
        return (x_new, d_prev, lam, k, done, out_score, stall_n,
                aux), out_score

    init = (x0, jnp.zeros_like(x0), jnp.asarray(conf.hf_initial_lambda),
            key, jnp.asarray(False), jnp.inf, jnp.asarray(0), aux0)
    carry, scores = jax.lax.scan(step, init,
                                 jnp.arange(conf.num_iterations))
    return unravel(carry[0]), scores, carry[-1]


def _optimize_impl(objective: Objective, params0, conf, key):
    algo = OptimizationAlgorithm(str(conf.optimization_algo))
    if algo == OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT:
        return _sgd(objective, params0, conf, key)
    if algo == OptimizationAlgorithm.HESSIAN_FREE:
        return _hessian_free(objective, params0, conf, key)
    return _line_searched(objective, params0, conf, key, algo)


def optimize(objective: Objective, params0, conf, key):
    """Run the configured solver; returns (params, per-iteration scores).

    Dispatch parity: `Solver.java:54-70`.
    """
    params, scores, _ = _optimize_impl(objective, params0, conf, key)
    return params, scores


def optimize_with_aux(objective: Objective, params0, conf, key):
    """Like `optimize`, but also returns the aux pytree from the last live
    iteration's `grad_score_aux` call (an empty tuple when the objective
    has no aux channel).  This is how compiled train steps get BatchNorm
    batch moments out of the solver without a second forward pass."""
    return _optimize_impl(objective, params0, conf, key)


class Solver:
    """OO facade mirroring the reference `Solver` builder usage."""

    def __init__(self, conf, objective: Objective):
        self.conf = conf
        self.objective = objective

    def optimize(self, params, key):
        return optimize(self.objective, params, self.conf, key)

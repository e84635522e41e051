"""Plain reference for logistic GAME with one fixed effect and K random
effects: the objective, its gradient with respect to each coordinate, and
block coordinate descent with a damped Newton step, per entity, written as
a loop over entities.  ``jax.numpy`` at matmul precision "highest"; no vmap,
no buckets, no padding; nothing of ``photon_ml_tpu.game`` is imported.

Model (labels y in {0, 1}, s = 2y - 1)::

    z_i = sum_c score_c(i),   score_fixed(i) = x_i . w,
                              score_random(i) = x_i . T[id_i]
    F   = sum_i softplus(-s_i z_i) + sum_c l2_c / 2 |params_c|^2

F sums over ALL rows and is what coordinate descent reports after every
update.  A coordinate is *trained* on its own rows: the fixed effect on
every row with weight 1; entity e of a random effect on all of its rows
with weight 1, or, when it has more rows than the active cap, on its active
sample, ``sample[e] = (row ids, weights)`` (the reference implementation's
reservoir sample, weights count / cap), taken as data.  The rows outside
the sample are passive: scored, never trained on.

A problem is a dict ``{"labels": (n,), "coordinates": [c, ...]}`` in update
order, each ``c`` a dict with ``name``, ``kind`` ("fixed" | "random"), ``x``
(n, d), ``l2`` and, for a random effect, ``ids`` (n,), ``entities`` (table
rows) and ``sample``.  Parameters are a dict name -> (d,) or (entities, d).

A FACTORED random effect (kind "factored", ``FactoredRandomEffectCoordinate
.scala:37-267``) keeps w_e = B gamma_e: parameters ``{"gamma": (entities, k),
"projection": (d, k)}``, ``score(i) = (B^T x_i) . gamma[id_i]``, penalty
``l2 / 2 |gamma|^2 + l2_projection / 2 |B|^2``, both leaves trained on the
same rows and weights as a plain random effect.  ``factored_update`` is its
update as the reference describes it: the per-entity problems on explicitly
projected rows, then the B problem on the MATERIALISED Kronecker design
x_i (x) gamma[id_i] of shape (n, d k) with vec(B) as its coefficient vector
(``kroneckerProductFeaturesAndCoefficients``, ``:251-266``), each solved by
plain Newton to convergence.  Departure: the program runs a budgeted NEWTON
on the lanes and TRON on B; both problems are strictly convex under their
L2, so the two agree where both are run to convergence, and only there.

Departures from Photon-ML, as in the program: Newton with the explicit
d x d Hessian where the reference runs TRON (on a 16-wide Hessian Newton is
TRON's step solved exactly), and Armijo halving for the damping.  With
``dtype=jnp.bfloat16`` every array, product and sum is held in bfloat16
(the d x d solve alone is done in float32 on the rounded Hessian): the
stand-in one precision below float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ARMIJO_C1 = 1e-4
MAX_HALVINGS = 20


def _loss(z, y):
    return jax.nn.softplus(-(2.0 * y - 1.0) * z)


def _d1(z, y):
    return jax.nn.sigmoid(z) - y


def _d2(z):
    p = jax.nn.sigmoid(z)
    return p * (1.0 - p)


def score(coord, params, dtype):
    x = jnp.asarray(coord["x"], dtype)
    if coord["kind"] == "fixed":
        return jnp.sum(x * jnp.asarray(params, dtype), axis=1, dtype=dtype)
    if coord["kind"] == "factored":
        latent = (x @ jnp.asarray(params["projection"], dtype)).astype(dtype)
        rows = jnp.asarray(params["gamma"], dtype)[np.asarray(coord["ids"])]
        return jnp.sum(latent * rows, axis=1, dtype=dtype)
    rows = jnp.asarray(params, dtype)[np.asarray(coord["ids"])]
    return jnp.sum(x * rows, axis=1, dtype=dtype)


def objective(problem, params, dtype=jnp.float64):
    """F over all rows, active and passive."""
    with jax.default_matmul_precision("highest"):
        y = jnp.asarray(problem["labels"], dtype)
        z = sum(score(c, params[c["name"]], dtype)
                for c in problem["coordinates"])
        value = jnp.sum(_loss(z, y), dtype=dtype)
        for c in problem["coordinates"]:
            for weight, p in _penalised(c, params[c["name"]]):
                p = jnp.asarray(p, dtype)
                value = value + jnp.asarray(0.5 * weight, dtype) * jnp.sum(
                    p * p, dtype=dtype)
        return value


def _penalised(coord, params):
    """[(l2 weight, array)] of a coordinate's parameters."""
    if coord["kind"] == "factored":
        return [(coord["l2"], params["gamma"]),
                (coord["l2_projection"], params["projection"])]
    return [(coord["l2"], params)]


def train_weights(coord, n):
    """(n,) weight of each row in the objective this coordinate is trained
    on: 1, or the sample's weight, or 0 for a passive row."""
    w = np.ones(n)
    if coord["kind"] != "fixed":
        ids = np.asarray(coord["ids"])
        for entity, (rows, weights) in coord["sample"].items():
            w[ids == entity] = 0.0
            w[np.asarray(rows)] = np.asarray(weights)
    return w


def gradients(problem, params, dtype=jnp.float64):
    """name -> gradient, with respect to that coordinate's parameters, of
    the objective the coordinate is trained on."""
    with jax.default_matmul_precision("highest"):
        y = jnp.asarray(problem["labels"], dtype)
        n = y.shape[0]
        z = sum(score(c, params[c["name"]], dtype)
                for c in problem["coordinates"])
        out = {}
        for c in problem["coordinates"]:
            x = jnp.asarray(c["x"], dtype)
            r = (_d1(z, y) * jnp.asarray(train_weights(c, n), dtype))[:, None]
            if c["kind"] == "factored":
                ids = np.asarray(c["ids"])
                gamma = jnp.asarray(params[c["name"]]["gamma"], dtype)
                b = jnp.asarray(params[c["name"]]["projection"], dtype)
                out[c["name"]] = {
                    "gamma": jnp.zeros(gamma.shape, dtype).at[ids].add(
                        (x @ b) * r) + jnp.asarray(c["l2"], dtype) * gamma,
                    "projection": x.T @ (gamma[ids] * r) + jnp.asarray(
                        c["l2_projection"], dtype) * b,
                }
                continue
            p = jnp.asarray(params[c["name"]], dtype)
            if c["kind"] == "fixed":
                g = jnp.sum(x * r, axis=0, dtype=dtype)
            else:
                g = jnp.zeros(p.shape, dtype).at[np.asarray(c["ids"])].add(
                    x * r)
            out[c["name"]] = g + jnp.asarray(c["l2"], dtype) * p
        return out


def newton(x, y, offsets, weights, w0, l2, iterations, dtype):
    """``iterations`` damped Newton steps on
    f(w) = sum_i weights_i softplus(-s_i (x_i . w + offsets_i)) + l2/2 |w|^2:
    the full step if it meets the Armijo condition, else halved until it
    does (the point stays where 20 halvings do not reach it)."""
    l2 = jnp.asarray(l2, dtype)

    def value_grad(w):
        z = jnp.sum(x * w, axis=1, dtype=dtype) + offsets
        value = jnp.sum(weights * _loss(z, y), dtype=dtype) + 0.5 * l2 * (
            jnp.sum(w * w, dtype=dtype))
        grad = jnp.sum(x * (weights * _d1(z, y))[:, None], axis=0,
                       dtype=dtype) + l2 * w
        return value, grad, z

    w = jnp.asarray(w0, dtype)
    value, grad, z = value_grad(w)
    for _ in range(iterations):
        if float(jnp.linalg.norm(grad.astype(jnp.float32))) == 0.0:
            break
        c = weights * _d2(z)
        hess = jnp.einsum("ni,n,nj->ij", x, c, x).astype(dtype) + l2 * (
            jnp.eye(x.shape[1], dtype=dtype))
        solve_dtype = jnp.float32 if dtype == jnp.bfloat16 else dtype
        step = -jnp.linalg.solve(
            hess.astype(solve_dtype), grad.astype(solve_dtype)
        ).astype(dtype)
        slope = float(jnp.sum(grad * step, dtype=dtype))
        alpha, moved = 1.0, False
        for _ in range(MAX_HALVINGS):
            trial = w + jnp.asarray(alpha, dtype) * step
            t_value, t_grad, t_z = value_grad(trial)
            if float(t_value) <= float(value) + ARMIJO_C1 * alpha * slope:
                moved = True
                break
            alpha *= 0.5
        if not moved:
            break
        w, value, grad, z = trial, t_value, t_grad, t_z
    return w


CONVERGED = 40  # damped Newton steps: far past quadratic convergence


def kronecker_design(x, gamma_rows):
    """(n, d k): row i is x_i (x) gamma_i, so that its dot with vec(B)
    (row-major over (d, k)) is (B^T x_i) . gamma_i."""
    n = x.shape[0]
    return jnp.einsum("nd,nk->ndk", x, gamma_rows).reshape(n, -1)


def factored_update(coord, labels, offsets, params, dtype=jnp.float64):
    """One alternation of a factored coordinate from ``params``, against
    the other coordinates' scores ``offsets``: every entity's gamma by
    Newton to convergence on its rows projected through B (its active
    sample where it has one), then vec(B) by Newton to convergence on the
    materialised Kronecker design of every trained row.  Returns the new
    ``{"gamma", "projection"}``."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(coord["x"], dtype)
        y = jnp.asarray(labels, dtype)
        offsets = jnp.asarray(offsets, dtype)
        ids = np.asarray(coord["ids"])
        b = jnp.asarray(params["projection"], dtype)
        gamma = jnp.asarray(params["gamma"], dtype)
        projected = x @ b
        for entity, rows in enumerate(_rows_by_entity(ids,
                                                      coord["entities"])):
            if rows.size == 0:
                continue
            weights = np.ones(rows.size)
            if entity in coord["sample"]:
                rows, weights = coord["sample"][entity]
                rows = np.asarray(rows)
            gamma = gamma.at[entity].set(newton(
                projected[rows], y[rows], offsets[rows],
                jnp.asarray(weights, dtype), gamma[entity], coord["l2"],
                CONVERGED, dtype))
        weights = train_weights(coord, ids.size)
        trained = np.flatnonzero(weights > 0)
        vec_b = newton(
            kronecker_design(x[trained], gamma[ids[trained]]), y[trained],
            offsets[trained], jnp.asarray(weights[trained], dtype),
            b.reshape(-1), coord["l2_projection"], CONVERGED, dtype)
        return {"gamma": gamma, "projection": vec_b.reshape(b.shape)}


def _rows_by_entity(ids, entities):
    ids = np.asarray(ids)
    order = np.argsort(ids, kind="stable")
    bounds = np.searchsorted(ids[order], np.arange(entities + 1))
    return [order[bounds[e]:bounds[e + 1]] for e in range(entities)]


def block_coordinate_descent(problem, cd_iterations, newton_iterations,
                             dtype=jnp.float64):
    """From zero parameters: ``cd_iterations`` sweeps over the coordinates
    in the problem's order, each update ``newton_iterations`` Newton steps
    (every entity of a random effect on its own, warm-started from its
    table row, against the other coordinates' current scores).  Returns
    (params, [F after each update])."""
    with jax.default_matmul_precision("highest"):
        coords = problem["coordinates"]
        y = jnp.asarray(problem["labels"], dtype)
        n = y.shape[0]
        params = {
            c["name"]: jnp.zeros(
                (c["x"].shape[1],) if c["kind"] == "fixed"
                else (c["entities"], c["x"].shape[1]), dtype)
            for c in coords
        }
        scores = {c["name"]: jnp.zeros((n,), dtype) for c in coords}
        groups = {
            c["name"]: _rows_by_entity(c["ids"], c["entities"])
            for c in coords if c["kind"] == "random"
        }
        values = []
        for _ in range(cd_iterations):
            for c in coords:
                name = c["name"]
                x = jnp.asarray(c["x"], dtype)
                offsets = sum(s for k, s in scores.items() if k != name)
                if c["kind"] == "fixed":
                    params[name] = newton(
                        x, y, offsets, jnp.ones((n,), dtype), params[name],
                        c["l2"], newton_iterations, dtype)
                else:
                    table = params[name]
                    for entity, rows in enumerate(groups[name]):
                        if rows.size == 0:
                            continue
                        weights = np.ones(rows.size)
                        if entity in c["sample"]:
                            rows, weights = c["sample"][entity]
                            rows = np.asarray(rows)
                        table = table.at[entity].set(newton(
                            x[rows], y[rows], offsets[rows],
                            jnp.asarray(weights, dtype), table[entity],
                            c["l2"], newton_iterations, dtype))
                    params[name] = table
                scores[name] = score(c, params[name], dtype)
                values.append(float(objective(problem, params, dtype)))
        return params, values

"""The program's L2 logistic GLM against the benchmark's plain reference
(``chipbench/reference.py``: float32 ``jax.numpy`` at matmul precision
"highest", written from the model equations, importing nothing of
``photon_ml_tpu``) at toy size: the objective's value and gradient through
``GLMObjective`` over the three layouts a design can have on one device, and
``train_glm``'s fetched model judged as ``chipbench/tasks/glm_solve.py``
judges the cell's, by the cell's own limits."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.core.tasks import TaskType
from photon_ml_tpu.core.types import LabeledBatch
from photon_ml_tpu.models.training import (
    GLMTrainingConfig,
    OptimizerType,
    train_glm,
)
from photon_ml_tpu.ops import sparse as sparse_ops
from photon_ml_tpu.ops.losses import LOGISTIC_LOSS
from photon_ml_tpu.ops.objective import GLMObjective, RegularizationContext
from photon_ml_tpu.ops.sparse import SparseFeatures, split_on_device, to_dense

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
try:
    from chipbench import reference
finally:
    sys.path.pop(0)

with open(os.path.join(_REPO, "chipbench", "configs",
                       "glm_hashed_sparse.json")) as f:
    CELL = json.load(f)

N, D, NUMERIC, CATEGORICAL, HOT = 384, 256, 3, 9, 8
LAYOUTS = ("ell", "hybrid", "dense")


def rows_of(seed):
    """The cell's schema at toy size: ``NUMERIC`` slots of fixed columns
    with log1p-exponential values, ``CATEGORICAL`` slots of value one in
    Zipf-drawn columns (a row may hold a pair twice, as hashed rows do),
    labels from a hidden model. float32, padded ELL without padding."""
    rng = np.random.default_rng([seed, 32])
    ranks = np.minimum(rng.zipf(1.3, (N, CATEGORICAL)), D - NUMERIC) - 1
    indices = np.concatenate(
        [np.broadcast_to(np.arange(NUMERIC), (N, NUMERIC)), ranks + NUMERIC],
        axis=1,
    ).astype(np.int32)
    values = np.concatenate(
        [np.log1p(rng.exponential(size=(N, NUMERIC))),
         np.ones((N, CATEGORICAL))],
        axis=1,
    ).astype(np.float32)
    w_true = rng.uniform(-1.0, 1.0, size=D)
    margin = 0.7 * np.sum(values * w_true[indices], axis=1)
    labels = (rng.uniform(size=N) < 1.0 / (1.0 + np.exp(-margin)))
    return (jnp.asarray(indices), jnp.asarray(values),
            jnp.asarray(labels, jnp.float32))


def batch_of(rows, layout):
    """The rows as a ``LabeledBatch`` of that layout. The hybrid is the
    device-side split's, with its labels in the split's stored order."""
    indices, values, labels = rows
    sf = SparseFeatures(indices, values, D)
    if layout == "hybrid":
        _, top = sparse_ops._top_column_counts(indices, d=D, h_max=D)
        features, _, _ = split_on_device(sf, top, HOT)
        labels = labels[features.row_perm]
    elif layout == "dense":
        features = jnp.asarray(to_dense(sf))
    else:
        features = sf
    ones = jnp.ones((N,), jnp.float32)
    return LabeledBatch(
        features=features, labels=labels,
        offsets=jnp.zeros((N,), jnp.float32), weights=ones, mask=ones,
    )


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("l2", [0.0, 1.0])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_value_and_gradient_match_the_reference(layout, l2, seed):
    rows = rows_of(seed)
    rng = np.random.default_rng([seed, 7])
    w = jnp.asarray(rng.normal(size=D) * 0.3, jnp.float32)
    objective = GLMObjective(loss=LOGISTIC_LOSS, l2_weight=l2)
    value, grad = objective.value_and_grad(w, batch_of(rows, layout))
    assert value.dtype == grad.dtype == jnp.float32
    want_value, want_grad, _ = reference.glm_value_grad(*rows, w, l2)
    assert reference.rel_gap(value, want_value) <= 1e-6
    assert reference.rel_l2(grad, want_grad) <= 1e-5


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_trained_model_is_judged_by_the_reference(layout, seed):
    """``chipbench/tasks/glm_solve.py compare`` at toy size: what the solver
    said of its last point against the reference AT the fetched model, and
    the reference's gradient there against its gradient at zero."""
    rows = rows_of(seed)
    stop, l2 = CELL["stopping_rule"], float(CELL["l2"])
    config = GLMTrainingConfig(
        task=TaskType.LOGISTIC_REGRESSION,
        optimizer=OptimizerType[CELL["optimizer"]],
        regularization=RegularizationContext("L2"),
        reg_weights=(l2,),
        max_iters=int(stop["max_iters"]),
        tolerance=float(stop["tolerance"]),
        num_corrections=int(CELL["num_corrections"]),
        track_models=False,
        path_mode=CELL["path_mode"],
    )
    (trained,) = train_glm(batch_of(rows, layout), config)
    w = np.asarray(trained.model.coefficients.means)
    assert w.dtype == np.float32 and w.shape == (D,)
    value, grad, _ = reference.glm_value_grad(*rows, w, l2)
    _, grad0, _ = reference.glm_value_grad(*rows, np.zeros_like(w), l2)
    got = {
        "value_gap": reference.rel_gap(trained.result.value, value),
        "grad_gap": reference.rel_l2(trained.result.grad, grad),
        "grad_left": float(jnp.linalg.norm(grad) / jnp.linalg.norm(grad0)),
    }
    for name, limit in CELL["limits"].items():
        assert got[name] <= float(limit), (name, got)

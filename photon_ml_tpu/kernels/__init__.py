"""Hand-written Pallas kernel suite for the wide-feature sparse path.

Under ``PHOTON_SPARSE_KERNEL=pallas`` (see
:mod:`photon_ml_tpu.kernels.dispatch`) ``ops/sparse.py`` routes its
three ELL contractions here and ``GLMObjective`` swaps whole objective
passes for the fused single-read sweeps in
:mod:`photon_ml_tpu.kernels.fused`. The default (``auto``) is the XLA
lowering on every platform: the suite runs in the Pallas interpreter
off-TPU and does not lower for TPU on the installed toolchain.
docs/KERNELS.md is the field guide.
"""

from photon_ml_tpu.kernels.dispatch import (
    ENV_VAR,
    KERNEL_MODES,
    design_reads,
    interpret_mode,
    kernel_mode,
    record_kernel_cost,
    use_pallas,
)
from photon_ml_tpu.kernels.ell import (
    ell_colsum,
    ell_matvec,
    ell_rmatvec,
    ell_scatter_add,
)
from photon_ml_tpu.kernels.fused import (
    fused_hessian_diagonal,
    fused_hessian_vector,
    fused_value_grad_curvature,
)

__all__ = [
    "ENV_VAR",
    "KERNEL_MODES",
    "kernel_mode",
    "use_pallas",
    "interpret_mode",
    "design_reads",
    "record_kernel_cost",
    "ell_matvec",
    "ell_rmatvec",
    "ell_colsum",
    "ell_scatter_add",
    "fused_value_grad_curvature",
    "fused_hessian_vector",
    "fused_hessian_diagonal",
]

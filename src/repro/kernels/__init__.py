"""Pallas TPU kernels for the paper's compute hot spots, with jnp oracles.

kernel_matvec — fused Gram x coef streaming evaluation (testing phase):
                B kernel expansions against a shared query grid in one
                launch (single-field is B = 1)
gram          — tiled RBF Gram materialization (training-side local solves)
color_step    — colored-sweep local solves with the B*M systems on the lane
                axis: triangular substitution + local GEMM (the
                ``engine="pallas"`` path of sn_train.colored_sweep; the
                gather/scatter around it is the plan engine's)
knn_fuse      — plan-based kNN-fusion serving: masked top-k selection
                kernel -> XLA gather of the selected representers ->
                evaluate kernel (the ``engine="pallas"`` path of
                fusion.fuse(rule="knn"))
ops           — general-shape jit wrappers and ``auto_interpret``, the one
                interpret-mode switch (interpret on CPU, compiled on TPU)
ref           — pure-jnp oracles used by tests and benchmarks
"""

from . import color_step, knn_fuse, ops, ref
from .color_step import color_solve
from .knn_fuse import knn_fuse_fused
from .ops import (
    auto_interpret, bucket_rows, kernel_matvec, rbf_gram, ssd_chunked_fused,
)

__all__ = [
    "auto_interpret",
    "bucket_rows",
    "color_solve",
    "color_step",
    "kernel_matvec",
    "knn_fuse",
    "knn_fuse_fused",
    "ops",
    "rbf_gram",
    "ref",
    "ssd_chunked_fused",
]

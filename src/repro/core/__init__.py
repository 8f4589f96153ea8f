"""The paper's primary contribution: Elastic Net -> squared-hinge SVM (SVEN).

Three tiers live here (DESIGN.md §6-§7):
  - constrained engine: `sven`/`sven_path`/`sven_batch` solve the paper's
    (t, lambda2) form, jit-native with optional gap-safe `keep` masks;
  - screening: `gap_safe_screen` + `sven_with_screening`;
  - glmnet-parity front-end: penalized (lambda1, lambda2) entry points
    (`enet`, `enet_path`, `lambda_grid`, scaling conversions), sklearn-style
    `ElasticNet`/`ElasticNetCV` estimators and batched `cross_validate`.
"""
from repro.core.sven import (
    sven,
    sven_path,
    sven_path_reference,
    SvenConfig,
    SvenSolution,
    trace_counts,
    reset_trace_counts,
)
from repro.core.batch import SvenBatchSolution, cv_folds, en_grid, sven_batch
from repro.core.reduction import (
    SvenOperator,
    build_svm_dataset,
    gram_blocks,
    gram_reference,
    recover_beta,
    svm_C,
)
from repro.core import elastic_net
from repro.core.distributed import (
    sharded_gram_stats,
    sven_sharded,
)
from repro.core.routing import (
    Calibration,
    RouteDecision,
    calibrate,
    clear_calibration,
    route_batch,
    route_solve,
    sven_routed,
)
from repro.core.screening import gap_safe_screen, sven_with_screening
from repro.core.api import (
    ElasticNet,
    EnetPath,
    EnetResult,
    PathConfig,
    enet,
    enet_batch,
    enet_path,
    lambda_grid,
    penalized_from_glmnet,
    penalized_from_sklearn,
    penalized_to_glmnet,
    standardize_fit,
    unscale_coef,
)
from repro.core.cv import (
    CVResult,
    ElasticNetCV,
    cross_validate,
    cross_validate_reference,
)

__all__ = [
    "sven",
    "sven_path",
    "sven_path_reference",
    "sven_batch",
    "SvenBatchSolution",
    "cv_folds",
    "en_grid",
    "SvenConfig",
    "SvenSolution",
    "trace_counts",
    "reset_trace_counts",
    "SvenOperator",
    "build_svm_dataset",
    "gram_blocks",
    "gram_reference",
    "recover_beta",
    "svm_C",
    "elastic_net",
    "gap_safe_screen",
    "sven_with_screening",
    # data-parallel sharded solve path (core/distributed.py, DESIGN.md §9)
    "sven_sharded",
    "sharded_gram_stats",
    # adaptive layout routing (core/routing.py, DESIGN.md §9.5)
    "sven_routed",
    "route_solve",
    "route_batch",
    "calibrate",
    "clear_calibration",
    "Calibration",
    "RouteDecision",

    # glmnet-parity penalized front-end (core/api.py, core/cv.py)
    "ElasticNet",
    "ElasticNetCV",
    "EnetPath",
    "EnetResult",
    "PathConfig",
    "CVResult",
    "enet",
    "enet_batch",
    "enet_path",
    "lambda_grid",
    "penalized_from_glmnet",
    "penalized_from_sklearn",
    "penalized_to_glmnet",
    "standardize_fit",
    "unscale_coef",
    "cross_validate",
    "cross_validate_reference",
]

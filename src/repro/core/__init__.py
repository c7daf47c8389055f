"""WLSH core: the paper's contribution as a composable library.

Layers:
  * math substrate — p-stable sampling/densities, collision probabilities
  * LSH families — weighted (Sec. 3.1) and derived (Sec. 3.2, Theorem 1)
  * planning — Eqs. 11-12 parameters, bound relaxation, threshold reduction
  * partition — maximal candidate subsets + greedy weighted set cover
  * index — WLSHIndex (Preprocess/Search), C2LSH/E2LSH/SL-/S2-ALSH baselines
"""

from .alsh import ALSHIndex, alsh_tables, rho_s2, rho_sl
from .c2lsh import C2LSH
from .collision import collision_prob
from .datagen import make_dataset, make_query_set, make_weight_set
from .derived import derived_sensitivity, ratio_bounds
from .distances import radius_bounds, weighted_lp, weighted_lp_np
from .e2lsh import E2LSH
from .families import LpFamilyParams, hash_codes_np, sample_lp_family
from .params import PlanConfig, beta_mu, threshold_reduction_factor
from .partition import PartitionResult, pairwise_beta, partition, tau_min
from .pstable import pstable_pdf, pstable_pdf_abs, sample_pstable
from .serving_plan import GroupServingPlan, MemberParams, ServingPlan
from .wlsh import WLSHIndex

__all__ = [
    "ALSHIndex",
    "C2LSH",
    "E2LSH",
    "GroupServingPlan",
    "LpFamilyParams",
    "MemberParams",
    "PartitionResult",
    "PlanConfig",
    "ServingPlan",
    "WLSHIndex",
    "alsh_tables",
    "beta_mu",
    "collision_prob",
    "derived_sensitivity",
    "hash_codes_np",
    "make_dataset",
    "make_query_set",
    "make_weight_set",
    "pairwise_beta",
    "partition",
    "pstable_pdf",
    "pstable_pdf_abs",
    "radius_bounds",
    "ratio_bounds",
    "rho_s2",
    "rho_sl",
    "sample_lp_family",
    "sample_pstable",
    "tau_min",
    "threshold_reduction_factor",
    "weighted_lp",
    "weighted_lp_np",
]

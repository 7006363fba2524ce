"""Paper core: long-tail early stopping for iterative clustering in the cloud.

Pipeline (paper §4):  sample → group → trace training groups to convergence →
fit h(r) regression → pick h* = f(r*) → early-stop production runs on device.
"""
from .rand_index import (rand_index, adjusted_rand_index, contingency_table,
                         rand_index_from_contingency, sharded_contingency)
from .regression import (RegressionModel, FitMetrics, fit_family, select_model,
                         pool_traces, rh_from_objectives, FAMILIES)
from .earlystop import LongTailModel, fit_longtail, change_rate
from .longtail_train import (TrainingPlan, config_fingerprint, harvest_config,
                             harvest_traces, fit_for_config)
from .kmeans import (kmeans_step, kmeans_fit_traced, kmeans_fit_earlystop,
                     kmeans_fit_full, kmeans_plus_plus_init, random_init,
                     assign_and_stats, trace_accuracy, trace_to_rh,
                     chunk_points, minibatch_update_centroids)
from .em_gmm import (GMMParams, em_step, em_fit_traced, em_fit_earlystop,
                     em_fit_full, init_from_kmeans, estep_stats, log_prob,
                     minibatch_mstep)
from .engine import (ClusteringEngine, EngineConfig, EngineResult,
                     RestartResult, KMeansAlgorithm, EMAlgorithm,
                     get_algorithm, ProvenanceMismatchError,
                     stats_wire_bytes)
from .artifacts import ClusterArtifact, fingerprint_key, load_registry_dir
from .sampling import GroupedData, random_groups, kfold_split, make_grouped
from .cost_model import (CostReport, report, landuse_case_study,
                         EC2_ON_DEMAND_USD_PER_HOUR, TPU_ON_DEMAND_USD_PER_HOUR,
                         Price, PriceTable, expected_spot_wall_s,
                         priced_wall_s, candidate_cost_usd)
from .planner import (IterationModel, ThroughputModel, ThroughputPoint,
                      PlanSpec, CandidatePlan, PlanReport, PlanError, plan)

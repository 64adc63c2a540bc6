"""repro — Sparsification of the Alignment Path Search Space in DTW.

A production-scale jax/Pallas reproduction and extension of the paper:
learn an occupancy prior over optimal alignment paths on the training
set, threshold it into a sparse search space, and run every downstream
workload — distances, retrieval, classification, differentiable
averaging — only on the surviving cells.

Layer map (one directory per layer; see README.md and DESIGN.md):

  core/      measures, DPs and the learned sparsification
             (index -> plan -> execute; DESIGN.md §1-§2)
  kernels/   Pallas TPU kernels + jnp scan twins for every DP hot loop
             (block-sparse schedule §3, cascade bounds §4, soft §10-§11)
  cluster/   soft-SP-DTW barycenters, k-means, centroid models (§10)
  classify/  1-NN / SVM / nearest-centroid evaluation harness
  monitor/   streaming corpus analytics over the sketch tier — anomaly
             scoring, drift detection, embedding map (§17)
  launch/    serving drivers and sharded jobs (SearchEngine, Gram,
             centroid fitting; §8)
  data/      offline synthetic-UCR datasets (§7.1)

This module re-exports the supported public API; the drivers under
launch/ are not re-exported and run as ``python -m repro.launch.<name>``.

The supported entry point is the fitted engine (DESIGN.md §12):

    spec = MeasureSpec("spdtw", theta=2.0)
    engine = fit(spec, corpus, labels=labels)
    nn, dist = engine.knn(queries)

The module-level kernel entries (``spdtw_gram`` …) are deprecated
wrappers over the same execute bodies, kept bit-identical for
back-compat.
"""
from .core import (
    ALL_MEASURES, BlockSparsePaths, CorpusIndex, EngineSnapshot, Measure,
    MeasureSpec, SimilarityEngine, SnapshotStore, SparsePaths, band_mask,
    block_sparsify, build_corpus_index, default_tile, dtw, dtw_sc,
    engine_for, fit, learn_sparse_paths, log_krdtw, log_krdtw_sc,
    log_sp_krdtw, make_measure, normalize_grid, optimal_path_mask,
    pairwise, pairwise_path_counts, soft_alignment, soft_dtw, soft_spdtw,
    soft_wdtw, spdtw, spdtw_pairwise, wdtw,
)
from .core import (
    SketchIndex, build_sketch_index, random_anchors, sketch_embed,
)
from .kernels import (
    Backend, available_backends, dtw_gram, dtw_pairs, knn_cascade,
    log_krdtw_gram, log_krdtw_pairs, resolve, resolve_plan,
    soft_spdtw_gram, soft_spdtw_pairs, spdtw_gram, spdtw_pairs,
)
from .kernels.soft_block import (
    soft_alignment_pairs, soft_spdtw_batch, soft_spdtw_gram_batch,
)
from .cluster import (
    CentroidModel, fit_class_centroids, soft_barycenter, soft_kmeans,
)
from .classify import (
    centroid_error_series, knn_error, knn_error_series, svm_error,
    svm_gram_series, svm_rws_series,
)
from .monitor import (
    AnomalyScorer, DriftMonitor, Monitor, fit_anomaly_scorer,
    fit_drift_monitor, fit_monitor, power_iteration_pca, roc_auc,
    sketch_map,
)

__all__ = [
    # fitted-engine API (the supported surface; DESIGN.md §12)
    "MeasureSpec", "SimilarityEngine", "engine_for", "fit",
    # learner/actor snapshots (DESIGN.md §16)
    "EngineSnapshot", "SnapshotStore",
    # backend registry
    "Backend", "available_backends", "resolve", "resolve_plan",
    # core: learned sparsification + measures
    "ALL_MEASURES", "BlockSparsePaths", "CorpusIndex", "Measure",
    "SparsePaths", "band_mask", "block_sparsify", "build_corpus_index",
    "default_tile", "dtw", "dtw_sc", "learn_sparse_paths", "log_krdtw",
    "log_krdtw_sc", "log_sp_krdtw", "make_measure", "normalize_grid",
    "optimal_path_mask", "pairwise", "pairwise_path_counts",
    "soft_alignment", "soft_dtw", "soft_spdtw", "soft_wdtw", "spdtw",
    "spdtw_pairwise", "wdtw",
    # sketch tier: sub-linear retrieval (DESIGN.md §13)
    "SketchIndex", "build_sketch_index", "random_anchors", "sketch_embed",
    # kernels: deprecated batched/Gram wrappers + cascade (use the engine)
    "dtw_gram", "dtw_pairs", "knn_cascade", "log_krdtw_gram",
    "log_krdtw_pairs", "soft_spdtw_gram", "soft_spdtw_pairs", "spdtw_gram",
    "spdtw_pairs",
    # differentiable layer
    "soft_alignment_pairs", "soft_spdtw_batch", "soft_spdtw_gram_batch",
    # cluster: barycenters and centroid models
    "CentroidModel", "fit_class_centroids", "soft_barycenter",
    "soft_kmeans",
    # classify: evaluation harness
    "centroid_error_series", "knn_error", "knn_error_series", "svm_error",
    "svm_gram_series", "svm_rws_series",
    # monitor: streaming corpus analytics (DESIGN.md §17)
    "AnomalyScorer", "DriftMonitor", "Monitor", "fit_anomaly_scorer",
    "fit_drift_monitor", "fit_monitor", "power_iteration_pca", "roc_auc",
    "sketch_map",
]

"""Sketch-space anomaly scoring with exact-decision escalation
(DESIGN.md §17).

The RWS sketch index (DESIGN.md §13) makes every fitted corpus an
(N, R) coordinate system; this module reads it as a monitoring surface:
the *score* of an arriving series is its k-NN distance to the corpus in
(R,) sketch space (two matmuls per batch after the R embedding DPs), and
the *decision* — flagged / clean at a threshold calibrated on
spec-seeded corpus score quantiles — is made in exact-distance space, so
it is bit-identical to scoring every query with the exact cascade:

  * clean fast path: one exact DP against the sketch-nearest candidate
    gives an upper bound ``d_ub >= d_nn``; ``d_ub <= tau`` proves the
    query has a corpus neighbour within the threshold;
  * flag fast path: the §4 admissible lower bounds (banded LB_Kim +
    support-windowed LB_Keogh, both orientations) give per-candidate
    floors; when even the *smallest* floor exceeds ``tau``, every
    candidate is certified farther than the threshold;
  * escalation: queries neither path certifies — the borderline band
    around ``tau`` — run the full exact cascade (``engine.knn``), the
    FastDTW-critique design rule (Wu & Keogh, PAPERS.md): the
    approximate tier keeps the exact path cheap and available, and the
    decision at the calibrated threshold never depends on sketch
    geometry being right.

``tau`` itself is the ``quantile`` of exact leave-one-out 1-NN
distances over a spec-seeded calibration subset of the corpus, so a
fitted scorer is reproducible from ``(engine, config)`` alone.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

# fold_in / rng salt separating anomaly calibration from other
# spec-seeded draws (sketch anchors use core.sketch.ANCHOR_SALT)
ANOMALY_SALT = 0xA70C


def roc_auc(scores, labels) -> float:
    """Rank (Mann-Whitney) ROC-AUC of ``scores`` against binary
    ``labels`` (1 = positive/outlier). Tie-averaged ranks, numpy only —
    the metric the anomaly benchmark gates at >= 0.9."""
    s = np.asarray(scores, np.float64)
    y = np.asarray(labels).astype(bool)
    n1 = int(y.sum())
    n0 = len(y) - n1
    assert n1 > 0 and n0 > 0, "roc_auc needs both classes present"
    order = np.argsort(s, kind="stable")
    ranks = np.empty(len(s), np.float64)
    i = 0
    sv = s[order]
    while i < len(s):
        j = i
        while j < len(s) and sv[j] == sv[i]:
            j += 1
        ranks[order[i:j]] = 0.5 * (i + j - 1) + 1.0   # average tied ranks
        i = j
    return float((ranks[y].sum() - n1 * (n1 + 1) / 2.0) / (n0 * n1))


def _sketch_knn_scores(feats: np.ndarray, sketch: np.ndarray,
                       sq: np.ndarray, k: int,
                       exclude: Optional[np.ndarray] = None) -> np.ndarray:
    """(B, R) query feats -> (B,) mean squared sketch distance to the k
    nearest corpus rows. ``exclude`` masks one corpus id per query
    (leave-one-out calibration)."""
    feats = np.asarray(feats, np.float64)
    S = np.asarray(sketch, np.float64)
    d2 = (feats * feats).sum(1)[:, None] + np.asarray(sq, np.float64)[None] \
        - 2.0 * (feats @ S.T)                                    # (B, N)
    d2 = np.maximum(d2, 0.0)
    if exclude is not None:
        d2[np.arange(len(feats)), np.asarray(exclude)] = np.inf
    k = int(min(k, d2.shape[1] - (1 if exclude is not None else 0)))
    k = max(k, 1)
    part = np.partition(d2, k - 1, axis=1)[:, :k]
    return part.mean(axis=1)


@dataclasses.dataclass(frozen=True)
class AnomalyScorer:
    """A fitted sketch-space anomaly scorer (DESIGN.md §17).

    engine:      the fitted ``SimilarityEngine`` (must carry a sketch
                 index, i.e. fit with ``sketch_r > 0``) the scorer
                 reads sketches, bounds and the exact cascade from;
    k:           sketch-space neighbours averaged into the score;
    quantile:    calibration quantile of the exact LOO 1-NN distances
                 that set ``tau``;
    tau:         the exact-distance decision threshold — a query is
                 flagged iff its exact 1-NN distance exceeds ``tau``;
    cal_dists:   exact LOO 1-NN distances of the seeded calibration
                 rows (sorted; the distribution ``tau`` is a quantile
                 of);
    cal_scores:  sketch k-NN scores of every corpus row under
                 leave-one-out (sorted; the reference distribution
                 ``calibrated`` normalizes against).

    ``decide`` is the serving entry point; ``decide_exact`` is the
    brute-force oracle the exactness tests compare against.
    """
    engine: object
    k: int
    quantile: float
    tau: float
    cal_dists: np.ndarray
    cal_scores: np.ndarray

    # ---- scoring ----------------------------------------------------------
    def score(self, Q=None, *, feats=None, impl: str = "auto") -> np.ndarray:
        """Sketch-space k-NN score of each query: (B, T) -> (B,).
        Pass precomputed ``feats`` ((B, R), from
        ``engine.sketch_embed``) to skip the embedding DPs."""
        si = self.engine.index.sketch
        if feats is None:
            assert Q is not None, "score needs Q or precomputed feats"
            feats = self.engine.sketch_embed(Q, impl=impl)
        return _sketch_knn_scores(np.asarray(feats), np.asarray(si.sketch),
                                  np.asarray(si.sq), self.k)

    def calibrated(self, scores) -> np.ndarray:
        """Empirical corpus quantile of raw sketch scores: the fraction
        of leave-one-out corpus scores at or below each value — a
        scale-free [0, 1] severity the counters and drift features can
        share across engines."""
        pos = np.searchsorted(self.cal_scores, np.asarray(scores),
                              side="right")
        return pos / max(len(self.cal_scores), 1)

    # ---- decisions --------------------------------------------------------
    def decide(self, Q=None, *, feats=None, impl: str = "auto",
               return_stats: bool = False):
        """Flag/clean decision per query, bit-identical to
        ``decide_exact`` by construction.

        Returns ``(flags, scores[, stats])``: flags is (B,) bool
        (True = anomalous, i.e. exact 1-NN distance > ``tau``), scores
        the raw sketch k-NN statistic. Stats count the fast-path
        certificates and the escalations (the borderline band that paid
        a full cascade)."""
        from repro.kernels import backends as bk
        from repro.kernels.ops import _pair_dp
        from repro.core.sketch import sketch_shortlist
        eng = self.engine
        index = eng.index
        si = index.sketch
        if feats is None:
            assert Q is not None, "decide needs Q or precomputed feats"
            Q = jnp.asarray(Q, jnp.float32)
            feats = eng.sketch_embed(Q, impl=impl)
        else:
            assert Q is not None, "decide needs the raw queries too " \
                "(the escalation path runs exact DPs)"
            Q = jnp.asarray(Q, jnp.float32)
        assert not (bk.is_traced(Q) or bk.is_traced(feats)), \
            "the monitor is a host-side serving surface (concrete inputs)"
        B = int(Q.shape[0])
        scores = _sketch_knn_scores(np.asarray(feats),
                                    np.asarray(si.sketch),
                                    np.asarray(si.sq), self.k)
        tau = jnp.float32(self.tau)
        impl_r = bk.resolve(impl).name

        # clean fast path: exact DP to the sketch-nearest candidate is an
        # upper bound on the true 1-NN distance
        cand, _ = sketch_shortlist(jnp.asarray(feats, jnp.float32), si, 1)
        d_ub = _pair_dp(Q, jnp.take(index.corpus, cand[:, 0], axis=0),
                        index, impl_r)                          # (B,)
        clean = np.asarray(d_ub <= tau)

        # flag fast path: min over candidates of the admissible §4 lower
        # bounds above tau certifies every candidate farther than tau
        _, lb = index.cascade_bounds(Q)
        certified = np.asarray(jnp.min(lb, axis=1) > tau)

        flags = certified.copy()
        borderline = ~clean & ~certified
        n_esc = int(borderline.sum())
        if n_esc:
            # escalation: the exact cascade decides the borderline band.
            # Fixed-slot padding (repeat the first borderline row) keeps
            # every escalation at the one compiled batch shape — without
            # it each distinct borderline count compiles a fresh cascade
            # and the serving tail measures the compiler (the same rule
            # the server scenario's continuous batching follows).
            rows = np.nonzero(borderline)[0]
            pad = np.concatenate([rows, np.full(B - n_esc, rows[0],
                                                dtype=rows.dtype)])
            _, d_exact = eng.knn(Q[pad], impl=impl)
            flags[borderline] = np.asarray(d_exact)[:n_esc] > \
                np.float32(self.tau)
        if not return_stats:
            return flags, scores
        stats = {"n_queries": B, "n_flagged": int(flags.sum()),
                 "n_clean_fast": int((clean & ~borderline).sum()),
                 "n_flag_fast": int((certified & ~borderline).sum()),
                 "n_escalated": n_esc,
                 "escalation_rate": n_esc / max(B, 1)}
        return flags, scores, stats

    def decide_exact(self, Q, *, impl: str = "auto"
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """The oracle: exact cascade 1-NN distance per query, flagged
        iff it exceeds ``tau``. Returns (flags, exact_nn_dist) — what
        ``decide`` must match bit for bit."""
        _, d = self.engine.knn(jnp.asarray(Q, jnp.float32), impl=impl)
        d = np.asarray(d)
        return d > np.float32(self.tau), d


def fit_anomaly_scorer(engine, *, k: int = 3, quantile: float = 0.95,
                       n_cal: int = 64, impl: str = "auto"
                       ) -> AnomalyScorer:
    """Calibrate an ``AnomalyScorer`` on a fitted engine's corpus.

    A spec-seeded subset of ``n_cal`` corpus rows (rng keyed from
    ``spec.seed`` + ``ANOMALY_SALT``) gets exact leave-one-out 1-NN
    distances through the fused Gram engine; ``tau`` is their
    ``quantile``. Sketch k-NN scores of *every* corpus row under
    leave-one-out (pure matmuls on the stored (N, R) sketch) form the
    reference score distribution for ``calibrated``. Deterministic:
    same engine + config -> bit-identical scorer.
    """
    index = engine.index
    assert index is not None and index.sketch is not None, \
        "anomaly scoring reads the sketch tier: fit with sketch_r > 0"
    si = index.sketch
    N = si.size
    assert N >= 2, "calibration needs at least two corpus series"
    rng = np.random.default_rng([int(engine.spec.seed), ANOMALY_SALT])
    n_cal = int(min(max(n_cal, 2), N))
    rows = np.sort(rng.permutation(N)[:n_cal])
    D = np.asarray(engine.gram(index.corpus[rows], impl=impl),
                   np.float64)                                  # (n_cal, N)
    D[np.arange(n_cal), rows] = np.inf
    cal_dists = np.sort(D.min(axis=1))
    tau = float(np.quantile(cal_dists, float(quantile)))
    S = np.asarray(si.sketch)
    cal_scores = np.sort(_sketch_knn_scores(
        S, S, np.asarray(si.sq), k, exclude=np.arange(N)))
    return AnomalyScorer(engine=engine, k=int(k), quantile=float(quantile),
                         tau=tau, cal_dists=cal_dists,
                         cal_scores=cal_scores)

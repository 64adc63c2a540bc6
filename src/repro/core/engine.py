"""Fitted-engine API: ``MeasureSpec -> fit(corpus) -> SimilarityEngine``
(DESIGN.md §12).

The paper's thesis is that SP-DTW / SP-K_rdtw are *one* learned sparse
search space shared by every downstream workload. This module is that
thesis as an API: ``fit(spec, corpus)`` resolves the support grid, the
block-sparse tile plan, the per-corpus search index and (optionally) the
centroid model exactly once, and returns a frozen ``SimilarityEngine``
whose every operation — ``pairs`` / ``gram`` / ``knn`` / ``grad`` /
``barycenter`` / ``classify`` — reuses those artifacts. No per-call
``sp/bsp/weights`` re-resolution, no scattered ``impl="auto"``
heuristics: backend choice is the capability lookup in
``repro.kernels.backends`` and plan resolution happened at fit time.

Series may be univariate (N, T) or multivariate (N, T, d): the block
kernels carry (T, d) through the tile-major channel layout
(``kernels.backends.to_tile_major``), and the lower-bound cascade covers
both — multivariate indexes carry per-channel envelopes (DESIGN.md §14),
so mv ``knn`` prunes with the same admissible bounds instead of falling
back to the full-Gram argmin. The kernel families (krdtw / sp_krdtw) get
their own log-semiring cascade: a unit-weight index plus the proven
K1/K2 slack terms turn the min-plus bounds into admissible bounds on
-log K_rdtw.

The legacy module-level entries (``ops.spdtw_gram`` …) remain as
deprecated wrappers over the same ``_impl`` bodies the engine calls —
bit-identical by construction, tested in ``tests/test_engine.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .dtw import band_mask
from .measures import (CorpusIndex, Measure, build_corpus_index,
                       make_measure)
from .occupancy import BlockSparsePaths, SparsePaths, learn_sparse_paths
from .spec import GRAM_FAMILIES, KERNEL_FAMILIES, MeasureSpec

_CASCADE_FAMILIES = ("dtw", "spdtw")   # admissible lower bounds exist
_SOFT_FAMILIES = ("dtw", "spdtw")      # min-plus DPs with a soft twin


def _band_sp(T: int, radius: int) -> SparsePaths:
    """A Sakoe-Chiba corridor wrapped as a SparsePaths (unit weights):
    the "band" support source of a MeasureSpec."""
    sup = np.asarray(band_mask(T, T, radius))
    return SparsePaths(weights=jnp.asarray(sup, jnp.float32),
                       support=jnp.asarray(sup), counts=jnp.zeros((T, T)),
                       theta=0.0, gamma=0.0)


def _weights_sp(weights) -> SparsePaths:
    """A raw (T, T) weight grid wrapped as a SparsePaths."""
    w = jnp.asarray(weights, jnp.float32)
    return SparsePaths(weights=w, support=w > 0,
                       counts=jnp.zeros_like(w), theta=0.0, gamma=0.0)


@dataclasses.dataclass(frozen=True)
class SimilarityEngine:
    """A measure fitted to (optionally) a corpus: the one object every
    workload goes through (DESIGN.md §12).

    Frozen record owning the build-once artifacts:

      spec            the ``MeasureSpec`` this engine realizes;
      T, d            series length / channel count the engine was fit
                      for (d = 1 univariate);
      sp              the resolved ``SparsePaths`` support (None for
                      dense-support families);
      weights         the dense (T, T) weight grid (None for the
                      baseline families with no DP grid);
      bsp             the block-sparse tile plan (the *plan* layer,
                      resolved once via the cached
                      ``backends.resolve_plan``; reverse plans cache on
                      it lazily per query length);
      corpus, labels  the fitted candidate set (None when the engine was
                      fit support-only);
      index           the per-corpus ``CorpusIndex`` of the lower-bound
                      cascade (univariate dissimilarity families only);
      centroid_model  fitted ``cluster.CentroidModel`` (optional);
      version         monotone refresh stamp of the learner/actor tier
                      (DESIGN.md §16): 0 for a fresh ``fit``, bumped by
                      ``with_corpus`` and restamped at publication by
                      ``core.snapshot.SnapshotStore`` — serving actors
                      report it so staleness is observable.

    All methods accept ``impl`` = "auto" | "pallas" | "scan" | "dense"
    (+ legacy "ref"), resolved by the capability walk in
    ``kernels.backends.resolve``.
    """
    spec: MeasureSpec
    T: int
    d: int = 1
    sp: Optional[SparsePaths] = None
    weights: Optional[jnp.ndarray] = None
    bsp: Optional[BlockSparsePaths] = None
    corpus: Optional[jnp.ndarray] = None
    labels: Optional[np.ndarray] = None
    index: Optional[CorpusIndex] = None
    centroid_model: Optional[object] = None
    version: int = 0

    # ---- introspection ---------------------------------------------------
    @property
    def family(self) -> str:
        """The measure family this engine evaluates."""
        return self.spec.family

    @property
    def is_kernel(self) -> bool:
        """True for similarity (log-kernel) families."""
        return self.spec.is_kernel

    @property
    def corpus_size(self) -> int:
        """Number of fitted corpus series (0 when support-only)."""
        return 0 if self.corpus is None else int(self.corpus.shape[0])

    @property
    def measure(self) -> Measure:
        """The legacy ``core.measures.Measure`` view of this engine
        (pair-level evaluators, visited-cell accounting)."""
        return make_measure(self.family, self.T, sp=self.sp,
                            radius=self.spec.radius, nu=self.spec.nu,
                            lags=self.spec.lags)

    def _corpus_or(self, B):
        if B is not None:
            return jnp.asarray(B, jnp.float32)
        assert self.corpus is not None, \
            "engine was fit without a corpus; pass B explicitly"
        return self.corpus

    # ---- execute layer ---------------------------------------------------
    def pairs(self, x, y, *, impl: str = "auto") -> jnp.ndarray:
        """Batched aligned-pair dissimilarity: (B, T[, d]) x same -> (B,).
        Kernel families return the negated log kernel, so every family
        is argmin-ready."""
        from repro.kernels import ops
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        f = self.family
        if f == "dtw":
            return ops._dtw_pairs(x, y, impl=impl)
        if f == "dtw_sc":
            return ops._dtw_pairs(x, y, impl=impl, radius=self.spec.radius)
        if f == "spdtw":
            return ops._spdtw_pairs(x, y, self.sp, bsp=self.bsp, impl=impl)
        if f in KERNEL_FAMILIES:
            sup = None if self.sp is None or f != "sp_krdtw" \
                else self.sp.support
            radius = self.spec.radius if f == "krdtw_sc" else None
            return -ops._log_krdtw_pairs(x, y, self.spec.nu, radius=radius,
                                         support=sup, impl=impl)
        m = self.measure
        return jax.vmap(m.pair)(x, y)

    def gram(self, A, B=None, *, impl: str = "auto",
             block_a: int = 64, thresholds=None, alive0=None) -> jnp.ndarray:
        """(Na, Nb) dissimilarity matrix against ``B`` (default: the
        fitted corpus) through the fused block-sparse Gram engines.
        Kernel families are negated into dissimilarities;
        ``thresholds``/``alive0`` engage the early-abandon sweep
        (dissimilarity families only)."""
        from repro.kernels import ops
        A = jnp.asarray(A, jnp.float32)
        B = self._corpus_or(B)
        f = self.family
        if f == "dtw":
            assert thresholds is None and alive0 is None, \
                "early abandon needs the spdtw plan path"
            return ops._dtw_gram(A, B, impl=impl, block_a=block_a)
        if f == "spdtw":
            return ops._spdtw_gram(A, B, sp=self.sp, bsp=self.bsp,
                                   impl=impl, block_a=block_a,
                                   thresholds=thresholds, alive0=alive0)
        if f in KERNEL_FAMILIES:
            return -self.gram_log(A, B, impl=impl, block_a=block_a)
        m = self.measure
        return m.cross(A, B, block=block_a)

    def gram_log(self, A, B=None, *, impl: str = "auto",
                 block_a: int = 64) -> jnp.ndarray:
        """(Na, Nb) log-kernel Gram matrix (kernel families only; the
        SVM workload's input)."""
        from repro.kernels import ops
        assert self.is_kernel, f"{self.family} is not a kernel"
        A = jnp.asarray(A, jnp.float32)
        B = self._corpus_or(B)
        sup = self.sp.support if (self.sp is not None and
                                  self.family == "sp_krdtw") else None
        radius = self.spec.radius if self.family == "krdtw_sc" else None
        return ops._log_krdtw_gram(A, B, self.spec.nu, support=sup,
                                   radius=radius, impl=impl,
                                   block_a=block_a)

    # ---- retrieval / classification --------------------------------------
    def knn(self, Q, *, impl: str = "auto", seed_k: int = 2,
            prefix_frac: float = 0.5, return_stats: bool = False,
            mode: str = "exact", top_c: Optional[int] = None,
            approx: bool = False):
        """1-NN of each query against the fitted corpus.

        ``mode="exact"`` (default): dissimilarity engines — univariate
        *and* multivariate — run the lower-bound cascade (DESIGN.md §4;
        bit-identical to full-Gram argmin, centroid-seeded when a
        centroid model was fit). Kernel engines (krdtw / sp_krdtw) run
        the log-semiring cascade (DESIGN.md §14) — bit-identical to
        ``-gram_log`` argmin. Only engines fit without a corpus index
        fall back to the exact Gram argmin.

        ``mode="sketch"`` (DESIGN.md §13; needs a spec fit with
        ``sketch_r > 0``): the Random Warping Series matmul shortlist of
        the ``top_c`` sketch-nearest candidates, re-ranked with the
        exact cascade machinery — bit-identical to exact mode whenever
        the shortlist contains the true neighbour; ``top_c`` is the
        recall dial and ``approx=True`` skips the re-rank entirely.
        Returns (nn_idx, nn_dist[, stats]).
        """
        from repro.kernels import ops
        assert mode in ("exact", "sketch"), mode
        Q = jnp.asarray(Q, jnp.float32)
        if mode == "sketch":
            from .sketch import sketch_knn
            assert self.index is not None and \
                self.index.sketch is not None, \
                "sketch mode needs a spec fit with sketch_r > 0"
            return sketch_knn(Q, self.index, top_c=top_c, approx=approx,
                              impl=impl, return_stats=return_stats)
        if self.index is not None:
            if self.index.kind in ("krdtw", "sp_krdtw"):
                return ops._krdtw_knn_cascade(
                    Q, self.index, impl=impl, seed_k=seed_k,
                    prefix_frac=prefix_frac, return_stats=return_stats)
            return ops._knn_cascade(Q, self.index, impl=impl, seed_k=seed_k,
                                    prefix_frac=prefix_frac,
                                    return_stats=return_stats,
                                    centroid_model=self.centroid_model)
        D = self.gram(Q, impl=impl)
        nn = jnp.argmin(D, axis=1).astype(jnp.int32)
        nnd = jnp.take_along_axis(D, nn[:, None], axis=1)[:, 0]
        if not return_stats:
            return nn, nnd
        return nn, nnd, {"n_queries": int(Q.shape[0]),
                         "n_candidates": self.corpus_size,
                         "pre_dp_prune": 0.0, "dp_pairs": Q.shape[0] *
                         self.corpus_size}

    def sketch_embed(self, X, *, impl: str = "auto") -> jnp.ndarray:
        """Project series into the engine's (R,) RWS sketch space
        (DESIGN.md §13): (B, T) -> (B, R), one masked DP per (series,
        anchor) pair under the fitted banded support and weights — the
        same features ``mode="sketch"`` retrieval shortlists on. This
        is the public seam for sketch-space consumers (``classify.svm``
        feature maps, the ``repro.monitor`` analytics tier); it needs a
        spec fit with ``sketch_r > 0``.
        """
        from .sketch import sketch_embed as _sketch_embed
        assert self.index is not None and self.index.sketch is not None, \
            "sketch_embed needs a spec fit with sketch_r > 0"
        si = self.index.sketch
        return _sketch_embed(jnp.asarray(X, jnp.float32), si.anchors,
                             bsp=self.index.bsp, weights=self.index.weights,
                             gamma=si.gamma, impl=impl)

    def classify(self, Q, *, impl: str = "auto",
                 via: str = "auto") -> np.ndarray:
        """Predicted labels for queries ``Q``: nearest-centroid when a
        centroid model was fit (``via="centroid"`` forces it, "knn"
        forces the cascade/Gram path), else 1-NN over the corpus
        labels."""
        assert via in ("auto", "knn", "centroid")
        use_centroid = (via == "centroid" or
                        (via == "auto" and self.centroid_model is not None))
        if use_centroid:
            assert self.centroid_model is not None, "no centroid model fit"
            from repro.classify.centroid import nearest_centroid_predict
            return np.asarray(nearest_centroid_predict(
                jnp.asarray(Q, jnp.float32), self.centroid_model,
                impl=impl))
        assert self.labels is not None, "engine was fit without labels"
        nn, _ = self.knn(Q, impl=impl)
        return np.asarray(self.labels)[np.asarray(nn)]

    # ---- differentiable layer --------------------------------------------
    def _soft_weights(self) -> jnp.ndarray:
        assert self.family in _SOFT_FAMILIES, \
            f"{self.family} has no soft (differentiable) twin"
        if self.weights is not None:
            return self.weights
        return jnp.ones((self.T, self.T), jnp.float32)

    def soft_pairs(self, x, y) -> jnp.ndarray:
        """Differentiable batched aligned-pair soft measure at the
        spec's ``gamma`` (custom VJP: block-sparse stash forward,
        reverse active-tile backward — DESIGN.md §11)."""
        from repro.kernels.soft_block import soft_spdtw_batch
        return soft_spdtw_batch(jnp.asarray(x, jnp.float32),
                                jnp.asarray(y, jnp.float32),
                                self._soft_weights(), float(self.spec.gamma))

    def soft_gram(self, A, B=None) -> jnp.ndarray:
        """Differentiable all-pairs soft Gram matrix at the spec's
        ``gamma`` (block-sparse scan forward, reverse-scan backward)."""
        from repro.kernels.soft_block import soft_spdtw_gram_batch
        return soft_spdtw_gram_batch(jnp.asarray(A, jnp.float32),
                                     self._corpus_or(B),
                                     self._soft_weights(),
                                     float(self.spec.gamma))

    def grad(self, x, y) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(values, d values / d x) of the soft measure for aligned
        pairs — the gradient never leaves the learned support."""
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        val, vjp = jax.vjp(lambda xx: self.soft_pairs(xx, y), x)
        return val, vjp(jnp.ones_like(val))[0]

    def barycenter(self, X=None, *, sample_weights=None, init=None,
                   steps: int = 100, lr: float = 0.05):
        """Fit one soft barycenter over ``X`` (default: the fitted
        corpus) under the engine's support and ``gamma``. Returns
        (centroid (T[, d]), per-step loss history)."""
        from repro.cluster.barycenter import soft_barycenter
        X = self._corpus_or(X)
        return soft_barycenter(X, self._soft_weights(),
                               float(self.spec.gamma), init=init,
                               steps=steps, lr=lr,
                               sample_weights=sample_weights)

    def fit_centroids(self, n_per_class: int = 1, *, steps: int = 60,
                      lr: float = 0.05, impl: str = "auto",
                      seed: Optional[int] = None) -> "SimilarityEngine":
        """Fit ``n_per_class`` soft-barycenter centroids per class label
        on the corpus and return a new engine carrying the model (the
        cascade auto-seeds from it; ``classify`` serves
        nearest-centroid). ``seed`` defaults to the spec's seed, so
        stochastic fitting is reproducible from the spec alone."""
        assert self.corpus is not None and self.labels is not None, \
            "centroid fitting needs a corpus with labels"
        from repro.cluster import fit_class_centroids
        model = fit_class_centroids(
            self.corpus, self.labels, self._soft_weights(),
            float(self.spec.gamma), n_per_class=n_per_class, steps=steps,
            lr=lr, impl=impl,
            seed=self.spec.seed if seed is None else seed, bsp=self.bsp)
        return dataclasses.replace(self, centroid_model=model)

    def with_corpus(self, corpus, labels=None) -> "SimilarityEngine":
        """Re-fit the corpus-dependent artifacts (index) on a new
        candidate set, reusing the resolved support and plan. Works on
        corpus *shards* too: the index artifacts (envelopes, sketch) are
        per-candidate rows, so fitting a shard equals slicing the full
        index — ``shard`` exploits that equivalence without recompute.

        Deterministic rebuild: every stochastic artifact (sketch
        anchors, and the corpus embedding against them) is keyed from
        ``spec.seed``, so ``with_corpus(C)`` is bit-identical to a fresh
        ``fit(spec, C, sp=..., bsp=...)`` on the same support — the
        invariant the learner tier (DESIGN.md §16) republishes under.
        The successor carries ``version + 1`` (monotone refresh lineage;
        ``SnapshotStore.publish`` restamps at publication)."""
        eng = fit(self.spec, corpus, labels=labels, sp=self.sp,
                  bsp=self.bsp, T=self.T)
        return dataclasses.replace(eng, version=self.version + 1)

    def shard(self, n_shards: int) -> Tuple["SimilarityEngine", ...]:
        """Partition the fitted corpus state into contiguous row shards.

        Returns ``n_shards`` engines (clamped to the corpus size), each
        carrying a contiguous slice of the corpus, labels and per-corpus
        index rows; the measure statics (support, weights, tile plan)
        are shared by reference. Shard s covers global corpus rows
        ``[offsets[s], offsets[s+1])`` with ``offsets`` as in
        ``np.array_split`` — sizes differ by at most one. Slicing, not
        re-fitting: envelopes and sketch rows are row-independent, so
        each shard engine is bit-identical to ``with_corpus(shard)``
        (tested). The mesh serving tier stacks these shards into one
        pytree (``launch/shard_index.py``, DESIGN.md §15)."""
        assert self.corpus is not None, "shard() needs a fitted corpus"
        n = self.corpus_size
        n_shards = max(1, min(int(n_shards), n))
        out = []
        for ids in np.array_split(np.arange(n), n_shards):
            sel = slice(int(ids[0]), int(ids[-1]) + 1)
            out.append(dataclasses.replace(
                self, corpus=self.corpus[sel],
                labels=None if self.labels is None else self.labels[sel],
                index=None if self.index is None else self.index.take(sel)))
        return tuple(out)


def fit(spec: MeasureSpec, corpus=None, *, labels=None,
        sp: Optional[SparsePaths] = None, weights=None,
        bsp: Optional[BlockSparsePaths] = None,
        support_corpus=None, n_support: Optional[int] = None,
        T: Optional[int] = None, centroids: int = 0,
        centroid_steps: int = 60, impl: str = "auto") -> SimilarityEngine:
    """Fit a ``MeasureSpec`` to data: resolve support, plan, index and
    (optionally) centroids exactly once (DESIGN.md §12).

    corpus:          (N, T) or (N, T, d) candidate set. Optional — a
                     support-only engine (pass ``sp``/``weights``/``T``
                     instead) still evaluates ``pairs``/``gram``.
    labels:          (N,) class labels riding with the corpus (enables
                     ``classify`` and centroid fitting).
    sp / weights /
    bsp:             pre-resolved support handles; given one of these,
                     the "learned" support source uses it instead of
                     re-learning from data.
    support_corpus:  series to learn the occupancy prior from (default:
                     the corpus; ``n_support`` caps how many are used —
                     the paper learns from the train split).
    T:               series length for support-only engines with no
                     handles (dense-support families).
    centroids:       fit N centroids per class at fit time (> 0 needs
                     labels).
    impl:            backend for any fitting-time evaluation.

    The tile plan comes from the single cached resolver
    (``kernels.backends.resolve_plan``), so repeated fits over the same
    grid — serving restarts, per-call wrapper shims — sparsify once.
    """
    from repro.kernels import backends as bk
    if corpus is not None:
        corpus = jnp.asarray(corpus, jnp.float32)
        T = int(corpus.shape[1])
        d = bk.series_dim(corpus)
    else:
        d = 1
    if not spec.is_sparse:
        # dense measures (dtw / krdtw / *_sc / baselines) take their
        # domain from the family itself (full grid or radius corridor):
        # stray grid handles from generic call sites are ignored rather
        # than silently reinterpreting the measure
        sp = weights = bsp = None
    # ---- resolve the support grid (once) ---------------------------------
    if sp is None and weights is not None:
        sp = _weights_sp(weights)
    if spec.is_sparse and sp is None and bsp is None:
        if spec.support == "learned":
            src = support_corpus if support_corpus is not None else corpus
            assert src is not None, \
                "learned support needs a corpus (or pass sp/weights)"
            src = jnp.asarray(src, jnp.float32)
            if n_support is not None:
                src = src[:n_support]
            sp = learn_sparse_paths(src, theta=spec.theta,
                                    gamma=spec.weight_gamma)
            T = int(src.shape[1]) if T is None else T
        elif spec.support == "band":
            assert T is not None, "band support needs corpus or T"
            sp = _band_sp(T, spec.radius)
    if T is None:
        T = sp.weights.shape[0] if sp is not None else \
            (bsp.T if bsp is not None else None)
    assert T is not None, "could not infer series length; pass corpus or T"
    # dense-support families plan over the all-ones grid
    w = sp.weights if sp is not None else None
    # ---- resolve the block plan (once, cached on the weight bytes) -------
    # only the min-plus families execute on the block-sparse plan; the
    # K_rdtw engines dispatch on support/radius and never read a bsp
    plan = None
    if spec.family in _CASCADE_FAMILIES:
        if bsp is not None:
            plan = bsp
        elif w is not None:
            assert not bk.is_traced(w), \
                "fit needs a host-concrete support grid (the tile plan " \
                "is static data); learn it outside the trace"
            plan = bk.resolve_plan(weights=w, tile=spec.tile)
        else:
            plan = bk.resolve_plan(T=T, tile=spec.tile)
    # ---- corpus-dependent artifacts --------------------------------------
    index = None
    if corpus is not None and spec.family in _CASCADE_FAMILIES:
        # univariate and multivariate alike: the envelope bounds are
        # per-channel for (N, T, d) corpora (DESIGN.md §14)
        if w is None and plan is not None and spec.is_sparse:
            # bsp-only fit: reassemble the grid so the cascade's bounds
            # see the real weights, not an all-ones stand-in
            w = jnp.asarray(bk.densify(plan)[:T, :T])
            sp = _weights_sp(w)
        iw = w if w is not None else np.ones((T, T), np.float32)
        index = build_corpus_index(corpus, iw, kind=spec.family, bsp=plan)
        if spec.sketch_r > 0 and d == 1:
            # sketch tier (DESIGN.md §13): anchors keyed off the spec's
            # seed, corpus embedded through the same block engines
            from .sketch import (ANCHOR_SALT, build_sketch_index,
                                 random_anchors)
            anchors = random_anchors(
                jax.random.fold_in(spec.key(), ANCHOR_SALT),
                spec.sketch_r, T, max_len=spec.sketch_len)
            si = build_sketch_index(corpus, anchors, bsp=index.bsp,
                                    weights=iw, impl=impl, seed=spec.seed)
            index = dataclasses.replace(index, sketch=si)
    elif corpus is not None and d == 1 and \
            spec.family in ("krdtw", "sp_krdtw"):
        # kernel-measure index (DESIGN.md §14): unit weights over the
        # support — K_rdtw is support-restricted but unweighted, and the
        # min-plus bound b1 the log-semiring cascade needs is on the
        # unit-weight masked path cost. The K1/K2 slack terms are
        # computed inside build_corpus_index from the same support.
        if spec.family == "sp_krdtw":
            assert sp is not None, "sp_krdtw fit did not resolve a support"
            sup_w = np.asarray(sp.support, np.float32)
        else:
            sup_w = np.ones((T, T), np.float32)
        index = build_corpus_index(
            corpus, sup_w, kind=spec.family,
            bsp=bk.resolve_plan(weights=sup_w, tile=spec.tile),
            nu=spec.nu)
    labels_np = None if labels is None else np.asarray(labels)
    engine = SimilarityEngine(
        spec=spec, T=T, d=d, sp=sp, weights=w, bsp=plan, corpus=corpus,
        labels=labels_np, index=index)
    if centroids > 0:
        engine = engine.fit_centroids(centroids, steps=centroid_steps,
                                      impl=impl)
    return engine


def engine_for(family: str = "spdtw", *, sp=None, bsp=None, weights=None,
               tile=None, gamma: float = 0.1, nu: float = 1.0,
               radius: int = 10, T: Optional[int] = None
               ) -> SimilarityEngine:
    """Support-only engine from whichever handles the caller holds — the
    shim the deprecated ``ops`` wrappers and ``cluster`` models route
    through. Plan resolution hits the cached resolver, so this is cheap
    to call per-op; steady-state code should still ``fit`` once."""
    support = "dense" if family in ("dtw", "krdtw", "euclidean", "corr",
                                    "daco", "dtw_sc", "krdtw_sc") \
        else "learned"
    spec = MeasureSpec(family=family, support=support, gamma=gamma, nu=nu,
                       radius=radius, tile=tile)
    return fit(spec, sp=sp, weights=weights, bsp=bsp, T=T)

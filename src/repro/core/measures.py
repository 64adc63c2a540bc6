"""Measure stack: index → plan → execute (DESIGN.md §1).

Every measure exposes ``cross(A, B) -> (Na, Nb)`` dissimilarity matrix
(for 1-NN) and kernels additionally expose ``gram_log(A, B)`` (for SVM).
``Measure`` is a plain parameter record with explicit dispatch — the old
registry of per-measure pair-lambdas is gone. Construction happens once
per dataset and owns the two build-once artifacts of the search stack:

  * the *plan*: the block-sparse tile schedule (``BlockSparsePaths``),
    derived from the learned weights at construction and shared by every
    kernel invocation;
  * the *index*: a per-corpus ``CorpusIndex`` (support extents, windowed
    envelopes, endpoint weights) built by ``build_index`` exactly once per
    corpus and consumed by the lower-bound cascade in
    ``repro.kernels.ops.knn_cascade`` (DESIGN.md §4).

All-pairs evaluation of the elastic measures routes through ``pairwise`` —
the unified dispatch over the fused Gram engines in ``repro.kernels``
(block-sparse Pallas kernel on TPU, active-tile jnp scan elsewhere, chunked
nested vmap for the dense measures). Nothing on this path materializes the
``jnp.repeat``/``jnp.tile`` pair expansion.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import baselines, bounds
from .dtw import band_cells as _band_cells
from .dtw import dtw as _dtw
from .dtw import dtw_sc as _dtw_sc
from .dtw import wdtw as _wdtw
from .krdtw import log_krdtw as _log_krdtw
from .krdtw import log_krdtw_sc as _log_krdtw_sc
from .krdtw import log_sp_krdtw as _log_sp_krdtw
from .occupancy import (BlockSparsePaths, SparsePaths, block_sparsify,
                        default_tile)


def pairwise(A: jnp.ndarray, B: jnp.ndarray, kind: str = "spdtw", *,
             sp: Optional[SparsePaths] = None,
             bsp: Optional[BlockSparsePaths] = None,
             weights: Optional[jnp.ndarray] = None,
             nu: float = 1.0, radius: Optional[int] = None,
             impl: str = "auto", block_a: int = 64) -> jnp.ndarray:
    """Unified all-pairs engine: (Na, T) x (Nb, T) -> (Na, Nb) values.

    kind: "spdtw" / "dtw" return dissimilarities; "krdtw" / "sp_krdtw"
    return *log kernel* values (callers negate for 1-NN). impl: "auto"
    picks the fused Pallas Gram kernel on TPU and the jnp engines elsewhere;
    "pallas" forces the kernel (interpret mode off-TPU, as in tests); "ref"
    forces the jnp engines; "dense" is the historical dense nested-vmap
    baseline kept for benchmarking.
    """
    from repro.kernels import ops  # deferred: kernels package imports core
    if kind == "spdtw":
        return ops._spdtw_gram(A, B, sp=sp, bsp=bsp, weights=weights,
                               impl=impl, block_a=block_a)
    if kind == "dtw":
        return ops._dtw_gram(A, B, impl=impl, block_a=block_a)
    if kind in ("krdtw", "sp_krdtw"):
        support = None
        if kind == "sp_krdtw":
            if sp is not None:
                support = sp.support
            elif weights is not None:
                support = weights > 0
            else:
                raise ValueError("sp_krdtw needs sp or weights")
        return ops._log_krdtw_gram(A, B, nu, support=support, radius=radius,
                                   impl=impl, block_a=block_a)
    raise ValueError(f"pairwise does not support kind {kind!r}")


def _chunked_cross(fn: Callable, A: jnp.ndarray, B: jnp.ndarray,
                   block: int = 128) -> jnp.ndarray:
    f = jax.jit(jax.vmap(jax.vmap(fn, in_axes=(None, 0)), in_axes=(0, None)))
    rows = []
    for s in range(0, A.shape[0], block):
        rows.append(f(A[s:s + block], B))
    return jnp.concatenate(rows, axis=0)


# ---------------------------------------------------------------------------
# Index layer: build-once per-corpus search index (DESIGN.md §4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CorpusIndex:
    """Everything the lower-bound cascade needs about a fixed corpus.

    Built once per (measure, corpus) by ``Measure.build_index`` /
    ``build_corpus_index`` and reused across every query batch:

      corpus:            (Nc, T) f32 candidate set, as searched against.
      weights:           dense (T, T) weight grid of the measure (0 = cell
                         outside the learned support); drives the seed DP
                         and the dense parity path.
      bsp:               the cached block-sparse tile plan (*plan* layer) —
                         the execute stage's schedule, built exactly once.
      lo, hi:            (T,) per-row support column windows (static).
      wmin_rows:         (T,) admissible per-row weight floor (static).
      env_lo, env_hi:    (Nc, T) windowed candidate envelopes (LB_Keogh).
      lo_t, hi_t,
      wmin_cols:         the transposed (per-column) counterparts; the
                         cascade envelopes the *query* under these at
                         query time for the reverse Keogh bound.
      w00, wTT:          endpoint weights (LB_Kim).
      sketch:            optional ``core.sketch.SketchIndex`` — the
                         Random Warping Series tier (DESIGN.md §13);
                         attached by ``fit`` when the spec asks for
                         sketching (``sketch_r > 0``), None otherwise.
      nu, log_s1,
      log_s2:            kernel-measure bound terms (DESIGN.md §14): for
                         krdtw/sp_krdtw indexes, the kernel bandwidth and
                         the proven K1/K2 slacks of the log-semiring
                         lower bound (``bounds.krdtw_log_slacks``); 0.0
                         for min-plus measures.

    Multivariate corpora ((Nc, T, d)) carry (Nc, T, d) per-channel
    envelopes; the bound machinery sums channel excesses, matching the
    dependent-DTW local cost.
    """
    kind: str
    corpus: jnp.ndarray
    weights: jnp.ndarray
    bsp: BlockSparsePaths
    lo: np.ndarray
    hi: np.ndarray
    wmin_rows: np.ndarray
    env_lo: jnp.ndarray
    env_hi: jnp.ndarray
    lo_t: np.ndarray
    hi_t: np.ndarray
    wmin_cols: np.ndarray
    w00: float
    wTT: float
    sketch: Optional[object] = None
    nu: float = 0.0
    log_s1: float = 0.0
    log_s2: float = 0.0

    @property
    def size(self) -> int:
        """Number of indexed corpus series."""
        return int(self.corpus.shape[0])

    def cascade_bounds(self, Q) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(lb1, lb2) of the cascade's bound stage for queries Q against
        this index, one compiled program (``bounds.cascade_bounds``)."""
        return bounds.cascade_bounds(
            Q, self.corpus, self.env_lo, self.env_hi, lo=self.lo,
            hi=self.hi, wmin_rows=self.wmin_rows, lo_t=self.lo_t,
            hi_t=self.hi_t, wmin_cols=self.wmin_cols, w00=self.w00,
            wTT=self.wTT)

    def take(self, sel) -> "CorpusIndex":
        """Candidate-sliced view of this index (the sharding primitive).

        ``sel`` is any row selector (slice or integer array). The static
        artifacts — weight grid, tile plan, support windows, endpoint
        weights, kernel slacks — describe the *measure* and are shared
        untouched; only the per-candidate rows (corpus, envelopes, and
        the sketch matrix when present) are sliced. Because the
        envelopes and sketches are computed row-independently, a sliced
        index is bit-identical to rebuilding the index on the sliced
        corpus — the invariant the sharded serving tier
        (``launch/shard_index.py``, DESIGN.md §15) rests on.
        """
        sk = self.sketch
        if sk is not None:
            sk = dataclasses.replace(sk, sketch=sk.sketch[sel],
                                     sq=sk.sq[sel])
        return dataclasses.replace(
            self, corpus=self.corpus[sel], env_lo=self.env_lo[sel],
            env_hi=self.env_hi[sel], sketch=sk)


def build_corpus_index(corpus: jnp.ndarray, weights,
                       kind: str = "spdtw",
                       bsp: Optional[BlockSparsePaths] = None,
                       tile: Optional[int] = None,
                       nu: Optional[float] = None) -> CorpusIndex:
    """Construct the search index for a corpus under a (T, T) weight grid.

    ``weights`` must be host-concrete (the tile plan and support windows
    are static data); ``corpus`` may be a traced array — the envelopes are
    pure jnp, so index construction works inside shard_map'd serving jobs.
    ``corpus`` may be (Nc, T) or multivariate (Nc, T, d) — the envelopes
    generalize per channel. For kernel kinds (krdtw/sp_krdtw) pass the
    bandwidth ``nu``: the K1/K2 slack terms of the log-semiring lower
    bound are computed here, once, from the support.
    """
    w = np.asarray(weights, np.float32)
    T = w.shape[0]
    support = w > 0
    lo, hi = bounds.support_extents(support)
    lo_t, hi_t = bounds.support_extents(support.T)
    wmin_rows = bounds.row_min_weights(w)
    wmin_cols = bounds.row_min_weights(w.T)
    env_lo, env_hi = bounds.envelopes(corpus, lo, hi)
    if bsp is None:
        bsp = block_sparsify(w, tile=tile or default_tile(T))
    log_s1 = log_s2 = 0.0
    if kind in ("krdtw", "sp_krdtw"):
        assert nu is not None, "kernel indexes need the bandwidth nu"
        log_s1, log_s2 = bounds.krdtw_log_slacks(
            support if kind == "sp_krdtw" else None, T=T)
    return CorpusIndex(
        kind=kind, corpus=jnp.asarray(corpus, jnp.float32),
        weights=jnp.asarray(w), bsp=bsp, lo=lo, hi=hi,
        wmin_rows=wmin_rows, env_lo=env_lo, env_hi=env_hi,
        lo_t=lo_t, hi_t=hi_t, wmin_cols=wmin_cols,
        w00=float(w[0, 0]), wTT=float(w[-1, -1]),
        nu=float(nu or 0.0), log_s1=log_s1, log_s2=log_s2)


# ---------------------------------------------------------------------------
# Measure: explicit parameter record + dispatch (no closure registry)
# ---------------------------------------------------------------------------

_KERNELS = ("krdtw", "krdtw_sc", "sp_krdtw")
_SPARSE = ("spdtw", "sp_krdtw")
_GRAM_KINDS = ("dtw", "spdtw", "krdtw", "sp_krdtw")  # fused-engine routed


@dataclasses.dataclass
class Measure:
    """One (dis)similarity measure with its meta-parameters baked in.

    The *execute* layer entry points are ``cross`` / ``gram_log`` (all
    pairs through the fused Gram engines) and ``pair`` / ``logk`` (single
    pairs, the paper's faithful evaluators). ``build_index`` produces the
    *index* layer for 1-NN search; the *plan* (block-sparse tile schedule)
    is built once here at construction and shared by all of them.
    """
    name: str
    T: int
    sp: Optional[SparsePaths] = None
    nu: float = 1.0
    radius: int = 10
    lags: int = 10
    bsp: Optional[BlockSparsePaths] = None
    visited_cells: Optional[int] = None
    _indices: Dict[tuple, CorpusIndex] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def __post_init__(self):
        if self.name not in ALL_MEASURES + ("dtw_sc", "krdtw_sc"):
            raise ValueError(f"unknown measure {self.name!r}")
        if self.name in _SPARSE:
            assert self.sp is not None, f"{self.name} needs a SparsePaths"
        if self.name == "spdtw" and self.bsp is None:
            # the plan layer: block-sparse tile schedule, built exactly once
            self.bsp = block_sparsify(self.sp, tile=default_tile(self.T))
        if self.visited_cells is None:
            self.visited_cells = self._visited_cells()

    def _visited_cells(self) -> int:
        """Paper Table VI's '# visited cells' accounting."""
        n, T = self.name, self.T
        if n in ("euclidean", "corr"):
            return T
        if n == "daco":
            return T * self.lags
        if n in ("dtw_sc", "krdtw_sc"):
            return _band_cells(T, T, self.radius)
        if n in _SPARSE:
            return self.sp.n_cells
        return T * T                       # dtw, krdtw

    # ---- pair-level evaluators -------------------------------------------
    @property
    def is_kernel(self) -> bool:
        """True for similarity (log-kernel) measures; False for
        dissimilarities."""
        return self.name in _KERNELS

    def pair(self, x, y):
        """Scalar dissimilarity between two series (kernels are negated)."""
        n = self.name
        if n == "euclidean":
            return baselines.euclidean(x, y)
        if n == "corr":
            return baselines.corr_dissimilarity(x, y)
        if n == "daco":
            return baselines.daco(x, y, self.lags)
        if n == "dtw":
            return _dtw(x, y)
        if n == "dtw_sc":
            return _dtw_sc(x, y, self.radius)
        if n == "spdtw":
            return _wdtw(x, y, self.sp.weights)
        return -self.logk(x, y)

    def logk(self, x, y):
        """Scalar log kernel value (kernels only)."""
        n = self.name
        if n == "krdtw":
            return _log_krdtw(x, y, self.nu)
        if n == "krdtw_sc":
            return _log_krdtw_sc(x, y, self.nu, self.radius)
        if n == "sp_krdtw":
            return _log_sp_krdtw(x, y, self.nu, self.sp.support)
        raise ValueError(f"{n} is not a kernel")

    # kept under the historical attribute names (callers treat these as
    # (x, y) -> scalar callables)
    @property
    def pair_fn(self) -> Callable:
        """(x, y) -> scalar dissimilarity callable (kernels negated)."""
        return self.pair

    @property
    def logk_fn(self) -> Optional[Callable]:
        """(x, y) -> scalar log-kernel callable; None for
        dissimilarity measures."""
        return self.logk if self.is_kernel else None

    # ---- all-pairs execute layer -----------------------------------------
    def cross(self, A, B, block: int = 128):
        """(Na, Nb) dissimilarity matrix through the fused Gram engines."""
        n = self.name
        if n == "dtw":
            return pairwise(A, B, "dtw", block_a=block)
        if n == "spdtw":
            return pairwise(A, B, "spdtw", sp=self.sp, bsp=self.bsp,
                            block_a=block)
        if n == "krdtw":
            return -pairwise(A, B, "krdtw", nu=self.nu, block_a=block)
        if n == "sp_krdtw":
            return -pairwise(A, B, "sp_krdtw", sp=self.sp, nu=self.nu,
                             block_a=block)
        return _chunked_cross(self.pair, A, B, block)

    def gram_log(self, A, B, block: int = 128):
        """(Na, Nb) log Gram matrix (kernels only)."""
        assert self.is_kernel, f"{self.name} is not a kernel"
        n = self.name
        if n == "krdtw":
            return pairwise(A, B, "krdtw", nu=self.nu, block_a=block)
        if n == "sp_krdtw":
            return pairwise(A, B, "sp_krdtw", sp=self.sp, nu=self.nu,
                            block_a=block)
        return _chunked_cross(self.logk, A, B, block)

    # ---- index layer ------------------------------------------------------
    @property
    def supports_cascade(self) -> bool:
        """True when the lower-bound cascade applies (dissimilarity DPs —
        admissible bounds for the log-kernel recursion are future work)."""
        return self.name in ("dtw", "spdtw")

    _INDEX_CACHE_MAX = 4                   # corpora cached per measure

    def build_index(self, corpus, *, force: bool = False) -> CorpusIndex:
        """Build (once) and cache the search index for ``corpus``.

        The cache is keyed on corpus *content* (shape + byte hash) — id()
        keys would go stale across ``jnp.asarray`` conversions and recycle
        after GC. The hash costs one host transfer of the corpus per call;
        steady-state serving holds the returned index directly
        (``launch.search.SearchEngine`` does) and never re-enters. At most
        ``_INDEX_CACHE_MAX`` corpora are retained (FIFO eviction), so
        rotating corpora cannot grow memory without bound. ``force=True``
        rebuilds.
        """
        assert self.supports_cascade, \
            f"{self.name} has no admissible lower bounds"
        corpus = jnp.asarray(corpus, jnp.float32)
        key = (corpus.shape, hash(np.asarray(corpus).tobytes()))
        if force or key not in self._indices:
            if self.name == "spdtw":
                w = self.sp.weights
                bsp = self.bsp
            else:                          # plain dtw: all-ones support
                w = np.ones((self.T, self.T), np.float32)
                if self.bsp is None:
                    self.bsp = block_sparsify(w, tile=default_tile(self.T))
                bsp = self.bsp
            while len(self._indices) >= self._INDEX_CACHE_MAX:
                self._indices.pop(next(iter(self._indices)))
            self._indices[key] = build_corpus_index(
                corpus, w, kind=self.name, bsp=bsp)
        return self._indices[key]

    def knn(self, queries, corpus, *, impl: str = "auto", seed_k: int = 2,
            return_stats: bool = False):
        """Exact 1-NN of each query against ``corpus`` via the cascade
        (bounds -> survivors -> fused masked DP with early abandoning).
        Returns (nn_idx, nn_dist[, stats])."""
        from repro.kernels import ops  # deferred: kernels imports core
        index = self.build_index(corpus)
        return ops._knn_cascade(jnp.asarray(queries, jnp.float32), index,
                                impl=impl, seed_k=seed_k,
                                return_stats=return_stats)


def make_measure(name: str, T: int, *,
                 sp: Optional[SparsePaths] = None,
                 radius: int = 10, nu: float = 1.0,
                 lags: int = 10) -> Measure:
    """Factory. ``T`` is the series length (for visited-cell accounting)."""
    return Measure(name, T, sp=sp, radius=radius, nu=nu, lags=lags)


ALL_MEASURES = ("corr", "daco", "euclidean", "dtw", "dtw_sc",
                "krdtw", "spdtw", "sp_krdtw")

"""Execute-layer entry points for the alignment kernels (DESIGN.md §12).

Backend policy lives in ``repro.kernels.backends``: every ``impl=``
argument ("auto" | "pallas" | "scan" | "ref" (alias) | "dense") is
interpreted by ``backends.resolve`` — one auditable capability lookup
(on TPU the Pallas SP-DTW kernels run compiled; elsewhere the scan
engines are the default and ``impl="pallas"`` forces interpret mode,
which is what the correctness tests sweep; soft and wavefront calls,
traced weight grids and other unsupported requirements walk the
fallback chain to scan or the dense oracle).

The supported public API is the fitted engine
(``repro.core.engine.fit`` → ``SimilarityEngine``); the module-level
functions here (``spdtw_gram``, ``knn_cascade``, …) are kept as thin
deprecated wrappers over the same ``_impl`` bodies the engine methods
call — bit-identical by construction, with a one-shot
``DeprecationWarning`` pointing at the engine method that replaces them.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import bounds as _bounds
from repro.core.dtw import INF
from repro.core.dtw import (band_mask as _band_mask, dtw as _dtw_pair,
                            wdtw as _wdtw_pair)
from repro.core.krdtw import log_krdtw as _log_krdtw_pair
from repro.core.measures import CorpusIndex
from repro.core.measures import _chunked_cross as _nested_cross
from repro.core.occupancy import BlockSparsePaths, SparsePaths
from repro.core.softdtw import soft_wdtw
from . import backends as bk
from . import ref
from .dtw_wavefront import wavefront_dtw
from .dtw_banded import banded_dtw
from .spdtw_block import spdtw_block
from .krdtw_wavefront import mask_to_diagonal_major, wavefront_log_krdtw
from .gram_block import (PAIR_BLOCK, gram_log_krdtw_block,
                         gram_prefix_bound, gram_spdtw_block, gram_spdtw_scan,
                         prefix_tile_count, spdtw_paired_scan)
from .soft_block import (gram_soft_spdtw_block, gram_soft_spdtw_scan,
                         soft_spdtw_batch, soft_spdtw_gram_batch,
                         soft_spdtw_paired_scan)

# legacy helper names, re-exported from the backend layer (the scattered
# per-function copies these replaced are gone — satellite of DESIGN.md §12)
_on_tpu = bk.on_tpu
_is_traced = bk.is_traced
_resolve_bsp = bk.resolve_plan
_resolve_dense_weights = bk.resolve_dense_weights
_densify = bk.densify


# ---------------------------------------------------------------------------
# Deprecation shim: public names warn once, then behave exactly as before
# ---------------------------------------------------------------------------

_WARNED: set = set()


def _deprecated(name: str, replacement: str) -> None:
    """One-shot DeprecationWarning for a legacy module-level entry."""
    if name not in _WARNED:
        _WARNED.add(name)
        warnings.warn(
            f"repro.kernels.ops.{name} is deprecated; use {replacement} "
            f"(MeasureSpec -> fit -> SimilarityEngine; DESIGN.md §12)",
            DeprecationWarning, stacklevel=3)


def _series_d(x) -> int:
    return bk.series_dim(x)


# ---------------------------------------------------------------------------
# Batched aligned-pair implementations
# ---------------------------------------------------------------------------

def _dtw_pairs(x: jnp.ndarray, y: jnp.ndarray, impl: str = "auto",
               radius: Optional[int] = None) -> jnp.ndarray:
    require = (bk.WAVEFRONT,) + ((bk.MULTIVARIATE,) if _series_d(x) > 1
                                 else ())
    backend = bk.resolve(impl, require=require).name
    # the wavefront kernel is univariate; scan/dense route to the vmapped
    # core DP (full support => no tiles to skip)
    if backend in ("scan", "dense") or _series_d(x) > 1:
        if radius is None:
            return ref.dtw_batch(x, y)
        return ref.dtw_band_batch(x, y, radius)
    return wavefront_dtw(x, y, radius=radius, interpret=not bk.on_tpu())


def dtw_pairs(x: jnp.ndarray, y: jnp.ndarray, impl: str = "auto",
              radius: Optional[int] = None) -> jnp.ndarray:
    """Batched DTW (optionally Sakoe-Chiba banded). x, y: (B, T) or
    (B, T, d) -> (B,). Deprecated: use ``engine.pairs``."""
    _deprecated("dtw_pairs", "fit(MeasureSpec('dtw'), ...).pairs")
    return _dtw_pairs(x, y, impl=impl, radius=radius)


def dtw_banded_pairs(x: jnp.ndarray, y: jnp.ndarray, radius: int,
                     impl: str = "auto") -> jnp.ndarray:
    """Batched banded DTW via the slanted-strip kernel (O(T*(2r+1)) work)."""
    backend = bk.resolve(impl, require=(bk.WAVEFRONT,)).name
    if backend in ("scan", "dense") or _series_d(x) > 1:
        return ref.dtw_band_batch(x, y, radius)
    return banded_dtw(x, y, radius, interpret=not bk.on_tpu())


def _spdtw_pairs(x: jnp.ndarray, y: jnp.ndarray, sp: SparsePaths = None,
                 bsp: Optional[BlockSparsePaths] = None,
                 impl: str = "auto", tile: int = 128) -> jnp.ndarray:
    backend = bk.resolve(impl).name
    if backend in ("scan", "dense"):
        # historical "ref": the vmapped dense masked DP (the paired
        # active-tile scan serves the cascade via ``_pair_dp``)
        return ref.wdtw_batch(
            x, y, bk.resolve_dense_weights(sp, bsp, T=x.shape[1]))
    if bsp is None:
        bsp = bk.resolve_plan(sp, tile=tile)
    return spdtw_block(x, y, bsp, T_orig=x.shape[1],
                       interpret=not bk.on_tpu())


def spdtw_pairs(x: jnp.ndarray, y: jnp.ndarray, sp: SparsePaths,
                bsp: Optional[BlockSparsePaths] = None,
                impl: str = "auto", tile: int = 128) -> jnp.ndarray:
    """Batched SP-DTW over a learned sparse search space. x, y: (B, T) or
    (B, T, d) -> (B,). Deprecated: use ``engine.pairs``."""
    _deprecated("spdtw_pairs", "fit(MeasureSpec('spdtw'), ...).pairs")
    return _spdtw_pairs(x, y, sp, bsp=bsp, impl=impl, tile=tile)


def _log_krdtw_pairs(x: jnp.ndarray, y: jnp.ndarray, nu: float,
                     radius: Optional[int] = None,
                     support: Optional[jnp.ndarray] = None,
                     impl: str = "auto") -> jnp.ndarray:
    backend = bk.resolve(impl, require=(bk.WAVEFRONT,)).name
    # the anti-diagonal wavefront kernel is univariate
    if backend in ("scan", "dense") or _series_d(x) > 1:
        if support is not None:
            return ref.log_krdtw_masked_batch(x, y, nu, support)
        if radius is not None:
            return ref.log_krdtw_band_batch(x, y, nu, radius)
        return ref.log_krdtw_batch(x, y, nu)
    mask_diag = None
    if support is not None:
        mask_diag = jnp.asarray(mask_to_diagonal_major(np.asarray(support)))
    return wavefront_log_krdtw(x, y, nu, radius=radius, mask_diag=mask_diag,
                               interpret=not bk.on_tpu())


def log_krdtw_pairs(x: jnp.ndarray, y: jnp.ndarray, nu: float,
                    radius: Optional[int] = None,
                    support: Optional[jnp.ndarray] = None,
                    impl: str = "auto") -> jnp.ndarray:
    """Batched log K_rdtw / K_rdtw_sc / SP-K_rdtw. (B, T) -> (B,).
    Deprecated: use ``engine.pairs`` / ``engine.gram_log``."""
    _deprecated("log_krdtw_pairs", "fit(MeasureSpec('krdtw'), ...).pairs")
    return _log_krdtw_pairs(x, y, nu, radius=radius, support=support,
                            impl=impl)


# ---------------------------------------------------------------------------
# All-pairs Gram engines (the classification hot path; no repeat/tile)
# ---------------------------------------------------------------------------

def _spdtw_gram(A: jnp.ndarray, B: jnp.ndarray, *,
                sp: Optional[SparsePaths] = None,
                bsp: Optional[BlockSparsePaths] = None,
                weights: Optional[jnp.ndarray] = None,
                impl: str = "auto", tile: Optional[int] = None,
                block_a: int = 64,
                thresholds: Optional[jnp.ndarray] = None,
                alive0: Optional[jnp.ndarray] = None,
                return_counts: bool = False):
    """``return_counts=True`` returns (G, counts): the Pallas kernel's
    (tile sweeps, live pair sweeps) device scalars, None on the other
    backends."""
    require = []
    if bsp is None and sp is None and bk.is_traced(weights):
        require.append(bk.TRACED_WEIGHTS)
    backend = bk.resolve(impl, require=tuple(require)).name
    if backend == "dense":
        w = bk.resolve_dense_weights(sp, bsp, weights, T=A.shape[1])
        out = _nested_cross(lambda a, b: _wdtw_pair(a, b, w), A, B, block_a)
        if alive0 is not None:
            out = jnp.where(jnp.asarray(alive0), out, INF)
        return (out, None) if return_counts else out
    bspr = bk.resolve_plan(sp, bsp, weights, tile=tile)
    if backend == "scan":
        out = gram_spdtw_scan(A, B, bspr, T_orig=A.shape[1],
                              block_a=block_a, thresholds=thresholds,
                              alive0=alive0)
        return (out, None) if return_counts else out
    return gram_spdtw_block(A, B, bspr, T_orig=A.shape[1],
                            thresholds=thresholds, alive0=alive0,
                            interpret=not bk.on_tpu(),
                            return_counts=return_counts)


def spdtw_gram(A: jnp.ndarray, B: jnp.ndarray, *,
               sp: Optional[SparsePaths] = None,
               bsp: Optional[BlockSparsePaths] = None,
               weights: Optional[jnp.ndarray] = None,
               impl: str = "auto", tile: Optional[int] = None,
               block_a: int = 64,
               thresholds: Optional[jnp.ndarray] = None,
               alive0: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(Na, Nb) SP-DTW Gram matrix through the fused block-sparse engine.

    A: (Na, T) or (Na, T, d); B likewise. impl: "auto" (pallas on TPU,
    scan elsewhere), "pallas" (interpret off TPU; what the parity tests
    sweep), "scan"/"ref" (jnp scan engine), or "dense" (chunked
    nested-vmap dense DP — the historical baseline, kept for
    benchmarking the speed-up). Weights traced under jit/vmap/grad
    cannot yield a host-side tile plan, so they transparently take the
    dense path (``backends.resolve`` walks the fallback chain — the
    pre-engine behaviour, fully traceable).

    ``thresholds`` ((Na,) per-A-row) and ``alive0`` ((Na, Nb) bool) engage
    the early-abandon sweep of the block engines (see ``gram_block``):
    dead or abandoned pairs report +INF. The dense baseline has no
    abandon sweep; it honours ``alive0`` by masking so the cascade stays
    exact across every impl.

    Deprecated as a module-level entry: use ``engine.gram``.
    """
    _deprecated("spdtw_gram", "fit(MeasureSpec('spdtw'), ...).gram")
    return _spdtw_gram(A, B, sp=sp, bsp=bsp, weights=weights, impl=impl,
                       tile=tile, block_a=block_a, thresholds=thresholds,
                       alive0=alive0)


def _soft_spdtw_pairs(x: jnp.ndarray, y: jnp.ndarray, *,
                      sp: Optional[SparsePaths] = None,
                      bsp: Optional[BlockSparsePaths] = None,
                      weights: Optional[jnp.ndarray] = None,
                      gamma: float = 1.0, impl: str = "auto") -> jnp.ndarray:
    if bk.resolve(impl).name == "dense":
        w = bk.resolve_dense_weights(sp, bsp, weights, T=x.shape[1])
        return jax.vmap(
            lambda a, b: soft_wdtw(a, b, w, float(gamma)))(x, y)
    if sp is None and weights is None:
        assert bsp is not None, "need one of sp / bsp / weights"
        return soft_spdtw_paired_scan(jnp.asarray(x, jnp.float32),
                                      jnp.asarray(y, jnp.float32),
                                      bsp, float(gamma), T_orig=x.shape[1])
    w = sp.weights if sp is not None else weights
    return soft_spdtw_batch(jnp.asarray(x, jnp.float32),
                            jnp.asarray(y, jnp.float32),
                            jnp.asarray(w), float(gamma))


def soft_spdtw_pairs(x: jnp.ndarray, y: jnp.ndarray, *,
                     sp: Optional[SparsePaths] = None,
                     bsp: Optional[BlockSparsePaths] = None,
                     weights: Optional[jnp.ndarray] = None,
                     gamma: float = 1.0, impl: str = "auto") -> jnp.ndarray:
    """Batched aligned-pair soft-SP-DTW, differentiable. x, y: (B, T) or
    (B, T, d) -> (B,).

    The default routes through ``soft_block.soft_spdtw_batch`` (custom
    VJP: block-sparse stash forward, reverse active-tile backward —
    DESIGN.md §11; gradients never leave the learned support);
    ``impl="dense"`` runs the vmapped core recursion — same values and
    the dense expected-alignment backward, kept as the parity baseline.
    A *bsp-only* caller is a serving call: it runs the paired scan on
    the caller's own plan (tile size preserved, no densify/re-sparsify
    round trip; autodiff still works by differentiating through the
    scan). There is no Pallas *paired* soft kernel.

    Deprecated as a module-level entry: use ``engine.soft_pairs`` /
    ``engine.grad``.
    """
    _deprecated("soft_spdtw_pairs",
                "fit(MeasureSpec('spdtw'), ...).soft_pairs")
    return _soft_spdtw_pairs(x, y, sp=sp, bsp=bsp, weights=weights,
                             gamma=gamma, impl=impl)


def _soft_spdtw_gram(A: jnp.ndarray, B: jnp.ndarray, *,
                     sp: Optional[SparsePaths] = None,
                     bsp: Optional[BlockSparsePaths] = None,
                     weights: Optional[jnp.ndarray] = None,
                     gamma: float = 1.0, impl: str = "auto",
                     tile: Optional[int] = None,
                     block_a: int = 64) -> jnp.ndarray:
    require = [bk.DIFFERENTIABLE]
    if bsp is None and sp is None and bk.is_traced(weights):
        require.append(bk.TRACED_WEIGHTS)
    backend = bk.resolve(impl, require=tuple(require)).name
    if backend == "dense":
        w = bk.resolve_dense_weights(sp, bsp, weights, T=A.shape[1])
        return _nested_cross(
            lambda a, b: soft_wdtw(a, b, w, float(gamma)), A, B, block_a)
    if impl == "auto" and bsp is None and tile is None and \
            (sp is not None or weights is not None):
        w = sp.weights if sp is not None else weights
        return soft_spdtw_gram_batch(jnp.asarray(A, jnp.float32),
                                     jnp.asarray(B, jnp.float32),
                                     jnp.asarray(w), float(gamma))
    bspr = bk.resolve_plan(sp, bsp, weights, tile=tile)
    if backend == "scan":
        return gram_soft_spdtw_scan(A, B, bspr, float(gamma),
                                    T_orig=A.shape[1], block_a=block_a)
    return gram_soft_spdtw_block(A, B, bspr, float(gamma),
                                 T_orig=A.shape[1],
                                 interpret=not bk.on_tpu())


def soft_spdtw_gram(A: jnp.ndarray, B: jnp.ndarray, *,
                    sp: Optional[SparsePaths] = None,
                    bsp: Optional[BlockSparsePaths] = None,
                    weights: Optional[jnp.ndarray] = None,
                    gamma: float = 1.0, impl: str = "auto",
                    tile: Optional[int] = None,
                    block_a: int = 64) -> jnp.ndarray:
    """(Na, Nb) soft-SP-DTW Gram matrix, differentiable on the default
    path.

    impl mirrors ``spdtw_gram``: "auto" routes through
    ``soft_block.soft_spdtw_gram_batch`` — custom VJP whose forward is
    the block-sparse Gram engine and whose backward is the reverse
    active-tile sweep over the stashed L blocks (DESIGN.md §11). Soft
    calls require DIFFERENTIABLE, which the pallas record omits (its
    soft kernels do not compile for the chip), so "auto" and "pallas"
    both resolve to the scan engines;
    "scan"/"ref" the forward jnp scan engine, "dense" the nested-vmap
    core recursion (traceable, and the only path for traced weight
    grids; its backward is the dense expected-alignment oracle). A
    caller-supplied ``bsp`` or ``tile`` pins the plan, so those calls
    keep the direct engine path (forward-only) instead of the VJP
    wrapper, which resolves its own default-tile plan from the weight
    bytes.

    Deprecated as a module-level entry: use ``engine.soft_gram``.
    """
    _deprecated("soft_spdtw_gram",
                "fit(MeasureSpec('spdtw'), ...).soft_gram")
    return _soft_spdtw_gram(A, B, sp=sp, bsp=bsp, weights=weights,
                            gamma=gamma, impl=impl, tile=tile,
                            block_a=block_a)


def _dtw_gram(A: jnp.ndarray, B: jnp.ndarray, *, impl: str = "auto",
              block_a: int = 64) -> jnp.ndarray:
    backend = bk.resolve(impl).name
    if backend in ("scan", "dense"):
        return _nested_cross(_dtw_pair, A, B, block_a)
    return gram_spdtw_block(A, B, bk.resolve_plan(T=A.shape[1]),
                            T_orig=A.shape[1], interpret=not bk.on_tpu())


def dtw_gram(A: jnp.ndarray, B: jnp.ndarray, *, impl: str = "auto",
             block_a: int = 64) -> jnp.ndarray:
    """(Na, Nb) dense DTW Gram matrix (full support => no tiles to skip).

    The scan/dense path is a chunked nested vmap (never a repeat/tile
    HBM expansion); the Pallas path reuses the fused engine with an
    all-ones weight grid so each stripe is still loaded into VMEM only
    once. Deprecated as a module-level entry: use ``engine.gram``.
    """
    _deprecated("dtw_gram", "fit(MeasureSpec('dtw'), ...).gram")
    return _dtw_gram(A, B, impl=impl, block_a=block_a)


def _log_krdtw_gram(A: jnp.ndarray, B: jnp.ndarray, nu: float, *,
                    support: Optional[jnp.ndarray] = None,
                    radius: Optional[int] = None, impl: str = "auto",
                    block_a: int = 64) -> jnp.ndarray:
    backend = bk.resolve(impl, require=(bk.WAVEFRONT,)).name
    if backend in ("scan", "dense") or bk.is_traced(support) or \
            _series_d(A) > 1:
        sup = None if support is None else jnp.asarray(support)
        if radius is not None:   # fold the corridor into the support mask
            band = _band_mask(A.shape[1], B.shape[1], radius)
            sup = band if sup is None else sup & band
        return _nested_cross(lambda a, b: _log_krdtw_pair(a, b, nu, sup),
                             A, B, block_a)
    return gram_log_krdtw_block(A, B, nu, support=support, radius=radius,
                                interpret=not bk.on_tpu())


def log_krdtw_gram(A: jnp.ndarray, B: jnp.ndarray, nu: float, *,
                   support: Optional[jnp.ndarray] = None,
                   radius: Optional[int] = None, impl: str = "auto",
                   block_a: int = 64) -> jnp.ndarray:
    """(Na, Nb) log K_rdtw / SP-K_rdtw Gram matrix via the fused kernel.

    A traced ``support`` (under jit/vmap/grad) cannot be re-laid-out
    host-side, and the anti-diagonal wavefront kernel is univariate, so
    those cases take the masked nested-vmap path, which is traceable and
    accepts (N, T, d). Deprecated as a module-level entry: use
    ``engine.gram_log``.
    """
    _deprecated("log_krdtw_gram", "fit(MeasureSpec('krdtw'), ...).gram_log")
    return _log_krdtw_gram(A, B, nu, support=support, radius=radius,
                           impl=impl, block_a=block_a)


# ---------------------------------------------------------------------------
# Lower-bound cascade: exact 1-NN without paying the DP per candidate
# ---------------------------------------------------------------------------

def _pair_dp(x: jnp.ndarray, y: jnp.ndarray, index: CorpusIndex, impl: str,
             thresholds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Batched aligned-pair SP-DTW for the cascade's seed/survivor stages.

    (B, T) -> (B,). "dense" keeps the historical dense masked DP (the
    exactness baseline); "scan" runs the active-tile paired scan (work
    proportional to surviving tiles); "pallas" the block kernel.
    """
    if impl == "dense":
        return ref.wdtw_batch(x, y, index.weights)
    if impl == "scan":
        return spdtw_paired_scan(x, y, index.bsp, T_orig=x.shape[1],
                                 thresholds=thresholds)
    return spdtw_block(x, y, index.bsp, T_orig=x.shape[1],
                       interpret=not bk.on_tpu())


def _stat_int(v):
    """Cascade counters land as host ints when concrete (BENCH artifacts
    require integral counts); traced values pass through untouched."""
    return v if bk.is_traced(v) else int(v)


def _knn_cascade(Q: jnp.ndarray, index: CorpusIndex, *, impl: str = "auto",
                 seed_k: int = 2, prefix_frac: float = 0.5,
                 block_a: int = 64, return_stats: bool = False,
                 centroid_model=None):
    Q = jnp.asarray(Q, jnp.float32)
    C = index.corpus
    Nq, T = Q.shape[:2]
    Nc = C.shape[0]
    seed_k = min(seed_k, Nc)
    impl_r = bk.resolve(impl).name
    # host spans time the eager cascade only: under jit or shard_map they
    # would time the tracing
    eager = not (bk.is_traced(Q) or bk.is_traced(C))

    def stage(name, **attrs):
        return tracing.span(name, **attrs) if eager \
            else contextlib.nullcontext()

    # --- stage 0: centroid-seeded threshold (k + 1 DPs per query) ---
    cand = d_cand = None
    n_centroids = 0
    if centroid_model is not None and \
            getattr(centroid_model, "medoids", None) is not None:
        with stage("cascade.seed_dp"):
            Z = jnp.asarray(centroid_model.centroids, jnp.float32)
            n_centroids = Z.shape[0]
            Dc = _spdtw_gram(Q, Z, bsp=index.bsp, weights=index.weights,
                             impl=impl, block_a=block_a)
            best_c = jnp.argmin(Dc, axis=1)
            cand = jnp.take(jnp.asarray(centroid_model.medoids, jnp.int32),
                            best_c)                            # (Nq,)
            d_cand = _pair_dp(Q, jnp.take(C, cand, axis=0), index, impl_r)

    with stage("cascade.bounds"):
        # --- stage 1: banded endpoint bound (exact corners + the pinned
        # first/last rows under per-row weight floors; DESIGN.md §14);
        # stage 2: support-windowed envelopes, both orientations. One
        # compiled program for both stages ---
        lb1, lb2 = index.cascade_bounds(Q)

    with stage("cascade.seed_dp"):
        # --- seed thresholds: exact DP on the seed_k best-bounded
        # candidates ---
        _, seed_idx = jax.lax.top_k(-lb2, seed_k)              # (Nq, k)
        xq = jnp.repeat(Q, seed_k, axis=0)
        yc = jnp.take(C, seed_idx.reshape(-1), axis=0)
        seed_d = _pair_dp(xq, yc, index, impl_r).reshape(Nq, seed_k)
        thr = jnp.min(seed_d, axis=1)                          # (Nq,)
        if d_cand is not None:
            thr = jnp.minimum(thr, d_cand)

        # --- survivors so far: bound <= threshold (non-strict keeps
        # ties) ---
        rows = jnp.arange(Nq)[:, None]
        alive2 = lb2 <= thr[:, None]
        alive2 = alive2.at[rows, seed_idx].set(False)          # known
        if cand is not None:
            alive2 = alive2.at[rows[:, 0], cand].set(False)

    # --- stage 3: truncated prefix-DP bound on the block plan ---
    n_prefix = prefix_tile_count(index.bsp, prefix_frac, T)
    if n_prefix > 0 and impl_r != "dense":
        with stage("cascade.prefix_bound"):
            lb3 = gram_prefix_bound(Q, C, index.bsp, n_prefix, T_orig=T,
                                    block_a=block_a)
            alive = alive2 & (lb3 <= thr[:, None])
    else:
        lb3 = lb2
        alive = alive2

    # --- stage 4: exact DP on the survivors, early abandoning ---
    eager = eager and not bk.is_traced(thr)
    counts = None
    # the plan the kernel sweeps: each surviving pair runs over plan_tiles
    # tiles of tile x tile cells
    attrs = {} if impl_r == "dense" else {"tile": index.bsp.tile,
                                          "plan_tiles": index.bsp.n_active}
    if impl_r == "pallas":
        attrs["block_pairs"] = PAIR_BLOCK[0] * PAIR_BLOCK[1]
    with stage("cascade.survivor_dp", **attrs):
        D = jnp.full((Nq, Nc), INF, jnp.float32).at[rows, seed_idx].set(
            seed_d)
        if cand is not None:
            D = D.at[rows[:, 0], cand].set(d_cand)
        if eager and impl_r == "scan":
            # gather the survivors: the DP only ever touches those pairs
            qi, ci = np.nonzero(np.asarray(alive))
            counts = (0, 0)
            if len(qi):
                d_surv, counts = spdtw_paired_scan(
                    jnp.take(Q, qi, axis=0), jnp.take(C, ci, axis=0),
                    index.bsp, T_orig=T, thresholds=jnp.take(thr, qi),
                    return_counts=True)
                D = D.at[qi, ci].set(d_surv)
            G_ab = None
        else:
            G, counts = _spdtw_gram(Q, C, bsp=index.bsp,
                                    weights=index.weights, impl=impl,
                                    block_a=block_a, thresholds=thr,
                                    alive0=alive, return_counts=True)
            D = jnp.where(alive, G, D)
            G_ab = G

    with stage("cascade.select"):
        nn = jnp.argmin(D, axis=1).astype(jnp.int32)
        nnd = jnp.take_along_axis(D, nn[:, None], axis=1)[:, 0]
        if not return_stats:
            return nn, nnd
        total = Nq * Nc
        dp_pairs = _stat_int(alive.sum()) + Nq * (
            seed_k + (n_centroids + 1 if cand is not None else 0))
        abandoned = (alive & (D >= 1e29)) if G_ab is None else \
            (alive & (G_ab >= 1e29))
        stats = {
            "n_queries": Nq, "n_candidates": Nc, "seed_k": seed_k,
            "n_centroids": n_centroids,
            "prefix_tiles": n_prefix, "plan_tiles": index.bsp.n_active,
            "stage1_prune": jnp.mean((lb1 > thr[:, None]).astype(
                jnp.float32)),
            "stage2_prune": jnp.mean((lb2 > thr[:, None]).astype(
                jnp.float32)),
            "stage3_prune": jnp.mean((lb3 > thr[:, None]).astype(
                jnp.float32)),
            "pre_dp_prune": 1.0 - dp_pairs / total,
            "dp_pairs": dp_pairs,
            "dp_abandoned": jnp.mean(abandoned.astype(jnp.float32)),
        }
        if counts is not None:
            stats["dp_tile_sweeps"], stats["dp_alive_pair_sweeps"] = counts
    return nn, nnd, stats


# ---------------------------------------------------------------------------
# Log-semiring cascade: exact kernel 1-NN for krdtw / sp_krdtw
# ---------------------------------------------------------------------------

def _krdtw_pair_eval(x: jnp.ndarray, y: jnp.ndarray, index: CorpusIndex,
                     impl: str) -> jnp.ndarray:
    """Exact kernel dissimilarity -log K_rdtw for aligned pair batches."""
    sup = None if index.kind == "krdtw" else (index.weights > 0)
    return -_log_krdtw_pairs(x, y, index.nu, support=sup, impl=impl)


def _krdtw_knn_cascade(Q: jnp.ndarray, index: CorpusIndex, *,
                       impl: str = "auto", seed_k: int = 2,
                       prefix_frac: float = 0.5, block_a: int = 64,
                       return_stats: bool = False):
    """Exact kernel 1-NN under the dissimilarity -log K_rdtw (DESIGN.md §14).

    Same shape as ``_knn_cascade``, but the bound stage runs in the log
    semiring: K1/K2 are upper-bounded by their proven slacks times
    exp(-nu * b) where b is an admissible min-plus bound on the
    *unit-weight* masked path cost — so the whole Kim/Keogh/prefix
    machinery is reused verbatim on the kernel index (which is built with
    unit weights over the support). Thresholds are exact dissimilarities
    of real candidates and the bound is admissible, so the returned
    neighbours are bit-identical to -gram_log argmin.
    """
    assert Q.ndim == 2, "the kernel measures are univariate"
    Q = jnp.asarray(Q, jnp.float32)
    C = index.corpus
    Nq, T = Q.shape
    Nc = C.shape[0]
    seed_k = min(seed_k, Nc)
    impl_r = bk.resolve(impl).name
    nu = index.nu

    # --- min-plus bound b1 on the unit-weight masked path cost ---
    _, b1 = index.cascade_bounds(Q)
    # --- b2: every K2 path pays the aligned endpoint factors ---
    b2 = (Q[:, 0, None] - C[None, :, 0]) ** 2
    if T > 1:
        b2 = b2 + (Q[:, -1, None] - C[None, :, -1]) ** 2
    lb2 = _bounds.lb_log_krdtw(b1, b2, nu, index.log_s1, index.log_s2)

    # --- seed thresholds: exact -log K on the best-bounded candidates ---
    _, seed_idx = jax.lax.top_k(-lb2, seed_k)                  # (Nq, k)
    xq = jnp.repeat(Q, seed_k, axis=0)
    yc = jnp.take(C, seed_idx.reshape(-1), axis=0)
    seed_d = _krdtw_pair_eval(xq, yc, index, impl_r).reshape(Nq, seed_k)
    thr = jnp.min(seed_d, axis=1)                              # (Nq,)

    rows = jnp.arange(Nq)[:, None]
    alive2 = lb2 <= thr[:, None]
    alive2 = alive2.at[rows, seed_idx].set(False)              # already known

    # --- prefix-DP tightens b1 (min-plus sweep on the unit-weight plan) ---
    n_prefix = prefix_tile_count(index.bsp, prefix_frac, T)
    if n_prefix > 0 and impl_r != "dense":
        b1p = jnp.maximum(b1, gram_prefix_bound(Q, C, index.bsp, n_prefix,
                                                T_orig=T, block_a=block_a))
        lb3 = _bounds.lb_log_krdtw(b1p, b2, nu, index.log_s1, index.log_s2)
        alive = alive2 & (lb3 <= thr[:, None])
    else:
        lb3 = lb2
        alive = alive2

    # --- exact -log K on the survivors ---
    eager = not (bk.is_traced(Q) or bk.is_traced(C) or bk.is_traced(thr))
    D = jnp.full((Nq, Nc), INF, jnp.float32).at[rows, seed_idx].set(seed_d)
    if eager:
        qi, ci = np.nonzero(np.asarray(alive))
        if len(qi):
            d_surv = _krdtw_pair_eval(jnp.take(Q, qi, axis=0),
                                      jnp.take(C, ci, axis=0), index, impl_r)
            D = D.at[qi, ci].set(d_surv)
    else:
        sup = None if index.kind == "krdtw" else (index.weights > 0)
        G = -_log_krdtw_gram(Q, C, nu, support=sup, impl=impl,
                             block_a=block_a)
        D = jnp.where(alive, G, D)
    nn = jnp.argmin(D, axis=1).astype(jnp.int32)
    nnd = jnp.take_along_axis(D, nn[:, None], axis=1)[:, 0]
    if not return_stats:
        return nn, nnd
    dp_pairs = _stat_int(alive.sum()) + Nq * seed_k
    stats = {
        "n_queries": Nq, "n_candidates": Nc, "seed_k": seed_k,
        "n_centroids": 0,
        "prefix_tiles": n_prefix, "plan_tiles": index.bsp.n_active,
        "stage1_prune": jnp.mean((lb2 > thr[:, None]).astype(jnp.float32)),
        "stage2_prune": jnp.mean((lb2 > thr[:, None]).astype(jnp.float32)),
        "stage3_prune": jnp.mean((lb3 > thr[:, None]).astype(jnp.float32)),
        "pre_dp_prune": 1.0 - dp_pairs / (Nq * Nc),
        "dp_pairs": dp_pairs,
        "dp_abandoned": 0.0,
    }
    return nn, nnd, stats


def knn_cascade(Q: jnp.ndarray, index: CorpusIndex, *, impl: str = "auto",
                seed_k: int = 2, prefix_frac: float = 0.5,
                block_a: int = 64, return_stats: bool = False,
                centroid_model=None):
    """Exact 1-NN of queries against an indexed corpus (DESIGN.md §4).

    The cascade: (1) LB_Kim endpoint bound, O(1)/pair; (2) support-windowed
    LB_Keogh envelopes, both orientations, O(T)/pair; seed the per-query
    threshold with the exact distance of the ``seed_k`` best-bounded
    candidates; (3) truncated prefix-DP bound over the first
    ``prefix_frac`` of the tile rows (sDTW/PrunedDTW-style, the strongest
    and priciest bound — it only runs on pairs the envelopes kept);
    (4) the fused masked DP on the survivors, with the early-abandon sweep
    killing pairs mid-DP. All bounds are admissible, thresholds are exact
    distances of real candidates, and within-DP abandoning is strict
    (``bound > thr``), so the returned neighbours are bit-identical to a
    full Gram evaluation followed by argmin — every candidate tied at the
    minimum is evaluated exactly, preserving argmin's first-index tie rule.

    Q: (Nq, T). Returns (nn_idx, nn_dist) int32/(float32); with
    ``return_stats`` a dict of per-stage prune rates rides along (entries
    are jnp scalars — convert host-side). Fully traceable: jit / shard_map
    safe because the index's plan and windows are static host data. On
    concrete (non-traced) inputs the survivor DP gathers the surviving
    pairs and runs the aligned-pair engine on just those — the CPU/GPU
    wall-clock win; under tracing it falls back to the masked Gram engine
    (static shapes), where the Pallas kernel skips fully-dead pair blocks.

    ``centroid_model`` (a ``cluster.CentroidModel``, or anything with
    ``.centroids`` (k, T) and ``.medoids`` (k,) corpus indices) switches
    on the centroid-seeded stage (DESIGN.md §10): the query's exact
    SP-DTW distance to its nearest centroid's *medoid* — a real corpus
    entry, found at fit time — seeds the per-query threshold with k + 1
    cheap DPs before any bound runs. The threshold only ever tightens
    with an exact distance of a real candidate, so exactness is
    untouched; the bounds simply prune more.

    Covers the dissimilarity measures (dtw / spdtw), univariate and
    multivariate — (Nq, T, d) queries use the per-channel envelopes of a
    multivariate index. The kernel measures (krdtw / sp_krdtw) run the
    log-semiring twin ``_krdtw_knn_cascade`` (DESIGN.md §14), routed by
    ``engine.knn``.

    Deprecated as a module-level entry: use ``engine.knn``.
    """
    _deprecated("knn_cascade", "fit(MeasureSpec('spdtw'), corpus).knn")
    return _knn_cascade(Q, index, impl=impl, seed_k=seed_k,
                        prefix_frac=prefix_frac, block_a=block_a,
                        return_stats=return_stats,
                        centroid_model=centroid_model)

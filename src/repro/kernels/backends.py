"""Backend registry + the single cached plan resolver (DESIGN.md §12).

Every ``impl=`` argument in the execute layer used to be interpreted by
scattered per-function heuristics (``_resolve``/``_resolve_bsp``/
``_resolve_dense_weights``/``_is_traced`` in ``kernels/ops.py``, plus two
more ad-hoc plan caches). This module replaces all of them with:

  * an explicit registry of the three execute backends —

      dense   chunked nested-vmap over the core DPs; traceable in every
              operand (the only path for weight grids that are jax
              Tracers) and the numerical oracle;
      scan    ``lax.scan`` over the active-tile schedule; the CPU/GPU
              production path (work scales with surviving tiles);
      pallas  the fused Pallas kernels (compiled on TPU, interpret mode
              elsewhere — what the parity tests sweep);

    each carrying *capability flags* (differentiable, multivariate,
    early-abandon, traced-weights, multivariate-grad). ``impl="auto"``
    becomes one auditable lookup: start from the platform default and
    walk the fallback chain (pallas → scan → dense) until every
    capability the call site requires is present;

  * the one cached weight-grid → ``BlockSparsePaths`` resolver
    (``resolve_plan``), keyed on the weight bytes, subsuming the former
    ``_cached_bsp`` / ``_ones_bsp`` / ``_resolve_bsp`` trio so repeated
    calls with the same grid sparsify exactly once;

  * the tile-major (channel-inner) series layout helpers that carry
    multivariate (T, d) series through the block kernels
    (``to_tile_major`` / ``from_tile_major``): channel k of tile ti
    lives in lanes ``[ti*d*S + k*S, ti*d*S + (k+1)*S)``, so per-tile
    BlockSpec indexing and all edge/halo dataflow stay 2-D and
    lanes-aligned while the cost-block formation sums over channels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.occupancy import (BlockSparsePaths, SparsePaths,
                                  block_sparsify, default_tile)

# ---------------------------------------------------------------------------
# Capability vocabulary
# ---------------------------------------------------------------------------

DIFFERENTIABLE = "differentiable"      # has a gradient path (custom VJP)
MULTIVARIATE = "multivariate"          # accepts (T, d>1) series, forward
MULTIVARIATE_GRAD = "multivariate-grad"  # ... and on the backward pass
EARLY_ABANDON = "early-abandon"        # honours thresholds/alive0 pruning
PRUNED_DP = "pruned-dp"                # in-DP PrunedDTW row boundaries +
#                                        boundary-dead tile skips when
#                                        thresholds are given
#                                        (DESIGN.md §14)
TRACED_WEIGHTS = "traced-weights"      # weight grid may be a jax Tracer
ANCHOR_EMBED = "anchor-embed"          # batched series-vs-anchor Gram
#                                        (the sketch tier's embedding,
#                                        DESIGN.md §13)
SHARDED = "sharded"                    # cascade runs fully traced under
#                                        shard_map with early abandoning
#                                        (the sharded serving tier,
#                                        DESIGN.md §15); the dense oracle
#                                        is host-only for serving
WAVEFRONT = "wavefront"                # plan-free DTW / banded DTW /
#                                        K_rdtw sweeps (pairs and the
#                                        K_rdtw Gram)

CAPABILITIES = (DIFFERENTIABLE, MULTIVARIATE, MULTIVARIATE_GRAD,
                EARLY_ABANDON, PRUNED_DP, TRACED_WEIGHTS, ANCHOR_EMBED,
                SHARDED, WAVEFRONT)


@dataclasses.dataclass(frozen=True)
class Backend:
    """One execute backend: a name, its capability set, and the next
    backend to try when a required capability is missing.

    The registry is data, not control flow: what used to be per-function
    ``if _is_traced(...)`` / ``if _on_tpu()`` special cases is now a
    single fallback walk in ``resolve`` over these records.
    """
    name: str
    caps: frozenset
    fallback: Optional[str]
    description: str

    def supports(self, *caps: str) -> bool:
        """True when every named capability is in this backend's set."""
        return all(c in self.caps for c in caps)


_REGISTRY: dict = {}


def register_backend(backend: Backend) -> None:
    """Add (or replace) a backend record in the registry."""
    unknown = set(backend.caps) - set(CAPABILITIES)
    if unknown:
        raise ValueError(f"unknown capabilities {sorted(unknown)}")
    _REGISTRY[backend.name] = backend


def get_backend(name: str) -> Backend:
    """Registry lookup by exact name (no aliasing, no fallback)."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown backend {name!r}; "
                         f"registered: {available_backends()}")
    return _REGISTRY[name]


def available_backends() -> Tuple[str, ...]:
    """Names of every registered backend, registration order."""
    return tuple(_REGISTRY)


register_backend(Backend(
    name="dense",
    caps=frozenset({DIFFERENTIABLE, MULTIVARIATE, MULTIVARIATE_GRAD,
                    TRACED_WEIGHTS, ANCHOR_EMBED, WAVEFRONT}),
    fallback=None,
    description="chunked nested-vmap over the core DPs; fully traceable "
                "(the only path for traced weight grids) and the oracle"))
register_backend(Backend(
    name="scan",
    caps=frozenset({DIFFERENTIABLE, MULTIVARIATE, MULTIVARIATE_GRAD,
                    EARLY_ABANDON, PRUNED_DP, ANCHOR_EMBED, SHARDED,
                    WAVEFRONT}),
    fallback="dense",
    description="lax.scan over the active-tile schedule; CPU/GPU "
                "production path, work scales with surviving tiles"))
# The pallas record lists only what compiles for the chip (tests/
# test_tpu_compile.py): the hard SP-DTW Gram and pair kernels. The soft
# forward/stash/backward kernels (DIFFERENTIABLE) and the wavefront /
# banded / K_rdtw kernels (WAVEFRONT) still read values at traced lane
# offsets, which Mosaic does not lower, so those calls resolve to scan.
register_backend(Backend(
    name="pallas",
    caps=frozenset({MULTIVARIATE, EARLY_ABANDON, PRUNED_DP, ANCHOR_EMBED,
                    SHARDED}),
    fallback="scan",
    description="fused Pallas SP-DTW kernels (compiled on TPU, interpret "
                "elsewhere); soft and wavefront sweeps fall back to scan"))

# legacy spelling accepted everywhere an ``impl=`` flows in
_ALIASES = {"ref": "scan"}


def on_tpu() -> bool:
    """True when the default jax backend is a TPU."""
    return jax.default_backend() == "tpu"


def default_backend() -> str:
    """Platform default for ``impl="auto"``: pallas on TPU, scan off."""
    return "pallas" if on_tpu() else "scan"


def is_traced(x) -> bool:
    """True when ``x`` is a jax Tracer (inside jit / vmap / grad)."""
    return isinstance(x, jax.core.Tracer)


def resolve(impl: str = "auto", *, require: Tuple[str, ...] = ()) -> Backend:
    """The one capability lookup behind every ``impl=`` argument.

    ``impl`` is a backend name, a legacy alias ("ref" → scan), or
    "auto" (the platform default). The chosen backend is walked down its
    fallback chain until every capability in ``require`` is supported;
    an unknown name or an unsatisfiable requirement raises. This is the
    single place where e.g. a traced weight grid routes to the dense
    oracle or a multivariate gradient routes off the Pallas kernel.
    """
    name = _ALIASES.get(impl, impl)
    if name == "auto":
        name = default_backend()
    b = get_backend(name)
    seen = set()
    while not b.supports(*require):
        seen.add(b.name)
        if b.fallback is None or b.fallback in seen:
            raise ValueError(
                f"no backend reachable from {impl!r} supports "
                f"{sorted(set(require) - b.caps)}")
        b = get_backend(b.fallback)
    return b


# ---------------------------------------------------------------------------
# The one cached weight-grid -> plan resolver
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _cached_plan(w_bytes: bytes, T: int, tile: int) -> BlockSparsePaths:
    w = np.frombuffer(w_bytes, np.float32).reshape(T, T)
    return block_sparsify(w, tile=tile)


@functools.lru_cache(maxsize=8)
def _ones_plan(T: int) -> BlockSparsePaths:
    """Fully-dense plan for plain DTW, keyed on T alone (no per-call
    ones-array allocation or hashing)."""
    return block_sparsify(np.ones((T, T), np.float32), tile=default_tile(T))


def resolve_plan(sp=None, bsp=None, weights=None, *,
                 T: Optional[int] = None,
                 tile: Optional[int] = None) -> BlockSparsePaths:
    """Host-side block plan from whichever handle the caller holds.

    The single cached resolver (DESIGN.md §12): an explicit ``bsp``
    passes through untouched (caller pinned the plan); an ``sp`` or raw
    weight grid is sparsified once per distinct byte content (repeated
    calls with the same grid — chunked evaluation loops, serving — hit
    the cache); no handle at all yields the cached all-ones plan for
    series length ``T`` (plain DTW). Traced weight grids have no
    host-side plan — callers must route those through the dense backend
    (``resolve`` with ``TRACED_WEIGHTS``) instead of calling this.
    """
    if bsp is not None:
        return bsp
    if sp is None and weights is None:
        assert T is not None, "need one of sp / bsp / weights / T"
        if tile is None:
            return _ones_plan(T)
        return _cached_plan(np.ones((T, T), np.float32).tobytes(), T, tile)
    w = sp.weights if sp is not None else weights
    if is_traced(w):
        raise TypeError("traced weight grid has no host-side tile plan; "
                        "resolve the dense backend instead")
    w = np.asarray(w, np.float32)
    T = w.shape[0]
    return _cached_plan(w.tobytes(), T, tile or default_tile(T))


def plan_cache_stats() -> dict:
    """Hit/miss counters of the cached resolver (the fit-once evidence
    the dispatch-overhead benchmark reads)."""
    info = _cached_plan.cache_info()
    ones = _ones_plan.cache_info()
    return {"hits": info.hits + ones.hits,
            "misses": info.misses + ones.misses,
            "entries": info.currsize + ones.currsize}


def densify(bsp: BlockSparsePaths) -> np.ndarray:
    """Reassemble the dense (T, T) weight grid from the compressed
    blocks of a plan."""
    S = bsp.tile
    Ti = bsp.slot.shape[0]
    w = bsp.blocks[bsp.slot]                       # (Ti, Tj, S, S)
    return w.transpose(0, 2, 1, 3).reshape(Ti * S, Ti * S)


def resolve_dense_weights(sp=None, bsp=None, weights=None, T=None):
    """Dense (T, T) weight grid from whichever handle the caller holds
    (``densify`` reassembles it from a bare block plan; no handle at all
    yields all-ones for length ``T``)."""
    if sp is not None:
        return sp.weights
    if weights is not None:
        return weights
    if bsp is None:
        assert T is not None, "need one of sp / bsp / weights / T"
        return jnp.ones((T, T), jnp.float32)
    w = densify(bsp)
    return jnp.asarray(w if T is None else w[:T, :T])


# ---------------------------------------------------------------------------
# Multivariate (T, d) series layout for the block kernels
# ---------------------------------------------------------------------------

def series_dim(X) -> int:
    """Channel count d of a series batch: (N, T) -> 1, (N, T, d) -> d."""
    return int(X.shape[2]) if X.ndim == 3 else 1


def to_tile_major(X, S: int, Tp: int, n_to: Optional[int] = None,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Lay a series batch out tile-major / channel-inner for the kernels.

    X: (N, T) or (N, T, d) -> (n_to or N, (Tp // S) * d * S) f32, where
    channel k of tile ti occupies lanes [ti*d*S + k*S, ti*d*S + (k+1)*S).
    For d = 1 this is exactly the historical zero-pad to (N, Tp) — the
    univariate layout is unchanged bit for bit. Rows pad to ``n_to``
    (kernel batch alignment), time pads to ``Tp`` (the plan's padded
    grid edge). ``dtype`` sets the compute precision (f64 for the
    oracle-grade parity checks of the soft engines).
    """
    X = jnp.asarray(X, dtype)
    if X.ndim == 2:
        X = X[:, :, None]
    N, T, d = X.shape
    n_to = N if n_to is None else n_to
    Xp = jnp.pad(X, ((0, n_to - N), (0, Tp - T), (0, 0)))
    Ti = Tp // S
    return Xp.reshape(n_to, Ti, S, d).transpose(0, 1, 3, 2) \
             .reshape(n_to, Ti * d * S)


def to_tile_stack(X, S: int, Tp: int, n_to: Optional[int] = None
                  ) -> jnp.ndarray:
    """``to_tile_major`` with the tile index moved to the leading axis:
    (Tp // S, n_to or N, d*S). The Pallas kernels block this layout, so a
    tile is addressed on a leading axis and every block's lane extent is
    the whole d*S — no dynamic lane offset, which Mosaic cannot prove
    aligned for S < 128."""
    Xt = to_tile_major(X, S, Tp, n_to=n_to)
    return Xt.reshape(Xt.shape[0], Tp // S, -1).transpose(1, 0, 2)


def from_tile_major(G: jnp.ndarray, S: int, d: int, T: int,
                    squeeze: bool = True) -> jnp.ndarray:
    """Invert ``to_tile_major`` (for gradients laid out like the series):
    (N, Ti*d*S) -> (N, T, d), or (N, T) when d == 1 and ``squeeze``."""
    N = G.shape[0]
    Ti = G.shape[1] // (d * S)
    out = G.reshape(N, Ti, d, S).transpose(0, 1, 3, 2) \
           .reshape(N, Ti * S, d)[:, :T]
    return out[:, :, 0] if (d == 1 and squeeze) else out

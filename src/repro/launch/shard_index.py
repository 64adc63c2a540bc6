"""Sharded-corpus serving: partition the fitted index over a mesh axis
and tree-reduce a global top-k (DESIGN.md §15).

Every shard_map job before this module sharded *work* (query rows, pair
blocks) while replicating the whole corpus on every host — corpus
capacity was bounded by one chip's HBM. This module shards the *state*:
the per-candidate rows of a fitted ``SimilarityEngine``'s corpus index
(series, LB_Keogh envelopes, RWS sketch rows) are partitioned into
contiguous shards over a named mesh axis, queries are broadcast, each
shard runs the full lower-bound cascade + survivor DP against only its
candidates, and the per-shard winners are merged into a global top-k —
so corpus capacity scales with chips while answers stay bit-identical
to the single-host cascade.

Layout (``ShardedIndex``): shard s owns global corpus rows
``[offsets[s], offsets[s+1])`` (``np.array_split`` sizes — ragged by at
most one row). For the equal-block shard_map layout every shard pads to
the max shard size with copies of **global row 0** carrying global id 0.
Pads are real candidates, so no masking is needed anywhere in the
cascade, and they can never corrupt the answer: a pad's distance equals
(or, when abandoned early, upper-bounds) the distance of real row 0, so
whenever a pad wins its shard the true row-0 candidate wins shard 0
with the same distance and the smaller (equal) global id — the merge's
tie rule returns the real row.

Merge (``merge_topk``): gathered per-shard candidates are ordered by
ascending global id (one ``argsort``), then ``jax.lax.top_k`` on the
negated distances picks the k best — ``top_k`` resolves ties by the
earliest position, i.e. the smallest global id, which is exactly the
first-index tie rule of the single-host ``argmin``. Admissible bounds +
strict abandoning make every per-shard winner exact, so the merged
top-1 is bit-identical to the unsharded cascade (property-tested for
shard counts 1/2/4, ragged sizes and forced ties).

Two execution paths with identical arithmetic:

  * ``mesh`` — ``shard_map`` over a ("shard",) mesh: sharded operands
    split on the leading shard axis, queries replicated, one
    ``all_gather`` of the (S, B, k) winners, replicated merge. The
    backend is resolved with the ``SHARDED`` capability (scan/pallas;
    the dense oracle is host-only for serving).
  * ``host`` — an eager Python loop over ``engine.shard(S)`` slices
    (no pads needed); used when fewer devices than shards exist and by
    the property tests.

``python -m repro.launch.scenarios`` drives this under MLPerf-style
load; ``launch/search.py`` serves through it with ``shards > 0``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import SimilarityEngine
from repro.kernels import backends as bk


def shard_offsets(n: int, n_shards: int) -> np.ndarray:
    """Global row offsets of the contiguous shard partition: (S + 1,)
    with shard s covering rows [offsets[s], offsets[s+1]) —
    ``np.array_split`` sizing (ragged by at most one row)."""
    sizes = [len(ids) for ids in np.array_split(np.arange(n), n_shards)]
    return np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """Stacked, padded per-shard corpus state (the shard_map operand set).

    corpus:          (S, Nmax, T[, d]) corpus rows, shard-major; rows
                     past a shard's true size are copies of global row 0.
    gid:             (S, Nmax) int32 global corpus index of each row
                     (pads carry 0 — the id of the row they duplicate).
    env_lo, env_hi:  (S, Nmax, T[, d]) LB_Keogh candidate envelopes,
                     sliced from the fitted index (bit-identical to a
                     per-shard rebuild).
    sketch:          (S, Nmax, R) RWS sketch rows when the engine was
                     fit with ``sketch_r > 0``, else None.
    sizes, offsets:  true shard sizes (S,) and global offsets (S + 1,).
    """
    corpus: jnp.ndarray
    gid: jnp.ndarray
    env_lo: jnp.ndarray
    env_hi: jnp.ndarray
    sketch: Optional[jnp.ndarray]
    sizes: np.ndarray
    offsets: np.ndarray

    @property
    def n_shards(self) -> int:
        """Number of shards S (the mesh axis length)."""
        return int(self.corpus.shape[0])

    @property
    def n_max(self) -> int:
        """Padded per-shard candidate count."""
        return int(self.corpus.shape[1])

    @property
    def n_total(self) -> int:
        """True (unpadded) corpus size across all shards."""
        return int(self.sizes.sum())

    def balance(self) -> dict:
        """Shard-balance stats for the serving artifact: per-shard
        sizes, spread, and the padding overhead of the equal-block
        layout."""
        sizes = self.sizes.astype(np.float64)
        return {
            "n_shards": self.n_shards,
            "sizes": [int(s) for s in self.sizes],
            "min_size": int(sizes.min()), "max_size": int(sizes.max()),
            "imbalance": float(sizes.max() / sizes.mean()),
            "pad_frac": float(1.0 - sizes.sum()
                              / (self.n_shards * self.n_max)),
        }


def shard_corpus_state(engine: SimilarityEngine,
                       n_shards: int) -> ShardedIndex:
    """Partition a fitted engine's per-candidate index state into the
    stacked equal-block layout of ``ShardedIndex``.

    Contiguous ``np.array_split`` shards; every shard pads to the max
    shard size with copies of global row 0 (global id 0) — see the
    module docstring for why that padding is exact. The measure statics
    (weights, tile plan, support windows) are not stacked: they are
    shared by every shard and closed over by the search job.
    """
    index = engine.index
    assert index is not None, \
        "sharded serving needs an engine fit with a corpus index"
    n = index.size
    S = max(1, min(int(n_shards), n))
    offs = shard_offsets(n, S)
    sizes = np.diff(offs)
    n_max = int(sizes.max())

    def stack(a):
        a = jnp.asarray(a)
        rows = []
        for s in range(S):
            blk = a[int(offs[s]):int(offs[s + 1])]
            pad = n_max - blk.shape[0]
            if pad:
                blk = jnp.concatenate(
                    [blk, jnp.broadcast_to(a[0:1], (pad,) + a.shape[1:])])
            rows.append(blk)
        return jnp.stack(rows)

    gid_rows = []
    for s in range(S):
        g = np.arange(int(offs[s]), int(offs[s + 1]), dtype=np.int32)
        gid_rows.append(np.pad(g, (0, n_max - len(g))))   # pads -> id 0
    return ShardedIndex(
        corpus=stack(index.corpus), gid=jnp.asarray(np.stack(gid_rows)),
        env_lo=stack(index.env_lo), env_hi=stack(index.env_hi),
        sketch=None if index.sketch is None else stack(index.sketch.sketch),
        sizes=sizes, offsets=offs)


# ---------------------------------------------------------------------------
# Per-shard search + global merge
# ---------------------------------------------------------------------------

def local_topk(Q: jnp.ndarray, index, k: int, *, impl: str = "auto",
               seed_k: int = 2, prefix_frac: float = 0.5,
               block_a: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k of one shard: (B, T[, d]) queries vs the shard's index.

    k = 1 runs the exact lower-bound cascade (bounds → seed DPs →
    survivor DP with early abandoning — the 1-NN serving path);
    k > 1 runs the fused masked Gram and ``lax.top_k`` (exact values,
    no bound pruning). Returns (dists, local_ids), both (B, k); ties
    resolve to the lowest local index, matching ``argmin``.
    """
    from repro.kernels import ops
    if k == 1:
        nn, nnd = ops._knn_cascade(Q, index, impl=impl, seed_k=seed_k,
                                   prefix_frac=prefix_frac,
                                   block_a=block_a)
        return nnd[:, None], nn[:, None]
    D = ops._spdtw_gram(Q, index.corpus, bsp=index.bsp,
                        weights=index.weights, impl=impl, block_a=block_a)
    neg, ids = jax.lax.top_k(-D, int(min(k, D.shape[1])))
    return -neg, ids.astype(jnp.int32)


def merge_topk(dists: jnp.ndarray, gids: jnp.ndarray,
               k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Tree-reduce the gathered per-shard candidates to the global top-k.

    dists/gids: (B, M) candidate distances and global corpus ids (M =
    S * k). Candidates are first ordered by ascending global id, then
    ``jax.lax.top_k`` on the negated distances picks the k best —
    ``top_k`` breaks ties by the earliest position, i.e. the smallest
    global id, which is the single-host ``argmin`` first-index rule.
    No ``psum`` anywhere: the reduction is one gather + one sort + one
    top_k. Returns (gids, dists), both (B, k), ascending distance.
    """
    ordg = jnp.argsort(gids, axis=1)
    dg = jnp.take_along_axis(dists, ordg, axis=1)
    gg = jnp.take_along_axis(gids, ordg, axis=1)
    neg, pos = jax.lax.top_k(-dg, int(min(k, dists.shape[1])))
    return jnp.take_along_axis(gg, pos, axis=1), -neg


def sharded_knn_job(engine: SimilarityEngine, mesh, *, axis: str = "shard",
                    k: int = 1, impl: str = "auto", seed_k: int = 2,
                    prefix_frac: float = 0.5):
    """Build the jitted shard_map search job for a fitted engine.

    Operands: replicated queries + the stacked ``ShardedIndex`` arrays
    split on the leading shard axis. Each shard reassembles a local
    ``CorpusIndex`` view (statics closed over from the fitted engine,
    per-candidate rows from its operand block), runs ``local_topk``,
    maps local winners to global ids, all_gathers the (S, B, k)
    winners and computes the replicated global merge. The backend is
    resolved under the ``SHARDED`` capability — the cascade must trace
    under shard_map (scan / pallas; the dense oracle raises).
    """
    bk.resolve(impl, require=(bk.SHARDED,))
    base = engine.index
    assert base is not None, \
        "sharded serving needs an engine fit with a corpus index"

    def local(q, cs, gid, elo, ehi):
        cs, gid, elo, ehi = cs[0], gid[0], elo[0], ehi[0]
        idx = dataclasses.replace(base, corpus=cs, env_lo=elo, env_hi=ehi,
                                  sketch=None)
        d_loc, i_loc = local_topk(q, idx, k, impl=impl, seed_k=seed_k,
                                  prefix_frac=prefix_frac)
        g_loc = jnp.take(gid, i_loc)                       # (B, k)
        all_d = jax.lax.all_gather(d_loc, axis)            # (S, B, k)
        all_g = jax.lax.all_gather(g_loc, axis)
        B = q.shape[0]
        dists = jnp.moveaxis(all_d, 0, 1).reshape(B, -1)
        gids = jnp.moveaxis(all_g, 0, 1).reshape(B, -1)
        return merge_topk(dists, gids, k)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(fn)


class ShardedSearch:
    """Sharded 1-NN serving over a fitted ``SimilarityEngine``.

    Partitions the engine's corpus state into ``n_shards`` shards and
    answers ``knn`` queries through the per-shard cascade + global
    top-k merge. When the process has at least ``n_shards`` devices the
    shard_map mesh path runs (state device-placed once at construction,
    shard axis named ``"shard"``); otherwise an eager host loop over
    the sliced shard engines computes the same merge — identical
    per-shard machinery either way, so both paths return the
    single-host cascade's answers (see module docstring).
    """

    def __init__(self, engine: SimilarityEngine, n_shards: int, *,
                 k: int = 1, impl: str = "auto", seed_k: int = 2,
                 prefix_frac: float = 0.5, use_mesh: Optional[bool] = None):
        bk.resolve(impl, require=(bk.SHARDED,))
        assert engine.index is not None, \
            "sharded serving needs an engine fit with a corpus index"
        self.engine = engine
        self.k = int(k)
        self.impl = impl
        self.seed_k = seed_k
        self.prefix_frac = prefix_frac
        self.shidx = shard_corpus_state(engine, n_shards)
        S = self.shidx.n_shards
        if use_mesh is None:
            use_mesh = S > 1 and jax.device_count() >= S
        self.mesh = None
        self._job = None
        self._placed = None
        self._shard_engines: Optional[Tuple[SimilarityEngine, ...]] = None
        if use_mesh:
            assert jax.device_count() >= S, \
                f"mesh path needs >= {S} devices, have {jax.device_count()}"
            self.mesh = jax.sharding.Mesh(
                np.asarray(jax.devices()[:S]), ("shard",))
            self._job = sharded_knn_job(
                engine, self.mesh, k=self.k, impl=impl, seed_k=seed_k,
                prefix_frac=prefix_frac)
            sh = NamedSharding(self.mesh, P("shard"))
            self._placed = tuple(
                jax.device_put(a, sh) for a in
                (self.shidx.corpus, self.shidx.gid,
                 self.shidx.env_lo, self.shidx.env_hi))
        else:
            self._shard_engines = engine.shard(S)

    @property
    def n_shards(self) -> int:
        """Number of corpus shards."""
        return self.shidx.n_shards

    @property
    def path(self) -> str:
        """Which execution path serves: "mesh" (shard_map) or "host"."""
        return "mesh" if self._job is not None else "host"

    def balance(self) -> dict:
        """Shard-balance stats (sizes, imbalance, pad fraction) plus
        the execution path — the serving artifact's shard story."""
        out = self.shidx.balance()
        out["path"] = self.path
        return out

    def knn(self, Q) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Global top-k over all shards: (B, T[, d]) -> (nn, dist),
        each (B,) when k == 1 else (B, k). Bit-identical top-1 to the
        single-host cascade (admissible per-shard bounds + the
        smallest-global-id merge tie rule)."""
        Q = jnp.asarray(Q, jnp.float32)
        if self._job is not None:
            g, d = self._job(Q, *self._placed)
        else:
            ds, gs = [], []
            for s, eng in enumerate(self._shard_engines):
                d_loc, i_loc = local_topk(
                    Q, eng.index, self.k, impl=self.impl,
                    seed_k=self.seed_k, prefix_frac=self.prefix_frac)
                ds.append(d_loc)
                gs.append(i_loc.astype(jnp.int32)
                          + jnp.int32(self.shidx.offsets[s]))
            g, d = merge_topk(jnp.concatenate(ds, axis=1),
                              jnp.concatenate(gs, axis=1), self.k)
        if self.k == 1:
            return g[:, 0], d[:, 0]
        return g, d

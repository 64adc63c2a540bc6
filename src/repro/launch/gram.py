"""Distributed SP-DTW / K_rdtw Gram-matrix job (the paper's production
workload: 1-NN and SVM need all-pairs (dis)similarity over big series sets).

shard_map over the flattened ("pod","data","model") device grid: the N x M
pair-block matrix is tiled row-wise across every chip; each chip runs the
**fused block-sparse Gram engine** (``repro.kernels.gram_block``) over its
row stripe against the full (replicated) second set — the Pallas
(A-tile, B-tile, active-tile) kernel on TPU, the active-tile jnp scan
elsewhere. The historical ``jnp.repeat``/``jnp.tile`` pair expansion is
gone: per-chip work is rows * M * n_active_tiles * S^2 and HBM holds only
the two series sets. The sparsification meta (active bitmap, tile schedule,
compressed weight blocks) is resolved host-side once per job and closed
over as constants. One all_gather reassembles the Gram matrix; work is
embarrassingly parallel, so the roofline is pure compute.

``--dryrun`` lowers + compiles the job on the 512-chip production mesh
(ShapeDtypeStructs only).

``--mode knn`` swaps the all-pairs Gram for the exact-1-NN cascade
(``kernels.ops.knn_cascade``): queries are sharded row-wise, each chip
bounds-prunes its query stripe against the replicated corpus and only the
survivors reach the fused masked DP — the classification/serving workload
inherits the cascade's pruning with the same shard layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.dtw import band_mask
from repro.core.engine import engine_for


def gram_job(mesh, weights, kind: str = "spdtw", nu: float = 1.0,
             tile: int | None = None, impl: str = "auto"):
    """Build the jitted distributed Gram computation for the given mesh.

    ``weights`` is a concrete host-side (T, T) array (the learned SP grid
    or a corridor mask): the engine is fitted here, outside the trace, so
    its block-sparse plan exists before tracing and is closed over as a
    constant — each chip then runs ``engine.gram`` on its row stripe.
    """
    axes = tuple(mesh.axis_names)
    w = np.asarray(weights, np.float32)
    eng = engine_for(kind, weights=None if kind == "dtw" else w, nu=nu,
                     tile=tile, T=w.shape[0])

    def local(xs, ys):
        if eng.is_kernel:
            # kernel kinds report raw *log-kernel* values (the SVM
            # workload's input), not the negated dissimilarity
            return eng.gram_log(xs, ys, impl=impl, block_a=xs.shape[0])
        return eng.gram(xs, ys, impl=impl, block_a=xs.shape[0])

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=P(axes, None),
        check_vma=False)
    return jax.jit(fn)


def knn_job(mesh, weights, kind: str = "spdtw", impl: str = "auto",
            seed_k: int = 2, prefix_frac: float = 0.5):
    """Build the jitted distributed exact-1-NN cascade for the given mesh.

    Queries shard row-wise; the corpus replicates. The whole cascade
    (bounds, seeds, survivor DP) is traceable because the engine's
    static parts (support grid, tile plan) are fitted from the host-side
    ``weights`` here, outside the trace; the corpus-dependent parts
    (envelopes) are pure jnp, so ``fit`` runs per-shard on the traced
    corpus stripe reusing the closed-over support.

    Only the dissimilarity kinds have admissible bounds — the kernel
    measures (sp_krdtw etc.) must take the full Gram job.
    """
    if kind not in ("dtw", "spdtw"):
        raise ValueError(f"knn cascade has no admissible bounds for "
                         f"{kind!r}; use mode='gram'")
    axes = tuple(mesh.axis_names)
    w = np.asarray(weights, np.float32)
    base = engine_for(kind, weights=None if kind == "dtw" else w,
                      T=w.shape[0])

    def local(qs, cs):
        eng = base.with_corpus(cs)
        nn, dist = eng.knn(qs, impl=impl, seed_k=seed_k,
                           prefix_frac=prefix_frac)
        return nn, dist

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=(P(axes), P(axes)),
        check_vma=False)
    return jax.jit(fn)


def run(n: int = 64, t: int = 64, kind: str = "spdtw",
        dryrun: bool = False, mesh=None, mode: str = "gram"):
    if mesh is None:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(jax.device_count(), 1)
    n_dev = mesh.size
    n = ((n + n_dev - 1) // n_dev) * n_dev   # pad rows to device count
    w = np.asarray(band_mask(t, t, max(t // 8, 1)), np.float32)
    with jax.set_mesh(mesh):
        if mode == "knn":
            job = knn_job(mesh, w, kind=kind)
        else:
            job = gram_job(mesh, w, kind=kind)
        if dryrun:
            xs = jax.ShapeDtypeStruct((n, t), jnp.float32)
            ys = jax.ShapeDtypeStruct((n, t), jnp.float32)
            sh = (NamedSharding(mesh, P(tuple(mesh.axis_names), None)),
                  NamedSharding(mesh, P(None, None)))
            lowered = jax.jit(job.__wrapped__, in_shardings=sh).lower(xs, ys)
            compiled = lowered.compile()
            ca = compiled.cost_analysis() or {}
            if isinstance(ca, list):     # jax 0.4.x: one dict per module
                ca = ca[0] if ca else {}
            ma = compiled.memory_analysis()
            return {"mode": mode,
                    "flops_per_device": float(ca.get("flops", 0.0)),
                    "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
                    "temp_bytes": ma.temp_size_in_bytes,
                    "devices": n_dev, "pairs": n * n}
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.normal(size=(n, t)).astype(np.float32))
        if mode == "knn":
            nn, dist = job(X, X)
            return np.asarray(nn), np.asarray(dist)
        G = job(X, X)
        return np.asarray(G)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--t", type=int, default=128)
    ap.add_argument("--kind", default="spdtw",
                    choices=("spdtw", "dtw", "sp_krdtw"))
    ap.add_argument("--mode", default="gram", choices=("gram", "knn"))
    args = ap.parse_args()
    if args.dryrun:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        out = run(args.n, args.t, args.kind, dryrun=True, mesh=mesh,
                  mode=args.mode)
    else:
        out = run(args.n, args.t, args.kind, mode=args.mode)
        if args.mode == "knn":
            nn, dist = out
            out = {"queries": nn.shape[0],
                   "self_match": float(np.mean(nn == np.arange(len(nn))))}
        else:
            out = {"shape": out.shape, "sym_err": float(
                np.abs(out - out.T).max())}
    print(out)

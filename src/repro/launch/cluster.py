"""Distributed soft-SP-DTW centroid fitting (DESIGN.md §10, §11).

Barycenter fitting is embarrassingly parallel over centroids, so the job
mirrors ``launch/gram.py``: shard_map over the flattened mesh axes with
the centroid stripe (k, T) row-sharded, the member set X (N, T) and the
(k, N) assignment-weight matrix riding along (weights sharded with the
centroids). Each chip runs the full Adam loop
(``cluster.barycenter.soft_barycenter``: block-sparse active-tile stash
forward, reverse active-tile expected-alignment backward, Adam) on its
centroid rows — no cross-chip communication at all until the final
all-gather of the fitted stripe, and per-step work on both passes
proportional to the learned support.
The learned weight grid is resolved host-side once per job and closed
over as a constant, exactly like the Gram job; ``--dryrun`` lowers +
compiles on the 512-chip production mesh from ShapeDtypeStructs only.

  PYTHONPATH=src python -m repro.launch.cluster --k 8 --n 64 --t 64
  PYTHONPATH=src python -m repro.launch.cluster --dryrun --multi-pod
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.dtw import band_mask


def cluster_job(mesh, weights, gamma: float = 0.1, *, steps: int = 30,
                lr: float = 0.05):
    """Build the jitted distributed barycenter-fitting computation.

    The returned function maps (Z0 (k, T) initial centroids, X (N, T)
    members, A (k, N) non-negative member weights) to (Z (k, T) fitted
    centroids, final per-centroid loss (k,)). k must divide the mesh
    size; all-zero A rows (padding centroids) come back untouched.
    """
    axes = tuple(mesh.axis_names)
    w = np.asarray(weights, np.float32)
    # fit once, host-side: the engine's plan is a compile-time constant
    # closed over by the sharded loop (DESIGN.md §12)
    from repro.core.engine import engine_for
    eng = engine_for("spdtw", weights=w, gamma=gamma)

    def local(Z0, X, A):
        def fit_one(z0, a):
            z, losses = eng.barycenter(X, init=z0, steps=steps, lr=lr,
                                       sample_weights=a)
            return z, losses[-1]

        return jax.vmap(fit_one)(Z0, A)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(axes, None)),
        out_specs=(P(axes, None), P(axes)),
        check_vma=False)
    return jax.jit(fn)


def run(k: int = 8, n: int = 64, t: int = 64, gamma: float = 0.1,
        steps: int = 20, dryrun: bool = False, mesh=None):
    if mesh is None:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(jax.device_count(), 1)
    n_dev = mesh.size
    k = ((k + n_dev - 1) // n_dev) * n_dev   # pad centroids to device count
    w = np.asarray(band_mask(t, t, max(t // 8, 1)), np.float32)
    with jax.set_mesh(mesh):
        job = cluster_job(mesh, w, gamma, steps=steps)
        if dryrun:
            Z0 = jax.ShapeDtypeStruct((k, t), jnp.float32)
            X = jax.ShapeDtypeStruct((n, t), jnp.float32)
            A = jax.ShapeDtypeStruct((k, n), jnp.float32)
            sh = (NamedSharding(mesh, P(tuple(mesh.axis_names), None)),
                  NamedSharding(mesh, P(None, None)),
                  NamedSharding(mesh, P(tuple(mesh.axis_names), None)))
            lowered = jax.jit(job.__wrapped__, in_shardings=sh).lower(
                Z0, X, A)
            compiled = lowered.compile()
            ca = compiled.cost_analysis() or {}
            if isinstance(ca, list):     # jax 0.4.x: one dict per module
                ca = ca[0] if ca else {}
            ma = compiled.memory_analysis()
            return {"mode": "cluster",
                    "flops_per_device": float(ca.get("flops", 0.0)),
                    "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
                    "temp_bytes": ma.temp_size_in_bytes,
                    "devices": n_dev, "centroids": k, "steps": steps}
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.normal(size=(n, t)).astype(np.float32))
        assign = rng.integers(0, k, size=n)
        A = jnp.asarray((assign[None, :] == np.arange(k)[:, None])
                        .astype(np.float32))
        Z0 = jnp.asarray(np.stack(
            [X[assign == c].mean(axis=0) if (assign == c).any()
             else np.zeros(t) for c in range(k)]).astype(np.float32))
        Z, loss = job(Z0, X, A)
        return np.asarray(Z), np.asarray(loss)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--k", type=int, default=512)
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--t", type=int, default=128)
    ap.add_argument("--gamma", type=float, default=0.1)
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    if args.dryrun:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        out = run(args.k, args.n, args.t, args.gamma, args.steps,
                  dryrun=True, mesh=mesh)
    else:
        Z, loss = run(args.k, args.n, args.t, args.gamma, args.steps)
        out = {"centroids": Z.shape, "mean_final_loss": float(loss.mean())}
    print(out)

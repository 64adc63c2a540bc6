"""Dense vs block-sparse soft-SP-DTW *backward* wall-clock (DESIGN.md §11).

PR 3 made SP-DTW differentiable but left the expected-alignment backward
on the masked-dense O(T^2) recursion per pair — barycenter fitting threw
away exactly the sparsification the paper is about. This benchmark times
the gradient of a barycenter-style loss (sum of aligned-pair soft
distances) both ways at equal outputs:

  * dense:  ``jax.grad`` through the vmapped core recursion
    (``core.softdtw.soft_wdtw`` custom VJP — the pre-PR-4 hot path);
  * sparse: ``jax.grad`` through ``kernels.soft_block.soft_spdtw_batch``
    (block-sparse stash forward + reverse active-tile sweep).

Per shape the sweep runs a ladder of supports with increasing *tile*
sparsity — fully dense, a Sakoe-Chiba corridor, the learned occupancy
support — so the artifact shows the backward wall-clock improving with
tile sparsity: the paper's "complexity linear in surviving cells" claim
extended to the gradient path. (Theta ladders at a fixed shape often
leave the tile bitmap unchanged — cell sparsity grows but no whole tile
dies — so the ladder varies the support family instead.) Timings are
medians over several jitted, block_until_ready'd calls (compile
excluded); the backwards are timed *directly* — the reverse active-tile
sweep on a precomputed L stash vs the jitted ``jax.vjp`` cotangent
application of the dense custom VJP on its saved residuals — no
grad-minus-forward subtraction, which is noise-dominated at ms scale.
End-to-end grad wall-clock (forward + backward) rides along.

Exactness: E-matrix parity of the reverse sweep against the dense
backward is asserted <= 1e-6 in f64 (the two are exact re-orderings of
the same recursion), and f32 gradient parity <= 1e-3 relative. Results
land in ``BENCH_softgrad.json`` at the repo root and in
``artifacts/bench`` via ``benchmarks.run``.
"""
from __future__ import annotations

import json
import os

import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _parity_check(T: int = 32, tile: int = 8, gamma: float = 0.3):
    """f64 E parity + f32 grad parity on a random sparse support."""
    import jax
    import jax.numpy as jnp
    from repro.core import SparsePaths, block_sparsify
    from repro.core.softdtw import soft_alignment, soft_wdtw
    from repro.kernels.soft_block import (soft_alignment_pairs,
                                          soft_spdtw_batch)

    rng = np.random.default_rng(0)
    sup = rng.random((T, T)) < 0.3
    sup |= np.eye(T, dtype=bool)
    w = np.where(sup, rng.uniform(0.5, 2.0, (T, T)), 0.0).astype(np.float32)
    sp = SparsePaths(weights=jnp.asarray(w), support=jnp.asarray(sup),
                     counts=jnp.asarray(w), theta=0.0, gamma=0.0)
    bsp = block_sparsify(sp, tile=tile)
    xs, ys = rng.normal(size=(4, T)), rng.normal(size=(4, T))
    with jax.enable_x64(True):
        x64, y64 = jnp.asarray(xs), jnp.asarray(ys)
        w64 = jnp.asarray(np.asarray(w, np.float64))
        Eb = np.asarray(soft_alignment_pairs(x64, y64, bsp, gamma,
                                             dtype=jnp.float64))
        Ed = np.stack([np.asarray(soft_alignment(x64[i], y64[i], w64, gamma))
                       for i in range(4)])
    e_parity = float(np.abs(Eb - Ed).max())
    assert e_parity <= 1e-6, f"E-matrix parity broke: {e_parity}"

    x = jnp.asarray(xs.astype(np.float32))
    y = jnp.asarray(ys.astype(np.float32))
    wj = jnp.asarray(w)
    g_blk = jax.grad(lambda a: jnp.sum(soft_spdtw_batch(a, y, wj, gamma)))(x)
    g_dns = jax.grad(lambda a: jnp.sum(jax.vmap(
        lambda u, v: soft_wdtw(u, v, wj, gamma))(a, y)))(x)
    scale = float(jnp.max(jnp.abs(g_dns))) or 1.0
    grad_rel = float(jnp.max(jnp.abs(g_blk - g_dns))) / scale
    assert grad_rel <= 1e-3, f"gradient parity broke: {grad_rel}"
    return e_parity, grad_rel


def _supports(T: int, learned_theta: float, smoke: bool):
    """Support ladder with increasing tile sparsity: dense -> corridor ->
    learned occupancy support."""
    import jax.numpy as jnp
    from repro.core import band_mask, learn_sparse_paths

    rng = np.random.default_rng(1)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    Xtr = jnp.asarray((base[None] + 0.3 * rng.normal(size=(16, T))
                       ).astype(np.float32))
    sp = learn_sparse_paths(Xtr, theta=learned_theta)
    ladder = [("dense", jnp.ones((T, T), jnp.float32)),
              ("band", jnp.asarray(band_mask(T, T, max(T // 6, 2)),
                                   jnp.float32)),
              ("learned", sp.weights)]
    return ladder[1:] if smoke else ladder


def _median_timer(fn, reps: int) -> float:
    """Median wall-clock of ``fn()`` after one warm-up call (the mean is
    too fragile for ms-scale kernels on shared CPU hosts)."""
    import statistics
    import time

    import jax

    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn())
        ts.append(time.time() - t0)
    return statistics.median(ts)


def _bench_shape(T: int, tile: int, B: int, gamma: float, reps: int,
                 smoke: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import block_sparsify
    from repro.core.softdtw import soft_wdtw
    from repro.kernels.soft_block import (soft_spdtw_batch,
                                          soft_spdtw_fwd_stash)

    rng = np.random.default_rng(2)
    base = np.sin(np.linspace(0, 3 * np.pi, T))
    x = jnp.asarray((base[None] + 0.5 * rng.normal(size=(B, T))
                     ).astype(np.float32))
    y = jnp.asarray((base[None] + 0.5 * rng.normal(size=(B, T))
                     ).astype(np.float32))

    rows = []
    for name, w in _supports(T, learned_theta=1.0, smoke=smoke):
        bsp = block_sparsify(np.asarray(w, np.float32), tile=tile)
        from repro.kernels.soft_block import soft_spdtw_bwd_block

        # backwards, timed directly at equal cotangents
        gbar = jnp.ones((B,), jnp.float32)
        _, stash = soft_spdtw_fwd_stash(x, y, bsp, gamma)
        _, dense_vjp = jax.vjp(lambda a, b: jax.vmap(
            lambda u, v: soft_wdtw(u, v, w, gamma))(a, b), x, y)
        dense_bwd = jax.jit(dense_vjp)
        d_b = _median_timer(lambda: dense_bwd(gbar), reps)
        s_b = _median_timer(
            lambda: soft_spdtw_bwd_block(x, y, bsp, gamma, stash, gbar),
            reps)

        # end-to-end grad wall-clock (what a barycenter step pays)
        dense_grad = jax.jit(jax.grad(lambda a, w=w: jnp.sum(jax.vmap(
            lambda u, v: soft_wdtw(u, v, w, gamma))(a, y))))
        sparse_grad = jax.jit(jax.grad(
            lambda a, w=w: jnp.sum(soft_spdtw_batch(a, y, w, gamma))))
        d_g = _median_timer(lambda: dense_grad(x), reps)
        s_g = _median_timer(lambda: sparse_grad(x), reps)

        rows.append({
            "support": name,
            "cells_fraction": float((np.asarray(w) > 0).mean()),
            "tile_sparsity": bsp.tile_sparsity,
            "active_tiles": bsp.n_active,
            "dense_bwd_s": d_b, "sparse_bwd_s": s_b,
            "dense_grad_s": d_g, "sparse_grad_s": s_g,
            "bwd_speedup": d_b / s_b,
            "grad_speedup": d_g / s_g,
        })
        print(f"[softgrad] T={T} tile={tile} {name}: tiles skipped "
              f"{100*bsp.tile_sparsity:.0f}%, backward dense "
              f"{d_b*1e3:.1f} ms vs sparse {s_b*1e3:.1f} ms "
              f"-> {d_b/s_b:.2f}x (grad {d_g/s_g:.2f}x)", flush=True)
    # sparser supports must not be slower (10% timing-noise slack)
    sparser_is_faster = all(
        rows[i + 1]["sparse_bwd_s"] <= rows[i]["sparse_bwd_s"] * 1.1
        for i in range(len(rows) - 1))
    return {"T": T, "tile": tile, "B": B, "gamma": gamma, "rows": rows,
            "learned_bwd_speedup": rows[-1]["bwd_speedup"],
            "sparser_is_faster": sparser_is_faster}


def run(fast: bool = True, reps: int = 5, smoke: bool = False):
    import jax

    if smoke:   # tiny CI shapes; BENCH_softgrad.json is left untouched
        shapes = [(32, 8, 8)]
        reps = 1
    elif fast:
        shapes = [(96, 16, 32), (128, 16, 32)]
    else:
        shapes = [(96, 16, 64), (128, 16, 64), (192, 16, 64)]

    e_parity, grad_rel = _parity_check()
    results = [_bench_shape(T, tile, B, gamma=0.1, reps=reps, smoke=smoke)
               for (T, tile, B) in shapes]
    out = {
        "backend": jax.default_backend(),
        "e_parity_f64": e_parity,
        "grad_rel_err_f32": grad_rel,
        "exact": True,
        "shapes": results,
        "min_bwd_speedup": min(s["learned_bwd_speedup"] for s in results),
    }
    if not smoke:
        assert all(s["sparser_is_faster"] for s in results), \
            "backward wall-clock must improve with tile sparsity"
        assert out["min_bwd_speedup"] > 1.0, \
            "block-sparse backward must beat the dense backward"
        with open(os.path.join(ROOT, "BENCH_softgrad.json"), "w") as f:
            json.dump(out, f, indent=1)
    print(f"[softgrad_speedup] learned-support backward speedup >= "
          f"{out['min_bwd_speedup']:.2f}x (E parity f64 {e_parity:.1e}, "
          f"grad rel err f32 {grad_rel:.1e})", flush=True)
    return out


def main(fast: bool = True):
    out = run(fast=fast)
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()

"""Expert-parallel Mixture-of-Experts with explicit all-to-all dispatch.

GShard-style one-hot dispatch einsums waste 2*N*E*C*d FLOPs on what is
really data movement, and leave the collective pattern to the SPMD
partitioner. Here the MoE layer is a shard_map over the whole mesh
(DESIGN.md §5):

  * tokens ride the ("pod","data") axes (DP),
  * the expert dimension E is sharded over "data" (EP = the axis the tokens
    already live on, so dispatch is a *within-axis* all_to_all),
  * each expert's FFN inner dim is sharded over "model" (TP inside the
    expert), closed by one psum after the combine,
  * experts are replicated over "pod" (pure DP across pods).

Per MoE layer the collective schedule is exactly: all_to_all (dispatch),
psum over model (TP reduction), all_to_all (return). Capacity-factor
semantics: tokens past C = cf * k * N_loc / E drop (standard GShard).

For tiny token counts (single-token decode) the layer falls back to fully
local replicated compute (ep_axis=None) — dispatch would cost more than it
saves.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def _router(x, w_router, top_k: int):
    """x: (N, d) -> (ids (N, k), weights (N, k), aux load-balance loss)."""
    logits = (x @ w_router).astype(jnp.float32)           # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * <f_e * p_e>
    E = w_router.shape[1]
    fe = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    pe = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(fe * pe)
    return ids, w.astype(x.dtype), aux


def _pack(x, ids, n_experts: int, capacity: int):
    """Build the (E, C, d) send buffer + combine metadata. All local.

    slot[i, j] is the row inside expert ids[i, j]'s capacity block; tokens
    past capacity drop.
    """
    N, k = ids.shape
    flat_ids = ids.reshape(-1)                             # (N*k,)
    onehot = jax.nn.one_hot(flat_ids, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                   # arrival order
    slot = jnp.take_along_axis(pos, flat_ids[:, None], axis=1)[:, 0]
    valid = slot < capacity
    dest = jnp.where(valid, flat_ids * capacity + slot, n_experts * capacity)
    # scatter token *indices* (4 bytes) rather than token rows (d floats):
    # the row movement happens in one gather, which keeps the scatter's
    # temp buffers O(E*C) instead of O(E*C*d)
    tok_idx = jnp.repeat(jnp.arange(N), k)
    buf_idx = jnp.full((n_experts * capacity + 1,), N, jnp.int32)
    buf_idx = buf_idx.at[dest].set(tok_idx, mode="drop")[:-1]
    xz = jnp.concatenate([x, jnp.zeros((1, x.shape[-1]), x.dtype)], axis=0)
    buf = xz[buf_idx]                                      # (E*C, d)
    return (buf.reshape(n_experts, capacity, -1),
            slot.reshape(N, k), valid.reshape(N, k))


def _expert_ffn(xe, w_gate, w_up, w_down):
    """xe: (E_loc, C_tot, d); weights (E_loc, d, ff_loc) / (E_loc, ff_loc, d).

    ff is model-sharded, so the result is a *partial* sum closed by the
    caller's psum.
    """
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_ffn(x: jnp.ndarray, params: dict, *, n_experts: int, top_k: int,
            capacity_factor: float, mesh=None,
            ep_axis: Optional[str] = "data",
            tp_axis: Optional[str] = "model") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE FFN. x: (B, S, d) -> (out, aux_loss (scalar)).

    params: router (d, E), gate/up (E, d, ff), down (E, ff, d).
    Sharding: gate/up/down P(ep, None, tp)/(ep, tp, None); router replicated.
    ep_axis=None => fully local fallback.
    """
    B, S, d = x.shape
    xf = x.reshape(B * S, d)

    def run(xl, router, wg, wu, wd, n_data: int, e_div: int):
        N_loc = xl.shape[0]
        e_loc = n_experts // e_div
        ids, wts, aux = _router(xl, router, top_k)
        cap = int(max(8, round(capacity_factor * top_k * N_loc / n_experts)))
        buf, slot, valid = _pack(xl, ids, n_experts, cap)
        if ep_axis is not None:
            buf = buf.reshape(n_data, e_loc, cap, d)
            buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            # axis 0 = source data shard; my e_loc experts see all shards
            buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, n_data * cap, d)
        else:
            buf = buf.reshape(e_loc, cap, d)
        ye = _expert_ffn(buf, wg, wu, wd)        # partial over tp_axis
        if ep_axis is not None:
            ye = ye.reshape(e_loc, n_data, cap, d).transpose(1, 0, 2, 3)
            ye = jax.lax.all_to_all(ye, ep_axis, split_axis=0,
                                    concat_axis=0, tiled=False)
            ye = ye.reshape(n_experts * cap, d)
        else:
            ye = ye.reshape(n_experts * cap, d)
        # combine (linear in ye, so the TP psum can come after it)
        flat_ids = ids.reshape(-1)
        rows = jnp.where(valid.reshape(-1),
                         flat_ids * cap + slot.reshape(-1), 0)
        g = ye[rows]
        g = jnp.where(valid.reshape(-1)[:, None], g, 0.0)
        out = jnp.sum(g.reshape(N_loc, top_k, d) * wts[..., None], axis=1)
        if ep_axis is not None and tp_axis is not None:
            out = jax.lax.psum(out, tp_axis)
        return out.astype(xl.dtype), aux[None]

    if ep_axis is None:
        out, aux = run(xf, params["router"], params["gate"], params["up"],
                       params["down"], 1, 1)
        return out.reshape(B, S, d), jnp.mean(aux)

    dp_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    n_data = mesh.shape[ep_axis]
    # jax.checkpoint INSIDE the shard_map body: the outer scan-level remat
    # does not reach through shard_map, so without this every group's
    # dispatch/gather buffers (~.25 GB each) survive to the backward pass
    fn = jax.shard_map(
        jax.checkpoint(
            lambda xl, r, wg, wu, wd: run(xl, r, wg, wu, wd, n_data,
                                          n_data)),
        mesh=mesh,
        in_specs=(P(dp_axes, None), P(None, None),
                  P(ep_axis, None, tp_axis), P(ep_axis, None, tp_axis),
                  P(ep_axis, tp_axis, None)),
        out_specs=(P(dp_axes, None), P(dp_axes)),
        check_vma=False)
    out, aux = fn(xf, params["router"], params["gate"], params["up"],
                  params["down"])
    return out.reshape(B, S, d), jnp.mean(aux)

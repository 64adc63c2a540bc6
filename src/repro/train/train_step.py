"""Jitted train/serve step factories with microbatching and optional
int8-compressed cross-pod gradient all-reduce.

train_step(params, opt_state, batch) -> (params, opt_state, metrics)

  * microbatch > 1: grad accumulation via lax.scan over batch slices
    (f32 accumulators) — activation memory / pipeline-bubble lever;
  * grad_compression="int8_pod": per-pod partial gradients are quantized to
    int8 (per-leaf absmax scale), psum'd over the slow cross-pod links,
    and dequantized — shard_map manual over "pod" only, everything else
    stays under the SPMD partitioner (DESIGN.md §5). Bounded relative
    error, validated in tests/test_train.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import Ctx
from .optimizer import AdamW


def _int8_psum(tree, axis: str):
    """Quantize -> integer psum -> dequantize, per leaf."""
    def one(g):
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int32)
        qsum = jax.lax.psum(q, axis)
        ssum = jax.lax.pmax(scale, axis)  # shared scale: conservative max
        # correction: each pod quantized with its own scale; re-quantize with
        # the shared scale for exactness of the sum semantics
        q2 = jnp.clip(jnp.round(g / ssum), -127, 127).astype(jnp.int32)
        qsum = jax.lax.psum(q2, axis)
        return (qsum.astype(jnp.float32) * ssum).astype(g.dtype)
    return jax.tree.map(one, tree)


def make_train_step(api, mesh, opt: AdamW, *, microbatch: int = 1,
                    grad_compression: Optional[str] = None,
                    donate: bool = True, accum_pspecs=None,
                    grad_sync: str = "per_microbatch"):
    """grad_sync="deferred": microbatch gradients accumulate as *unreduced
    per-data-shard partials* inside a shard_map over the DP axes and cross
    the wire once per step instead of once per microbatch (§Perf H2).
    Requires params replicated over "data" (i.e. non-EP archs)."""
    cfg = api.cfg
    ctx = Ctx(mesh)

    def loss_fn(params, batch):
        return api.train_loss(params, batch, ctx)

    if accum_pspecs is not None and mesh is not None:
        from repro.launch.shapes import specs_to_shardings
        accum_sh = specs_to_shardings(accum_pspecs, mesh)
    else:
        accum_sh = None

    def cst_accum(tree):
        # ZeRO-2-ish: reduce-scatter each microbatch's bf16 grads into
        # data-sharded f32 accumulators (memory and wire halved vs naive
        # f32 all-reduced accumulation)
        if accum_sh is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree, accum_sh)

    def grads_of(params, batch):
        if microbatch == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def mb_slice(b, i):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(
                    x, i * (x.shape[0] // microbatch),
                    x.shape[0] // microbatch, axis=0), b)

        def body(carry, i):
            acc, ltot = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb_slice(batch, i))
            g = cst_accum(g)
            acc = jax.tree.map(
                lambda a, gg: a + gg.astype(jnp.float32), acc, g)
            return (cst_accum(acc), ltot + l), None

        zeros = cst_accum(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (g, ltot), _ = jax.lax.scan(
            body, (zeros, jnp.float32(0)), jnp.arange(microbatch))
        g = jax.tree.map(lambda x: x / microbatch, g)
        return ltot / microbatch, g

    def grads_deferred(params, batch):
        dp = ctx.dp
        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]

        def per_shard(params, local_batch):
            # local microbatch accumulation; the model axis stays under the
            # SPMD partitioner (auto), so TP psums still happen inside
            def mb_slice(b, i):
                return jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * (x.shape[0] // microbatch),
                        x.shape[0] // microbatch, axis=0), b)

            def body(carry, i):
                acc, ltot = carry
                l, g = jax.value_and_grad(loss_fn)(
                    params, mb_slice(local_batch, i))
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), acc, g)
                return (acc, ltot + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g, ltot), _ = jax.lax.scan(
                body, (zeros, jnp.float32(0)), jnp.arange(microbatch))
            # THE one cross-data sync per step (optionally int8-compressed)
            if grad_compression == "int8":
                g = _int8_psum(g, dp)
            else:
                g = jax.tree.map(lambda x: jax.lax.psum(x, dp), g)
            g = jax.tree.map(lambda x: x / (microbatch * n_dp), g)
            loss = jax.lax.pmean(ltot / microbatch, dp)
            return loss, g

        return jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      jax.tree.map(lambda _: P(ctx.dp), batch)),
            out_specs=(P(), jax.tree.map(lambda _: P(), params)),
            check_vma=False,
            axis_names=frozenset(ctx.dp))(params, batch)

    def step(params, opt_state, batch):
        if grad_sync == "deferred":
            loss, grads = grads_deferred(params, batch)
            new_params, new_opt = opt.update(grads, opt_state, params)
            gnorm = jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads)))
            return new_params, new_opt, {"loss": loss, "grad_norm": gnorm}
        if grad_compression == "int8_pod" and "pod" in mesh.axis_names:
            # manual over "pod": per-pod partial grads -> int8 psum
            def pod_grads(params, batch):
                loss, g = grads_of(params, batch)
                g = _int8_psum(g, "pod")
                loss = jax.lax.pmean(loss, "pod")
                return loss, g

            pspecs = api.param_pspecs()
            from repro.launch.shapes import specs_to_shardings  # noqa
            loss, grads = jax.shard_map(
                pod_grads, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), params),
                          jax.tree.map(lambda _: P("pod"), batch)),
                out_specs=(P(), jax.tree.map(lambda _: P(), params)),
                check_vma=False,
                axis_names=frozenset({"pod"}))(params, batch)
        else:
            loss, grads = grads_of(params, batch)
        new_params, new_opt = opt.update(grads, opt_state, params)
        gnorm = jnp.sqrt(sum(
            jnp.sum(g.astype(jnp.float32) ** 2)
            for g in jax.tree.leaves(grads)))
        return new_params, new_opt, {"loss": loss, "grad_norm": gnorm}

    donate_args = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_args)


def make_serve_step(api, mesh, *, greedy: bool = True):
    """One decode step: (params, cache, token, pos) -> (next_token, cache)."""
    ctx = Ctx(mesh)

    def step(params, cache, token, pos):
        logits, new_cache = api.decode_step(params, cache, token, pos, ctx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return nxt, new_cache

    return jax.jit(step, donate_argnums=(1,))


def make_prefill(api, mesh, S_cache: int):
    ctx = Ctx(mesh)
    return jax.jit(lambda params, batch: api.prefill(params, batch, ctx,
                                                     S_cache))

"""Soft-SP-DTW barycenter averaging (DESIGN.md §10, §11).

A barycenter under the smoothed sparsified measure is the minimizer of

    F(z) = sum_b a_b * soft_spdtw(z, x_b) / sum_b a_b

over the member set {x_b} with non-negative member weights a_b. F is
differentiable through the custom VJP of the measure layer
(``kernels.soft_block.soft_spdtw_batch``): the forward runs the
block-sparse active-tile scan *stashing per-tile L blocks*, and the
backward walks the cached tile plan in reverse (the expected-alignment
sweep of DESIGN.md §11) — both passes scale with active tiles, so every
Adam step of the fit pays work proportional to the learned support, not
O(T^2). The centroid is fitted by plain first-order optimization — Adam
(``adam_init`` / ``adam_update`` below: f32 parameters, no weight decay),
``lax.scan`` over steps. Everything here is pure and traceable:
``soft_barycenter`` runs unchanged inside jit / vmap / shard_map (the
sharded fitting job in ``launch/cluster.py`` vmaps it over a centroid
stripe), provided the weight grid is a host-concrete compile-time
artifact — which the learned support always is (DESIGN.md §2).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.soft_block import soft_spdtw_batch

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8


class AdamState(NamedTuple):
    """Adam's step count and first / second moments (pytrees like the
    parameters)."""
    step: jnp.ndarray
    m: Any
    v: Any


def adam_init(params) -> AdamState:
    """Zero moments for a pytree of f32 ``params``."""
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(jnp.zeros((), jnp.int32), zeros, zeros)


def adam_update(grads, state: AdamState, params, lr: float):
    """One bias-corrected Adam step (b1 0.9, b2 0.95, eps 1e-8, no
    weight decay). Returns (new params, new state)."""
    step = state.step + 1
    b1c = 1.0 - ADAM_B1 ** step.astype(jnp.float32)
    b2c = 1.0 - ADAM_B2 ** step.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                     state.m, grads)
    v = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                     state.v, grads)
    new = jax.tree.map(
        lambda p, m, v: p - lr * ((m / b1c) / (jnp.sqrt(v / b2c) + ADAM_EPS)),
        params, m, v)
    return new, AdamState(step, m, v)


def barycenter_loss(z: jnp.ndarray, X: jnp.ndarray, weights: jnp.ndarray,
                    gamma: float,
                    sample_weights: Optional[jnp.ndarray] = None
                    ) -> jnp.ndarray:
    """Weighted mean soft-SP-DTW from candidate centroid ``z`` (T,) to the
    member set ``X`` (B, T); ``weights`` is the learned (T, T) grid and
    must stay host-concrete for the block-sparse passes to engage. An
    all-zero ``sample_weights`` row (a padding centroid in the sharded
    job) yields loss 0 with zero gradient. Returns a scalar."""
    zb = jnp.broadcast_to(z, X.shape)
    d = soft_spdtw_batch(zb, X, weights, float(gamma))
    if sample_weights is None:
        return jnp.mean(d)
    sw = sample_weights.astype(d.dtype)
    return jnp.sum(d * sw) / jnp.maximum(jnp.sum(sw), 1e-8)


def soft_barycenter(X: jnp.ndarray, weights: jnp.ndarray, gamma: float = 0.1,
                    *, init: Optional[jnp.ndarray] = None, steps: int = 100,
                    lr: float = 0.05,
                    sample_weights: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fit one barycenter by Adam on the soft-SP-DTW VJP.

    X: (B, T) members; ``init`` defaults to the (weighted) Euclidean
    mean; ``weights`` is the learned (T, T) grid (keep it host-concrete:
    a traced grid silently falls back to the dense O(T^2) backward).
    Returns (centroid (T,), per-step loss history (steps,)). Pure and
    traceable; callers jit (the sharded job in ``launch/cluster.py``
    does). Every step runs the block-sparse stash forward + reverse
    active-tile backward (DESIGN.md §11).
    """
    X = jnp.asarray(X, jnp.float32)
    if init is None:
        if sample_weights is None:
            z0 = jnp.mean(X, axis=0)
        else:
            sw = jnp.asarray(sample_weights, jnp.float32)
            swb = sw.reshape((-1,) + (1,) * (X.ndim - 1))
            z0 = jnp.sum(X * swb, axis=0) / \
                jnp.maximum(jnp.sum(sw), 1e-8)
    else:
        z0 = jnp.asarray(init, jnp.float32)
    state = adam_init(z0)

    def step(carry, _):
        z, st = carry
        loss, g = jax.value_and_grad(barycenter_loss)(
            z, X, weights, gamma, sample_weights)
        z2, st2 = adam_update(g, st, z, lr)
        return (z2, st2), loss

    (z, _), losses = jax.lax.scan(step, (z0, state), None, length=steps)
    return z, losses

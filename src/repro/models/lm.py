"""Generic decoder LM covering dense/GQA, MLA+MoE, Mamba, hybrid and VLM
architectures, with scan-over-groups stacking (compile time flat in depth).

Parameter pytree:
  { "embed": (V, d), "final_norm": (d,),
    "groups": [ per-pattern-position dict, every leaf stacked (G, ...) ] }

Three entry points (all pure):
  train_loss(params, batch)                -> scalar loss
  prefill(params, tokens, ...)             -> (last hidden, cache)
  decode_step(params, cache, token, pos)   -> (logits, new cache)

TP strategy per DESIGN.md §5: attention q-heads sharded over "model" with
KV heads repeated to match (Megatron GQA trick); archs whose head counts
don't divide the model axis run attention replicated (attn_shard =
"replicated") and shard only FFN/embedding. Decode caches shard the
*sequence* axis over "model" (flash-decode) which is head-count agnostic.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .config import LayerSpec, ModelConfig
from jax.ad_checkpoint import checkpoint_name

from .layers import (FLAGS, attention, chunked_cross_entropy, rms_norm,
                     rope, _unroll)
from .mamba import init_mamba_state, mamba_decode_step, mamba_mixer
from .moe import moe_ffn

DTYPE = jnp.bfloat16


# --------------------------------------------------------------------------
# parameter schema: name -> (shape, init-scale, PartitionSpec)
# --------------------------------------------------------------------------

def _attn_schema(cfg: ModelConfig) -> Dict[str, tuple]:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.attn_shard == "heads":
        return {
            "norm1": ((d,), 0.0, P(None)),
            "wq": ((d, H, hd), 0.02, P(None, "model", None)),
            "wk": ((d, Hkv, hd), 0.02, P(None, None, None)),
            "wv": ((d, Hkv, hd), 0.02, P(None, None, None)),
            "wo": ((H, hd, d), 0.02, P("model", None, None)),
        }
    if cfg.attn_shard == "head_dim":
        # TP inside each head: hd must divide the model axis; the scores/
        # output contractions over hd produce per-chunk psums (§Perf)
        return {
            "norm1": ((d,), 0.0, P(None)),
            "wq": ((d, H, hd), 0.02, P(None, None, "model")),
            "wk": ((d, Hkv, hd), 0.02, P(None, None, "model")),
            "wv": ((d, Hkv, hd), 0.02, P(None, None, "model")),
            "wo": ((H, hd, d), 0.02, P(None, "model", None)),
        }
    return {  # replicated
        "norm1": ((d,), 0.0, P(None)),
        "wq": ((d, H, hd), 0.02, P(None, None, None)),
        "wk": ((d, Hkv, hd), 0.02, P(None, None, None)),
        "wv": ((d, Hkv, hd), 0.02, P(None, None, None)),
        "wo": ((H, hd, d), 0.02, P(None, None, None)),
    }


def _mla_schema(cfg: ModelConfig) -> Dict[str, tuple]:
    d, H = cfg.d_model, cfg.n_heads
    hd, rhd, dv = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    out = {
        "norm1": ((d,), 0.0, P(None)),
        "w_dkv": ((d, r), 0.02, P(None, None)),
        "kv_norm": ((r,), 0.0, P(None)),
        "w_krope": ((d, rhd), 0.02, P(None, None)),
        "w_uk": ((r, H, hd), 0.02, P(None, "model", None)),
        "w_uv": ((r, H, dv), 0.02, P(None, "model", None)),
        "wo": ((H, dv, d), 0.02, P("model", None, None)),
    }
    if cfg.q_lora_rank:
        out.update({
            "w_dq": ((d, cfg.q_lora_rank), 0.02, P(None, None)),
            "q_norm": ((cfg.q_lora_rank,), 0.0, P(None)),
            "w_uq": ((cfg.q_lora_rank, H, hd), 0.02, P(None, "model", None)),
            "w_uq_rope": ((cfg.q_lora_rank, H, rhd), 0.02,
                          P(None, "model", None)),
        })
    else:
        out.update({
            "w_q": ((d, H, hd), 0.02, P(None, "model", None)),
            "w_q_rope": ((d, H, rhd), 0.02, P(None, "model", None)),
        })
    return out


def _mamba_schema(cfg: ModelConfig) -> Dict[str, tuple]:
    d, di, ds = cfg.d_model, cfg.d_inner, cfg.ssm_state
    dtr = max(d // 16, 1)
    return {
        "norm1": ((d,), 0.0, P(None)),
        "in_x": ((d, di), 0.02, P(None, "model")),
        "in_z": ((d, di), 0.02, P(None, "model")),
        "conv_w": ((cfg.d_conv, di), 0.02, P(None, "model")),
        "conv_b": ((di,), 0.0, P("model")),
        "w_B": ((di, ds), 0.02, P("model", None)),
        "w_C": ((di, ds), 0.02, P("model", None)),
        "dt_down": ((di, dtr), 0.02, P("model", None)),
        "dt_up": ((dtr, di), 0.02, P(None, "model")),
        "dt_bias": ((di,), 0.0, P("model")),
        "A_log": ((di, ds), 0.0, P("model", None)),
        "D": ((di,), 0.0, P("model")),
        "out": ((di, d), 0.02, P("model", None)),
    }


def _mlp_schema(cfg: ModelConfig) -> Dict[str, tuple]:
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "norm2": ((d,), 0.0, P(None)),
        "w_gate": ((d, ff), 0.02, P(None, "model")),
        "w_up": ((d, ff), 0.02, P(None, "model")),
        "w_down": ((ff, d), 0.02, P("model", None)),
    }


def _moe_schema(cfg: ModelConfig) -> Dict[str, tuple]:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    out = {
        "norm2": ((d,), 0.0, P(None)),
        "router": ((d, E), 0.02, P(None, None)),
        "gate": ((E, d, ff), 0.02, P("data", None, "model")),
        "up": ((E, d, ff), 0.02, P("data", None, "model")),
        "down": ((E, ff, d), 0.02, P("data", "model", None)),
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ff
        out.update({
            "sh_gate": ((d, sff), 0.02, P(None, "model")),
            "sh_up": ((d, sff), 0.02, P(None, "model")),
            "sh_down": ((sff, d), 0.02, P("model", None)),
        })
    return out


def layer_schema(cfg: ModelConfig, spec: LayerSpec) -> Dict[str, tuple]:
    out: Dict[str, tuple] = {}
    if spec.mixer == "attn":
        out.update(_attn_schema(cfg))
    elif spec.mixer == "mla":
        out.update(_mla_schema(cfg))
    elif spec.mixer == "mamba":
        out.update(_mamba_schema(cfg))
    if spec.ffn == "mlp":
        out.update(_mlp_schema(cfg))
    elif spec.ffn == "moe":
        out.update(_moe_schema(cfg))
    return out


def model_schema(cfg: ModelConfig):
    """Full-pytree schema: {path: (shape, scale, pspec)} mirrors params."""
    groups = []
    for spec in cfg.pattern:
        sch = layer_schema(cfg, spec)
        groups.append({k: ((cfg.n_groups,) + shp, sc, P(*((None,) + tuple(ps))))
                       for k, (shp, sc, ps) in sch.items()})
    return {
        "embed": ((cfg.vocab, cfg.d_model), 0.02, P("model", None)),
        "final_norm": ((cfg.d_model,), 0.0, P(None)),
        "groups": groups,
    }


def _map_schema(schema, fn):
    if isinstance(schema, dict) and "groups" in schema:
        return {
            "embed": fn(*schema["embed"]),
            "final_norm": fn(*schema["final_norm"]),
            "groups": [{k: fn(*v) for k, v in g.items()}
                       for g in schema["groups"]],
        }
    raise ValueError


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=DTYPE):
    leaves_spec = model_schema(cfg)
    counter = [0]

    def mk(shape, scale, _):
        counter[0] += 1
        key = jax.random.fold_in(rng, counter[0])
        if scale == 0.0:
            return jnp.zeros(shape, dtype)
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)

    return _map_schema(leaves_spec, mk)


def param_pspecs(cfg: ModelConfig):
    return _map_schema(model_schema(cfg), lambda shp, sc, ps: ps)


def abstract_params(cfg: ModelConfig, dtype=DTYPE):
    return _map_schema(model_schema(cfg),
                       lambda shp, sc, ps: jax.ShapeDtypeStruct(shp, dtype))


# --------------------------------------------------------------------------
# sharding constraint helper
# --------------------------------------------------------------------------

class Ctx:
    """Mesh context threaded through the forward pass (None = no mesh)."""

    def __init__(self, mesh=None):
        self.mesh = mesh
        if mesh is not None and "pod" in mesh.axis_names:
            self.dp = ("pod", "data")
        else:
            self.dp = ("data",)

    def cst(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    def dp_divides(self, n: int) -> bool:
        if self.mesh is None:
            return False
        sz = int(np.prod([self.mesh.shape[a] for a in self.dp]))
        return n % sz == 0


# --------------------------------------------------------------------------
# layer application
# --------------------------------------------------------------------------

def _apply_attn(x, p, spec: LayerSpec, cfg: ModelConfig, ctx: Ctx,
                cache=None, pos=None):
    """Returns (out, new_cache). cache = {"k","v"} with S (ring for window)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", xn, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xn, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xn, p["wv"])

    decode = cache is not None and pos is not None
    positions = (jnp.full((S,), 0, jnp.int32) + pos if decode
                 else jnp.arange(S))
    q = rope(q, positions, spec.rope_theta)
    k = rope(k, positions, spec.rope_theta)

    if decode:
        S_c = cache["k"].shape[1]
        write = pos % S_c if spec.window is not None else pos
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, write, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, write, 0, 0))
        new_cache = {"k": ck, "v": cv}
        kv_len = jnp.full((B,), jnp.minimum(pos + 1, S_c), jnp.int32)
        # flash-decode: cache S sharded over "model"; q replicated
        o = attention(q, ck, cv, causal=False, kv_len=kv_len,
                      q_offset=pos, window=None)
    else:
        new_cache = None
        if cfg.attn_shard == "heads" and ctx.mesh is not None:
            G = H // Hkv
            q = ctx.cst(q, ctx.dp, None, "model", None)
            k = jnp.repeat(k, G, axis=2)     # Megatron GQA: repeat KV heads
            v = jnp.repeat(v, G, axis=2)
            k = ctx.cst(k, ctx.dp, None, "model", None)
            v = ctx.cst(v, ctx.dp, None, "model", None)
        elif cfg.attn_shard == "head_dim" and ctx.mesh is not None:
            q = ctx.cst(q, ctx.dp, None, None, "model")
            k = ctx.cst(k, ctx.dp, None, None, "model")
            v = ctx.cst(v, ctx.dp, None, None, "model")
        if FLAGS["flash"]:
            from .flash import flash_attention
            o = flash_attention(q, k, v, True, spec.window, 0, 1024, None)
        else:
            o = attention(q, k, v, causal=True, window=spec.window)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(x.dtype), p["wo"])
    # pin the bf16 convert *before* the TP psum: otherwise XLA reduces the
    # f32 dot accumulator over the wire (2x collective volume, §Perf H2)
    out = jax.lax.optimization_barrier(out.astype(x.dtype))
    # name the TP-boundary output so the save_tp remat policy can keep it
    # (the rematerialized forward then skips this psum entirely, §Perf H2)
    out = checkpoint_name(out, "tp_out")
    return x + ctx.cst(out, ctx.dp, None, None), new_cache


def _mla_qkv(xn, p, cfg: ModelConfig, positions):
    if cfg.q_lora_rank:
        cq = rms_norm(xn @ p["w_dq"], p["q_norm"], cfg.norm_eps)
        q_nope = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
        q_rope = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq_rope"])
    else:
        q_nope = jnp.einsum("bsd,dhk->bshk", xn, p["w_q"])
        q_rope = jnp.einsum("bsd,dhk->bshk", xn, p["w_q_rope"])
    q_rope = rope(q_rope, positions, 10_000.0)
    ckv = rms_norm(xn @ p["w_dkv"], p["kv_norm"], cfg.norm_eps)
    krope = rope((xn @ p["w_krope"])[:, :, None, :], positions, 10_000.0)
    return q_nope, q_rope, ckv, krope[:, :, 0, :]


def _apply_mla(x, p, spec: LayerSpec, cfg: ModelConfig, ctx: Ctx,
               cache=None, pos=None):
    B, S, d = x.shape
    H, hd, dv, rhd = cfg.n_heads, cfg.head_dim, cfg.v_head_dim, \
        cfg.rope_head_dim
    xn = rms_norm(x, p["norm1"], cfg.norm_eps)
    decode = cache is not None and pos is not None
    positions = (jnp.zeros((S,), jnp.int32) + pos if decode
                 else jnp.arange(S))
    q_nope, q_rope, ckv, krope = _mla_qkv(xn, p, cfg, positions)

    if decode:
        # absorbed MLA decode: score against the *compressed* cache
        ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv, (0, pos, 0))
        kr_c = jax.lax.dynamic_update_slice(cache["krope"], krope,
                                            (0, pos, 0))
        new_cache = {"ckv": ckv_c, "krope": kr_c}
        q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])  # absorb W_uk
        s = (jnp.einsum("bshr,btr->bhst", q_c, ckv_c)
             + jnp.einsum("bshk,btk->bhst", q_rope, kr_c)
             ).astype(jnp.float32) * (hd + rhd) ** -0.5
        S_c = ckv_c.shape[1]
        kv_pos = jnp.arange(S_c)
        s = jnp.where(kv_pos[None, None, None, :] <= pos, s, -1e30)
        a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        ctxv = jnp.einsum("bhst,btr->bshr", a, ckv_c)          # (B,S,H,r)
        v_ctx = jnp.einsum("bshr,rhv->bshv", ctxv, p["w_uv"])
        out = jnp.einsum("bshv,hvd->bsd", v_ctx, p["wo"])
        return x + out, new_cache

    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhv->bshv", ckv, p["w_uv"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, S, H, rhd))],
        axis=-1)
    q = ctx.cst(q, ctx.dp, None, "model", None)
    k = ctx.cst(k, ctx.dp, None, "model", None)
    v = ctx.cst(v, ctx.dp, None, "model", None)
    if FLAGS["flash"]:
        from .flash import flash_attention
        o = flash_attention(q, k, v, True, None, 0, 1024,
                            (hd + rhd) ** -0.5)
    else:
        o = attention(q, k, v, causal=True, scale=(hd + rhd) ** -0.5)
    out = jnp.einsum("bshv,hvd->bsd", o.astype(x.dtype), p["wo"])
    return x + ctx.cst(out, ctx.dp, None, None), None


def _apply_ffn(x, p, spec: LayerSpec, cfg: ModelConfig, ctx: Ctx):
    """Returns (out, aux_loss)."""
    xn = rms_norm(x, p["norm2"], cfg.norm_eps)
    if spec.ffn == "mlp":
        h = jax.nn.silu(xn @ p["w_gate"]) * (xn @ p["w_up"])
        out = jax.lax.optimization_barrier((h @ p["w_down"]).astype(x.dtype))
        out = checkpoint_name(out, "tp_out")
        return x + out, jnp.float32(0)
    # MoE
    B, S, _ = x.shape
    use_ep = ctx.mesh is not None and ctx.dp_divides(B * S)
    moe_out, aux = moe_ffn(
        xn, p, n_experts=cfg.n_experts, top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor, mesh=ctx.mesh,
        ep_axis="data" if use_ep else None)
    out = x + moe_out
    if cfg.n_shared_experts:
        h = jax.nn.silu(xn @ p["sh_gate"]) * (xn @ p["sh_up"])
        out = out + h @ p["sh_down"]
    return out, aux


def _apply_layer(x, p, spec: LayerSpec, cfg: ModelConfig, ctx: Ctx,
                 cache=None, pos=None):
    new_cache = None
    if spec.mixer == "attn":
        x, new_cache = _apply_attn(x, p, spec, cfg, ctx, cache, pos)
    elif spec.mixer == "mla":
        x, new_cache = _apply_mla(x, p, spec, cfg, ctx, cache, pos)
    elif spec.mixer == "mamba":
        xn = rms_norm(x, p["norm1"], cfg.norm_eps)
        if cache is not None and pos is not None:
            out, new_cache = mamba_decode_step(
                xn, p, (cache["h"], cache["conv"]), d_state=cfg.ssm_state)
            new_cache = {"h": new_cache[0], "conv": new_cache[1]}
        else:
            out = mamba_mixer(xn, p, d_state=cfg.ssm_state)
        x = x + out
    aux = jnp.float32(0)
    if spec.ffn != "none":
        x, aux = _apply_ffn(x, p, spec, cfg, ctx)
    return x, new_cache, aux


# --------------------------------------------------------------------------
# forward passes
# --------------------------------------------------------------------------

def _embed(params, tokens, cfg: ModelConfig, ctx: Ctx):
    x = jnp.take(params["embed"], tokens, axis=0).astype(DTYPE)
    return ctx.cst(x, ctx.dp, None, None)


def forward_hidden(params, tokens, cfg: ModelConfig, ctx: Ctx,
                   patches=None, remat: bool = True):
    """Token (+ optional VLM patch) embedding -> final hidden states."""
    x = _embed(params, tokens, cfg, ctx)
    if patches is not None:
        x = jnp.concatenate([patches.astype(DTYPE), x], axis=1)
        x = ctx.cst(x, ctx.dp, None, None)

    def group_body(x, gp):
        aux_t = jnp.float32(0)
        for li, spec in enumerate(cfg.pattern):
            x, _, aux = _apply_layer(x, gp[li], spec, cfg, ctx)
            aux_t += aux
        x = ctx.cst(x, ctx.dp, None, None)
        return x, aux_t

    if remat:
        if FLAGS["remat_policy"] == "save_tp":
            pol = jax.checkpoint_policies.save_only_these_names("tp_out")
            body = jax.checkpoint(group_body, policy=pol)
        else:
            body = jax.checkpoint(group_body)
    else:
        body = group_body
    x, auxes = jax.lax.scan(lambda c, xs: body(c, xs), x,
                            params["groups"], unroll=_unroll())
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.sum(auxes)


def train_loss(params, batch, cfg: ModelConfig, ctx: Ctx,
               aux_weight: float = 0.01, remat: bool = True):
    """batch: {"tokens": (B, S+1) int32, optional "patches": (B, Np, d)}."""
    tokens = batch["tokens"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    patches = batch.get("patches")
    x, aux = forward_hidden(params, inp, cfg, ctx, patches=patches,
                            remat=remat)
    if patches is not None:
        x = x[:, patches.shape[1]:]   # loss on text positions only
    mask = (tgt >= 0).astype(jnp.float32)
    loss = chunked_cross_entropy(x, params["embed"], jnp.maximum(tgt, 0),
                                 mask)
    return loss + aux_weight * aux


def init_cache(cfg: ModelConfig, B: int, S_max: int, dtype=DTYPE):
    """Decode cache pytree (leading G dim per pattern position)."""
    caches = []
    G = cfg.n_groups
    for spec in cfg.pattern:
        if spec.mixer == "attn":
            S_c = min(spec.window, S_max) if spec.window else S_max
            caches.append({
                "k": jnp.zeros((G, B, S_c, cfg.n_kv_heads, cfg.head_dim),
                               dtype),
                "v": jnp.zeros((G, B, S_c, cfg.n_kv_heads, cfg.head_dim),
                               dtype)})
        elif spec.mixer == "mla":
            caches.append({
                "ckv": jnp.zeros((G, B, S_max, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((G, B, S_max, cfg.rope_head_dim), dtype)})
        elif spec.mixer == "mamba":
            h, conv = init_mamba_state(B, cfg.d_inner, cfg.ssm_state,
                                       cfg.d_conv, dtype)
            caches.append({
                "h": jnp.zeros((G,) + h.shape, h.dtype),
                "conv": jnp.zeros((G,) + conv.shape, conv.dtype)})
        else:
            caches.append({})
    return caches


def decode_step(params, cache, token, pos, cfg: ModelConfig, ctx: Ctx):
    """token: (B, 1) int32; pos: scalar int32. Returns (logits, cache)."""
    x = _embed(params, token, cfg, ctx)

    def group_body(x, xs):
        gp, gc = xs
        new_gc = []
        for li, spec in enumerate(cfg.pattern):
            x, nc, _ = _apply_layer(x, gp[li], spec, cfg, ctx,
                                    cache=gc[li] if gc[li] else None,
                                    pos=pos)
            new_gc.append(nc if nc is not None else gc[li])
        return x, new_gc

    x, new_cache = jax.lax.scan(group_body, x, (params["groups"], cache),
                                unroll=_unroll())
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0, :] @ params["embed"].T).astype(jnp.float32)
    return logits, new_cache


def prefill(params, tokens, cfg: ModelConfig, ctx: Ctx, S_cache: int,
            patches=None):
    """Forward pass that also builds the decode cache (inference prefill)."""
    x = _embed(params, tokens, cfg, ctx)
    if patches is not None:
        x = jnp.concatenate([patches.astype(DTYPE), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.arange(S)

    def group_body(x, gp):
        new_gc = []
        for li, spec in enumerate(cfg.pattern):
            # run the layer, then extract the cacheable KV/state
            if spec.mixer == "attn":
                xn = rms_norm(x, gp[li]["norm1"], cfg.norm_eps)
                k = jnp.einsum("bsd,dhk->bshk", xn, gp[li]["wk"])
                v = jnp.einsum("bsd,dhk->bshk", xn, gp[li]["wv"])
                k = rope(k, positions, spec.rope_theta)
                if spec.window:
                    w = min(spec.window, S)
                    kc, vc = k[:, -w:], v[:, -w:]
                else:
                    kc, vc = k, v
                new_gc.append({"k": kc.astype(DTYPE), "v": vc.astype(DTYPE)})
                x, _, _ = _apply_layer(x, gp[li], spec, cfg, ctx)
            elif spec.mixer == "mla":
                xn = rms_norm(x, gp[li]["norm1"], cfg.norm_eps)
                ckv = rms_norm(xn @ gp[li]["w_dkv"], gp[li]["kv_norm"],
                               cfg.norm_eps)
                krope = rope((xn @ gp[li]["w_krope"])[:, :, None, :],
                             positions, 10_000.0)[:, :, 0, :]
                new_gc.append({"ckv": ckv.astype(DTYPE),
                               "krope": krope.astype(DTYPE)})
                x, _, _ = _apply_layer(x, gp[li], spec, cfg, ctx)
            elif spec.mixer == "mamba":
                xn = rms_norm(x, gp[li]["norm1"], cfg.norm_eps)
                out, st = mamba_mixer(xn, gp[li], d_state=cfg.ssm_state,
                                      return_state=True)
                x = x + out
                new_gc.append({"h": st[0], "conv": st[1]})
                if spec.ffn != "none":
                    x, _ = _apply_ffn(x, gp[li], spec, cfg, ctx)
            else:
                x, _, _ = _apply_layer(x, gp[li], spec, cfg, ctx)
        x = ctx.cst(x, ctx.dp, None, None)
        return x, new_gc

    x, cache = jax.lax.scan(group_body, x, params["groups"],
                            unroll=_unroll())
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, -1, :], cache

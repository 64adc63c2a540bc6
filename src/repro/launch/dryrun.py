import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run (assignment deliverable e).

For every (architecture x input shape x mesh) cell, two compilations:

  A. the REAL production step — scan-over-groups, microbatched (train),
     donated buffers — .lower().compile() on the production mesh. This is
     the runnability proof: memory_analysis() shows it fits a 16 GB chip.

  B. (single-pod only) COST PROBES: the same step at n_groups = 1 and 2
     with every inner scan unrolled (layers.set_probe_mode). XLA's
     cost_analysis counts loop bodies once, so probes make the counts
     exact, and because groups are homogeneous,

        total(G) = probe(1) + (G - 1) * (probe(2) - probe(1))

     recovers FLOPs / bytes / per-collective wire bytes of the full-depth
     model exactly. Train cells add: x microbatch for the grad part + a
     separate optimizer-update probe (counted once per step).

The XLA_FLAGS line above MUST run before any other import touches jax —
device count locks at first backend init. Run:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse        # noqa: E402
import dataclasses     # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config          # noqa: E402
from repro.launch import hlo_analysis                   # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.launch.shapes import (SHAPES, cell_supported,  # noqa: E402
                                 input_specs, specs_to_shardings)
from repro.models import Ctx, build                     # noqa: E402
from repro.models.layers import set_probe_mode          # noqa: E402
from repro.train.optimizer import AdamW                 # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "artifacts", "dryrun")
TRAIN_MICROBATCH = 16

# Memory-policy overrides for the very large configs: bf16 Adam moments and
# no f32 master (optimizer.py docstring); everything else: f32 + ZeRO-1.
OPT_OVERRIDES = {
    "deepseek-v2-236b": dict(moment_dtype=jnp.bfloat16, keep_master=False),
    "jamba-v0.1-52b": dict(moment_dtype=jnp.bfloat16, keep_master=False),
}


def _reduced_depth(cfg, g: int):
    return dataclasses.replace(
        cfg, n_layers=g * len(cfg.pattern),
        n_enc_layers=g if cfg.n_enc_layers else 0)


def _opt_setup(api, mesh):
    opt = AdamW(lr=3e-4, **OPT_OVERRIDES.get(api.cfg.name, {}))
    pspecs = api.param_pspecs()
    params_abs = api.abstract_params()
    opt_abs = jax.eval_shape(opt.init, params_abs)
    opt_specs = opt.state_pspecs(pspecs, zero1=True, shapes=params_abs,
                                 data_size=mesh.shape["data"])
    param_sh = specs_to_shardings(pspecs, mesh)
    opt_sh = jax.tree.map(lambda ps: specs_to_shardings(ps, mesh), opt_specs,
                          is_leaf=lambda x: isinstance(
                              x, jax.sharding.PartitionSpec))
    return opt, params_abs, opt_abs, param_sh, opt_sh


def _cost_of(compiled):
    ca = compiled.cost_analysis() or {}
    colls = hlo_analysis.parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": colls["wire_bytes_per_device"],
            "coll_per_op": {k: v["wire_bytes"]
                            for k, v in colls["per_op"].items()},
            "coll_counts": {k: v["count"]
                            for k, v in colls["per_op"].items()}}


def _combine(p1, p2, G, scale=1.0, extra=None):
    """total(G) = p1 + (G-1)(p2-p1), then x scale, then + extra."""
    def lin(a, b):
        return scale * (a + (G - 1) * (b - a))
    out = {"flops": lin(p1["flops"], p2["flops"]),
           "bytes": lin(p1["bytes"], p2["bytes"]),
           "coll": lin(p1["coll"], p2["coll"])}
    ops = set(p1["coll_per_op"]) | set(p2["coll_per_op"])
    out["coll_per_op"] = {o: lin(p1["coll_per_op"].get(o, 0.0),
                                 p2["coll_per_op"].get(o, 0.0)) for o in ops}
    if extra is not None:
        out["flops"] += extra["flops"]
        out["bytes"] += extra["bytes"]
        out["coll"] += extra["coll"]
        for o, v in extra["coll_per_op"].items():
            out["coll_per_op"][o] = out["coll_per_op"].get(o, 0.0) + v
    return out


def _probe(cfg, shape: str, mesh, g: int):
    """Compile the G=g cost probe; returns per-device cost dict."""
    rcfg = _reduced_depth(cfg, g)
    api = build(rcfg)
    ctx = Ctx(mesh)
    cell = input_specs(rcfg, shape, mesh, api=api)
    pspecs = api.param_pspecs()
    param_sh = specs_to_shardings(pspecs, mesh)
    params_abs = api.abstract_params()
    set_probe_mode(True)
    try:
        if cell.kind == "train":
            # grads-only at one microbatch of the global batch
            batch, = cell.args
            shard, = cell.in_shardings
            mb = {k: jax.ShapeDtypeStruct(
                (v.shape[0] // TRAIN_MICROBATCH,) + v.shape[1:], v.dtype)
                for k, v in batch.items()}

            opt = AdamW(lr=3e-4, **OPT_OVERRIDES.get(cfg.name, {}))
            z1 = opt.state_pspecs(pspecs, zero1=True, shapes=params_abs,
                                  data_size=mesh.shape["data"]).m
            z1_sh = specs_to_shardings(z1, mesh)

            def grads(params, b):
                return jax.value_and_grad(
                    lambda p: api.train_loss(p, b, ctx))(params)

            jitted = jax.jit(grads, in_shardings=(param_sh, shard),
                             out_shardings=(None, z1_sh))
            compiled = jitted.lower(params_abs, mb).compile()
        elif cell.kind == "prefill":
            jitted = jax.jit(
                lambda p, b: api.prefill(p, b, ctx, cell.seq_len),
                in_shardings=(param_sh,) + cell.in_shardings)
            compiled = jitted.lower(params_abs, *cell.args).compile()
        else:
            token, cache, pos = cell.args
            token_sh, cache_sh, pos_sh = cell.in_shardings
            jitted = jax.jit(
                lambda p, c, t, s: api.decode_step(p, c, t, s, ctx),
                in_shardings=(param_sh, cache_sh, token_sh, pos_sh),
                out_shardings=(None, cache_sh))
            compiled = jitted.lower(params_abs, cache, token, pos).compile()
    finally:
        set_probe_mode(False)
    return _cost_of(compiled)


def _opt_probe(cfg, mesh):
    """Optimizer-update cost at full depth (elementwise: no loop issue)."""
    api = build(cfg)
    opt, params_abs, opt_abs, param_sh, opt_sh = _opt_setup(api, mesh)
    grads_abs = params_abs
    jitted = jax.jit(opt.update,
                     in_shardings=(param_sh, opt_sh, param_sh),
                     out_shardings=(param_sh, opt_sh),
                     donate_argnums=(1,))
    compiled = jitted.lower(grads_abs, opt_abs, params_abs).compile()
    return _cost_of(compiled)


def compile_real_step(cfg, shape: str, mesh):
    """Program A: production step; returns (compiled, cell)."""
    api = build(cfg)
    ctx = Ctx(mesh)
    cell = input_specs(cfg, shape, mesh, api=api)
    pspecs = api.param_pspecs()
    param_sh = specs_to_shardings(pspecs, mesh)
    params_abs = api.abstract_params()
    if cell.kind == "train":
        from repro.train.train_step import make_train_step
        opt, params_abs, opt_abs, param_sh, opt_sh = _opt_setup(api, mesh)
        opt_specs = opt.state_pspecs(api.param_pspecs(), zero1=True,
                                     shapes=params_abs,
                                     data_size=mesh.shape["data"])
        step = make_train_step(api, mesh, opt, microbatch=TRAIN_MICROBATCH,
                               donate=False, accum_pspecs=opt_specs.m)
        jitted = jax.jit(
            step.__wrapped__,
            in_shardings=(param_sh, opt_sh) + cell.in_shardings,
            out_shardings=(param_sh, opt_sh, None),
            donate_argnums=(0, 1))
        return jitted.lower(params_abs, opt_abs, *cell.args).compile(), cell
    if cell.kind == "prefill":
        jitted = jax.jit(
            lambda p, b: api.prefill(p, b, ctx, cell.seq_len),
            in_shardings=(param_sh,) + cell.in_shardings)
        return jitted.lower(params_abs, *cell.args).compile(), cell
    token, cache, pos = cell.args
    token_sh, cache_sh, pos_sh = cell.in_shardings
    jitted = jax.jit(
        lambda p, c, t, s: api.decode_step(p, c, t, s, ctx),
        in_shardings=(param_sh, cache_sh, token_sh, pos_sh),
        out_shardings=(None, cache_sh), donate_argnums=(1,))
    return jitted.lower(params_abs, cache, token, pos).compile(), cell


ATTN_SHARD_OVERRIDE = [None]


def dryrun_cell(arch: str, shape: str, multi_pod: bool,
                variant: str = "base", probes: bool = True) -> dict:
    cfg = get_config(arch)
    if ATTN_SHARD_OVERRIDE[0]:
        cfg = dataclasses.replace(cfg, attn_shard=ATTN_SHARD_OVERRIDE[0])
    ok, why = cell_supported(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    with jax.set_mesh(mesh):
        t0 = time.time()
        compiled, cell = compile_real_step(cfg, shape, mesh)
        t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        result = {
            "arch": arch, "shape": shape, "variant": variant,
            "mesh": mesh_name, "status": "ok", "kind": cell.kind,
            "seq_len": cell.seq_len, "batch": cell.batch,
            "tokens_per_step": cell.tokens_per_step,
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes_est": (ma.argument_size_in_bytes
                                   + ma.output_size_in_bytes
                                   + ma.temp_size_in_bytes
                                   - ma.alias_size_in_bytes),
                "fits_16GB": bool(
                    (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                    < 16e9),
            },
            "params_total": cfg.param_count(),
            "params_active": cfg.active_param_count(),
        }
        if not probes or multi_pod:
            return result

        # ---- cost probes (single-pod roofline) ----
        t0 = time.time()
        p1 = _probe(cfg, shape, mesh, 1)
        p2 = _probe(cfg, shape, mesh, 2)
        G = cfg.n_groups
        if cell.kind == "train":
            opt_cost = _opt_probe(cfg, mesh)
            cost = _combine(p1, p2, G, scale=TRAIN_MICROBATCH,
                            extra=opt_cost)
        else:
            cost = _combine(p1, p2, G)
        t_probe = time.time() - t0
        rl = hlo_analysis.roofline_terms(cost["flops"], cost["bytes"],
                                         cost["coll"])
        n_dev = mesh.size
        mf = 6.0 if cell.kind == "train" else 2.0
        model_flops = mf * cfg.active_param_count() * cell.tokens_per_step
        result.update({
            "probe_s": round(t_probe, 2),
            "flops_per_device": cost["flops"],
            "bytes_per_device": cost["bytes"],
            "coll_bytes_per_device": cost["coll"],
            "coll_per_op": cost["coll_per_op"],
            "roofline": {
                "compute_s": rl.compute_s, "memory_s": rl.memory_s,
                "collective_s": rl.collective_s, "dominant": rl.dominant,
                "bound_time_s": rl.bound_time_s,
            },
            "model_flops_total": model_flops,
            "model_flops_per_device": model_flops / n_dev,
            "useful_flops_ratio": (model_flops / n_dev / cost["flops"]
                                   if cost["flops"] else 0.0),
        })
        return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--variant", default="base",
                    help="label for perf-iteration artifacts")
    ap.add_argument("--no-flash", action="store_true")
    ap.add_argument("--remat-policy", default="minimal",
                    choices=("minimal", "save_tp"))
    ap.add_argument("--kv-chunk", type=int, default=0,
                    help="override attention kv_chunk (0 = default)")
    ap.add_argument("--attn-shard", default=None,
                    choices=("heads", "head_dim", "replicated"))
    args = ap.parse_args()
    from repro.models.layers import FLAGS
    FLAGS["flash"] = not args.no_flash
    FLAGS["remat_policy"] = args.remat_policy
    if args.kv_chunk:
        FLAGS["kv_chunk"] = args.kv_chunk
    ATTN_SHARD_OVERRIDE[0] = args.attn_shard

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = ([(a, s) for a in ARCH_IDS for s in SHAPES]
             if args.all else [(args.arch, args.shape)])

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
            if args.variant != "base":
                tag += f"__{args.variant}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path) and not args.force:
                print(f"[skip-cached] {tag}", flush=True)
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            t0 = time.time()
            try:
                res = dryrun_cell(arch, shape, mp, variant=args.variant,
                                  probes=not args.no_probes)
            except Exception as e:  # noqa: BLE001
                failures += 1
                res = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()}
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
            status = res["status"]
            extra = ""
            if status == "ok" and "roofline" in res:
                extra = (f" dominant={res['roofline']['dominant']}"
                         f" useful={res.get('useful_flops_ratio', 0):.2f}"
                         f" mem_ok={res['memory']['fits_16GB']}")
            elif status == "ok":
                extra = f" mem_ok={res['memory']['fits_16GB']}"
            print(f"  -> {status}{extra} ({time.time()-t0:.0f}s)",
                  flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()

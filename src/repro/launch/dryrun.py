import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes (single-pod 16x16 and multi-pod 2x16x16), prove the
sharding config is coherent, and record memory/cost/collective statistics
for the roofline analysis.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--daso] [--jobs-file f]

Per (arch, shape, mesh) this lowers:
  train_4k     sync train_step (Horovod-analog baseline); with --daso on the
               multi-pod mesh, additionally the DASO B=4 cycle (send /
               receive / local / local) whose HLO carries the cross-pod
               collectives only in the send/receive sub-steps.
  prefill_32k  prefill (returns populated KV cache)
  decode_32k   serve_step: ONE token against a seq-length cache
  long_500k    serve_step with recurrent state / ring window cache
               (sliding-window variant for full-attention archs)

Records land in experiments/dryrun/<arch>__<shape>__<mesh>[__daso].json.
--unroll-groups N lowers with N unrolled pattern groups instead of the full
scanned stack (used by the roofline per-layer cost extraction).
"""
import argparse
import functools
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.daso import DasoConfig, daso_train_step, sync_train_step
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (INPUT_SHAPES, batch_shardings, batch_specs,
                                cache_shardings, decode_specs, make_policy,
                                make_param_shardings, needs_window_override,
                                param_bytes, params_struct)
from repro.models.lm import forward, init_cache
from repro.optim.optimizers import sgd
from repro.serve.engine import make_decode_fn
from repro.sharding import use_policy
from repro.train.step import make_lm_loss

SDS = jax.ShapeDtypeStruct
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

FSDP_TRAIN_BYTES = 6e9    # enable ZeRO-3 when params*4/model_shards exceeds
FSDP_SERVE_BYTES = 10e9   # enable weight-gathered serving above this


def _scalar_sh(mesh):
    return NamedSharding(mesh, P())


def _mesh_dict(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _record(lowered, compiled, t_lower, t_compile, mesh, extra):
    from repro.launch.hlo_stats import collective_stats
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):  # older jax: one dict per device
        ca = ca[0] if ca else {}
    rec = {
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_estimate_per_device": (mem.argument_size_in_bytes
                                         + mem.output_size_in_bytes
                                         + mem.temp_size_in_bytes
                                         - mem.alias_size_in_bytes),
        },
        "cost": {"flops": ca.get("flops", -1.0),
                 "bytes_accessed": ca.get("bytes accessed", -1.0)},
        "collectives": collective_stats(compiled.as_text(),
                                        _mesh_dict(mesh)),
    }
    rec.update(extra)
    return rec


def build_train_lowering(cfg, mesh, *, daso: bool, unroll_groups: int = 0,
                         fsdp=None, remat: bool = True, q_chunk: int = 1024,
                         vocab_chunk: int = 0, n_micro: int = 1):
    """Returns a jax .lower()-ed sync train step (or DASO cycle)."""
    params = params_struct(cfg)
    pb = param_bytes(params)
    model_shards = mesh.shape["model"]
    if fsdp is None:
        fsdp = pb * 4 / model_shards > FSDP_TRAIN_BYTES
    n_replicas = mesh.shape.get("pod", 1) if daso else 0
    policy = make_policy(mesh, daso=daso, fsdp=fsdp)

    if unroll_groups:
        plen = len(cfg.layer_pattern)
        cfg = cfg.replace(n_layers=unroll_groups * plen)
        params = params_struct(cfg)

    loss_fn = make_lm_loss(cfg, q_chunk=q_chunk, remat=remat,
                           vocab_chunk=vocab_chunk,
                           unroll_layers=bool(unroll_groups),
                           mamba_chunk=512 if unroll_groups else 64)
    optimizer = sgd(momentum=0.9, weight_decay=1e-4)
    opt = jax.eval_shape(optimizer.init, params)
    specs = batch_specs(cfg, "train_4k")
    bspecs, bsh = batch_shardings(specs, policy, n_replicas=n_replicas)
    if daso:
        R = n_replicas
        params = jax.tree.map(lambda x: SDS((R,) + x.shape, x.dtype), params)
        p_sh = make_param_shardings(cfg, params, policy, replicated=True)
        o_sh = {"mu": p_sh}
        opt = jax.eval_shape(
            lambda p: {"mu": jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p)}, params)
        inflight = params
        dcfg = DasoConfig(n_replicas=R,
                          global_world=R * mesh.shape["data"], b_max=4)
        steps = [daso_train_step(loss_fn, optimizer, dcfg, mode=m,
                                 staleness=1, spmd_axis_name="pod",
                                 n_micro=n_micro)
                 for m in ("send", "receive", "local", "local")]

        def cycle(params, opt_state, inflight, batches, lr):
            metrics = None
            for i, s in enumerate(steps):
                b = jax.tree.map(lambda x: x[i], batches)
                params, opt_state, inflight, metrics = s(
                    params, opt_state, inflight, b, lr)
            return params, opt_state, inflight, metrics

        batches = jax.tree.map(lambda x: SDS((4,) + x.shape, x.dtype), bspecs)
        bsh4 = jax.tree.map(
            lambda s: NamedSharding(mesh, P(*((None,) + s.spec))), bsh)
        with use_policy(policy):
            lowered = jax.jit(
                cycle,
                in_shardings=(p_sh, {"mu": o_sh["mu"]}, p_sh, bsh4,
                              _scalar_sh(mesh)),
                donate_argnums=(0, 1, 2)).lower(
                params, opt, inflight, batches,
                SDS((), jnp.float32))
        return lowered, {"fsdp": bool(fsdp), "param_bytes": pb,
                         "variant": "daso_cycle_b4"}

    p_sh = make_param_shardings(cfg, params, policy)
    o_sh = {"mu": p_sh}
    step = sync_train_step(loss_fn, optimizer, n_micro=n_micro)
    with use_policy(policy):
        lowered = jax.jit(step, in_shardings=(p_sh, o_sh, bsh,
                                              _scalar_sh(mesh)),
                          donate_argnums=(0, 1)).lower(
            params, opt, bspecs, SDS((), jnp.float32))
    return lowered, {"fsdp": bool(fsdp), "param_bytes": pb,
                     "variant": "sync_step"}


def build_prefill_lowering(cfg, mesh, *, unroll_groups: int = 0,
                           q_chunk: int = 1024):
    seq, gb, _ = INPUT_SHAPES["prefill_32k"]
    params = params_struct(cfg)
    pb = param_bytes(params)
    fsdp = pb / mesh.shape["model"] > FSDP_SERVE_BYTES
    policy = make_policy(mesh, fsdp=fsdp)
    if unroll_groups:
        cfg = cfg.replace(n_layers=unroll_groups * len(cfg.layer_pattern))
        params = params_struct(cfg)
    specs = batch_specs(cfg, "prefill_32k")
    bspecs, bsh = batch_shardings(specs, policy)
    p_sh = make_param_shardings(cfg, params, policy)

    def prefill(params, batch):
        cache = init_cache(cfg, gb, seq, dtype=cfg.cdtype())
        out = forward(params, batch["tokens"], cfg,
                      prefix_embeds=batch.get("prefix_embeds"),
                      cache=cache, q_chunk=q_chunk,
                      unroll_layers=bool(unroll_groups),
                      mamba_chunk=512 if unroll_groups else 64)
        return out["logits"][:, -1], out["cache"]

    with use_policy(policy):
        lowered = jax.jit(prefill, in_shardings=(p_sh, bsh)).lower(
            params, bspecs)
    return lowered, {"fsdp": bool(fsdp), "param_bytes": pb,
                     "variant": "prefill"}


def build_decode_lowering(cfg, mesh, shape_name: str, *,
                          unroll_groups: int = 0):
    seq, gb, _ = INPUT_SHAPES[shape_name]
    params = params_struct(cfg)
    pb = param_bytes(params)
    fsdp = pb / mesh.shape["model"] > FSDP_SERVE_BYTES
    wo = needs_window_override(cfg, shape_name)
    policy = make_policy(mesh, fsdp=fsdp, seq_sharded=(gb == 1))
    if unroll_groups:
        cfg = cfg.replace(n_layers=unroll_groups * len(cfg.layer_pattern))
        params = params_struct(cfg)
    d = decode_specs(cfg, shape_name)
    p_sh = make_param_shardings(cfg, params, policy)
    c_sh = cache_shardings(d["cache"], cfg, policy, gb)
    b_axes = policy.resolve("batch")
    b_axes = b_axes if isinstance(b_axes, tuple) else (b_axes,)
    nb = 1
    for a in b_axes:
        nb *= mesh.shape[a]
    tok_sh = NamedSharding(mesh, P(b_axes if gb % nb == 0 else None, None))

    serve_step = make_decode_fn(cfg, window_override=wo)

    def step(params, cache, token, pos):
        out = serve_step(params, cache, token, pos)
        return out["logits"], out["cache"]

    with use_policy(policy):
        lowered = jax.jit(step, in_shardings=(
            p_sh, c_sh, tok_sh, _scalar_sh(mesh)),
            donate_argnums=(1,)).lower(
            params, d["cache"], d["token"], d["pos"])
    return lowered, {"fsdp": bool(fsdp), "param_bytes": pb,
                     "variant": f"serve_step(window={wo})" if wo
                     else "serve_step"}


def run_one(arch: str, shape_name: str, *, multi_pod: bool, daso: bool = False,
            unroll_groups: int = 0, compile_too: bool = True):
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = INPUT_SHAPES[shape_name][2]
    t0 = time.time()
    if kind == "train":
        lowered, extra = build_train_lowering(cfg, mesh, daso=daso,
                                              unroll_groups=unroll_groups)
    elif kind == "prefill":
        lowered, extra = build_prefill_lowering(cfg, mesh,
                                                unroll_groups=unroll_groups)
    else:
        lowered, extra = build_decode_lowering(cfg, mesh, shape_name,
                                               unroll_groups=unroll_groups)
    t_lower = time.time() - t0
    if not compile_too:
        return {"ok": True, "lower_s": round(t_lower, 2), **extra}
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    rec = _record(lowered, compiled, t_lower, t_compile, mesh, extra)
    rec.update({"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "devices": 512 if multi_pod else 256,
                "unroll_groups": unroll_groups})
    print(compiled.memory_analysis())
    return rec


def _out_path(arch, shape, multi_pod, daso, unroll_groups):
    tag = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}"
    if daso:
        tag += "__daso"
    if unroll_groups:
        tag += f"__u{unroll_groups}"
    return os.path.join(OUT_DIR, tag + ".json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--daso", action="store_true",
                    help="lower the DASO B=4 cycle (train_4k, multi-pod)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--unroll-groups", type=int, default=0)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.configs.base import ARCH_IDS
    archs = [args.arch] if args.arch else [a for a in ARCH_IDS
                                           if a != "resnet50"]
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    os.makedirs(OUT_DIR, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            path = _out_path(arch, shape, args.multi_pod, args.daso,
                             args.unroll_groups)
            if args.skip_existing and os.path.exists(path):
                continue
            label = f"{arch} x {shape} ({'2x16x16' if args.multi_pod else '16x16'}{' daso' if args.daso else ''})"
            print(f"== {label}", flush=True)
            try:
                rec = run_one(arch, shape, multi_pod=args.multi_pod,
                              daso=args.daso,
                              unroll_groups=args.unroll_groups)
                print(f"   flops={rec['cost']['flops']:.3e} "
                      f"coll={rec['collectives']['_total_bytes']:.3e}B "
                      f"lower={rec['lower_s']}s compile={rec['compile_s']}s",
                      flush=True)
            except Exception as e:
                failures += 1
                rec = {"ok": False, "arch": arch, "shape": shape,
                       "error": repr(e),
                       "traceback": traceback.format_exc()}
                print(f"   FAILED: {e!r}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

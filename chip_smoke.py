#!/usr/bin/env python3
"""Chip smoke test: DASO training of llama3.2-1b at its published widths
on one TPU, through the same entry points as `repro.launch.train`.

    python chip_smoke.py                  # one chip (needs a TPU)
    python chip_smoke.py --four-chips     # the cross-chip exchange, 4 TPUs
    python chip_smoke.py --cpu-rehearsal  # every phase at --tiny size, CPU

Phases of the default run, all in this one process:

  a. device check: the first JAX device must be a TPU; anything else is a
     failure (no CPU fallback) unless --cpu-rehearsal asks for the CPU.
  b. DASO training, 2 virtual replicas on the chip (the macro-cycle
     executor), at full width with the depth cut from 16 to 2 layers.
  c. the same seed on the per-step reference executor: same mode history,
     loss traces allclose within LOSS_RTOL.
  d. the exchange kernels (Eq. (1) merge, bf16 pack/unpack, int8
     quantize/dequantize) compiled, over this model's parameter arena,
     against the jnp references in `repro.kernels.ref`.

`--four-chips` runs only the cross-chip check instead: the same job on the
`chip:2 x host:2` topology as one process over 4 TPUs (MeshPlacement),
against the one-device run of the same R=2 job.

Lines before the last are bring-up observations (compile and step seconds,
peak bytes), not benchmark metrics. The last line is one JSON object,
`{"ok": true, "device": {...}}`; a failed check exits non-zero."""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "llama3.2-1b"
LAYERS = 2                 # the cut in depth: 16 -> 2, widths as published
STEPS = 20
# sequences per replica and step. The compiler's memory analysis for a
# described v5e chip puts the send/receive cycle program's arguments +
# temporaries at 17.0e9 bytes, but on a TPU v5e its peak_bytes_in_use
# stays at 7.6e9 of the 16 GiB, so the batch is not cut
PER_NODE_BATCH = 2
# peak SGD learning rate. The launcher's default of 0.05 is sized for its
# reduced widths; at the published widths it diverges within 20 steps, and
# at 0.01 the loss turns back up after about 9 steps. In such a run two
# programs that round differently drift apart far beyond their rounding,
# and the equivalence checks below would measure the instability instead
LR = 0.003
# macro vs per-step (and mesh vs one device) losses with bf16 parameters:
# the programs round differently (scan vs per-step dispatch, sharded
# matmuls), and a bf16 ulp is 2**-8 of the value
LOSS_RTOL = 2e-2
LOSS_RTOL_F32 = 1e-5       # --cpu-rehearsal: float32 parameters

FOUR_CHIP_TOPOLOGY = "chip:2 x host:2"


def fail(msg: str):
    print(json.dumps({"ok": False, "error": msg}), flush=True)
    sys.exit(1)


def check(cond: bool, msg: str):
    if not cond:
        fail(msg)


def launcher_args(tiny, *extra):
    """The launcher's own arguments for this job (`launch/train.py`
    parser and defaults), plus `extra` flags. The tiny rehearsal also
    shortens the sequences, so that it stays a CPU test of control flow."""
    from repro.launch import train
    size = ["--tiny", "--seq-len", "128"] if tiny else ["--seq-len", "1024"]
    return train.build_parser().parse_args(
        ["--arch", ARCH, "--strategy", "daso", "--b-max", "4",
         "--per-node-batch", str(PER_NODE_BATCH), "--steps", str(STEPS),
         "--lr", str(LR), *size, *extra])


def model_config(tiny: bool):
    from repro.configs import get_config
    from repro.launch import train
    if tiny:
        return train.arch_config(launcher_args(tiny))
    full = get_config(ARCH)
    print(f"reduced: n_layers {full.n_layers} -> {LAYERS} (widths as "
          f"published: d_model {full.d_model}, heads {full.n_heads}/"
          f"{full.n_kv_heads} kv, head_dim {full.head_dim}, d_ff "
          f"{full.d_ff}, vocab {full.vocab_size}, {full.param_dtype} "
          f"params; {PER_NODE_BATCH} x 1024 tokens per replica and step)")
    return full.replace(n_layers=LAYERS)


def train_once(args, cfg, out_dir, tag):
    """One training run through `run_training`, traced so that compile and
    steady step seconds come from the executor's cycle spans (each ends
    when the cycle's metrics are on the host)."""
    from repro.launch import train
    from repro.obs.trace import Tracer, load_events
    from repro.train.loop import run_training
    job = train.build_job(args, cfg)
    path = os.path.join(out_dir, f"{tag}.trace.jsonl")
    tracer = Tracer(path)
    t0 = time.perf_counter()
    result = run_training(job.loss_fn, job.params0, job.data_fn,
                          job.loop_cfg, lr_fn=job.lr_fn, log=print,
                          tracer=tracer)
    wall = time.perf_counter() - t0
    tracer.close()
    print(f"[{tag}] observation: wall {wall:.2f}s")
    cycles = [e for e in load_events(path) if e.get("name") == "cycle"]
    warm = [e["dur"] / 1e6 / e["args"]["steps"] for e in cycles
            if not e["args"].get("fresh_compile")]
    if warm:  # the per-step reference path records no cycle spans
        step_s = statistics.median(warm)
        compile_s = sum(e["dur"] / 1e6 - step_s * e["args"]["steps"]
                        for e in cycles if e["args"].get("fresh_compile"))
        # every warm cycle is listed: one far above the median is a
        # program that recompiled for new input shardings or layouts
        print(f"[{tag}] observation: compile ~{compile_s:.2f}s (first "
              f"cycle of each shape less its steady time), steady "
              f"{step_s:.4f}s/step (median of {len(warm)} warm cycles: "
              f"{', '.join(f'{s:.4f}' for s in warm)})")
    return job, result


def modes(result):
    return [m for (_, m, _, _) in result.controller.history]


def max_rel_diff(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def check_losses(name, losses):
    import numpy as np
    print(f"[{name}] losses: {[round(x, 5) for x in losses]}")
    check(len(losses) == STEPS, f"{name}: {len(losses)} losses for "
          f"{STEPS} steps")
    check(bool(np.all(np.isfinite(losses))), f"{name}: non-finite loss")


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_in_use")


def phase_one_chip(tiny, rtol, out_dir):
    import jax
    cfg = model_config(tiny)
    macro_args = launcher_args(tiny, "--nodes", "2", "--local-world", "1")
    job, macro = train_once(macro_args, cfg, out_dir, "macro")
    check_losses("macro", macro.losses)
    hist = modes(macro)
    print(f"[macro] mode history: {hist}")
    check(any(m.startswith("send") for m in hist)
          and any(m.startswith("receive") for m in hist),
          "mode history has no non-blocking global exchange")
    peak, _ = peak_bytes(jax.devices()[0])
    print(f"[macro] observation: peak_bytes_in_use {peak}")
    stats = macro.executor_stats
    print(f"[macro] executor: {stats.dispatches} dispatches, "
          f"{stats.compiles} compiled cycle shapes, "
          f"{stats.fallback_steps} tail-fallback steps")
    macro_losses, macro_hist = macro.losses, hist
    del macro

    step_args = launcher_args(tiny, "--nodes", "2", "--local-world", "1",
                              "--executor", "per_step")
    _, ref = train_once(step_args, cfg, out_dir, "per_step")
    check_losses("per_step", ref.losses)
    check(modes(ref) == macro_hist, "macro and per-step mode histories "
          "differ")
    d = max_rel_diff(macro_losses, ref.losses)
    print(f"[equivalence] macro vs per_step: max relative loss difference "
          f"{d:.3e} (rtol {rtol:g})")
    check(d <= rtol, f"macro vs per_step losses differ by {d:.3e} > "
          f"{rtol:g}")
    del ref
    return job.params0


def phase_kernels(params, interpret):
    """The five exchange kernels over this model's parameter arena, each
    against its jnp reference in kernels/ref.py."""
    import jax
    import jax.numpy as jnp
    from repro.core import flatbuf
    from repro.kernels import ops, ref

    layout = flatbuf.build_layout(params)
    arenas = jax.jit(lambda p: flatbuf.pack(p, layout))(params)
    check(len(arenas) == 1, f"expected one arena, got {list(arenas)}")
    (key, arena), = arenas.items()
    print(f"[kernels] arena {key} {arena.shape} ({layout.n_leaves} leaves),"
          f" interpret={interpret}")

    def report(name, ok, detail):
        print(f"[kernels] {name}: {detail}")
        check(ok, f"kernel {name} does not match ref.py: {detail}")

    stale = jax.jit(lambda a: (a.astype(jnp.float32) * 0.5 + 0.25)
                    .astype(a.dtype))(arena)
    kw = dict(staleness=2, global_world=2)
    got = ops.eq1_merge(arena, stale, interpret=interpret, **kw)
    want = jax.jit(lambda a, b: ref.eq1_merge_ref(a, b, **kw))(arena, stale)
    # one rounding of the merged value to the arena dtype: the kernel and
    # XLA may each round the f32 quotient differently by an ulp
    ulp = float(jnp.finfo(arena.dtype).eps)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))
                        / jnp.maximum(jnp.abs(want.astype(jnp.float32)),
                                      1e-30)))
    report("eq1_merge", err <= ulp, f"max rel diff {err:.3e} "
           f"(<= 1 {arena.dtype} ulp {ulp:.3e})")
    del stale, got, want

    x32 = arena.astype(jnp.float32)
    packed = ops.bf16_pack(x32, interpret=interpret)
    n_bad = int(jnp.sum(packed != x32.astype(jnp.bfloat16)))
    report("bf16_pack", n_bad == 0, f"{n_bad} elements differ")
    unpacked = ops.bf16_unpack(packed, interpret=interpret)
    n_bad = int(jnp.sum(unpacked != packed.astype(jnp.float32)))
    report("bf16_unpack", n_bad == 0, f"{n_bad} elements differ")
    del packed, unpacked

    v, s = ops.quantize_int8(x32, interpret=interpret)
    vr, sr = jax.jit(ref.quantize_int8_block_ref)(x32)
    s_err = float(jnp.max(jnp.abs(s - sr) / jnp.maximum(sr, 1e-30)))
    v_err = int(jnp.max(jnp.abs(v.astype(jnp.int32) - vr.astype(jnp.int32))))
    # a 1-ulp scale difference may flip a rounding boundary
    report("quantize_int8", s_err <= 1e-6 and v_err <= 1,
           f"scale max rel diff {s_err:.3e}, max value diff {v_err}")
    del vr, sr
    d = ops.dequantize_int8(v, s, interpret=interpret)
    dr = jax.jit(ref.dequantize_int8_block_ref)(v, s)
    n_bad = int(jnp.sum(d != dr))
    report("dequantize_int8", n_bad == 0, f"{n_bad} elements differ")


def phase_four_chips(tiny, rtol, out_dir):
    import jax
    cfg = model_config(tiny)
    topo = ["--topology", FOUR_CHIP_TOPOLOGY]
    _, mesh = train_once(launcher_args(tiny, *topo, "--distributed"), cfg,
                         out_dir, "four_chip")
    check_losses("four_chip", mesh.losses)
    held = []
    for dev in jax.devices():
        peak, now = peak_bytes(dev)
        held.append(peak)
        print(f"[four_chip] observation: device {dev.id} "
              f"peak_bytes_in_use {peak} bytes_in_use {now}")
    # every device holds its replica's full parameter copy (replicas are
    # sharded over host, the batch over chip), so none may stay near empty
    n_params = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(mesh.params))
    if tiny and all(h is None for h in held):
        print("[four_chip] the cpu backend reports no memory stats; "
              "per-device placement not checked")
    else:
        check(all(h is not None and h >= n_params for h in held),
              f"a device never held one replica's parameters "
              f"({n_params} bytes): peaks {held}")

    _, one = train_once(launcher_args(tiny, *topo), cfg, out_dir,
                        "one_device")
    check_losses("one_device", one.losses)
    check(modes(one) == modes(mesh), "mesh and one-device mode histories "
          "differ")
    d = max_rel_diff(mesh.losses, one.losses)
    print(f"[equivalence] 4-chip mesh vs one device: max relative loss "
          f"difference {d:.3e} (rtol {rtol:g})")
    check(d <= rtol, f"mesh vs one-device losses differ by {d:.3e} > "
          f"{rtol:g}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip DASO check on 4 TPUs")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="every phase at --tiny size on the CPU (4 virtual "
                         "devices with --four-chips), kernels in interpret "
                         "mode; reports the CPU as its device")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="directory for the run traces")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=4")
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if platform != want:
        fail(f"first JAX device is {platform!r}, not {want!r}")
    n_want = 4 if args.four_chips else 1
    if len(devices) < n_want:
        fail(f"{len(devices)} {platform} device(s), need {n_want}")
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    entries = len(os.listdir(cache)) if cache and os.path.isdir(cache) else 0
    print(f"device: {device}, compile cache: {cache} ({entries} entries "
          f"at start)")
    os.makedirs(args.out, exist_ok=True)
    tiny = args.cpu_rehearsal
    rtol = LOSS_RTOL_F32 if tiny else LOSS_RTOL

    if args.four_chips:
        phase_four_chips(tiny, rtol, args.out)
    else:
        params = phase_one_chip(tiny, rtol, args.out)
        phase_kernels(params, interpret=args.cpu_rehearsal)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

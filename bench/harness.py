"""One run of one cell: set-up, the timed window, the trace, the check.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the program's own objects: the strategy from
`repro.train.loop.build_strategy`, the loss from
`repro.train.step.make_lm_loss`, momentum SGD, one
`repro.core.executor.MacroCycleExecutor`, the carry from the seeded
weights (`bench/weights.py`) through `strategy.init_carry`. It drives that one
executor and carry through the first DASO cycle from the seed (the
numbers `correct` compares), then through warm cycles that measure the
steady cycle time, and hands the same carry to the window. The window is
one call of the program's `run_compiled_training`, sized to about
`--seconds` in whole cycles; nothing in it compiles. The harness copies no
loop body of the program: it only feeds batches from a pool made in
set-up (`bench/traffic.py`) and notes the time at each cycle's first
batch and after each cycle (the loop's checkpoint callback).

With `--trace 1` the profiler records a few steady cycles of the window
and the per-layer metrics (`bench/metrics/<name>.py`) are read from that
trace; without it the end-to-end metrics are reported. Either way the
run then frees the program's state and follows the same first cycle with
the plain reference (`bench/reference.py`); `bench/checks.py` compares."""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import types

from bench import checks, flops, model, reference, spec, traffic, weights
from bench import trace as tr_mod
from bench.spec import BenchError

WARM_CYCLES = 2          # after the checked one; the second sets the size
TRACE_FIRST = 2          # window cycle at which the profiler starts
TRACE_CYCLES = 3         # cycles it records
MIN_CYCLES = TRACE_FIRST + TRACE_CYCLES + 1
PROGRAM_NAME = "jit_program"   # module of a macro-cycle program
CACHE_DIR = os.path.join(spec.ROOT, ".bench_cache", "jax")
GIB = 2 ** 30


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the chips of this "
                    "machine and print one JSON result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def check_devices(chips: int, peaks: dict):
    """The cell's devices and the peak entry of their kind. Anything but a
    TPU of a kind in `bench/peaks.json`, or fewer chips than the cell asks
    for, is an error: no path falls back."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX's first device is {devs[0].platform!r}, "
                         f"not a TPU")
    if len(devs) < chips:
        raise BenchError(f"{len(devs)} TPU device(s), the cell needs "
                         f"{chips}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks[kind]


class CompileLog:
    """Durations of JAX's backend-compile step (a compile or a load from
    the persistent cache), with the time each ended."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), duration))

    def between(self, t0, t1):
        return [d for t, d in self.events if t0 <= t <= t1]


class GcLog:
    """Pauses of Python's garbage collector (generation, seconds, end
    time), from `gc.callbacks`: the host loop between cycle programs pays
    them."""

    def __init__(self):
        self.pauses, self._t0 = [], None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            t = time.perf_counter()
            self.pauses.append((info["generation"], t - self._t0, t))
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._on)

    def between(self, t0, t1):
        return [(g, d) for g, d, t in self.pauses if t0 <= t <= t1]


class Feed:
    """The cell's `data_fn`: batches from the pool, and the host time at
    each cycle's first batch. It also starts and stops the profiler at
    the cycle boundaries `arm_trace` names, with a `bench.traced_window`
    span over the traced cycles."""

    def __init__(self, pool, b_max):
        self.pool, self.b_max = pool, b_max
        self.starts = {}
        self.trace_at = None

    def arm_trace(self, first_step, stop_step, log_dir):
        self.trace_at = (first_step, stop_step, log_dir)
        self.traced = None

    def __call__(self, step):
        import jax
        if step % self.b_max == 0:
            if self.trace_at is not None:
                self._trace_hook(step)
            self.starts[step] = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.pool_fetch"):
            return self.pool[step % len(self.pool)]

    def _trace_hook(self, step):
        import jax
        first, stop, log_dir = self.trace_at
        if step == first:
            jax.profiler.start_trace(log_dir)
            self._span = jax.profiler.TraceAnnotation("bench.traced_window")
            self._span.__enter__()
            self._t0 = time.perf_counter()
        elif step == stop:
            t1 = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.traced = (first, stop, t1 - self._t0)


def _program_readings(carry, cfg, seed, n_rep):
    """{"mom", "upd", "div", "sent", "spread"}: per replica, the norm of
    each leaf's momentum, of its change from the seeded weights, of its
    distance from replica 0, of the in-flight exchange buffer's change
    from the seeded weights and of that buffer's distance from replica
    0's, from the program's carry."""
    import jax
    import jax.numpy as jnp
    # the very program that made the run's weights: the same generator
    # traced inside another program may round a few elements differently
    p0 = weights.make_params(cfg, seed)

    def norms(params, mu, inflight, p0):
        p0 = jax.tree.leaves(p0)

        def red(x):
            return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                                    axis=tuple(range(1, x.ndim))))
        mom = [red(m) for m in jax.tree.leaves(mu)]
        leaves = jax.tree.leaves(params)
        upd = [red(p.astype(jnp.float32) - b.astype(jnp.float32)[None])
               for p, b in zip(leaves, p0)]
        div = [red(p.astype(jnp.float32) - p[:1].astype(jnp.float32))
               for p in leaves]
        sent = [red(x.astype(jnp.float32) - b.astype(jnp.float32)[None])
                for x, b in zip(jax.tree.leaves(inflight), p0)]
        spread = [red(x.astype(jnp.float32) - x[:1].astype(jnp.float32))
                  for x in jax.tree.leaves(inflight)]
        return tuple(map(jnp.stack, (mom, upd, div, sent, spread)))

    got = jax.device_get(jax.jit(norms)(carry[0], carry[1]["mu"], carry[2],
                                        p0))
    paths = weights.leaf_paths(cfg)
    return {k: [dict(zip(paths, map(float, v[:, r]))) for r in range(n_rep)]
            for k, v in zip(("mom", "upd", "div", "sent", "spread"), got)}


def _check_layout(arch, cfg):
    import jax
    from repro.models.lm import init_params
    want = jax.eval_shape(lambda k: init_params(arch, k),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(weights.make_fn(cfg), weights.seed_key(0))
    same = (jax.tree.structure(want) == jax.tree.structure(got)
            and all((a.shape, a.dtype) == (b.shape, b.dtype) for a, b in
                    zip(jax.tree.leaves(want), jax.tree.leaves(got))))
    if not same:
        raise BenchError("the program's parameter layout differs from "
                         "bench/weights.py's")


def build_program(cell):
    """The program's strategy and executor for this cell, built as its
    own entry points build them."""
    from repro.core.executor import MacroCycleExecutor
    from repro.optim.optimizers import sgd
    from repro.train.loop import TrainLoopConfig, build_strategy
    from repro.train.step import make_lm_loss
    cfg, tr = cell.config, cell.traffic
    if cell.chips != 1:
        raise BenchError(f"cell {cell.name!r} asks for {cell.chips} chips; "
                         f"the harness drives one (no mesh placement yet)")
    arch = model.arch_config(cfg)
    _check_layout(arch, cfg)
    loop_cfg = TrainLoopConfig(
        strategy=tr["strategy"], n_steps=10 ** 9, n_replicas=tr["replicas"],
        local_world=tr["local_world"], b_max=tr["b_max"],
        topology=tr["topology"], warmup_frac=0.0, cooldown_frac=0.0,
        lr=tr["lr"], loss_window=10 ** 9)
    strategy = build_strategy(
        make_lm_loss(arch), loop_cfg,
        sgd(momentum=tr["momentum"], weight_decay=tr["weight_decay"]))
    return strategy, MacroCycleExecutor(strategy, tail_fallback=False)


def device_pool(host_pool, device):
    """The pool as the feed serves it: one batch per step, on the chip."""
    import jax
    n = len(next(iter(host_pool.values())))
    return [{k: jax.device_put(v[i], device) for k, v in host_pool.items()}
            for i in range(n)]


class Runner:
    """Drives the program's `run_compiled_training` over one executor and
    one carry, a call at a time, noting the host time after every cycle
    (its checkpoint callback) and, after the first cycle, the numbers the
    check compares."""

    def __init__(self, cell, strategy, ex, feed, seed):
        self.cell, self.strategy, self.ex, self.feed = (cell, strategy, ex,
                                                        feed)
        self.seed, self.b_max = seed, cell.traffic["b_max"]
        self.done, self.prog, self.carry = {}, None, None
        lr = float(cell.traffic["lr"])
        self.lr_fn = lambda _step: lr

    def _keep(self, step, carry, _losses):
        self.done[step] = time.perf_counter()
        self.carry = carry
        if step == self.b_max and self.prog is None:
            self.prog = _program_readings(carry, self.cell.config, self.seed,
                                          self.cell.traffic["replicas"])

    def call(self, first, last):
        """Steps [first, last) in one call; returns its SimResult."""
        from repro.core.executor import run_compiled_training
        if first == 0:
            self.carry = self.strategy.init_carry(
                weights.make_params(self.cell.config, self.seed))
        carry, self.carry = self.carry, None
        res = run_compiled_training(
            self.strategy, None, self.feed, self.lr_fn, last,
            executor=self.ex, start_step=first, carry=carry,
            ckpt_every=self.b_max, ckpt_cb=self._keep)
        if first == 0:
            self.prog["losses"] = list(res.losses[:self.b_max])
        return res


def run_cell(cell, seed, seconds, trace_on, devices, peak, start, *,
             log=print):
    """Set-up, window, trace and check of one run. Returns the result
    dict (without `checks`) and the list of (check, value, limit)."""
    import jax
    import numpy as np

    cfg, tr = cell.config, cell.traffic
    compiles = CompileLog()
    gcs = GcLog()
    strategy, ex = build_program(cell)
    host_pool = traffic.make_pool(cfg["vocab_size"], tr, seed)
    B = tr["b_max"]
    feed = Feed(device_pool(host_pool, devices[0]), B)
    runner = Runner(cell, strategy, ex, feed, seed)
    # the checked cycle, then the warm ones, on the same executor and carry
    runner.call(0, B)
    w0 = B * (1 + WARM_CYCLES)
    runner.call(B, w0)
    done = runner.done
    cycle_s = done[w0] - feed.starts[w0 - B]
    n_cycles = max(MIN_CYCLES, round(seconds / cycle_s))
    log(f"set-up: steady cycle {cycle_s:.4f} s ({B} steps) -> window of "
        f"{n_cycles} cycles")
    # set-up's garbage (tracing, compiling, the weights) is collected here,
    # so that no collection in the window pays for it
    gc.collect()

    trace_dir = None
    if trace_on:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        feed.arm_trace(w0 + TRACE_FIRST * B,
                       w0 + (TRACE_FIRST + TRACE_CYCLES) * B, trace_dir)
    w_end = w0 + n_cycles * B
    res = runner.call(w0, w_end)
    t_first, t_last = feed.starts[w0], done[w_end]
    setup_s = t_first - start
    window_s = t_last - t_first

    starts = [feed.starts[s] for s in range(w0, w_end, B)] + [t_last]
    step_ms = [(b - a) / B * 1e3 for a, b in zip(starts, starts[1:])]
    losses = np.asarray(res.losses, np.float64)
    in_window = compiles.between(t_first, t_last)
    shapes = sorted({tuple(m for _, m, _, _ in strategy.controller.history[
        s:s + B]) for s in range(w0, w_end, B)})
    log(f"window: {n_cycles} cycles, {w_end - w0} steps, modes {shapes}, "
        f"compiles in window {len(in_window)}")
    log(f"window: ms per step by cycle {[round(x, 3) for x in step_ms]}")
    if in_window:
        log(f"warning: {len(in_window)} compile(s) inside the window")
    paused = gcs.between(t_first, t_last)
    gcs.close()
    log(f"window: garbage collections {len(paused)} "
        f"({sum(g == 2 for g, _ in paused)} full), "
        f"{sum(d for _, d in paused):.4f} s, longest "
        f"{max((d for _, d in paused), default=0.0):.4f} s")
    drop = [m["moe_drop_frac"] for m in res.metrics if "moe_drop_frac" in m]
    if drop:
        log(f"window: moe_drop_frac mean {statistics.mean(drop):.6f} "
            f"max {max(drop):.6f}")
    log(f"window: loss {losses[0]:.5f} -> {losses[-1]:.5f}")
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in
           jax.devices()[:max(cell.chips, 1)]]
    log(f"peak_bytes_in_use per device: {mem}")
    mem_peak = max((m for m in mem if m is not None), default=None)

    tokens_per_step = (tr["replicas"] * tr["sequences_per_replica"]
                       * tr["seq_len"])
    result = {"correct": False, "attempted": int(w_end - w0),
              "failed": int(np.sum(~np.isfinite(losses))),
              "metrics": {}, "device": {
                  "platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": mem_peak}}
    if not trace_on:
        e2e = {
            "tokens_per_s_per_chip": tokens_per_step * (w_end - w0)
            / window_s / cell.chips,
            "step_ms_p95": float(np.percentile(step_ms, 95)),
            "peak_hbm_gib": (mem_peak / GIB if mem_peak is not None
                             else None),
            "setup_s": setup_s}
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                "unit": m["unit"]}
    else:
        if feed.traced is None:
            raise BenchError("the window ended before the traced cycles")
        t_a, t_b, traced_wall = feed.traced
        events = tr_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        window = tr_mod.span(events, "bench.traced_window")
        if window is None:
            raise BenchError("the trace holds no bench.traced_window span")
        planes = tr_mod.device_planes(events)
        busy = [tr_mod.busy_ns(events, p, window) for p in planes]
        # what a per-layer metric reader (bench/metrics/<name>.py) sees
        run = types.SimpleNamespace(
            cfg=cfg, traffic=tr, chips=cell.chips, peak=peak,
            flops_per_token=flops.flops_per_token(cfg, tr["seq_len"]),
            compile_setup_s=sum(compiles.between(start, t_first)),
            events=events, window=window, program_name=PROGRAM_NAME,
            traced_steps=t_b - t_a, traced_tokens=tokens_per_step
            * (t_b - t_a))
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = (sum(busy) / len(busy) / 1e9
                                      if busy else 0.0)
        result["device"]["window_s"] = (window[1] - window[0]) / 1e9
        result["breakdown"] = tr_mod.breakdown(events, window)
        log(f"trace: {len(events)} events, device planes {planes}, "
            f"traced {t_b - t_a} steps over {traced_wall:.4f} s")

    # the check: free the program's state, then the plain reference
    prog = runner.prog
    del res, feed, runner, ex, strategy
    gc.collect()
    ref = reference.run(cfg, tr, seed, host_pool, B,
                        devices=list(devices))
    values = checks.readings(prog, ref)
    result["correct"] = checks.judge(values, cell.limits)
    log(f"check: program losses {prog['losses']}")
    log(f"check: reference losses {ref['losses']}")
    return result, [(k, values[k], cell.limits[k]) for k in checks.CHECKS]


def _fmt(v):
    return v if v is None or math.isfinite(v) else str(v)


def main(argv, start):
    args = parse_args(argv)
    try:
        cell = spec.load_cell(args.workload)
        src = os.path.join(spec.ROOT, "src")
        if not os.path.isdir(os.path.join(src, "repro")):
            raise BenchError(f"no program under {src}")
        sys.path.insert(0, src)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        import jax
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devices, peak = check_devices(cell.chips, spec.load_peaks())
        result, compared = run_cell(
            cell, args.seed, args.seconds, bool(args.trace), devices, peak,
            start, log=lambda s: print(s, flush=True))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    result["checks"] = {k: {"value": _fmt(v), "limit": lim}
                        for k, v, lim in compared}
    print(f"correct {result['correct']}", file=sys.stderr)
    for k, v, lim in compared:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0

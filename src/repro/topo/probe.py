"""Runtime topology probing: measure what the links actually deliver and
feed it back into the lowered schedule.

A `TopologySpec` carries hand-written bandwidth *annotations*; the lowering
(`repro.topo.lower.derive_inner_periods`) freezes per-level periods from
them. On a drifting cluster those annotations go stale — the reason
DS-Sync-style degraded-network adaptation exists. This module closes the
loop with three probes feeding one hook:

  * **active probe** (`active_probe`) — time one real `level_group_mean`
    per replica level on the live mesh at startup (and optionally every K
    cycles): a few extra collectives, ground truth per level;
  * **passive probe** (`fit_level_costs`) — the PR 8 tracer already spans
    every per-level sync (`obs.meters.LevelMeter.measured_sync_s`); the
    per-level median of those samples is a probe that costs zero extra
    traffic;
  * **skew probe** (`skew_permutation`) — per-replica cycle-time skew
    (heartbeat step deltas on the live runtime, injected slowdowns in the
    fault simulator) sorted into a regrouping permutation, so
    similar-speed replicas share inner groups.

All three produce plain dicts/tuples consumed by
`DasoController.retune` / `HierDasoController.retune` (period re-derivation
+ effective-DCN-scale inference) and `DasoStrategy.set_group_permutation`
(reshuffle); the resilience supervisor wires them together under
``autotune_every`` (resilience/supervisor.py), the launcher under
``--autotune`` (docs/tuning.md walks the whole loop).

The cost model is deliberately first-order — ``t_l = bytes / bw_l`` — so
that probing a cluster that matches its annotations is a *strict no-op*:
`annotated_level_costs` -> `derive_retuned_periods` reproduces the static
lowering bit-for-bit (doctested below; latency/wire-format refinements
live in benchmarks.comm_model.topology_level_costs).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, Iterable, Optional, Sequence, Tuple

from repro.topo.spec import TopologySpec

# key for the outermost level in cost dicts: the controllers have no spec,
# so the outer level travels under a fixed name rather than its spec name
OUTER_KEY = "_outer"


def annotated_level_costs(spec: TopologySpec,
                          param_bytes: float = 4e6) -> Dict[str, float]:
    """Nominal seconds-per-sync of every non-degenerate replica level under
    the pure bandwidth model ``t_l = param_bytes / bw_l`` (outermost under
    `OUTER_KEY`). This is the probe's reference point: `retune` infers the
    effective DCN scale from measured/annotated ``_outer`` ratio, and
    `derive_retuned_periods` on these exact costs reproduces the static
    lowering — the no-op invariant tests/test_tuning.py pins.

    >>> s = TopologySpec.parse("chip:4 x host:2@50e9 x pod:2@25e9")
    >>> c = annotated_level_costs(s, param_bytes=100e9)
    >>> c["host"], c["_outer"]
    (2.0, 4.0)
    """
    costs: Dict[str, float] = {}
    for lvl in spec.levels[1:-1]:
        if spec.group_size(lvl.name) == 1:
            continue  # elided from the schedule — nothing to retune
        costs[lvl.name] = param_bytes / lvl.bandwidth
    costs[OUTER_KEY] = param_bytes / spec.outer.bandwidth
    return costs


def measured_bandwidths(spec: TopologySpec, costs: Dict[str, float],
                        param_bytes: float = 4e6) -> Dict[str, float]:
    """Invert measured per-sync costs back to effective bytes/s, keyed by
    spec level name — the dict `repro.topo.lower.derive_inner_periods`
    accepts as its ``bandwidths`` override (this is how measurement enters
    the lowering). Non-positive costs are dropped (a failed probe leaves
    the annotation in force).

    >>> s = TopologySpec.parse("chip:4 x host:2@50e9 x pod:2@25e9")
    >>> bw = measured_bandwidths(s, {"host": 2.0, "_outer": 4.0},
    ...                          param_bytes=100e9)
    >>> bw["host"], bw["pod"]
    (50000000000.0, 25000000000.0)
    """
    out: Dict[str, float] = {}
    for name, t in costs.items():
        if not t or t <= 0:
            continue
        out[spec.outer.name if name == OUTER_KEY else name] = param_bytes / t
    return out


def derive_retuned_periods(spec: TopologySpec, costs: Dict[str, float], *,
                           b_max: int = 4,
                           param_bytes: float = 4e6) -> Dict[str, int]:
    """Re-derive the inner periods from *measured* costs: the same
    bandwidth-ratio rule as the static lowering, with measurements standing
    in for annotations (bandwidth is bytes over time, so cost ratios and
    bandwidth ratios are the same quantity). ``%period`` pins keep winning.

    Annotated costs reproduce the static schedule exactly:

    >>> from repro.topo.lower import derive_inner_periods
    >>> s = TopologySpec.parse("chip:4 x host:2@50e9 x pod:2@25e9")
    >>> (derive_retuned_periods(s, annotated_level_costs(s))
    ...  == derive_inner_periods(s, b_max=4))
    True

    A host link measured at quarter speed syncs that level less often:

    >>> c = annotated_level_costs(s)
    >>> c["host"] *= 4
    >>> derive_retuned_periods(s, c)
    {'host': 4}
    """
    from repro.topo.lower import derive_inner_periods
    return derive_inner_periods(
        spec, b_max=b_max,
        bandwidths=measured_bandwidths(spec, costs,
                                       param_bytes=param_bytes))


@dataclass(frozen=True)
class ProbeResult:
    """One active-probe round: measured seconds-per-sync per level (keys as
    in `annotated_level_costs`), a per-level value checksum (the
    determinism witness — under ``deterministic_reduce`` two probes of the
    same mesh produce identical checksums), and the probe payload size."""
    costs: Dict[str, float]
    checksums: Dict[str, float]
    rounds: int
    param_bytes: float


def active_probe(spec: TopologySpec, *, n_values: int = 1 << 12,
                 rounds: int = 3, deterministic: bool = True,
                 mask=None) -> ProbeResult:
    """Time one real `level_group_mean` per replica level on the live mesh.

    Builds a deterministic dummy arena of ``n_values`` floats per replica,
    jits the exact group mean each level's schedule runs (same group
    sizes, same membership mask, same reduce order), and times it
    ``rounds`` times after a compile warm-up, keeping the per-level
    minimum (the least-noise estimate of the true cost). The returned
    costs feed `HierDasoController.retune` against
    `annotated_level_costs(spec, result.param_bytes)`; the checksums are
    the probe's own numerics regression handle."""
    import jax
    import jax.numpy as jnp

    from repro.core.daso import DasoConfig, Exchange, level_group_mean

    r = spec.n_replicas
    ex = Exchange.of(DasoConfig(n_replicas=r, global_world=spec.world,
                                deterministic_reduce=deterministic),
                     membership=mask)
    arena = (jnp.arange(r * n_values, dtype=jnp.float32)
             .reshape(r, n_values) / float(r * n_values))
    tree = {"probe": arena}
    targets = [(lvl.name, spec.group_size(lvl.name))
               for lvl in spec.levels[1:-1]
               if spec.group_size(lvl.name) > 1]
    targets.append((OUTER_KEY, r))

    costs: Dict[str, float] = {}
    checksums: Dict[str, float] = {}
    for name, g in targets:
        fn = jax.jit(lambda t, g=g: level_group_mean(t, g, ex=ex))
        out = jax.block_until_ready(fn(tree))  # compile outside the timing
        checksums[name] = float(jnp.sum(out["probe"]))
        best = float("inf")
        for _ in range(max(1, rounds)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(tree))
            best = min(best, time.perf_counter() - t0)
        costs[name] = best
    return ProbeResult(costs=costs, checksums=checksums,
                       rounds=max(1, rounds),
                       param_bytes=float(arena.size * 4))


def fit_level_costs(samples: Iterable[Tuple[str, float]]
                    ) -> Dict[str, float]:
    """Passive probe: per-level cost from sync-span samples the tracer
    already collects during normal training (``(level_name, seconds)``
    pairs — `obs.meters.LevelMeter.measured_sync_s` or the trace's
    per-level comm spans). The per-level *median* is the estimate: robust
    to the one-off spikes (compile, checkpoint stall) that pollute a mean.

    >>> fit_level_costs([("host", 2.0), ("host", 100.0), ("host", 2.5),
    ...                  ("_outer", 4.0)])
    {'host': 2.5, '_outer': 4.0}
    """
    by_level: Dict[str, list] = {}
    for name, s in samples:
        by_level.setdefault(name, []).append(float(s))
    out: Dict[str, float] = {}
    for name, xs in by_level.items():
        xs = sorted(xs)
        n = len(xs)
        out[name] = (xs[n // 2] if n % 2
                     else 0.5 * (xs[n // 2 - 1] + xs[n // 2]))
    return out


def skew_permutation(slowdowns: Sequence[float], *,
                     rel_tol: float = 0.1) -> Optional[Tuple[int, ...]]:
    """Straggler-aware regrouping permutation: slot order = replicas sorted
    by slowdown (stable, so equal-speed replicas keep their relative
    order). Consecutive slots share an inner group
    (`DasoStrategy.set_group_permutation`), so similar-speed replicas are
    packed together and a straggler's inner barrier delays only its own
    group — the recoverable part of the wait (`wasted_wait_s`).

    Skew below `rel_tol` (max/min - 1) returns None: the identity keeps
    the unpermuted fast-path HLO, and a near-uniform fleet should not pay
    a recompile for noise.

    >>> skew_permutation([1.0, 3.0, 1.0, 3.0])
    (0, 2, 1, 3)
    >>> skew_permutation([1.0, 1.02, 0.99, 1.0]) is None
    True
    """
    xs = [float(s) for s in slowdowns]
    if not xs or min(xs) <= 0:
        return None
    if max(xs) / min(xs) - 1.0 <= rel_tol:
        return None
    return tuple(sorted(range(len(xs)), key=lambda i: (xs[i], i)))


def wasted_wait_s(slowdowns: Sequence[float], mask, group_size: int,
                  perm: Optional[Tuple[int, ...]],
                  t_compute_s: float) -> float:
    """Per-step straggler wait an inner-group barrier wastes: every active
    replica waits for its group's slowest member, so the waste is
    ``sum_r (group_max_slowdown - own_slowdown) * t_compute``. The global
    makespan is gated by the worst straggler regardless — this is the
    *recoverable* slack reshuffling targets, and the honest metric
    BENCH_tuning.json gates (`reshuffle_wait_ratio`).

    >>> wasted_wait_s([1.0, 3.0, 1.0, 3.0], None, 2, None, 1.0)
    4.0
    >>> wasted_wait_s([1.0, 3.0, 1.0, 3.0], None, 2, (0, 2, 1, 3), 1.0)
    0.0
    """
    n = len(slowdowns)
    order = list(perm) if perm is not None else list(range(n))
    total = 0.0
    for g0 in range(0, n, max(1, group_size)):
        members = order[g0:g0 + max(1, group_size)]
        active = [r for r in members if mask is None or mask[r]]
        if not active:
            continue
        worst = max(slowdowns[r] for r in active)
        total += sum(worst - slowdowns[r] for r in active)
    return total * t_compute_s

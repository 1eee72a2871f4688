"""Relaxed-synchronization baseline strategies: gossip / EASGD / DOWNPOUR.

DASO (core/daso.py) is one point in the design space the paper positions
itself in; this module adds the three classic neighbors under the same
`register_strategy` registry so every executor surface — macro-cycle
compilation, per-step oracle, checkpoint TrainState, elastic membership,
the supervisor's fault plans — drives them through the identical Strategy
interface (and tests/test_strategies.py proves it with one shared
conformance battery):

  * **gossip** — pairwise parameter exchange over the replica axis: every
    B steps each replica averages with ONE partner, a ring shift whose
    offset rotates between exchanges so information percolates the whole
    ring. No global collective — the partner copy moves as a permutation
    of the packed flat-buffer arena (`jnp.roll` on the replica axis, which
    GSPMD lowers to collective-permute on a sharded mesh), wire-encoded at
    the non-blocking tier ("How to scale distributed deep learning?",
    Jin et al.).
  * **easgd** — Elastic Averaging SGD: replicas are pulled toward a
    tracked center variable by an elastic term `params ← (1-α)·params +
    α·center`, while the center tracks the replica mean as a moving
    average `center ← (1-β)·center + β·mean(params)` with β = α·n_active
    (Zhang et al., 2015). One global all-reduce per exchange step.
  * **downpour** — DOWNPOUR's parameter server modeled as SPMD state:
    each replica accumulates a local delta against the last server
    snapshot (the `anchor` carry slot); a push applies the sum of active
    deltas to the server copy and redistributes it. The masked replica
    mean times n_active IS the delta sum, so the whole push is one
    all-reduce — a designated-replica server would break the
    one-program-per-cycle SPMD contract for no modeling gain (Dean et
    al., 2012).

All three run the *periodic* schedule (`PeriodicController`): blocking
warm-up/cool-down phases exactly like DASO, and one exchange every B
steps in between — B inherits the paper's plateau halve/reset rule, so
the exchange period adapts to training progress just like DASO's send
period. None of them has a non-blocking in-flight exchange, so overlap
is rejected up front.

Carry layouts (the conformance suite's checkpoint leg round-trips each):

    gossip    (params_R, opt_R)             2 slots
    easgd     (params_R, opt_R, center_R)   3 slots
    downpour  (params_R, opt_R, anchor_R)   3 slots
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import flatbuf
from repro.core.daso import (Exchange, _member_step, _step_metrics,
                             blocking_sync, freeze_inactive, replica_mean,
                             replicate_params)
from repro.core.executor import DasoStrategy, register_strategy
from repro.core.schedule import DasoController, Mode, split_mode, split_ov


# -- periodic controllers ------------------------------------------------------

@dataclass
class PeriodicController(DasoController):
    """DASO's phase structure with the send/wait pair collapsed to one
    periodic exchange token: blocking warm-up/cool-down, then one
    `exchange_token(step)` every B steps of the cycling phase. There is
    never an exchange in flight (`_inflight_since` stays None), so the
    base class's macro-cycle planner and plateau-driven B halving work
    unchanged — a plateau shortens the exchange period exactly like it
    shortens DASO's send period."""
    #: outer-mode token emitted every B cycling steps (subclasses override
    #: the class attr or `exchange_token` for per-exchange variation)
    exchange_base = Mode.HARD_AVG
    #: exchanges emitted so far (drives gossip's rotating partner offset;
    #: checkpointed so a resumed ring continues where it left off)
    _n_ex: int = field(init=False, default=0)

    _STATE_FIELDS = DasoController._STATE_FIELDS + ("_n_ex",)

    def exchange_token(self, step: int) -> str:
        return self.exchange_base

    def mode_for_step(self, step: int) -> Tuple[str, int]:
        ph = self.phase(step)
        if ph in ("warmup", "cooldown"):
            self._inflight_since = None
            self._ov_last = None
            mode = Mode.BLOCKING
        elif self._would_send(step):
            self._last_send = step
            mode = self.exchange_token(step)
            self._n_ex += 1
        else:
            mode = Mode.LOCAL
        self.history.append((step, mode, self._b, self._w))
        return mode, 1


@dataclass
class GossipController(PeriodicController):
    """Each exchange pairs replica i with replica (i + shift) mod R; the
    shift rotates 1..R-1 between exchanges so consecutive exchanges use
    different partners and the ring mixes globally (a fixed shift of 1
    would need R-1 exchanges to percolate; the rotation is the cheap
    deterministic stand-in for randomized gossip matching)."""
    exchange_base = Mode.GOSSIP

    def exchange_token(self, step: int) -> str:
        r = self.cfg.n_replicas
        shift = (self._n_ex % (r - 1)) + 1 if r > 1 else 1
        return f"{Mode.GOSSIP}~{shift}"


@dataclass
class EasgdController(PeriodicController):
    exchange_base = Mode.ELASTIC


@dataclass
class DownpourController(PeriodicController):
    exchange_base = Mode.PUSH


# -- gossip exchange primitive -------------------------------------------------

def gossip_mix(tree, *, ex: Exchange, shift: int):
    """One pairwise gossip exchange over the leading replica axis:
    ``row_i ← (row_i + row_{(i+shift) mod R}) / 2``.

    Runs on the packed flat-buffer arenas (one permutation per dtype arena
    regardless of leaf count), whatever `ex.leafwise` says. Only the
    PARTNER copy is encoded at the cycling tier `ex.send_wire` — the wire
    format models what crosses the network, and a replica's own row never
    leaves the chip. There is no reduction anywhere (`ex.deterministic`
    does not apply), so the result is bit-identical for any device layout
    (the 2-proc == 1-proc contract holds without a deterministic-reduce
    fallback), and on a replica-sharded mesh the ring shift lowers to data
    movement (collective-permute family), never an all-reduce.

    `ex.mask`: rows mix only when BOTH endpoints are active; a pair with a
    dead endpoint keeps its own row (dead rows stay frozen ghosts). Under
    partial membership the exchange is therefore mass-preserving only
    pairwise, not globally — the property-test guarantee (mean
    preservation for any shift schedule) is stated for full membership."""
    wire_format, use_kernels, mask = ex.send_wire, ex.kernels, ex.mask
    layout = flatbuf.build_layout(tree, batch_dims=1)
    arenas = flatbuf.pack(tree, layout)
    r = layout.batch_shape[0]
    if not 1 <= shift < max(r, 2):
        raise ValueError(f"gossip shift {shift} outside 1..{r - 1}")

    col = None
    if mask is not None:
        m = jnp.asarray(mask, jnp.bool_)
        col = (m & jnp.roll(m, -shift))[:, None]  # both endpoints active

    def mix(arena):
        partner = jnp.roll(arena, -shift, axis=0)
        if not jnp.issubdtype(arena.dtype, jnp.floating):
            out = jnp.round(0.5 * (arena.astype(jnp.float32)
                                   + partner.astype(jnp.float32)))
        else:
            if wire_format == "int8":
                partner = flatbuf.wire_roundtrip(partner, "int8",
                                                 use_kernels=use_kernels)
            elif wire_format == "bf16":
                partner = flatbuf.encode_wire(partner, "bf16",
                                              use_kernels=use_kernels)
            out = 0.5 * (arena.astype(jnp.float32)
                         + partner.astype(jnp.float32))
        out = out.astype(arena.dtype)
        return out if col is None else jnp.where(col, out, arena)

    return flatbuf.unpack({k: mix(a) for k, a in arenas.items()}, layout)


# -- assembled train steps -----------------------------------------------------

def gossip_train_step(loss_fn, optimizer, cfg, *, mode: str, shift: int = 1,
                      n_micro: int = 1, ex: Optional[Exchange] = None):
    """step(params_R, opt_R, batch_R, lr) -> (params_R, opt_R, metrics).
    `mode` is local | blocking | gossip (shift decoded by the caller);
    `ex` (default `Exchange.of(cfg)`) as in `daso_train_step`."""
    assert mode in (Mode.LOCAL, Mode.BLOCKING, Mode.GOSSIP), mode
    ex = Exchange.of(cfg) if ex is None else ex
    lstep = _member_step(loss_fn, optimizer, ex, n_micro=n_micro)

    def step(params, opt_state, batch, lr):
        params, opt_state, loss_r, aux_r = lstep(params, opt_state, batch,
                                                 lr)
        if mode == Mode.GOSSIP:
            params = gossip_mix(params, ex=ex, shift=shift)
        elif mode == Mode.BLOCKING:
            params = blocking_sync(params, ex=ex)
        return params, opt_state, _step_metrics(ex, loss_r, aux_r)

    return step


def easgd_train_step(loss_fn, optimizer, cfg, *, mode: str, alpha: float,
                     n_micro: int = 1, ex: Optional[Exchange] = None):
    """step(params_R, opt_R, center_R, batch_R, lr)
        -> (params_R, opt_R, center_R, metrics).

    `mode` elastic: the ONE outer collective is the masked replica mean m
    of the post-step params, at the cycling tier; then the elastic pull
    `params ← (1-α)params + α·center` and the center update `center ←
    (1-β)center + β·m` with β = α·n_active (the symmetric coupling of
    Zhang et al. §2: the center moves by α per attached replica). `mode`
    blocking resets the center to the freshly synced params — a full
    average IS the consensus, so warm-up/cool-down leave nothing elastic
    to track. The center rows are global state (identical across replicas
    by construction) and are never membership-frozen; dead PARAM rows
    stay frozen ghosts."""
    assert mode in (Mode.LOCAL, Mode.BLOCKING, Mode.ELASTIC), mode
    ex = Exchange.of(cfg) if ex is None else ex
    lstep = _member_step(loss_fn, optimizer, ex, n_micro=n_micro)
    beta = alpha * ex.n_active

    def lerp(a_tree, b_tree, t):
        # (1-t)·a + t·b in f32; integer leaves round back (same treatment
        # as the arena mean in core/daso.py)
        def leaf(x, y):
            out = ((1.0 - t) * x.astype(jnp.float32)
                   + t * y.astype(jnp.float32))
            if not jnp.issubdtype(x.dtype, jnp.floating):
                out = jnp.round(out)
            return out.astype(x.dtype)
        return jax.tree.map(leaf, a_tree, b_tree)

    def step(params, opt_state, center, batch, lr):
        params, opt_state, loss_r, aux_r = lstep(params, opt_state, batch,
                                                 lr)
        if mode == Mode.ELASTIC:
            m = replica_mean(params, ex=ex, wire=ex.send_wire)
            params = freeze_inactive(lerp(params, center, alpha),
                                     params, ex.mask)
            center = lerp(center, m, beta)
        elif mode == Mode.BLOCKING:
            params = blocking_sync(params, ex=ex)
            center = jax.tree.map(jnp.array, params)
        return params, opt_state, center, _step_metrics(ex, loss_r, aux_r)

    return step


def downpour_train_step(loss_fn, optimizer, cfg, *, mode: str,
                        push_scale: float = 1.0, n_micro: int = 1,
                        ex: Optional[Exchange] = None):
    """step(params_R, opt_R, anchor_R, batch_R, lr)
        -> (params_R, opt_R, anchor_R, metrics).

    `anchor` is the server's parameter copy at the last push (identical
    across replicas). A push applies the SUM of the active replicas'
    accumulated deltas to the server — computed as
    ``n_active · masked_mean(params - anchor)``, which is one masked
    all-reduce at the cycling tier, the SPMD rendering of DOWNPOUR's
    server addition — then redistributes: ``params = anchor = server``.
    `push_scale` is the server-side learning rate on the delta sum (1.0 =
    apply verbatim). Dead rows contribute zero delta (masked out) and keep
    their frozen ghost params; the anchor rows update everywhere (server
    state)."""
    assert mode in (Mode.LOCAL, Mode.BLOCKING, Mode.PUSH), mode
    ex = Exchange.of(cfg) if ex is None else ex
    lstep = _member_step(loss_fn, optimizer, ex, n_micro=n_micro)

    def step(params, opt_state, anchor, batch, lr):
        params, opt_state, loss_r, aux_r = lstep(params, opt_state, batch,
                                                 lr)
        if mode == Mode.PUSH:
            delta = jax.tree.map(
                lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32),
                params, anchor)
            dmean = replica_mean(delta, ex=ex, wire=ex.send_wire)

            def apply(a, d):
                out = (a.astype(jnp.float32)
                       + push_scale * ex.n_active * d.astype(jnp.float32))
                if not jnp.issubdtype(a.dtype, jnp.floating):
                    out = jnp.round(out)
                return out.astype(a.dtype)

            server = jax.tree.map(apply, anchor, dmean)
            params = freeze_inactive(server, params, ex.mask)
            anchor = jax.tree.map(jnp.array, server)
        elif mode == Mode.BLOCKING:
            params = blocking_sync(params, ex=ex)
            anchor = jax.tree.map(jnp.array, params)
        return params, opt_state, anchor, _step_metrics(ex, loss_r, aux_r)

    return step


# -- strategies ----------------------------------------------------------------

class PeriodicStrategy(DasoStrategy):
    """Shared base for the baseline family: replica-axis carry, a
    `PeriodicController` schedule, no overlap, no in-flight buffer. The
    DasoStrategy surface (membership baking, step-fn cache, cycle
    planning, first-active finalize) is inherited unchanged — subclasses
    provide the controller class and the per-mode step builder."""
    controller_cls = PeriodicController

    def __init__(self, loss_fn, optimizer, cfg, *, membership=None,
                 controller=None, n_micro=1):
        assert cfg is not None, f"{self.name} strategy requires a DasoConfig"
        if cfg.overlap != "off":
            raise ValueError(
                f"strategy {self.name!r} has no non-blocking exchange to "
                "overlap; run it with overlap='off'")
        if cfg.n_replicas < 2:
            raise ValueError(f"strategy {self.name!r} exchanges between "
                             f"replicas; n_replicas must be >= 2, got "
                             f"{cfg.n_replicas}")
        if controller is None:
            controller = self.make_controller(cfg)
        elif not isinstance(controller, PeriodicController):
            raise TypeError(
                f"strategy {self.name!r} needs a periodic controller "
                f"(use {type(self).__name__}.make_controller); got "
                f"{type(controller).__name__}")
        super().__init__(loss_fn, optimizer, cfg, membership=membership,
                         controller=controller, n_micro=n_micro)

    @classmethod
    def make_controller(cls, cfg, *, loss_window: int = 50):
        return cls.controller_cls(cfg, loss_window=loss_window)


@register_strategy("gossip")
class GossipStrategy(PeriodicStrategy):
    """Pairwise gossip averaging; 2-slot carry (params, opt_state)."""
    controller_cls = GossipController

    def init_carry(self, params0):
        params = replicate_params(params0, self.cfg.n_replicas)
        opt_state = replicate_params(self.optimizer.init(params0),
                                     self.cfg.n_replicas)
        return (params, opt_state)

    def build_step(self, mode, staleness):
        outer, inner = split_mode(mode)
        self._inner_syncs_of(inner)  # no topology: reject inner syncs
        base, shift = split_ov(outer)
        raw = gossip_train_step(self.loss_fn, self.optimizer, self.cfg,
                                mode=base, shift=max(shift, 1),
                                n_micro=self.n_micro,
                                ex=self.exchange)

        def step(carry, batch, lr):
            params, opt_state = carry
            params, opt_state, m = raw(params, opt_state, batch, lr)
            return (params, opt_state), m

        return step


@register_strategy("easgd")
class EasgdStrategy(PeriodicStrategy):
    """Elastic Averaging SGD; 3-slot carry (params, opt_state, center).

    `alpha` is the elastic coupling (per-exchange pull toward the
    center); the center's own rate is β = α·n_active, so stability needs
    α·n_replicas < 1. Default: α = 0.5 / n_replicas (β = 0.5 with the
    full world active)."""
    controller_cls = EasgdController

    def __init__(self, loss_fn, optimizer, cfg, *,
                 alpha: Optional[float] = None, **kw):
        super().__init__(loss_fn, optimizer, cfg, **kw)
        self.alpha = 0.5 / cfg.n_replicas if alpha is None else float(alpha)
        if not 0.0 < self.alpha * cfg.n_replicas < 1.0:
            raise ValueError(
                f"easgd needs 0 < alpha * n_replicas < 1 for a stable "
                f"center (beta = alpha * n_active); got alpha={self.alpha} "
                f"with n_replicas={cfg.n_replicas}")

    def init_carry(self, params0):
        params = replicate_params(params0, self.cfg.n_replicas)
        opt_state = replicate_params(self.optimizer.init(params0),
                                     self.cfg.n_replicas)
        center = jax.tree.map(jnp.array, params)
        return (params, opt_state, center)

    def build_step(self, mode, staleness):
        outer, inner = split_mode(mode)
        self._inner_syncs_of(inner)
        base, _ = split_ov(outer)
        raw = easgd_train_step(self.loss_fn, self.optimizer, self.cfg,
                               mode=base, alpha=self.alpha,
                               n_micro=self.n_micro,
                               ex=self.exchange)

        def step(carry, batch, lr):
            params, opt_state, center = carry
            params, opt_state, center, m = raw(params, opt_state, center,
                                               batch, lr)
            return (params, opt_state, center), m

        return step


@register_strategy("downpour")
class DownpourStrategy(PeriodicStrategy):
    """DOWNPOUR-style delta pushes; 3-slot carry (params, opt_state,
    anchor). `push_scale` is the server-side rate on the delta sum."""
    controller_cls = DownpourController

    def __init__(self, loss_fn, optimizer, cfg, *, push_scale: float = 1.0,
                 **kw):
        super().__init__(loss_fn, optimizer, cfg, **kw)
        if push_scale <= 0:
            raise ValueError(f"push_scale must be positive, got {push_scale}")
        self.push_scale = float(push_scale)

    def init_carry(self, params0):
        params = replicate_params(params0, self.cfg.n_replicas)
        opt_state = replicate_params(self.optimizer.init(params0),
                                     self.cfg.n_replicas)
        anchor = jax.tree.map(jnp.array, params)
        return (params, opt_state, anchor)

    def build_step(self, mode, staleness):
        outer, inner = split_mode(mode)
        self._inner_syncs_of(inner)
        base, _ = split_ov(outer)
        raw = downpour_train_step(self.loss_fn, self.optimizer, self.cfg,
                                  mode=base, push_scale=self.push_scale,
                                  n_micro=self.n_micro,
                                  ex=self.exchange)

        def step(carry, batch, lr):
            params, opt_state, anchor = carry
            params, opt_state, anchor, m = raw(params, opt_state, anchor,
                                               batch, lr)
            return (params, opt_state, anchor), m

        return step

"""Sharding policy: logical-axis -> mesh-axis mapping + rule-based param specs.

Model code annotates activations with *logical* axis names via `constrain`;
the active MeshPolicy (a contextvar, so smoke tests on 1 device run with no
policy and every annotation is a no-op) maps them onto physical mesh axes.

Logical axes:
  batch   -> ("pod", "data") multi-pod, ("data",) single-pod
  seq     -> usually unsharded for training; "data" for split-KV long decode
  model   -> "model" (tensor parallel: heads / ffn hidden / vocab / experts)
  replica -> "pod" (the DASO per-pod parameter replica axis)
"""
from __future__ import annotations

import contextlib
import contextvars
import re
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshPolicy:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: Optional[str] = "model"
    replica_axis: Optional[str] = None  # "pod" when DASO replicas are active
    seq_axis: Optional[str] = None      # set for split-KV long-context decode
    fsdp_axis: Optional[str] = None     # shard the non-TP weight dim (ZeRO-3)

    def resolve(self, logical: Optional[str]):
        if logical is None:
            return None
        if logical == "batch":
            return self.batch_axes if len(self.batch_axes) > 1 else self.batch_axes[0]
        if logical == "model":
            return self.model_axis
        if logical == "replica":
            return self.replica_axis
        if logical == "seq":
            return self.seq_axis
        if logical == "fsdp":
            return self.fsdp_axis
        raise ValueError(f"unknown logical axis {logical!r}")

    def spec(self, *logical) -> P:
        return P(*[self.resolve(l) for l in logical])

    def sharding(self, *logical) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))


_POLICY: contextvars.ContextVar[Optional[MeshPolicy]] = contextvars.ContextVar(
    "mesh_policy", default=None)


def current_policy() -> Optional[MeshPolicy]:
    return _POLICY.get()


@contextlib.contextmanager
def use_policy(policy: Optional[MeshPolicy]):
    tok = _POLICY.set(policy)
    try:
        yield policy
    finally:
        _POLICY.reset(tok)


_DEVICE_LOCAL: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "device_local", default=False)


def device_local() -> bool:
    """True while a program is traced whose arrays all lie on one device:
    no placement spreads a replica's batch (or the replica axis) over
    devices. The executor says so (`trace_device_local`) as it traces a
    program without a placement; anything else reads False."""
    return _DEVICE_LOCAL.get()


@contextlib.contextmanager
def trace_device_local(local: bool):
    tok = _DEVICE_LOCAL.set(bool(local))
    try:
        yield
    finally:
        _DEVICE_LOCAL.reset(tok)


def constrain(x, *logical):
    """Annotate activation x with logical axis names (None = unsharded dim).

    No-op when no policy is active (single-device smoke tests) — and also when
    the value's rank doesn't match (lets the same model code run vmapped).
    """
    pol = current_policy()
    if pol is None:
        return x
    if len(logical) != x.ndim:
        return x
    return jax.lax.with_sharding_constraint(x, pol.sharding(*logical))


# ---------------------------------------------------------------------------
# Rule-based parameter PartitionSpecs.
#
# Rules are matched against the '/'-joined tree path of each leaf; the first
# match wins. Specs are expressed in logical axes and resolved by the policy.
# A leading "replica" axis is prepended when the params carry the DASO
# per-pod replica dimension.
# ---------------------------------------------------------------------------

# (path regex, logical spec per trailing dim).
_RULES = (
    # embeddings / unembed: vocab over model
    (r"embed/tok$",            ("model", "fsdp")),
    (r"unembed/w$",            ("fsdp", "model")),
    # attention projections: fused head dim over model
    (r"(wq|wk|wv)$",           ("fsdp", "model")),
    (r"wo$",                   ("model", "fsdp")),
    # dense / shared-expert FFN
    (r"(w1|w3)$",              ("fsdp", "model")),
    (r"w2$",                   ("model", "fsdp")),
    # MoE expert weights — handled dynamically (expert vs tensor sharding)
    (r"moe/(we1|we3)$",        "MOE_IN"),
    (r"moe/we2$",              "MOE_OUT"),
    (r"moe/router$",           (None, None)),
    # mamba
    (r"in_proj$",              ("fsdp", "model")),
    (r"out_proj$",             ("model", "fsdp")),
    (r"(x_proj|dt_proj)$",     (None, None)),
    (r"conv_w$",               ("model", None)),
    (r"(conv_b|dt_bias|A_log|Dskip)$", ("model",) ),
    # rglru
    (r"(wx|wy)$",              ("fsdp", "model")),
    (r"(w_a|w_i)$",            ("model", "fsdp")),
    (r"(a_param|b_a|b_i|conv1d_b)$", ("model",)),
    (r"conv1d_w$",             ("model", None)),
    # norms, biases, scalars: replicated
    (r".*",                    None),
)


def _leaf_spec(path: str, ndim: int, moe_sharding: str) -> Tuple:
    for pat, spec in _RULES:
        if re.search(pat, path):
            if spec == "MOE_IN":    # (E, D, F)
                spec = (("model", "fsdp", None) if moe_sharding == "expert"
                        else (None, "fsdp", "model"))
            elif spec == "MOE_OUT":  # (E, F, D)
                spec = (("model", None, "fsdp") if moe_sharding == "expert"
                        else (None, "model", "fsdp"))
            if spec is None:
                spec = (None,) * ndim
            spec = tuple(spec)
            # stacked-layer leading dims (scan over layer groups) are unsharded
            if len(spec) < ndim:
                spec = (None,) * (ndim - len(spec)) + spec
            assert len(spec) == ndim, (path, spec, ndim)
            return spec
    raise AssertionError("unreachable")


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def param_specs(params, policy: MeshPolicy, *, moe_sharding: str = "expert",
                replicated: bool = False):
    """PartitionSpec pytree matching `params` (works on ShapeDtypeStructs too).

    replicated=True -> params carry a leading DASO replica dim sharded over
    the replica ("pod") axis. Dims not divisible by the resolved axis size
    fall back to replicated (e.g. granite's 49155 vocab vs 16-way model
    axis — noted in EXPERIMENTS.md §Perf).
    """
    def one(path, leaf):
        path = _path_str(path)
        ndim = len(leaf.shape)
        if replicated:
            spec = _leaf_spec(path, ndim - 1, moe_sharding)
            spec = ("replica",) + spec
        else:
            spec = _leaf_spec(path, ndim, moe_sharding)
        phys = [policy.resolve(s) for s in spec]
        phys = [a if leaf.shape[i] % _axis_size(policy.mesh, a) == 0 else None
                for i, a in enumerate(phys)]
        return P(*phys)

    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params, policy: MeshPolicy, **kw):
    specs = param_specs(params, policy, **kw)
    return jax.tree.map(lambda s: NamedSharding(policy.mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))

"""Logical-axis sharding: one rules table maps model-level axis names to
mesh axes; models annotate activations/params with logical names only.

Mesh layout (DESIGN.md section 4):
  multi-pod: (pod, data, model) = (2, 16, 16)   single-pod: (data, model)

Default rules:
  batch   -> (pod, data)        FSDP/DP axes
  fsdp    -> (pod, data)        parameter & optimizer-state sharding (ZeRO-3)
  heads/kv/dff/vocab/experts -> model   (tensor / expert parallel)
  qdout   -> (pod, data, model) out-channels of a rotated quant_dot
                                weight (see models/mlp.py)
  embed/seq -> replicated (overridable per launch config, e.g. long-context
  decode shards the KV-cache sequence dim)

No mesh context set (CPU smoke tests) -> every constraint is an identity.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]

_state = threading.local()

DEFAULT_RULES: Dict[str, Axis] = {
    "batch": ("pod", "data"),
    "moebatch": ("pod", "data"),  # batch dim of MoE dispatch tensors; serve
                                  # rules set it None so 'experts' wins the
                                  # data axis and dispatch goes all-to-all
    "fsdp": ("pod", "data"),
    "heads": "model",
    "kv": "model",
    "dff": "model",
    "vocab": "model",
    "experts": "model",
    # the out-channels of a weight that a rotated quant_dot consumes: the
    # Hadamard spans its contraction axis, so the site shard_maps over
    # the out-channels only, and the weight is stored split the same way
    # over every axis -- never gathered for the call
    "qdout": ("pod", "data", "model"),
    "embed": None,
    "seq": None,
    "seqpar": None,   # residual-stream sequence parallelism (opt-in)
    "kvseq": None,
    "state": None,
    "layers": None,
}


def _ctx():
    if not hasattr(_state, "mesh"):
        _state.mesh = None
        _state.rules = dict(DEFAULT_RULES)
    return _state


@contextlib.contextmanager
def sharding_rules(mesh: Optional[Mesh], overrides: Optional[Dict[str, Axis]] = None):
    st = _ctx()
    prev = (st.mesh, st.rules)
    st.mesh = mesh
    st.rules = dict(DEFAULT_RULES)
    if overrides:
        st.rules.update(overrides)
    try:
        yield
    finally:
        st.mesh, st.rules = prev


def _resolve_axis(mesh: Mesh, logical: Optional[str]) -> Axis:
    if logical is None:
        return None
    st = _ctx()
    ax = st.rules.get(logical, None)
    if ax is None:
        return None
    axes = (ax,) if isinstance(ax, str) else tuple(ax)
    present = tuple(a for a in axes if a in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def resolve_spec(logical_axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None) -> P:
    mesh = mesh if mesh is not None else _ctx().mesh
    if mesh is None:
        return P()
    return P(*(_resolve_axis(mesh, a) for a in logical_axes))


def constrain(x: jnp.ndarray, *logical_axes: Optional[str]) -> jnp.ndarray:
    """Annotate activation sharding by logical axis names (no-op w/o mesh).

    Divisibility guard: any mesh axis that does not evenly divide the
    corresponding dim is dropped from the constraint (e.g. batch=1
    long-context decode)."""
    mesh = _ctx().mesh
    if mesh is None:
        return x
    assert len(logical_axes) == x.ndim, (logical_axes, x.shape)
    parts = _build_parts(mesh, logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*parts)))


def _build_parts(mesh: Mesh, logical_axes, shape):
    """Resolve logical axes -> mesh axes with (a) the divisibility guard and
    (b) first-occurrence-wins de-duplication (a mesh axis may shard at most
    one dim; e.g. MoE maps both 'experts' and 'dff' to 'model' -- the
    earlier dim takes it, expert-parallel over ffn-parallel)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used = set()
    parts = []
    for dim, a in zip(shape, logical_axes):
        r = _resolve_axis(mesh, a)
        if r is None:
            parts.append(None)
            continue
        axes = (r,) if isinstance(r, str) else r
        keep = []
        total = 1
        for ax in axes:
            if ax not in used and dim % (total * sizes[ax]) == 0:
                keep.append(ax)
                used.add(ax)
                total *= sizes[ax]
        parts.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return parts


def make_resolver(mesh: Mesh):
    """Returns ``one(spec, shape) -> NamedSharding`` applying the rules
    table, the divisibility guard, and mesh-axis de-duplication."""
    def one(spec, shape):
        return NamedSharding(mesh, P(*_build_parts(mesh, spec, shape)))
    return one


def current_mesh() -> Optional[Mesh]:
    return _ctx().mesh

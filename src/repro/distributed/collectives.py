"""Manual collectives for distributed-optimization tricks.

``int8_ring_all_reduce``: XLA's all-reduce runs in the tensor dtype, so
f32 gradients cross the (slow, cross-pod) link at 4 bytes/element. With
error-feedback int8 compression (optim.adamw.compress_grads) the payload
is int8-representable; this shard_map ring moves int8 + one f32 scale per
hop and accumulates in f32 -- a 4x cut of cross-pod gradient traffic.
Validated numerically in tests on a host-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.jaxapi import shard_map


def _ring_body(x_local: jnp.ndarray, axis: str):
    """x_local: this shard's (already int8-compressed values as f32)
    contribution. Ring-reduce over `axis` with int8 payload per hop."""
    # jax.lax.axis_size only exists in newer jax; psum(1) is the portable
    # spelling of "number of shards on this axis"
    n = int(jax.lax.psum(1, axis))
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def quant(v):
        s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(v / s), -127, 127).astype(jnp.int8)
        return q, s

    def body(i, carry):
        acc, send = carry
        q, s = quant(send)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv = q.astype(jnp.float32) * s
        return acc + recv, recv

    acc, _ = jax.lax.fori_loop(0, n - 1, body, (x_local, x_local))
    return acc


def int8_ring_all_reduce(contribs: jnp.ndarray, mesh: Mesh, axis: str) -> jnp.ndarray:
    """Ring all-reduce with int8 wire format over one mesh axis.

    contribs: (n, ...) with the leading dim sharded over ``axis`` -- each
    shard's local summand. Returns (n, ...) where every row is the ring
    sum as accumulated at that shard (f32 accumulation, int8 payload).
    This is the demonstration ring (store-and-forward); the
    bandwidth-optimal variant (reduce-scatter + all-gather in int8) swaps
    the loop body, not the wire format."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
    )
    def run(xs):
        red = _ring_body(xs[0], axis)
        return red[None]

    return run(contribs)

"""Routed working-set exchange — the parameter-server pull/push as explicit
all-to-alls (shard_map), replacing GSPMD's value-blind gather.

GSPMD cannot know which table shard a dynamic id lives on, so a gather from
a row-sharded table lowers to "every shard computes masked partials of the
FULL working set + all-reduce" — per-device wire ~= 2x working-set bytes
(measured 930 MB/step on baidu-ctr train_mb8k).  The paper's parameter
server routes each request to the owning node instead.  This module does
the same on TPU:

  pull:  bucket ids by owning shard -> all_to_all requests -> local gather
         -> all_to_all rows back -> unpermute     (wire ~= rows moved once)
  push:  reverse route of row gradients -> local sparse-AdaGrad update

Load balance: ids map to slots via the bijection
    slot(id) = (id % n_shards) * rows_per_shard + id // n_shards
(hash-sharding), so Zipf-hot heads spread uniformly across shards.  Each
bucket has a fixed capacity; overflowed requests are dropped (returned rows
are zero, updates discarded) and COUNTED — production monitoring watches
that counter exactly like PS-shard overload. Capacity is a config knob;
tests run with capacity = worst case (lossless).

Trainers reach this exchange through ``repro.core.embedding_backend.
RoutedBackend`` (``--placement routed`` in the launcher); this module stays
the raw shard_map layer.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def slot_of(ids: jnp.ndarray, rows_per_shard: int, n_shards: int) -> jnp.ndarray:
    """Logical id -> physical slot under hash-sharding."""
    return (ids % n_shards) * rows_per_shard + ids // n_shards


def _bucket(ids: jnp.ndarray, targets: jnp.ndarray, n_shards: int, cap: int):
    """Place each id into (target, position) with per-target capacity.

    Returns (buckets (n_shards, cap) int32 local-row requests padded with -1,
    slot_of_id (len(ids),) position of each id in the flattened buckets or -1
    if dropped, n_dropped scalar)."""
    onehot = (targets[:, None] == jnp.arange(n_shards)[None, :]).astype(jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=1) - 1
    keep = pos < cap
    flat_slot = jnp.where(keep, targets * cap + pos, n_shards * cap)
    buckets = jnp.full((n_shards * cap + 1,), -1, jnp.int32)
    buckets = buckets.at[flat_slot].set(ids.astype(jnp.int32), mode="drop")
    return buckets[:-1].reshape(n_shards, cap), jnp.where(keep, flat_slot, -1), \
        jnp.sum(1 - keep.astype(jnp.int32))


def make_routed_pull_push(
    mesh,
    rows_per_shard: int,
    dim: int,
    cap_local: int,
    cap_route: int,
    shard_axes: Tuple[str, ...] = ("data", "model"),
):
    """Build (pull, push) jitted shard_map functions for one table.

    Table layout: (rows, dim) row-sharded over ``shard_axes`` (flattened,
    n_shards devices-on-those-axes), rows hash-permuted by ``slot_of``.
    ids layout: (n_shards * cap_local,) sharded over the same axes — each
    device owns cap_local (deduplicated) ids.
    """
    axes = tuple(a for a in shard_axes if a in mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    def pull_body(table_shard, my_ids):
        # table_shard: (rows_per_shard, dim); my_ids: (cap_local,) padded w/ dup
        me_targets = (my_ids % n_shards).astype(jnp.int32)
        local_rows = (my_ids // n_shards).astype(jnp.int32)
        buckets, slot_of_id, dropped = _bucket(local_rows, me_targets, n_shards, cap_route)
        # route requests: a2a (n_shards, cap) -> requests addressed to me
        reqs = jax.lax.all_to_all(
            buckets, axes, split_axis=0, concat_axis=0, tiled=True
        )
        valid = reqs >= 0
        rows = jnp.take(table_shard, jnp.maximum(reqs, 0).reshape(-1), axis=0)
        rows = rows * valid.reshape(-1, 1).astype(rows.dtype)
        rows = rows.reshape(n_shards, cap_route, dim)
        # route responses back
        resp = jax.lax.all_to_all(rows, axes, split_axis=0, concat_axis=0, tiled=True)
        flat = jnp.concatenate(
            [resp.reshape(n_shards * cap_route, dim),
             jnp.zeros((1, dim), resp.dtype)], axis=0)
        working = jnp.take(flat, jnp.where(slot_of_id >= 0, slot_of_id,
                                           n_shards * cap_route), axis=0)
        return working, slot_of_id, dropped[None]

    def push_body(table_shard, accum_shard, my_ids, row_grads, lr, eps):
        me_targets = (my_ids % n_shards).astype(jnp.int32)
        local_rows = (my_ids // n_shards).astype(jnp.int32)
        buckets, slot_of_id, dropped = _bucket(local_rows, me_targets, n_shards, cap_route)
        # place grads into bucket slots, route to owners
        gbuf = jnp.zeros((n_shards * cap_route + 1, dim), row_grads.dtype)
        gbuf = gbuf.at[jnp.where(slot_of_id >= 0, slot_of_id, n_shards * cap_route)
                       ].set(row_grads, mode="drop")
        gsend = gbuf[:-1].reshape(n_shards, cap_route, dim)
        greq = jax.lax.all_to_all(buckets, axes, split_axis=0, concat_axis=0, tiled=True)
        grecv = jax.lax.all_to_all(gsend, axes, split_axis=0, concat_axis=0, tiled=True)
        valid = (greq >= 0).reshape(-1)
        rows = jnp.maximum(greq.reshape(-1), 0)
        g = grecv.reshape(-1, dim) * valid[:, None].astype(grecv.dtype)
        g = g.astype(jnp.float32)
        # SPARSE shard-local AdaGrad: touch only the requested rows — a dense
        # read-modify-write of the 2 GB shard per step would be O(shard), not
        # O(working set).  Duplicate rows (several requesters) first combine
        # their g^2 in the accumulator scatter, then each contribution's
        # delta uses the fully-updated denominator (same convention as
        # SparseAdagrad.apply_rows).
        new_accum = accum_shard.at[rows].add(g * g)
        a_rows = jnp.take(new_accum, rows, axis=0)
        delta = -lr * g / (jnp.sqrt(a_rows) + eps)
        new_table = table_shard.at[rows].add(delta.astype(table_shard.dtype))
        return new_table, new_accum, dropped[None]

    table_spec = P(axes, None)
    ids_spec = P(axes)
    pull = jax.shard_map(
        pull_body, mesh=mesh,
        in_specs=(table_spec, ids_spec),
        out_specs=(P(axes, None), ids_spec, P(axes)),
        check_vma=False,
    )
    push = jax.shard_map(
        push_body, mesh=mesh,
        in_specs=(table_spec, table_spec, ids_spec, P(axes, None), P(), P()),
        out_specs=(table_spec, table_spec, P(axes)),
        check_vma=False,
    )
    return pull, push


def reference_pull(table, ids, rows_per_shard, n_shards):
    """Oracle: dense gather through the same hash-slot mapping."""
    return jnp.take(table, slot_of(ids, rows_per_shard, n_shards), axis=0)

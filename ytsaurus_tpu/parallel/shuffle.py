"""Distributed sort: range partition → ICI all-to-all → per-device sort.

TPU-native redesign of the reference MapReduce Sort pipeline
(server/controller_agent/controllers/sort_controller.cpp: TPartitionTask +
TSortTask; job side: job_proxy/partition_job.cpp routing rows by partitioner
and partition_sort_job.cpp k-way merging):

  reference                               this framework
  ---------                               --------------
  samples_fetcher → partition key bounds  per-shard key samples → host pivots
  partition jobs route rows to chunks     searchsorted(pivots) on device
  shuffle = readers pull blocks over TCP  ONE jax.lax.all_to_all over ICI
  partition_sort heap merge per partition lexsort per device

Static shapes: a first (cheap) pass computes the exact (src, dst) transfer
matrix; the host sizes the exchange quota from its max and compiles the
exchange program for that bucket, so skewed data costs one recompile instead
of an overflow failure.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ytsaurus_tpu.chunks.columnar import Column, pad_capacity
from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.ops.segments import packed_sort_indices
from ytsaurus_tpu.parallel.distributed import ShardedTable
from ytsaurus_tpu.parallel.mesh import SHARD_AXIS
from ytsaurus_tpu.schema import SortOrder, TableSchema


def _encode_key_plane(data: jax.Array, valid: jax.Array):
    """(null_rank, value) encoding: null sorts before any value."""
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    return valid.astype(jnp.int8), jnp.where(valid, data, jnp.zeros_like(data))


def _lex_less_const(row_planes, pivot_planes, pivot_idx, or_equal: bool):
    """Lexicographic row < pivots[pivot_idx] over encoded planes.

    row_planes: [(v, d)] each (cap,); pivot_planes: [(v, d)] each (n_piv,).
    """
    shape = row_planes[0][0].shape
    result = jnp.full(shape, or_equal, dtype=bool)
    for (rv, rd), (pv, pd) in reversed(list(zip(row_planes, pivot_planes))):
        p_v, p_d = pv[pivot_idx], pd[pivot_idx]
        lt = (rv < p_v) | ((rv == p_v) & (rd < p_d))
        eq = (rv == p_v) & (rd == p_d)
        result = lt | (eq & result)
    return result


def _partition_ids(row_planes, pivot_planes, n_pivots: int) -> jax.Array:
    """For each row, the number of pivots ≤ row (lexicographic) — i.e. its
    destination shard in [0, n_pivots]."""
    cap = row_planes[0][0].shape[0]
    pid = jnp.zeros(cap, dtype=jnp.int32)
    for i in range(n_pivots):
        # row >= pivots[i]  ⇔  not (row < pivots[i])
        ge = ~_lex_less_const(row_planes, pivot_planes, i, or_equal=False)
        pid = pid + ge.astype(jnp.int32)
    return pid


def quantile_pivots(sample_rows: "list[tuple]", n: int,
                    key_arity: int) -> "list[tuple]":
    """n-1 quantile pivots from sampled (valid, value) key tuples; the
    shared samples→bounds step of every range-partition path (ref
    partitioning_parameters_evaluator.cpp)."""
    sample_rows = sorted(sample_rows)
    pivots = []
    for j in range(1, n):
        pivots.append(sample_rows[(j * len(sample_rows)) // n]
                      if sample_rows
                      else tuple((False, 0) for _ in range(key_arity)))
    return pivots


def _sample_pivots(table: ShardedTable, key_names: list[str],
                   samples_per_shard: int = 256) -> list[tuple]:
    """Host-side: evenly sample keys from every shard, take quantile pivots.
    Ref: ytlib/table_client/samples_fetcher.h + partitioning_parameters_
    evaluator.cpp."""
    n = table.n_shards
    cap = table.capacity
    # Gather only the sample rows on device; transfer n*samples values, not
    # the whole plane.
    idx_parts = []
    for s in range(n):
        count = table.row_counts[s]
        if count == 0:
            continue
        idx_parts.append(np.linspace(0, count - 1,
                                     min(samples_per_shard, count),
                                     dtype=np.int64) + s * cap)
    if not idx_parts:
        return [tuple((False, 0) for _ in key_names) for _ in range(n - 1)]
    idx = jnp.asarray(np.concatenate(idx_parts))
    key_data = {}
    for name in key_names:
        col = table.columns[name]
        # analyze: allow(host-sync): pivot sampling reads O(shards*samples) gathered keys once per sort
        key_data[name] = (np.asarray(col.data[idx]), np.asarray(col.valid[idx]))
    sample_rows: list[tuple] = []
    for i in range(len(idx)):
        sample_rows.append(tuple(
            # analyze: allow(host-sync): key_data is host numpy (gathered above); .item() is a scalar read
            (bool(key_data[name][1][i]), key_data[name][0][i].item())
            for name in key_names))
    return quantile_pivots(sample_rows, n, len(key_names))


def route_rows(planes: dict, pid: jax.Array, n: int, quota: int,
               cap: int) -> tuple[dict, jax.Array]:
    """Inside shard_map: scatter local rows into per-destination blocks and
    all_to_all them.  `pid` in [0, n) for live rows, n for discards.
    Returns (received planes, received-row mask); receive capacity n*quota."""
    order = jnp.argsort(pid, stable=True)
    pid_sorted = pid[order]
    dest_counts = jax.vmap(lambda d: (pid_sorted == d).sum())(jnp.arange(n + 1))
    starts = jnp.concatenate([jnp.zeros(1, jnp.int64),
                              jnp.cumsum(dest_counts)[:-1]])
    pos = jnp.arange(cap)
    slot = pos - starts[jnp.clip(pid_sorted, 0, n)]
    send_index = jnp.clip(pid_sorted, 0, n - 1) * quota + slot
    in_quota = (slot < quota) & (pid_sorted < n)
    send_index = jnp.where(in_quota, send_index, n * quota)

    def route(plane):
        plane_sorted = plane[order]
        buf = jnp.zeros(n * quota + 1, dtype=plane.dtype)
        buf = buf.at[send_index].set(plane_sorted)
        return buf[: n * quota].reshape(n, quota)

    sent_mask = jnp.zeros(n * quota + 1, dtype=bool).at[send_index].set(
        in_quota)[: n * quota].reshape(n, quota)
    recv_mask = jax.lax.all_to_all(sent_mask, SHARD_AXIS, 0, 0,
                                   tiled=False).reshape(-1)
    recv: dict = {}
    for name, (data, valid) in planes.items():
        r_data = jax.lax.all_to_all(route(data), SHARD_AXIS, 0, 0,
                                    tiled=False).reshape(-1)
        r_valid = jax.lax.all_to_all(route(valid), SHARD_AXIS, 0, 0,
                                     tiled=False).reshape(-1)
        recv[name] = (r_data, r_valid & recv_mask)
    return recv, recv_mask


def transfer_counts(pid: jax.Array, row_valid: jax.Array, n: int) -> jax.Array:
    """Inside shard_map: (1, n) per-destination counts for quota sizing."""
    pid = jnp.where(row_valid, pid, n)
    counts = jax.vmap(lambda dest: (pid == dest).sum())(jnp.arange(n))
    return counts[None, :]


def sort_table(table: ShardedTable, key_columns: Sequence[str],
               descending: bool = False) -> ShardedTable:
    """Globally sort a ShardedTable by `key_columns` across the mesh.

    Result: shard i holds the i-th key range, sorted within the shard —
    i.e. globally sorted in shard-major order.
    """
    mesh = table.mesh
    n = table.n_shards
    key_names = list(key_columns)
    for name in key_names:
        if name not in table.columns:
            raise YtError(f"No such key column {name!r}",
                          code=EErrorCode.QueryExecutionError)
    if n == 1:
        return _sort_single(table, key_names, descending)

    return _sort_table_sharded(table, key_names, descending)


def _sort_table_sharded(table: ShardedTable, key_names: "list[str]",
                        descending: bool) -> ShardedTable:
    from ytsaurus_tpu.utils.tracing import child_span
    mesh = table.mesh
    n = table.n_shards
    pivots = _sample_pivots(table, key_names)
    # Pivot planes as device constants: [(valid_rank, value)] per key.
    pivot_planes = []
    for ki, name in enumerate(key_names):
        col = table.columns[name]
        vals = np.array([p[ki][1] for p in pivots])
        ranks = np.array([1 if p[ki][0] else 0 for p in pivots], dtype=np.int8)
        pivot_planes.append((jnp.asarray(ranks),
                             jnp.asarray(vals.astype(col.data.dtype))))

    cap = table.capacity
    names = [c.name for c in table.schema]

    # --- pass 1: exact transfer matrix ---------------------------------------
    def count_pass(key_planes_in, row_valid):
        row_planes = [_encode_key_plane(d, v) for d, v in key_planes_in]
        pid = _partition_ids(row_planes, pivot_planes, n - 1)
        if descending:
            pid = (n - 1) - pid                 # shard 0 takes the top range
        pid = jnp.where(row_valid, pid, n)      # padding rows → discard slot
        counts = jax.vmap(
            lambda dest: (pid == dest).sum())(jnp.arange(n))
        return counts[None, :]                  # (1, n) per shard

    key_planes_global = [(table.columns[k].data, table.columns[k].valid)
                         for k in key_names]
    with child_span("sort.partition", shards=n):
        counts = shard_map(
            count_pass, mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
            out_specs=P(SHARD_AXIS), check_vma=False)(
                key_planes_global, table.row_valid)
        # analyze: allow(host-sync): receive quotas are a host decision — one transfer-matrix read per shuffle
        counts_np = np.asarray(counts)          # (n_src, n_dst)

    # Skew-robust sizing (ref: the partition tree's multi-level splitting,
    # controllers/sort_controller.cpp:459+, re-expressed for a fixed-shape
    # collective): receive capacity is the EXACT per-destination need
    # (max column sum), not n x the hottest (src,dst) cell; a hot cell is
    # drained over multiple all_to_all rounds with a constant block size
    # instead of inflating every device's buffers.
    max_cell = max(int(counts_np.max()), 1)
    recv_cap = pad_capacity(max(int(counts_np.sum(axis=0).max()), 1))
    quota = pad_capacity(
        max((recv_cap + n - 1) // n, (max_cell + 7) // 8, 1))
    rounds = (max_cell + quota - 1) // quota
    # Per-destination packing offsets: rows from src s land at
    # [prefix[s], prefix[s] + counts[s, d]) on destination d.
    prefix_np = np.zeros((n, n), dtype=np.int64)    # (dst, src)
    prefix_np[:, 1:] = np.cumsum(counts_np.T, axis=1)[:, :-1]
    prefix_sharded = jax.device_put(
        jnp.asarray(prefix_np),
        jax.sharding.NamedSharding(mesh, P(SHARD_AXIS)))

    # --- pass 2: multi-round route + all_to_all + local sort ------------------
    def exchange(columns_in, key_planes_in, row_valid, prefix_in):
        row_planes = [_encode_key_plane(d, v) for d, v in key_planes_in]
        pid = _partition_ids(row_planes, pivot_planes, n - 1)
        if descending:
            pid = (n - 1) - pid
        pid = jnp.where(row_valid, pid, n)
        prefix = prefix_in.reshape(n)               # my dst row: per-src base
        # Stable cell rank of each local row within its (src, dst) cell.
        order = jnp.argsort(pid, stable=True)
        pid_sorted = pid[order]
        dest_counts = jax.vmap(
            lambda d: (pid_sorted == d).sum())(jnp.arange(n + 1))
        starts = jnp.concatenate([jnp.zeros(1, jnp.int64),
                                  jnp.cumsum(dest_counts)[:-1]])
        pos = jnp.arange(cap)
        cell_rank = pos - starts[jnp.clip(pid_sorted, 0, n)]
        planes_sorted = {name: (columns_in[name][0][order],
                                columns_in[name][1][order])
                         for name in names}
        recv_planes = {name: (
            jnp.zeros(recv_cap, dtype=planes_sorted[name][0].dtype),
            jnp.zeros(recv_cap, dtype=bool)) for name in names}
        recv_mask = jnp.zeros(recv_cap, dtype=bool)
        for r in range(rounds):
            in_round = (pid_sorted < n) & (cell_rank >= r * quota) & \
                (cell_rank < (r + 1) * quota)
            slot = cell_rank - r * quota
            send_index = jnp.clip(pid_sorted, 0, n - 1) * quota + slot
            send_index = jnp.where(in_round, send_index, n * quota)

            sent_mask = jnp.zeros(n * quota + 1, dtype=bool).at[
                send_index].set(in_round)[: n * quota].reshape(n, quota)
            arrived = jax.lax.all_to_all(sent_mask, SHARD_AXIS, 0, 0,
                                         tiled=False)     # (n_src, quota)
            # Destination positions for this round's block from each src.
            dst_pos = prefix[:, None] + r * quota + jnp.arange(quota)[None, :]
            dst_pos = jnp.where(arrived, dst_pos, recv_cap)
            dst_flat = dst_pos.reshape(-1)
            recv_mask = jnp.concatenate(
                [recv_mask, jnp.zeros(1, dtype=bool)]).at[dst_flat].set(
                arrived.reshape(-1))[:recv_cap] | recv_mask
            for name in names:
                data_s, valid_s = planes_sorted[name]

                def send(plane):
                    buf = jnp.zeros(n * quota + 1, dtype=plane.dtype)
                    buf = buf.at[send_index].set(plane)
                    return buf[: n * quota].reshape(n, quota)

                rd = jax.lax.all_to_all(send(data_s), SHARD_AXIS, 0, 0,
                                        tiled=False).reshape(-1)
                rv = jax.lax.all_to_all(send(valid_s), SHARD_AXIS, 0, 0,
                                        tiled=False).reshape(-1)
                acc_d, acc_v = recv_planes[name]
                # Rounds write DISJOINT position ranges, so plain scatter
                # over the accumulated planes composes them.
                acc_d = jnp.concatenate(
                    [acc_d, jnp.zeros(1, dtype=acc_d.dtype)]).at[
                    dst_flat].set(rd)[:recv_cap]
                acc_v = jnp.concatenate(
                    [acc_v, jnp.zeros(1, dtype=bool)]).at[dst_flat].set(
                    rv & arrived.reshape(-1))[:recv_cap]
                recv_planes[name] = (acc_d, acc_v)
        # Rebuild validity strictly from arrivals (the accumulator ORs).
        recv_planes = {name: (d, v & recv_mask)
                       for name, (d, v) in recv_planes.items()}
        # Local sort of received rows by key (absent rows sink last).
        items = [((~recv_mask), jnp.ones_like(recv_mask), False, 1)]
        for name in key_names:
            d, v = recv_planes[name]
            items.append((d, v & recv_mask, descending, 64))
        order2 = packed_sort_indices(items)
        out = {name: (d[order2], v[order2])
               for name, (d, v) in recv_planes.items()}
        out_count = recv_mask.sum()
        return out, out_count[None]

    columns_global = {name: (table.columns[name].data,
                             table.columns[name].valid) for name in names}
    # all_to_all payload: routed rows x per-row plane bytes (+1 for each
    # validity bit plane) — the wire cost tag on the shuffle span.
    bytes_per_row = sum(
        np.dtype(table.columns[name].data.dtype).itemsize + 1
        for name in names)
    with child_span("sort.shuffle", shards=n, rounds=rounds,
                    all_to_all_bytes=int(counts_np.sum()) * bytes_per_row):
        mapped = shard_map(
            exchange, mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                      P(SHARD_AXIS)),
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)), check_vma=False)
        out_columns_planes, out_counts = jax.jit(mapped)(
            columns_global, key_planes_global, table.row_valid,
            prefix_sharded)

    # analyze: allow(host-sync): conservation check — one stacked counts transfer per shuffle
    out_counts_np = [int(c) for c in np.asarray(out_counts)]
    lost = table.total_rows - sum(out_counts_np)
    if lost != 0:
        raise YtError(f"Shuffle lost {lost} rows (quota={quota})",
                      code=EErrorCode.QueryExecutionError)
    out_columns: dict[str, Column] = {}
    for col_schema in table.schema:
        data, valid = out_columns_planes[col_schema.name]
        src = table.columns[col_schema.name]
        out_columns[col_schema.name] = Column(
            type=col_schema.type, data=data, valid=valid,
            dictionary=src.dictionary)
    sorted_schema = _sorted_schema(table.schema, key_names, descending)
    # Row-presence mask per shard from the received counts.
    rv = shard_map(
        lambda c: (jnp.arange(recv_cap) < c[0])[None, :],
        mesh=mesh, in_specs=P(SHARD_AXIS), out_specs=P(SHARD_AXIS),
        check_vma=False)(out_counts).reshape(-1)
    return ShardedTable(schema=sorted_schema, mesh=mesh, capacity=recv_cap,
                        columns=out_columns, row_counts=out_counts_np,
                        row_valid=rv)


def _sort_single(table: ShardedTable, key_names: list[str],
                 descending: bool = False) -> ShardedTable:
    """One-device mesh: plain packed-key sort, same result contract."""
    mask = table.row_valid
    items = [((~mask), jnp.ones_like(mask), False, 1)]
    for name in key_names:
        col = table.columns[name]
        items.append((col.data, col.valid & mask, descending, 64))
    order = packed_sort_indices(items)
    out_columns = {
        name: Column(type=col.type, data=col.data[order],
                     valid=col.valid[order], dictionary=col.dictionary)
        for name, col in table.columns.items()}
    return ShardedTable(
        schema=_sorted_schema(table.schema, key_names, descending),
        mesh=table.mesh, capacity=table.capacity, columns=out_columns,
        row_counts=list(table.row_counts), row_valid=mask[order])


def _sorted_schema(schema: TableSchema, key_names: list[str],
                   descending: bool) -> TableSchema:
    order = SortOrder.descending if descending else SortOrder.ascending
    cols = []
    reordered = [schema.get(k) for k in key_names] + \
        [c for c in schema if c.name not in key_names]
    for i, col in enumerate(reordered):
        cols.append(col.with_sort_order(order if i < len(key_names) else None))
    return TableSchema(columns=tuple(cols))

"""Driver `select_dynamic_stream`: the configuration's table as a sorted
dynamic table, written through the system's own write path, and the traffic
file's queries sent back to back through `client.select_rows` by one client
(closed loop) at read-latest.  The window writes nothing.

Set-up, in commit order:

- load: `create` (dynamic, sorted on the key columns), `reshard_table` at
  the configuration's pivots, `mount_table`; `insert_rows` in key order,
  one transaction per batch; `freeze_table`; refresh pair 1 (RF1's lines
  by `insert_rows`, RF2's keys by `delete_rows`, one transaction each);
  `freeze_table`;
- warm: refresh pair 2, left in the dynamic stores, then each query once.
  Pair 2 is written here because the harness reopens the cluster from its
  files between load and warm-up, and a dynamic store lives in the serving
  process, not in those files.

A request's line carries what the program counted of the tablet snapshots
and the coordinator's fan-in (`snapshot_s`, `snapshot_cache_misses`,
`coalesce_s`, `shards_coalesced`); a program whose QueryStatistics lacks
one gives None there: no reading, not 0.  The answers are compared with
`tpch_refresh_spec`: the load with both pairs applied.
"""

import numpy as np

from drivers import select_stream
from reference import tpch_refresh_spec

COUNTERS = {"snapshot_s": "snapshot_time",
            "snapshot_cache_misses": "snapshot_cache_misses",
            "coalesce_s": "coalesce_time",
            "shards_coalesced": "shards_coalesced"}


class DynamicLines:
    """The window's record, each request line with the snapshot and
    fan-in counters of the select just answered."""

    def __init__(self, record, yt):
        self.record, self.yt = record, yt

    def __getattr__(self, name):
        return getattr(self.record, name)

    def request(self, op, t0, t1, **fields):
        stats = self.yt.last_query_statistics
        for field, counter in COUNTERS.items():
            fields[field] = getattr(stats, counter, None)
        self.record.request(op, t0, t1, **fields)


LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def staging_compiles_per_row_count():
    """Programs lowered to concatenate chunks whose row counts are new at
    capacities already seen: what a select's tablet snapshots and the
    coordinator's fan-in (both `concat_chunks`) lower once the capacities
    are warm.  Every run of this cell draws new per-tablet row counts, so
    a program that lowers anything here compiles its staging anew in every
    run, ~200 s on one v5e (PERF.md section 4), and set-up cannot fit the
    run's limit."""
    import jax
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk, concat_chunks
    from ytsaurus_tpu.schema import TableSchema

    schema = TableSchema.make([("k", "int64"), ("s", "string")])

    def part(rows, vocab):
        return ColumnarChunk.from_arrays(
            schema, {"k": np.arange(rows), "s": np.arange(rows) % 3},
            dictionaries={"s": np.array(vocab, dtype=object)})

    def pair(first, second):
        return [part(first, [b"a", b"b", b"c"]),
                part(second, [b"b", b"c", b"d"])]

    concat_chunks(pair(100, 200))
    probes = [pair(101, 199), pair(120, 180)]
    lowered = []

    def listen(event, duration, **kwargs):
        if event == LOWERING_EVENT:
            lowered.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for chunks in probes:
            concat_chunks(chunks)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return len(lowered)


def pivot_keys(orderkey, tablets):
    """Pivots at equal row-count quantiles, moved to order boundaries: the
    key prefix (l_orderkey,) padded with null, which sorts before every
    line of the order."""
    cuts = [int(orderkey[len(orderkey) * i // tablets])
            for i in range(1, tablets)]
    if len(set(cuts)) != len(cuts) or cuts[0] == int(orderkey[0]):
        raise ValueError(f"{tablets} tablets do not fit {len(orderkey)} rows")
    return [(cut, None) for cut in cuts]


class Driver(select_stream.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.columns = self.config["columns"]
        self.base = self.pairs = None

    # -- set-up ---------------------------------------------------------

    def prepare(self):
        """The load's host arrays and the refresh pairs from the seed; the
        reference reads the rows visible after both pairs.  A program
        whose staging compiles per row count is refused first, in
        seconds, rather than cut by the run's limit."""
        lowered = staging_compiles_per_row_count()
        if lowered:
            raise SystemExit(
                f"benchmark: this program lowered {lowered} programs to "
                f"concatenate chunks of new row counts at known capacities; "
                f"the dynamic cell's set-up would compile its tablet "
                f"snapshots and fan-in anew in every run")
        super().prepare()
        refresh = self.ctx.module("generators",
                                  self.config["refresh_generator"]).generate
        self.base = self.host
        self.pairs = refresh(self.config, self.ctx.seed, self.sizes,
                             self.base)
        self.host = tpch_refresh_spec.visible(self.base, self.pairs)
        self.rows = len(self.host["l_orderkey"])
        self.ctx.phase("prepare: data and refresh pairs from the seed")

    def rows_of(self, host, lo=0, hi=None):
        """Row dicts of host[lo:hi] as a client writes them."""
        names = [c["name"] for c in self.columns]
        values = []
        for name in names:
            column = host[name][lo:hi]
            if name in self.vocabs:
                column = np.array(self.vocabs[name], dtype=object)[column]
            values.append(column.tolist())
        return [dict(zip(names, row)) for row in zip(*values)]

    def refresh(self, yt, pair):
        yt.insert_rows(self.table, self.rows_of(pair["insert"]))
        yt.delete_rows(self.table, [tuple(k) for k in pair["delete"].tolist()])

    def load(self, yt):
        from ytsaurus_tpu.schema import TableSchema

        schema = TableSchema.make(
            [(c["name"], c["type"], c["sort_order"]) if c.get("sort_order")
             else (c["name"], c["type"]) for c in self.columns],
            unique_keys=True)
        yt.create("table", self.table, recursive=True,
                  attributes={"schema": schema, "dynamic": True})
        if self.config["tablets"] > 1:
            yt.reshard_table(self.table, pivot_keys(
                self.base["l_orderkey"], self.config["tablets"]))
        yt.mount_table(self.table)
        rows, batch = len(self.base["l_orderkey"]), self.config["load_batch"]
        for lo in range(0, rows, batch):
            yt.insert_rows(self.table, self.rows_of(self.base, lo, lo + batch))
        self.ctx.phase("load: insert_rows")
        yt.freeze_table(self.table)
        self.ctx.phase("load: freeze_table")
        self.refresh(yt, self.pairs[0])
        yt.freeze_table(self.table)
        self.ctx.phase("load: refresh pair 1, freeze_table")

    def warm(self, yt):
        self.refresh(yt, self.pairs[1])
        self.ctx.phase("warm: refresh pair 2")
        super().warm(yt)

    # -- the measured window --------------------------------------------

    def window(self, yt, seconds, record):
        super().window(yt, seconds, DynamicLines(record, yt))

    # -- correctness, after the window ------------------------------------

    def control_answer(self, query, control):
        if control["kind"] == "stale":
            return tpch_refresh_spec.evaluate(
                query["reference"], self.base, self.vocabs,
                self.pairs[:control["pairs_read"]])
        return super().control_answer(query, control)

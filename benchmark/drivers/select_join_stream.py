"""Driver `select_join_stream`: the configuration's static tables, each
published from its generator's host arrays, and the traffic file's join
queries sent back to back through `client.select_rows` by one client (closed
loop).  Warm-up and the measured window are `select_stream`'s; a request's
`source_rows` are the rows of every table together, and its line carries
the seconds the program itself counted in the join (`join_s`,
`join_sync_s`).
"""

import numpy as np

from drivers import select_stream
from reference import tpch_join_spec


def publish(yt, path, columns, host, vocabs):
    """One static table of one chunk from host arrays (`vocabs`: the
    sorted vocabularies of the columns that hold codes)."""
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.client import publish_table_chunks
    from ytsaurus_tpu.schema import TableSchema

    schema = TableSchema.make(
        [(c["name"], c["type"], c["sort_order"]) if c.get("sort_order")
         else (c["name"], c["type"]) for c in columns])
    chunk = ColumnarChunk.from_arrays(
        schema, host,
        dictionaries={c["name"]: np.array(
            [v.encode() for v in vocabs[c["name"]]], dtype=object)
            for c in columns if c["name"] in vocabs})
    yt.create("table", path, recursive=True, attributes={"schema": schema})
    publish_table_chunks(yt, yt.cluster.chunk_store, path, [chunk])
    rows = len(next(iter(host.values())))
    if yt.get(path + "/@row_count") != rows:
        raise RuntimeError(f"{path}: row_count attribute differs from the "
                           f"load")


class JoinLines:
    """The window's record, with the join's seconds of the select just
    answered added to each request line.  A program whose
    QueryStatistics has no such counter gives None: no reading, not 0."""

    def __init__(self, record, yt):
        self.record, self.yt = record, yt

    def __getattr__(self, name):
        return getattr(self.record, name)

    def request(self, op, t0, t1, **fields):
        stats = self.yt.last_query_statistics
        self.record.request(
            op, t0, t1, join_s=getattr(stats, "join_time", None),
            join_sync_s=getattr(stats, "join_sync_time", None), **fields)


class Driver(select_stream.Driver):
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.sizes = ctx.sizes
        self.tables = self.config["tables"]
        paths = {name: table["path"] for name, table in self.tables.items()}
        self.queries = [dict(q, ql=q["ql"].format(**paths))
                        for q in self.traffic["queries"]]
        self.rows = sum(self.table_rows(name) for name in self.tables)
        self.answers = []          # (query index, rows) of every request
        self.host, self.vocabs = {}, {}

    def table_rows(self, name):
        return self.sizes[self.tables[name]["rows"]]

    # -- set-up ---------------------------------------------------------

    def prepare(self):
        """Every table's host arrays from the seed, in the file's order: a
        table made from another's arrays (`from_tables`) comes after it."""
        for name, table in self.tables.items():
            generate = self.ctx.module("generators",
                                       table["generator"]).generate
            given = {other: self.host[other]
                     for other in table.get("from_tables", ())}
            self.host[name], vocabs = generate(self.config, self.ctx.seed,
                                               self.sizes, **given)
            self.vocabs.update(vocabs)

    def load(self, yt):
        for name, table in self.tables.items():
            publish(yt, table["path"], table["columns"], self.host[name],
                    self.vocabs)

    def window(self, yt, seconds, record):
        super().window(yt, seconds, JoinLines(record, yt))

    def bytes_needed_per_request(self):
        """HBM bytes the queries need: the columns they read of every
        table x its rows x the width the configuration states for the
        device, whatever implements the join (averaged over the queries)."""
        widths = self.config["device_bytes_per_value"]
        per_query = []
        for query in self.queries:
            needed = 0
            for name, read in query["columns_read"].items():
                types = {c["name"]: c["type"]
                         for c in self.tables[name]["columns"]}
                needed += sum(widths[types[column]] for column in read) \
                    * self.table_rows(name)
            per_query.append(needed)
        return sum(per_query) / len(per_query)

    # -- correctness, after the window ------------------------------------

    def check(self, yt, control=None):
        """Every answer of the window against the numpy reference, exact.
        With `control`, the reference with its join broken as the control
        says stands in the program's place (one answer per query)."""
        wants = [tpch_join_spec.evaluate(q["reference"], self.host,
                                         self.vocabs) for q in self.queries]
        answers = self.answers
        if control is not None:
            answers = [(i, self.control_answer(q, control))
                       for i, q in enumerate(self.queries)]
        compared = {"rows_mismatched": 0}
        seen = {}
        for index, rows in answers:
            # answers of one query over static tables repeat: compare each
            # distinct one once
            key = (index, repr(rows))
            if key not in seen:
                seen[key] = tpch_join_spec.compare(rows, wants[index])
            compared["rows_mismatched"] += seen[key]
        tier = self.traffic.get("require_tier")
        if tier and control is None:
            compared["requests_off_tier"] = sum(
                1 for r in self.ctx.record.requests
                if r.get("tier") != tier)
        return compared

    def control_answer(self, query, control):
        if control["kind"] == "join":
            return tpch_join_spec.evaluate(query["reference"], self.host,
                                           self.vocabs,
                                           shift=control["shift"])
        raise ValueError(f"unknown control {control['kind']!r}")

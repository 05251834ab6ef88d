"""Driver `select_stream`: one static table published from the generator's
host arrays, and the traffic file's queries sent back to back through
`client.select_rows` by one client (closed loop).
"""

import time

import numpy as np

from reference import ql_spec

# The serving plane's default deadline (30 s) is shorter than one cold chip
# compile; only the warm-up calls carry this, timed calls use the default.
COLD_TIMEOUT = 1200.0


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.table = self.config["table"]
        self.sizes = ctx.sizes
        self.rows = self.sizes["rows"]
        self.queries = [dict(q, ql=q["ql"].format(table=self.table))
                        for q in self.traffic["queries"]]
        self.answers = []          # (query index, rows) of every request
        self.host = self.vocabs = None

    # -- set-up ---------------------------------------------------------

    def prepare(self):
        """The host arrays from the seed: the loader publishes them, the
        reference reads them."""
        generate = self.ctx.module("generators",
                                   self.config["generator"]).generate
        self.host, self.vocabs = generate(self.config, self.ctx.seed,
                                          self.sizes)

    def load(self, yt):
        from ytsaurus_tpu.chunks.columnar import ColumnarChunk
        from ytsaurus_tpu.client import publish_table_chunks
        from ytsaurus_tpu.schema import TableSchema

        schema = TableSchema.make(
            [(c["name"], c["type"]) for c in self.config["columns"]])
        chunk = ColumnarChunk.from_arrays(
            schema, self.host,
            dictionaries={name: np.array([v.encode() for v in vocab],
                                         dtype=object)
                          for name, vocab in self.vocabs.items()})
        yt.create("table", self.table, recursive=True,
                  attributes={"schema": schema})
        publish_table_chunks(yt, yt.cluster.chunk_store, self.table, [chunk])
        if yt.get(self.table + "/@row_count") != self.rows:
            raise RuntimeError("row_count attribute differs from the load")

    def warm(self, yt):
        """Every program shape the window uses: each query once, with a
        long deadline (a traced run's `window_compiles` metric shows that
        the window compiled nothing)."""
        for query in self.queries:
            yt.select_rows(query["ql"], timeout=COLD_TIMEOUT)

    # -- the measured window --------------------------------------------

    def window(self, yt, seconds, record):
        """Queries back to back until `seconds` have passed; the request
        in flight at the deadline is finished and counted."""
        annotate = self.ctx.annotate
        n = 0
        t_start = record.start()
        while time.perf_counter() - t_start < seconds:
            index = n % len(self.queries)
            query = self.queries[index]
            n += 1
            t0 = time.perf_counter()
            try:
                with annotate("bench.select." + query["name"]):
                    rows = yt.select_rows(query["ql"])
            except Exception as err:   # a failed request is counted, not fatal
                record.failed("select", t0, time.perf_counter(), err)
                continue
            t1 = time.perf_counter()
            stats = yt.last_query_statistics
            record.request("select", t0, t1, source_rows=self.rows,
                           execute_s=stats.execute_time,
                           compile_count=stats.compile_count,
                           tier=stats.execution_tier)
            self.answers.append((index, rows))

    def bytes_needed_per_request(self):
        """HBM bytes the queries need: the columns they read x rows x the
        width the configuration states for the device, whatever the
        program does to get them (averaged over the traffic's queries)."""
        widths = self.config["device_bytes_per_value"]
        types = {c["name"]: c["type"] for c in self.config["columns"]}
        per_query = [sum(widths[types[name]] for name in q["columns_read"])
                     * self.rows for q in self.queries]
        return sum(per_query) / len(per_query)

    # -- correctness, after the window ------------------------------------

    def check(self, yt, control=None):
        """Every answer of the window against the numpy reference.  With
        `control`, the reference in the control's form stands in the
        program's place instead (one answer per query)."""
        compared = {"rows_mismatched": 0, "rel_gap_max": 0.0}
        wants = [ql_spec.evaluate(q["reference"], self.host, self.vocabs)
                 for q in self.queries]
        answers = self.answers
        if control is not None:
            answers = [(i, self.control_answer(q, control))
                       for i, q in enumerate(self.queries)]
        seen = {}
        for index, rows in answers:
            # answers of one query over a static table repeat: compare each
            # distinct one once
            key = (index, repr(rows))
            if key not in seen:
                seen[key] = ql_spec.compare(
                    self.queries[index]["reference"], rows, wants[index])
            mismatched, gap = seen[key]
            compared["rows_mismatched"] += mismatched
            compared["rel_gap_max"] = max(compared["rel_gap_max"], gap)
        tier = self.traffic.get("require_tier")
        if tier and control is None:
            compared["requests_off_tier"] = sum(
                1 for r in self.ctx.record.requests
                if r.get("tier") != tier)
        return compared

    def control_answer(self, query, control):
        if control["kind"] == "precision":
            return ql_spec.evaluate(query["reference"], self.host,
                                    self.vocabs,
                                    dtype=np.dtype(control["dtype"]).type)
        raise ValueError(f"unknown control {control['kind']!r}")

"""Driver `select_cascade_stream`: `select_join_stream` for queries that
join more than two tables.  Set-up, warm-up and the window are that
driver's (any number of static tables, each made from the seed and from the
tables before it); a request's line also carries the seconds the program
counted in each stage of the join cascade (`join_stage1_s`,
`join_stage2_s`, ... in execution order), and the answers are compared with
`tpch_cascade_spec`: ordered rows exact in their keys, sums by their
relative gap.
"""

import numpy as np

from drivers import select_join_stream, select_stream
from reference import tpch_cascade_spec


class CascadeLines(select_join_stream.JoinLines):
    """`JoinLines`, with one field more for each join stage the select
    just answered ran.  A program whose QueryStatistics has no
    `join_stage_seconds` adds none: no reading, not 0."""

    def request(self, op, t0, t1, **fields):
        stages = getattr(self.yt.last_query_statistics,
                         "join_stage_seconds", None) or ()
        for position, seconds in enumerate(stages, start=1):
            fields[f"join_stage{position}_s"] = seconds
        super().request(op, t0, t1, **fields)


class Driver(select_join_stream.Driver):
    def window(self, yt, seconds, record):
        select_stream.Driver.window(self, yt, seconds,
                                    CascadeLines(record, yt))

    # -- correctness, after the window ------------------------------------

    def check(self, yt, control=None):
        """Every answer of the window against the numpy reference.  With
        `control`, the reference in the control's form stands in the
        program's place (one answer per query)."""
        wants = [tpch_cascade_spec.evaluate(q["reference"], self.host,
                                            self.vocabs)
                 for q in self.queries]
        answers = self.answers
        if control is not None:
            answers = [(i, self.control_answer(q, control))
                       for i, q in enumerate(self.queries)]
        compared = {"rows_mismatched": 0, "rel_gap_max": 0.0}
        seen = {}
        for index, rows in answers:
            # answers of one query over static tables repeat: compare each
            # distinct one once
            key = (index, repr(rows))
            if key not in seen:
                seen[key] = tpch_cascade_spec.compare(
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
        if control["kind"] == "join":
            return tpch_cascade_spec.evaluate(
                query["reference"], self.host, self.vocabs,
                shift=(control["table"], control["shift"]))
        if control["kind"] == "precision":
            return tpch_cascade_spec.evaluate(
                query["reference"], self.host, self.vocabs,
                dtype=np.dtype(control["dtype"]).type)
        raise ValueError(f"unknown control {control['kind']!r}")

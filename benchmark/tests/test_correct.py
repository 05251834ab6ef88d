"""`correct` has to be able to fail.  On the CPU, at the rehearsal's sizes:

- the control of each cell (the reference in a lower precision, or with
  one stated guarantee broken, put in the program's place) comes out as not
  correct, while the program itself comes out correct;
- a run of the harness with the timed path broken underneath (an answer
  altered where it is produced, one among many; two rows of an ordered
  answer swapped) sees `correct` come out false.

    python3 -m pytest benchmark/tests -q

The chip's look-up is skipped (`--rehearse`); the rest of a run is driven
as `run.py` drives it.
"""

import json
import os
import sys
import time

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402

CELLS = ["tpch_q1_sf1", "tpch_group_topk_sf01"]


@pytest.fixture(scope="module")
def jax():
    return run.start_jax(rehearse=True)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(bench, jax, workload, seed=7, seconds=1.5, **kw):
    args = run.parse_args(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse"])
    return run.run_cell(bench, args, jax, time.perf_counter(), **kw)


def holds(compared):
    return all(pair["value"] <= pair["limit"] for pair in compared.values())


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct_and_control_is_not(bench, jax, workload):
    result, control = run_cell(bench, jax, workload, with_control=True)
    assert result["correct"], result["compared"]
    assert not holds(control), control


def alter_first_number(rows):
    """The answer altered where it is produced: one value of one row."""
    rows = [dict(r) for r in rows]
    for row in rows:
        for name, value in row.items():
            if isinstance(value, float):
                row[name] = value * (1 + 1e-8)
                return rows
    raise AssertionError("nothing to alter")


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(bench, jax, workload, monkeypatch):
    from ytsaurus_tpu.client import YtClient
    real = YtClient.select_rows
    calls = {"n": 0}

    def altered(self, query, *a, **kw):
        rows = real(self, query, *a, **kw)
        # timed calls carry no timeout= (warm-up calls all do); only the
        # tenth is altered: one wrong answer among many has to be enough
        calls["n"] += "timeout" not in kw
        return alter_first_number(rows) if calls["n"] == 10 and rows \
            and "timeout" not in kw else rows

    monkeypatch.setattr(YtClient, "select_rows", altered)
    # long enough for ten top-k calls of ~0.4-0.5 s on a loaded CPU
    result, _ = run_cell(bench, jax, workload, seconds=8)
    assert calls["n"] >= 10, calls
    assert not result["correct"], result["compared"]


def test_tie_order_altered_is_not_correct(bench, jax, monkeypatch):
    """The top-k cell's exact numbers: two rows of the ordered answer
    swapped where it is produced."""
    from ytsaurus_tpu.client import YtClient
    real = YtClient.select_rows

    def swapped(self, query, *a, **kw):
        rows = real(self, query, *a, **kw)
        return [rows[1], rows[0]] + rows[2:] if len(rows) > 1 else rows

    monkeypatch.setattr(YtClient, "select_rows", swapped)
    result, _ = run_cell(bench, jax, "tpch_group_topk_sf01")
    assert result["compared"]["rows_mismatched"]["value"] > 0
    assert not result["correct"]

#!/usr/bin/env python3
"""Builder's tool, not part of a benchmark run: the numbers `correct`
compares, read for many seeds in one process, the program's beside the
control's.  The limits in the traffic files are set from these readings
(PERF.md section 2).

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 --seconds 5
"""

import argparse
import gc
import json
import os
import sys
import time

import run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True, help="comma-separated")
    own, rest = ap.parse_known_args()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    jax = run.start_jax("--rehearse" in rest)
    if jax is None:
        return 1
    for seed in own.seeds.split(","):
        args = run.parse_args(rest + ["--seed", seed])
        result, control = run.run_cell(bench, args, jax, time.perf_counter(),
                                       with_control=True)
        print(json.dumps({
            "seed": int(seed), "correct": result["correct"],
            "attempted": result["attempted"],
            "program": {k: v["value"] for k, v in result["compared"].items()},
            "control": {k: v["value"] for k, v in control.items()},
            "setup_s": result["metrics"].get("setup_s", {}).get("value"),
            "memory_peak_bytes": result["device"]["memory_peak_bytes"]}),
            flush=True)
        # one process, many clusters: let go of the last one's planes
        from ytsaurus_tpu import client
        client._cluster_registry.clear()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

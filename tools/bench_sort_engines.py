"""Compare sort-engine wall times on the current backend.

Usage: python tools/bench_sort_engines.py [--rows N] [--words W]
       [--engines network,lsd32,radix,radix_scatter]

Times stable_argsort_u32 per engine at the given scale and prints one
line per engine; used to pick LSD_SORT_THRESHOLD / engine defaults on
real hardware (the cliffs are TPU-generation specific).
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sync(x):
    np.asarray(x.ravel()[:1])


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=16 * 1024 * 1024)
    parser.add_argument("--words", type=int, default=2)
    parser.add_argument("--iters", type=int, default=3)
    parser.add_argument("--engines", default="radix,radix_scatter,"
                                             "lsd32,network")
    parser.add_argument("--timeout", type=float, default=240.0,
                        help="skip remaining iters past this many seconds")
    args = parser.parse_args()

    from ytsaurus_tpu.utils.backend import (
        ensure_backend,
        place_compile_cache,
    )
    place_compile_cache()
    jax = ensure_backend()
    import jax.numpy as jnp

    from ytsaurus_tpu.ops.segments import stable_argsort_u32

    platform = jax.devices()[0].platform
    key = jax.random.PRNGKey(0)
    words = [jax.random.randint(jax.random.fold_in(key, i), (args.rows,),
                                0, 1 << 31, dtype=jnp.uint32) * 2
             for i in range(args.words)]
    print(f"# rows={args.rows} words={args.words} device={platform}")
    for engine in args.engines.split(","):
        # The engine is read from env at trace time; a fresh jit per
        # engine keeps the traces separate.
        os.environ["YT_TPU_SORT_ENGINE"] = engine
        run = jax.jit(lambda ws: stable_argsort_u32(ws))
        t0 = time.perf_counter()
        try:
            out = run(words)
            _sync(out)
        except Exception as exc:  # noqa: BLE001 - report and continue
            print(f"{engine}: FAILED {exc!r}")
            continue
        compile_s = time.perf_counter() - t0
        times = []
        deadline = time.monotonic() + args.timeout
        for _ in range(args.iters):
            if time.monotonic() > deadline:
                break
            t0 = time.perf_counter()
            out = run(words)
            _sync(out)
            times.append(time.perf_counter() - t0)
        best = min(times) if times else float("nan")
        print(f"{engine}: best={best * 1e3:.1f}ms compile={compile_s:.1f}s "
              f"({args.rows / best / 1e6:.0f}M rows/s)")


if __name__ == "__main__":
    main()

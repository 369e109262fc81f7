"""Readings for the limits of ``correct``: sound runs and the control.

    python benchmarks/chip/control.py --workload <name> --seeds <n> \
        --seconds <s> [--first-seed <n>] [--codec none|quant8+zstd]

Runs the cell ``--seeds`` times in one process, each on its own seed, and
prints one JSON line per run with the numbers ``correct`` compares.  With
``--codec none`` (the configuration's own) these are the sound runs that
give each limit its lower reading; with ``--codec quant8+zstd`` the
program's lossy int8 path stands in for a precision below the one the
configuration states, and its readings are the upper ones.  The
benchmark's own runs never call this.  Needs a TPU, as the harness does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--codec", default="none")
    args = ap.parse_args(argv)

    import jax

    import runner
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU; nothing run", file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        r = runner.run(args.workload, seed, args.seconds, False,
                       t_start=time.perf_counter(), codec=args.codec)
        info = r["_info"]
        print(json.dumps({
            "workload": args.workload, "codec": args.codec, "seed": seed,
            "correct": r["correct"], "failed": r["failed"],
            "checks": {k: c["value"] for k, c in r["checks"].items()},
            "cells": info.get("cells"), "errors": info.get("errors"),
            "metrics": {k: m["value"] for k, m in r["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Chip benchmark of notebook execution migration: one cell, one run.

    python benchmarks/chip/harness.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with a TPU.  The cell
(``BENCHMARK.json``'s ``workloads``) names a configuration and a traffic
mix; the run builds that notebook from the seed, warms it up, drives it in
a closed loop for ``--seconds`` and then checks what the window produced
against a plain reference (``refcheck.py``).  With ``--trace 0`` the result
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each number compared with its limit.  The
same numbers end standard error.  Without a TPU, or with fewer chips than
the cell asks for, the run prints no result and exits 1.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    import runner
    cell = runner.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"harness: needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s); nothing run",
              file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    # every program goes to the cache, so that only a checkout's first run
    # compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = runner.run(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)
    info = result.pop("_info")
    print(json.dumps({"info": info}, default=str), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

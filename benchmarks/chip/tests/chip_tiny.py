"""Tiny sizes for the CPU tests: the same notebooks, shapes a test run
holds.  The tests steer the size through ``runner.run``'s overrides; the
harness's command line has no such option.

Everything is found by name, so that a configuration or a cell comes with
data files alone:

- ``tiny/<config>.json`` holds the two override dicts ``runner.run`` takes,
  ``{"config": {...}, "traffic": {...}}``;
- every ``workloads/<cell>.json`` is a case, listed in ``BENCHMARK.json``
  or not; an unlisted cell gets a one-chip entry for the configuration
  that the file's ``config`` key names.
"""
from __future__ import annotations

import glob
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
TINY = os.path.join(HERE, "tiny")
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import runner  # noqa: E402


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def workload_files() -> dict[str, str]:
    """Each workload file by cell name."""
    return {os.path.basename(p)[:-len(".json")]: p for p in sorted(
        glob.glob(os.path.join(CHIP, "workloads", "*.json")))}


def _bench() -> dict:
    """``BENCHMARK.json``, with a one-chip cell for each workload file it
    does not list, and its configuration where that is not listed
    either."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed = {w["name"] for w in bench["workloads"]}
    for name, path in workload_files().items():
        if name in listed:
            continue
        config = load_json(path)["config"]
        if all(c["name"] != config for c in bench["configs"]):
            bench["configs"].append({
                "name": config,
                "file": f"benchmarks/chip/configs/{config}.json"})
        bench["workloads"].append({
            "name": name, "config": config,
            "traffic": name.removeprefix(config + "."), "chips": 1})
    return bench


BENCH = _bench()
WORKLOADS = tuple(workload_files())
# the planted faults run on the cheapest cell at the tiny size
FAULT_CELL = "spacenet7_kmeans.refilter"


def tiny(config: str) -> dict:
    """The configuration's tiny size, ``tiny/<config>.json``."""
    path = os.path.join(TINY, f"{config}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no tiny size for configuration {config!r}: {path} is missing")
    return load_json(path)


def run(workload: str, seed: int = 2**31 + 17, seconds: float = 0.5,
        **kw) -> dict:
    """One run of ``workload`` at the tiny size, on whatever device JAX
    has."""
    size = tiny(next(w["config"] for w in BENCH["workloads"]
                     if w["name"] == workload))
    return runner.run(workload, seed, seconds, False,
                      t_start=time.perf_counter(),
                      config_overrides=size["config"],
                      traffic_overrides=size["traffic"], bench=BENCH, **kw)

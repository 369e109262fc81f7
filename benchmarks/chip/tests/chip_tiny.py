"""Tiny sizes for the CPU tests: the same notebooks, shapes a test run
holds.  The tests steer the size through ``runner.run``'s overrides; the
harness's command line has no such option."""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import runner  # noqa: E402

MODEL = {"name": "mamba2-tiny", "family": "ssm", "num_layers": 2,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 0, "d_ff": 0,
         "vocab_size": 512, "ssm_state": 16, "ssm_expand": 2,
         "ssm_headdim": 32, "ssm_chunk": 16, "conv_width": 4,
         "tie_embeddings": True}
SIZES = {
    "mamba2_370m_train": ({"model": MODEL}, {"batch": 2, "seq": 32}),
    "spacenet7_kmeans": ({"num_scenes": 12, "scene_shape": [48, 48, 4]}, {}),
}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
# built and checked here, not yet measured on the chip (PERF.md, Open
# questions): the paper's SpaceNet 7 notebook
BENCH["configs"].append({
    "name": "spacenet7_kmeans",
    "file": "benchmarks/chip/configs/spacenet7_kmeans.json"})
BENCH["workloads"].append({
    "name": "spacenet7_kmeans.refilter", "config": "spacenet7_kmeans",
    "traffic": "refilter", "chips": 1})
WORKLOADS = tuple(w["name"] for w in BENCH["workloads"])
# the planted faults run on the cheapest cell at the tiny size
FAULT_CELL = "spacenet7_kmeans.refilter"


# the notebooks' other cycles, kept for cells a later PR may add with a
# workload file alone (PERF.md, Open questions)
OTHER_CYCLES = {
    "eval": ("mamba2_370m_train.cycle", {"cycle": ["eval", "inspect"]}),
    "tweak": ("spacenet7_kmeans.refilter", {
        "setup": ["load", "filter"], "cycle": ["tweak", "kmeans", "report"],
        "quantiles": [0.85], "k_values": [3, 4, 5, 6, 7, 8],
        "cycles_per_round": 1}),
}


def run(workload: str, seed: int = 2**31 + 17, seconds: float = 0.5,
        traffic: dict | None = None, **kw) -> dict:
    """One run of ``workload`` at the tiny size, on whatever device JAX has;
    ``traffic`` replaces parts of the cell's traffic file."""
    config, tiny = SIZES[workload.split(".")[0]]
    return runner.run(workload, seed, seconds, False,
                      t_start=time.perf_counter(), config_overrides=config,
                      traffic_overrides={**tiny, **(traffic or {})},
                      bench=BENCH, **kw)

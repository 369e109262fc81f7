"""One run of one cell: set-up, the measured window, the checks, metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name:

- ``BENCHMARK.json`` (at the checkout's root) names the cell's
  configuration, its chips and its metrics;
- ``configs/<config>.json`` holds the configuration's sizes, and
  ``configs/<config>.py`` the notebook cells built from them;
- ``workloads/<cell>.json`` holds the traffic: which cells run once as
  set-up, which cells repeat as one cycle, each cell's declared cost and
  the parameters the cells read;
- ``metrics/<metric>.py`` reads one metric from a finished run.

The session is the paper's two-environment runtime: ``HybridRuntime`` over
``local`` and ``remote`` (speedup 10), loopback transport, the block
policy, no knowledge base.  Placement is the runtime's own decision on the
declared costs.  One user runs the cycle in a closed loop with no think
time; the window ends at the first cycle boundary, on a whole round of
cycles, after ``seconds`` have passed.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MAX_WARMUP_ROUNDS = 4


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class CellSpec:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    notebook: object            # configs/<config>.py
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT,
              bench: dict | None = None) -> CellSpec:
    """The cell's spec, from ``BENCHMARK.json`` unless ``bench`` gives it
    (tests run cells the benchmark does not list yet)."""
    if bench is None:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", f"{workload}.json")) as f:
        traffic = json.load(f)
    notebook = _load_module(os.path.join(HERE, "configs", f"{c['name']}.py"),
                            f"bench_config_{c['name']}")
    return CellSpec(workload, int(w["chips"]), c["name"], config, traffic,
                    notebook,
                    [m for m in bench["end_to_end"] if _applies(m, workload)],
                    [m for m in bench["per_layer"] if _applies(m, workload)])


# ---------------------------------------------------------------------------
# what the seed decides
# ---------------------------------------------------------------------------

def paired_order(values, rng: np.random.Generator) -> list:
    """``values`` in a seeded order that keeps the work of every seed the
    same: sorted values are paired smallest with largest, and the pairs and
    the order within each pair are shuffled.  Any run of whole pairs then
    does the same work whatever the seed."""
    s = sorted(values)
    pairs = [[s[i], s[-1 - i]] for i in range(len(s) // 2)]
    if len(s) % 2:
        pairs.append([s[len(s) // 2]])
    out = []
    for j in rng.permutation(len(pairs)):
        pair = pairs[int(j)]
        out.extend(pair[::-1] if rng.integers(2) else pair)
    return out


def derive_keys(seed: int, traffic: dict) -> tuple[dict, np.random.Generator]:
    """31-bit keys for the cells and a generator for the checks' samples,
    all from ``--seed`` (any integer)."""
    ss = np.random.SeedSequence(seed % (1 << 64))
    words = ss.generate_state(3)
    rng = np.random.default_rng(words[2])
    keys = {"weights": int(words[0] >> 1), "data": int(words[1] >> 1),
            "quantiles": paired_order(traffic.get("quantiles", []), rng),
            "k_values": paired_order(traffic.get("k_values", []), rng)}
    return keys, rng


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class Session:
    """The notebook under ``HybridRuntime``, with the benchmark's probes."""

    def __init__(self, cell: CellSpec, sources: dict[str, str], codec: str,
                 rng: np.random.Generator):
        from repro.core import (ExecutionEnvironment, HybridRuntime,
                                Notebook, StateReducer)
        from probes import Probes

        traffic = cell.traffic
        nb = Notebook(cell.config_name)
        self.index = {}
        for name in list(traffic["setup"]) + list(traffic["cycle"]):
            self.index[name] = len(nb.cells)
            nb.add_cell(sources[name], cost=float(traffic["costs"][name]))
        self.nb = nb
        self.rt = HybridRuntime(
            nb, envs={"local": ExecutionEnvironment("local"),
                      "remote": ExecutionEnvironment("remote", speedup=10.0)},
            reducer=StateReducer(codec=codec), policy="block",
            use_knowledge=False)
        self.probes = Probes(self.rt, rng)
        self.probes.install()
        self.log: list[str] = []
        self.placement: list[str] = []
        self.errors: list[str] = []

    def run(self, name: str) -> float:
        """Run one cell; returns its turnaround in seconds (from the call
        to its outputs being ready)."""
        from probes import block_env
        p = self.probes
        p.cell_pos, p.executed, p.exec_env = len(self.log), False, None
        self.log.append(name)
        cell = self.nb.cells[self.index[name]]
        before = len(cell.annotations)
        t0 = time.perf_counter()
        self.rt.run_cell(self.index[name])
        for env in self.rt.envs.values():
            block_env(env)
        dt = time.perf_counter() - t0
        self.placement.append(p.exec_env or "?")
        self.errors.extend(a for a in cell.annotations[before:]
                           if "serialization failure" in a)
        return dt

    def close(self) -> None:
        self.probes.uninstall()
        self.rt.close()


class CompileWatch:
    """Counts JAX compile events (tracing, lowering, backend compile)."""

    def __init__(self):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name.startswith("/jax/core/compile"):
            self.events += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class HostPeak:
    """Peak resident memory of this process while it runs, sampled from
    ``/proc/self/statm`` every ``interval`` seconds by a thread that holds
    the interpreter lock only to read the file."""

    def __init__(self, interval: float = 0.01):
        self.interval = interval
        self.peak = 0
        self._page = os.sysconf("SC_PAGE_SIZE")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _rss(self) -> int:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * self._page

    def _loop(self) -> None:
        while True:
            self.peak = max(self.peak, self._rss())
            if self._stop.wait(self.interval):
                return

    def __enter__(self):
        self.peak = self._rss()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, self._rss())


@dataclass
class Run:
    """What a finished run hands to the metric readers."""
    setup_s: float
    window_s: float
    cells: int
    turnarounds: list[float]
    spans: dict[str, float]
    wire_bytes: int
    hashed_bytes: int
    host_peak_bytes: int
    hbm_peak_bytes: int | None
    device_kind: str
    trace: dict | None = None
    trace_events: dict | None = None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = ROOT, config_overrides: dict | None = None,
        traffic_overrides: dict | None = None, codec: str | None = None,
        fault=None, bench: dict | None = None) -> dict:
    """One run of ``workload``.  Tests steer the size through the
    overrides, the control through ``codec``, and a planted fault through
    ``fault`` (called with the runtime when the window opens)."""
    import refcheck
    import tracefold

    cell = load_cell(workload, root, bench)
    config = {**cell.config, **(config_overrides or {})}
    traffic = {**cell.traffic, **(traffic_overrides or {})}
    cell.config, cell.traffic = config, traffic
    keys, rng = derive_keys(seed, traffic)
    sources = cell.notebook.cells(config, traffic, keys)
    dev = jax.devices()[0]

    watch = CompileWatch()
    session = Session(cell, sources, codec or config["codec"], rng)
    outputs: list[dict] = []        # the notebook's outputs after each cycle
    cycle_ends: list[int] = []

    def run_cycle(turnarounds=None):
        for name in traffic["cycle"]:
            dt = session.run(name)
            if turnarounds is not None:
                turnarounds.append(dt)
        cycle_ends.append(len(session.log) - 1)
        outputs.append(refcheck.snapshot(
            session.rt.envs["local"].state.ns, cell.notebook.OUTPUTS))

    try:
        for name in traffic["setup"]:
            session.run(name)
        # warm up on every parameter value once, then until a whole round
        # of cycles compiles nothing
        per_round = int(traffic.get("cycles_per_round", 1))
        period = max(per_round, len(keys["quantiles"]), len(keys["k_values"]))
        warm = 0
        for _ in range(MAX_WARMUP_ROUNDS + -(-period // per_round)):
            before = watch.events
            for _ in range(per_round):
                run_cycle()
            warm += per_round
            if warm >= period and watch.events == before:
                break
        setup_s = time.perf_counter() - t_start
        warm_compiles = watch.events

        if fault is not None:
            fault(session.rt)
        session.probes.reset_counters()
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        turnarounds: list[float] = []
        cycles = 0
        window_pos = len(session.log)
        with HostPeak() as host, \
                jax.profiler.TraceAnnotation(tracefold.WINDOW):
            t0 = time.perf_counter()
            while True:
                run_cycle(turnarounds)
                cycles += 1
                if time.perf_counter() - t0 >= seconds \
                        and cycles % per_round == 0:
                    break
            window_s = time.perf_counter() - t0
        host_peak = host.peak
        if trace:
            jax.profiler.stop_trace()
        hbm_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        window_compiles = watch.events - warm_compiles
    except Exception as e:  # noqa: BLE001 — a failed run still reports
        session.close()
        watch.close()
        return _failed(cell, dev, session, e)
    failed = len(session.errors)

    # -- checks on the program's own state ------------------------------------
    p = session.probes
    fps = refcheck.Fingerprints()
    local, remote = (session.rt.envs[n].state.ns for n in ("local", "remote"))
    restore_bad, restore_n = refcheck.restore_bad(local, remote, fps)
    kernel_bad, kernel_n = refcheck.kernel_bad(p.leaf_sample, p.chunk_sample)
    log, migrations = list(session.log), list(p.migrations)
    spans, calls = dict(p.spans), dict(p.calls)
    wire_bytes, hashed_bytes = p.wire_bytes, p.hashed_bytes
    placement = session.placement[window_pos:]
    errors = list(session.errors)
    session.close()
    watch.close()
    del session, local, remote, p, fps
    gc.collect()

    # -- the reference: the same cells in one namespace ---------------------
    t_ref = time.perf_counter()
    ref_outputs, delta_missed, delta_extra = refcheck.replay(
        [sources[n] for n in log], migrations, cell.notebook.OUTPUTS,
        cycle_ends)
    output_bad, output_n = refcheck.output_bad(
        outputs, ref_outputs, cell.notebook.OUTPUTS, refcheck.Fingerprints())
    reference_s = time.perf_counter() - t_ref

    checks = {"restore_bad": restore_bad, "delta_missed": delta_missed,
              "kernel_bad": kernel_bad, "output_bad": output_bad}
    correct = failed == 0 and all(v <= refcheck.LIMITS[k]
                                  for k, v in checks.items())

    events = None
    reduced = None
    if trace:
        events = tracefold.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = tracefold.reduce(events)
    record = Run(setup_s=setup_s, window_s=window_s, cells=len(turnarounds),
                 turnarounds=turnarounds, spans=spans, wire_bytes=wire_bytes,
                 hashed_bytes=hashed_bytes, host_peak_bytes=host_peak,
                 hbm_peak_bytes=hbm_peak, device_kind=dev.device_kind,
                 trace=reduced, trace_events=events)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        reader = _load_module(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py"),
                              f"bench_metric_{m['name']}")
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": hbm_peak}
    result = {"correct": correct, "attempted": len(turnarounds) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": refcheck.LIMITS[k]}
                        for k, v in checks.items()}
    result["_info"] = {
        "cycles": cycles, "cells": len(turnarounds), "window_s": window_s,
        "setup_s": setup_s, "reference_s": reference_s,
        "warmup_compiles": warm_compiles, "window_compiles": window_compiles,
        "restore_leaves": restore_n, "kernel_digests": kernel_n,
        "output_leaves": output_n, "delta_extra": delta_extra,
        "migrations": len(migrations), "calls": calls,
        "last_migrations": [(m["src"], m["dst"], m["sent"])
                            for m in migrations[-2 * len(traffic["cycle"]):]],
        "placement": sorted(set(zip(traffic["cycle"] * cycles, placement))),
        "errors": errors, "host_peak_bytes": host_peak}
    return result


def _failed(cell, dev, session, exc) -> dict:
    import refcheck
    return {"correct": False, "attempted": len(session.log), "failed":
            len(session.errors) + 1, "metrics": {},
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": (dev.memory_stats() or {}).get(
                           "peak_bytes_in_use")},
            "checks": {k: {"value": None, "limit": v}
                       for k, v in refcheck.LIMITS.items()},
            "_info": {"errors": session.errors + [repr(exc)]}}

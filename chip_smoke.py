"""Chip smoke test: the notebook migration path end to end on one TPU.

    python chip_smoke.py [--seed N]

A four-cell notebook runs under ``HybridRuntime`` (envs ``local`` and
``remote``, loopback transport, ``policy="block"``) for three sessions, so
its state migrates both ways.  The set-up cell builds mamba2-370m at full
width and depth with AdamW state on the chip; the train cell, whose forced
cost sends it to ``remote``, takes three jitted steps on a token batch
drawn from ``--seed``.  Every migration is checked bit for bit, every grid
the capture path builds must live on the chip, the chip's digests and
chunk keys of a sample of leaves must equal XLA:CPU's, and the quantize
kernel must equal its XLA formulation on the chip.

Earlier lines of output are JSON records of the phases; the last line is
``{"ok": true, "device": {...}}``.  Any failed check exits non-zero without
that line, as does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import resource
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

BATCH, SEQ = 4, 512          # tokens per step: 4 x 512, remat="full"
STEPS = 3
SESSIONS = 3
CELL_COSTS = (1.0, 0.1, 600.0, 0.1)   # forced: the train cell goes remote


class SmokeFailure(Exception):
    pass


def _emit(**rec) -> None:
    print(json.dumps(rec, default=float), flush=True)


def _cells(config_expr: str, seed: int, batch: int, seq: int):
    setup = f"""
import functools
import jax, jax.numpy as jnp
from repro.configs import TrainConfig, get_config
from repro.models import LM
from repro.optim import adamw_update, init_opt_state
cfg = {config_expr}
lm = LM(cfg, max_seq={seq + 1})
tc = TrainConfig(total_steps=20, warmup_steps=2, remat='full')
params = lm.init(jax.random.PRNGKey({seed}))
opt = init_opt_state(params)
losses = []

def train_step(params, opt, toks):
    (loss, _), grads = jax.value_and_grad(
        functools.partial(lm.loss, remat=tc.remat), has_aux=True)(
        params, {{'tokens': toks}})
    opt, params, _ = adamw_update(tc, opt, grads, params)
    return params, opt, loss
"""
    data = f"""
import numpy as np
toks = jnp.asarray(np.random.default_rng({seed}).integers(
    0, cfg.vocab_size, ({batch}, {seq + 1}), dtype=np.int32))
"""
    train = f"""
for _ in range({STEPS}):
    params, opt, loss = jax.jit(train_step, donate_argnums=(0, 1))(
        params, opt, toks)
    losses.append(float(loss))
"""
    readback = "final_loss = losses[-1]\n"
    return setup, data, train, readback


def _leaves_of(obj):
    from repro.core.reducer import _is_array
    return [l for l in jax.tree_util.tree_leaves(obj) if _is_array(l)]


def _bits(a) -> np.ndarray:
    from repro.core.reducer import host_array
    a = np.ascontiguousarray(host_array(a))
    return a.reshape(-1).view(np.uint8)


def _peak_rss() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _ready(env) -> None:
    jax.block_until_ready([v for v in env.state.ns.values()
                           if isinstance(v, jax.Array)])


def _watch_capture(rt, home_dev, stats):
    """Observe the runtime's own migrations: time capture and restore,
    check every restored leaf against its source, and check that every
    grid the capture path hashes lives on ``home_dev``.  Returns a
    function that stops the grid check."""
    from repro.kernels.hash_delta import ops

    red, eng = rt.reducer, rt.engine
    pack, place = ops._pack_batch, ops.to_device

    def _on_home(x):
        if x.devices() != {home_dev}:
            raise SmokeFailure(f"capture grid on {x.devices()}, "
                               f"not {home_dev}")
        stats["grids"] += 1
        return x

    ops._pack_batch = lambda *a, **k: _on_home(pack(*a, **k))
    ops.to_device = lambda *a, **k: _on_home(place(*a, **k))

    def _timed(fn, key):
        def wrapper(*a, **k):
            t0 = time.perf_counter()
            out = fn(*a, **k)
            stats[key] += time.perf_counter() - t0
            return out
        return wrapper

    red.delta_names = _timed(red.delta_names, "capture_s")
    red.serialize_names = _timed(red.serialize_names, "capture_s")
    red.deserialize = _timed(red.deserialize, "restore_s")
    migrate = eng.migrate

    def _migrate(src, dst, *a, **k):
        stats["capture_s"] = stats["restore_s"] = 0.0
        t0 = time.perf_counter()
        res = migrate(src, dst, *a, **k)
        _ready(dst)
        wall = time.perf_counter() - t0
        if res.noop:
            return res
        if eng.last_ser.skipped:
            raise SmokeFailure(f"{src.name}->{dst.name} skipped "
                               f"{eng.last_ser.skipped}")
        nleaves = nbytes = 0
        for name in res.names:
            a, b = _leaves_of(src.state[name]), _leaves_of(dst.state[name])
            if len(a) != len(b):
                raise SmokeFailure(f"{name}: {len(a)} leaves became {len(b)}")
            for x, y in zip(a, b):
                if (x.shape != y.shape or x.dtype != y.dtype
                        or not np.array_equal(_bits(x), _bits(y))):
                    raise SmokeFailure(f"{name}: restored leaf differs "
                                       f"from its source ({x.shape} "
                                       f"{x.dtype})")
                nleaves += 1
                nbytes += x.nbytes
        stats["directions"].add((src.name, dst.name))
        _emit(phase="migration", src=src.name, dst=dst.name,
              names=len(res.names), leaves_checked=nleaves,
              array_bytes=nbytes, wire_bytes=res.nbytes,
              wall_s=wall, capture_s=stats["capture_s"],
              restore_s=stats["restore_s"], host_peak_rss_bytes=_peak_rss())
        return res

    eng.migrate = _migrate

    def _unwatch():
        ops._pack_batch, ops.to_device = pack, place
    return _unwatch


def _cross_check(leaves, cpu) -> dict:
    """Chip digests and chunk keys of ``leaves`` vs XLA:CPU's, bit for bit
    (a receiver on a host computes the same keys)."""
    from repro.core.chunkstore import (
        array_chunk_digests, array_chunk_digests_many,
    )
    from repro.kernels.hash_delta import ops

    on_cpu = [jax.device_put(l, cpu) for l in leaves]
    t0 = time.perf_counter()
    chip = ops.digest_leaves(leaves)
    t_chip = time.perf_counter() - t0
    host = ops.digest_leaves(on_cpu)
    if chip != host:
        raise SmokeFailure("leaf digests differ between the chip and "
                           "XLA:CPU")
    payloads = [np.asarray(l).tobytes() for l in on_cpu]
    keys, _ = array_chunk_digests_many(payloads)
    with jax.default_device(cpu):
        host_keys = [array_chunk_digests(p) for p in payloads]
    if keys != host_keys:
        raise SmokeFailure("chunk keys differ between the chip and XLA:CPU")
    return {"leaves": len(leaves), "digest_chip_s": t_chip,
            "chip_mode": ops.kernel_mode(leaves[0]),
            "host_mode": ops.kernel_mode(on_cpu[0]),
            "elements": int(sum(l.size for l in leaves)),
            "chunks": sum(len(k) for k in keys)}


def _quant_check(leaf, cpu) -> dict:
    """Pallas quantize on the chip must equal the XLA formulation on the
    chip bit for bit.  Against XLA:CPU the codes are only reported: the
    TPU's float32 division is not correctly rounded, so a code whose
    quotient sits near a rounding boundary may differ (the codec is lossy
    by design, and only the sender quantizes)."""
    from repro.kernels.quant_blockwise.ops import _quantize, quantize
    qc, sc = (np.asarray(v) for v in quantize(leaf))
    qx, sx = (np.asarray(v) for v in _quantize(leaf, mode="xla"))
    if not (np.array_equal(qc, qx) and np.array_equal(sc, sx)):
        raise SmokeFailure("quantize kernel differs from its XLA "
                           "formulation on the chip")
    qh, sh = (np.asarray(v) for v in quantize(jax.device_put(leaf, cpu)))
    return {"codes": int(qc.size), "scales": int(sc.size),
            "codes_differing_from_cpu": int((qc != qh).sum()),
            "scales_differing_from_cpu": int((sc != sh).sum())}


def run(config_expr: str, *, seed: int, batch: int = BATCH,
        seq: int = SEQ) -> None:
    from repro.core import ExecutionEnvironment, HybridRuntime, Notebook
    from repro.core.reducer import StateReducer

    home_dev = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    compile_s: dict[str, float] = {}
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compile_s.__setitem__(
            name, compile_s.get(name, 0.0) + secs)
        if name.startswith("/jax/core/compile/") else None)

    nb = Notebook("chip-smoke")
    names = ("setup", "data", "train", "readback")
    for src, cost in zip(_cells(config_expr, seed, batch, seq), CELL_COSTS):
        nb.add_cell(src, cost=cost)
    # codec "none": the chunks carry raw bytes, so what is timed is the
    # capture path and not zlib on the host
    rt = HybridRuntime(
        nb, envs={"local": ExecutionEnvironment("local"),
                  "remote": ExecutionEnvironment("remote", speedup=10.0)},
        reducer=StateReducer(codec="none"), policy="block",
        use_knowledge=False)
    stats = {"capture_s": 0.0, "restore_s": 0.0, "grids": 0,
             "directions": set()}
    unwatch = _watch_capture(rt, home_dev, stats)

    t_run = time.perf_counter()
    for s in range(SESSIONS):
        for i, name in enumerate(names):
            before = len(rt.engine.log)
            t0 = time.perf_counter()
            rt.run_cell(i)
            _ready(rt.envs[rt.current_env])
            _emit(phase="cell", session=s + 1, cell=name,
                  env=rt.current_env, wall_s=time.perf_counter() - t0,
                  migrations=len(rt.engine.log) - before)
    run_s = time.perf_counter() - t_run
    rt.close()
    unwatch()        # the cross-check below hashes on the CPU on purpose

    failures = [a for c in nb.cells for a in c.annotations
                if "serialization failure" in a]
    if failures:
        raise SmokeFailure(f"serialization failure: {failures}")
    if stats["directions"] != {("local", "remote"), ("remote", "local")}:
        raise SmokeFailure(f"migrations went only {stats['directions']}")
    home = rt.envs["local"].state
    losses = home["losses"]
    if len(losses) != STEPS or not all(map(math.isfinite, losses)) \
            or any(b >= a for a, b in zip(losses, losses[1:])):
        raise SmokeFailure(f"losses of the last session: {losses}")
    _emit(phase="train", losses=losses, migrations=rt.migrations,
          run_s=run_s, grids_on_chip=stats["grids"])

    # a partial last tile, small leaves, and the largest leaf (split
    # across batches at full size)
    leaves = _leaves_of((home["params"], home["opt"]))
    odd = np.random.default_rng(seed).standard_normal(300 * 1024 + 7)
    sample = [jax.device_put(l, home_dev) for l in
              [odd.astype(np.float32)] + leaves[:4]
              + [max(leaves, key=lambda l: l.size)]]
    _emit(phase="cross_check", **_cross_check(sample, cpu))
    _emit(phase="quant8", **_quant_check(sample[-1], cpu))

    mem = home_dev.memory_stats() or {}
    _emit(phase="resources",
          hbm_peak_bytes=mem.get("peak_bytes_in_use"),
          hbm_limit_bytes=mem.get("bytes_limit"),
          host_peak_rss_bytes=_peak_rss(),
          compile_s=compile_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    from repro.launch.cache import use_compile_cache
    cache = use_compile_cache()
    _emit(phase="device", platform=dev.platform, kind=dev.device_kind,
          count=len(jax.devices()), jax=jax.__version__, cache=cache,
          host_peak_rss_bytes=_peak_rss())
    try:
        run("get_config('mamba2-370m')", seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

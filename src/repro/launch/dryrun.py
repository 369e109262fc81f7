"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

The CPU backend stands in for the mesh: ``main`` asks XLA for
``REPRO_DRYRUN_DEVICES`` (default 512) host devices before JAX starts.
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
from jax.sharding import AxisType

from repro.configs import SHAPES, TrainConfig, get_config, shape_applicable
from repro.configs.registry import ASSIGNED_ARCHS
from repro.distributed.context import DistContext
from repro.distributed.steps import (
    build_decode_step, build_prefill_step, build_train_step,
)
from repro.launch.memmodel import model_memory
from repro.launch.mesh import make_production_mesh
from repro.models import LM

HW = {
    "peak_flops": 197e12,   # bf16 per chip (TPU v5e-class)
    "hbm_bw": 819e9,        # bytes/s per chip
    "link_bw": 50e9,        # bytes/s per ICI link
    "hbm_bytes": 16e9,      # per chip
}

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(?P<rtype>\(?[a-z0-9\[\],\s{}]*?\)?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.I)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_SET_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes per collective op; estimate wire bytes with a
    ring model: all-reduce moves 2(g-1)/g of its bytes, all-gather and
    all-to-all (g-1)/g, reduce-scatter (g-1) shards."""
    per_op: dict[str, dict] = {}
    wire = 0.0
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op").lower()
        nbytes = _shape_bytes(m.group("rtype"))
        gm = _GROUP_RE.search(line)
        if gm:
            gsize = int(gm.group(2))
        else:
            gs = _GROUP_SET_RE.search(line)
            gsize = len(gs.group(1).split(",")) if gs else 2
        if op == "all-reduce":
            w = 2.0 * (gsize - 1) / gsize * nbytes
        elif op == "reduce-scatter":
            w = (gsize - 1) * nbytes           # result is the scattered shard
        elif op in ("all-gather", "all-to-all"):
            w = (gsize - 1) / gsize * nbytes
        else:  # collective-permute
            w = float(nbytes)
        d = per_op.setdefault(op, {"count": 0, "result_bytes": 0, "wire_bytes": 0.0})
        d["count"] += 1
        d["result_bytes"] += nbytes
        d["wire_bytes"] += w
        wire += w
    return {"per_op": per_op, "wire_bytes_per_device": wire}


def _combine(base: dict, body: dict, units: float) -> dict:
    """total = nonloop + units * per-layer-body (clamped at >= body levels)."""
    out = {}
    for k in ("flops", "bytes", "wire"):
        delta = max(body[k] - base[k], 0.0)
        nonloop = max(base[k] - delta, 0.0)
        out[k] = nonloop + units * delta
    per_op = {}
    ops = set(base["per_op"]) | set(body["per_op"])
    for op in ops:
        b0 = base["per_op"].get(op, {"count": 0, "wire_bytes": 0.0})
        b1 = body["per_op"].get(op, {"count": 0, "wire_bytes": 0.0})
        dc = max(b1["count"] - b0["count"], 0)
        dw = max(b1["wire_bytes"] - b0["wire_bytes"], 0.0)
        per_op[op] = {"count": (b0["count"] - dc) + units * dc,
                      "wire_bytes": (b0["wire_bytes"] - dw) + units * dw}
    out["per_op"] = per_op
    return out


def _make_mesh(multi_pod: bool):
    dbg = os.environ.get("REPRO_DEBUG_MESH")
    if dbg:  # e.g. "4,8" or "2,2,8" — development-only shrink
        dims = tuple(int(x) for x in dbg.split(","))
        axes = ("pod", "data", "model")[3 - len(dims):]
        mesh = jax.make_mesh(dims, axes,
                             axis_types=(AxisType.Auto,) * len(dims))
        return mesh, f"debug_{dbg.replace(',', 'x')}"
    mesh = make_production_mesh(multi_pod=multi_pod)
    return mesh, ("multipod_2x16x16" if multi_pod else "pod_16x16")


def _lower_compile(cfg, shape, mesh, tc, sp_decode, mode="tp", moe_impl="gspmd"):
    ctx = DistContext.create(cfg, mesh, sp_decode=sp_decode, mode=mode)
    ctx.extra["moe_impl"] = moe_impl
    lm = LM(cfg, max_seq=shape.seq_len)
    t0 = time.perf_counter()
    with mesh:
        if shape.kind == "train":
            jf, args = build_train_step(lm, tc, ctx, shape)
        elif shape.kind == "prefill":
            jf, args = build_prefill_step(lm, ctx, shape)
        else:
            jf, args = build_decode_step(lm, ctx, shape)
        lowered = jf.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
    return compiled, t_lower, t_compile


def _collect_costs(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    out = {"flops": float(ca.get("flops", 0.0)),
           "bytes": float(ca.get("bytes accessed", 0.0)),
           "wire": float(coll["wire_bytes_per_device"]),
           "per_op": coll["per_op"]}
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             tc: TrainConfig | None = None, sp_decode: bool = True,
             save_hlo: bool = False, out_dir: str = "experiments/dryrun",
             tag: str = "", skip_cost_pass: bool = False,
             mode: str = "tp", moe_impl: str = "gspmd") -> dict:
    import dataclasses

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    mesh, mesh_name = _make_mesh(multi_pod)
    res = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "applicable": ok}
    if not ok:
        res["skip_reason"] = why
        _write(res, out_dir, arch, shape_name, mesh_name, tag)
        return res

    tc = tc or TrainConfig()
    n_dev = mesh.size

    # ---- pass 1: full scanned program (proves compile; memory truth) ----
    compiled, t_lower, t_compile = _lower_compile(cfg, shape, mesh, tc,
                                                  sp_decode, mode, moe_impl)
    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        mem = {"argument": int(ma.argument_size_in_bytes),
               "output": int(ma.output_size_in_bytes),
               "temp": int(ma.temp_size_in_bytes),
               "alias": int(ma.alias_size_in_bytes)}
        mem["peak"] = mem["argument"] + mem["output"] + mem["temp"] - mem["alias"]
        mem["fits_hbm"] = mem["peak"] <= HW["hbm_bytes"]
    scanned_costs = _collect_costs(compiled)
    hlo_text = compiled.as_text() if save_hlo else None
    del compiled

    # ---- pass 2: exact-cost extrapolation (XLA counts loop bodies once) ----
    if skip_cost_pass:
        costs = scanned_costs
        units = 1.0
    else:
        pat = len(cfg.block_pattern) or 1
        tc1 = dataclasses.replace(tc, microbatches=1)
        cfg1 = dataclasses.replace(cfg, num_layers=pat, exact_costs=True)
        cfg2 = dataclasses.replace(cfg, num_layers=2 * pat, exact_costs=True)
        c1, _, s1 = _lower_compile(cfg1, shape, mesh, tc1, sp_decode, mode,
                                   moe_impl)
        r1 = _collect_costs(c1)
        del c1
        c2, _, s2 = _lower_compile(cfg2, shape, mesh, tc1, sp_decode, mode,
                                   moe_impl)
        r2 = _collect_costs(c2)
        del c2
        units = cfg.num_layers / pat
        costs = _combine(r1, r2, units)
        costs["cost_pass_compile_s"] = round(s1 + s2, 2)

    ctx_mm = DistContext.create(cfg, mesh, sp_decode=sp_decode, mode=mode)
    try:
        mm = model_memory(cfg, shape, ctx_mm, tc, LM(cfg, max_seq=shape.seq_len))
    except Exception as e:  # noqa: BLE001
        mm = {"error": str(e)}
    res.update({
        "n_devices": n_dev,
        "kind": shape.kind,
        "mem_model": mm,
        "mode": mode,
        "flops_per_device": costs["flops"],
        "bytes_accessed_per_device": costs["bytes"],
        "wire_bytes_per_device": costs["wire"],
        "collectives": costs["per_op"],
        "scanned_raw": {k: scanned_costs[k] for k in ("flops", "bytes", "wire")},
        "memory": mem,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_pass_compile_s": costs.get("cost_pass_compile_s", 0.0),
        "params": cfg.count_params(),
        "active_params": cfg.count_params(active_only=True),
        "tokens_per_step": shape.tokens_per_step,
        "tag": tag or "baseline",
        "config": {"remat": tc.remat, "microbatches": tc.microbatches,
                   "sp_decode": sp_decode},
    })
    _write(res, out_dir, arch, shape_name, mesh_name, tag)
    if save_hlo and hlo_text:
        fn = _fname(arch, shape_name, mesh_name, tag).replace(".json", ".hlo.txt")
        with open(os.path.join(out_dir, fn), "w") as f:
            f.write(hlo_text)
    return res


def _fname(arch, shape_name, mesh_name, tag):
    return f"{arch}__{shape_name}__{mesh_name}{('__' + tag) if tag else ''}.json"


def _write(res, out_dir, arch, shape_name, mesh_name, tag):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, _fname(arch, shape_name, mesh_name, tag)),
              "w") as f:
        json.dump(res, f, indent=1)


def main():
    # before the first device query: XLA fixes the device count at start-up
    os.environ["XLA_FLAGS"] = (
        os.environ.get("REPRO_XLA_EXTRA", "")
        + " --xla_force_host_platform_device_count="
        + os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full", choices=["none", "dots", "full"])
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--no-sp-decode", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--mode", default="tp", choices=["tp", "fsdp"])
    ap.add_argument("--moe-impl", default="gspmd", choices=["gspmd", "shardmap"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    tc = TrainConfig(remat=args.remat, microbatches=args.microbatches)
    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'multipod' if mp else 'pod'}"
                try:
                    r = run_cell(arch, shape, mp, tc=tc,
                                 sp_decode=not args.no_sp_decode,
                                 save_hlo=args.save_hlo, out_dir=args.out,
                                 tag=args.tag, mode=args.mode,
                                 moe_impl=args.moe_impl)
                except Exception as e:  # noqa: BLE001
                    failures.append(label)
                    print(f"[FAIL] {label}: {e}")
                    traceback.print_exc()
                    continue
                if not r.get("applicable", True):
                    print(f"[SKIP] {label}: {r['skip_reason']}")
                    continue
                mem = r.get("memory", {})
                print(f"[OK]   {label}: {r['flops_per_device']/1e9:.1f} GF/dev, "
                      f"mem {mem.get('peak', 0)/1e9:.2f} GB "
                      f"(fits={mem.get('fits_hbm')}), "
                      f"wire {r['wire_bytes_per_device']/1e6:.1f} MB/dev, "
                      f"compile {r['compile_s']:.0f}s")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  -", f)
        raise SystemExit(1)
    print("\nDry-run complete: all cells lowered + compiled.")


if __name__ == "__main__":
    main()

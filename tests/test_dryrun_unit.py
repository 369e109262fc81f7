"""Dry-run plumbing units: HLO collective parsing + cost extrapolation.

(Loads only the pure helpers, exec'd from the source file, so the test
process never imports the launcher and the model stack behind it.)
"""
import importlib.util
import os
import types

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src", "repro", "launch", "dryrun.py")


def _load_helpers():
    src = open(_SRC).read()
    # keep the pure helpers only: no jax import, no model stack
    start = src.index("_DTYPE_BYTES")
    end = src.index("def _make_mesh")
    body = src[start:end]
    header = "import re\n\n"
    mod = types.ModuleType("dryrun_helpers")
    exec(header + body, mod.__dict__)
    return mod


H = _load_helpers()

HLO = """
  %all-reduce.1 = f32[64,4096]{1,0} all-reduce(%x), replica_groups=[4,8]<=[32]
  %all-gather.2 = bf16[2048,128]{1,0} all-gather(%y), replica_groups=[2,16]<=[32]
  %reduce-scatter.3 = f32[128]{0} reduce-scatter(%z), replica_groups={{0,1,2,3}}
  %all-reduce-start.4 = f32[100]{0} all-reduce-start(%w), replica_groups=[1,2]<=[2]
  %all-reduce-done.4 = f32[100]{0} all-reduce-done(%all-reduce-start.4)
  %collective-permute.5 = bf16[10,10]{1,0} collective-permute(%p), source_target_pairs={{0,1}}
"""


def test_shape_bytes():
    assert H._shape_bytes("f32[64,4096]") == 64 * 4096 * 4
    assert H._shape_bytes("(f32[10], bf16[20])") == 10 * 4 + 20 * 2
    assert H._shape_bytes("pred[8]") == 8


def test_parse_collectives():
    out = H.parse_collectives(HLO)
    per = out["per_op"]
    assert per["all-reduce"]["count"] == 2          # start counted, done not
    assert per["all-gather"]["count"] == 1
    assert per["reduce-scatter"]["count"] == 1
    assert per["collective-permute"]["count"] == 1
    ar = 64 * 4096 * 4
    assert abs(per["all-reduce"]["wire_bytes"] -
               (2 * 7 / 8 * ar + 2 * 1 / 2 * 100 * 4)) < 1e-6
    # reduce-scatter: (group-1) x result bytes
    assert per["reduce-scatter"]["wire_bytes"] == 3 * 128 * 4


def test_combine_extrapolation():
    base = {"flops": 100.0, "bytes": 10.0, "wire": 4.0,
            "per_op": {"all-reduce": {"count": 2, "wire_bytes": 4.0}}}
    body = {"flops": 160.0, "bytes": 16.0, "wire": 7.0,
            "per_op": {"all-reduce": {"count": 3, "wire_bytes": 7.0}}}
    out = H._combine(base, body, units=10)
    # delta=60 -> nonloop=40 -> total = 40 + 10*60
    assert out["flops"] == 40 + 600
    assert out["bytes"] == 4 + 60
    assert out["wire"] == 1 + 30
    assert out["per_op"]["all-reduce"]["count"] == 1 + 10


def test_roofline_analyze():
    spec = importlib.util.spec_from_file_location(
        "roofline", _SRC.replace("dryrun.py", "roofline.py"))
    R = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(R)
    rec = {"arch": "a", "shape": "train_4k", "mesh": "pod_16x16",
           "applicable": True, "kind": "train", "n_devices": 256,
           "flops_per_device": 197e12, "bytes_accessed_per_device": 819e9,
           "wire_bytes_per_device": 100e9, "tokens_per_step": 1000,
           "active_params": 1e9, "memory": {"peak": 8e9, "fits_hbm": True}}
    a = R.analyze(rec)
    assert abs(a["compute_s"] - 1.0) < 1e-9
    assert abs(a["memory_s"] - 1.0) < 1e-9
    assert abs(a["collective_s"] - 2.0) < 1e-9
    assert a["dominant"] == "collective"
    assert abs(a["roofline_frac"] - 0.5) < 1e-9

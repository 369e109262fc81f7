"""The comparison that decides ``correct``.

Four numbers, each an exact count with the limit 0:

- ``restore_bad``: at the end of the window, leaves of names that both
  environments hold and that differ between them in shape, dtype or any
  bit.  A whole-namespace round trip leaves the two sides equal.
- ``delta_missed``: over every migration of the run, names whose content
  had changed since the two environments last agreed on them and that the
  migration did not send.  The reference tracks content by a plain pickle
  of each name, arrays by their bytes, over a replay of the same cells in
  one namespace.
- ``kernel_bad``: sampled leaf digests and chunk keys from the window's
  last digest calls that differ from :mod:`hashref`, computed on the host.
- ``output_bad``: leaves of the notebook's outputs (losses, centroids) that
  differ from the same cells run in one namespace with no migration.

The reference imports nothing of the program: the replay runs the cell
sources with ``exec`` in a plain dict, and the fingerprints use
:mod:`pickle`, :mod:`hashlib` and NumPy.  The cells themselves may import
what a notebook would, the model library among it.
"""
from __future__ import annotations

import hashlib
import io
import marshal
import pickle
import sys
import types

import jax
import numpy as np

import hashref

LIMITS = {"restore_bad": 0, "delta_missed": 0, "kernel_bad": 0,
          "output_bad": 0}


def host_copy(a) -> np.ndarray:
    """A host NumPy copy of an array that leaves no host copy cached on a
    device array (``np.asarray`` of one keeps its host copy for as long as
    the array lives)."""
    if isinstance(a, jax.Array):
        a = jax.device_put(a, a.sharding, may_alias=False)
    return np.asarray(a)


def _is_array(x) -> bool:
    return isinstance(x, (np.ndarray, jax.Array))


def _by_value(fn) -> bool:
    mod = sys.modules.get(getattr(fn, "__module__", None) or "")
    return mod is None or getattr(mod, fn.__qualname__.split(".")[0],
                                  None) is not fn


def _function(code: bytes, name: str, defaults, cells):
    """Rebuilds a function pickled by its code (globals left empty)."""
    return types.FunctionType(
        marshal.loads(code), {"__builtins__": __builtins__}, name, defaults,  # noqa: S302
        tuple(types.CellType(c) for c in cells) or None)


class _Pickler(pickle.Pickler):
    """Plain pickle, arrays taken out of the stream, and functions defined
    in a notebook pickled by their code (they cannot be found by name)."""

    def __init__(self, f, arrays: list):
        super().__init__(f, protocol=pickle.HIGHEST_PROTOCOL)
        self.arrays = arrays

    def persistent_id(self, obj):
        if _is_array(obj):
            self.arrays.append(obj)
            return len(self.arrays) - 1
        return None

    def reducer_override(self, obj):
        if isinstance(obj, types.FunctionType) and _by_value(obj):
            cells = tuple(c.cell_contents for c in obj.__closure__ or ())
            return (_function, (marshal.dumps(obj.__code__), obj.__name__,
                                obj.__defaults__, cells))
        return NotImplemented


class Fingerprints:
    """Content fingerprints of namespace values.

    An array's fingerprint is kept by identity while the array is alive:
    a JAX array never changes, and a NumPy array is made read-only once
    read, so that a cell that wrote into it would fail rather than leave a
    stale fingerprint."""

    def __init__(self):
        self._arrays: dict[int, tuple[object, bytes]] = {}

    def array(self, a) -> bytes:
        hit = self._arrays.get(id(a))
        if hit is not None and hit[0] is a:
            return hit[1]
        h = host_copy(a)
        d = hashlib.blake2b(digest_size=16)
        d.update(f"{h.dtype.str}{h.dtype.name}{h.shape}".encode())
        d.update(np.ascontiguousarray(h).reshape(-1).view(np.uint8).data)
        fp = d.digest()
        if isinstance(a, np.ndarray):
            a.flags.writeable = False
        self._arrays[id(a)] = (a, fp)
        return fp

    def value(self, obj) -> bytes | None:
        """Fingerprint of any value; None where plain pickle fails (such a
        value counts as changed every time)."""
        arrays: list = []
        buf = io.BytesIO()
        try:
            _Pickler(buf, arrays).dump(obj)
        except (pickle.PicklingError, TypeError, AttributeError):
            return None
        d = hashlib.blake2b(buf.getvalue(), digest_size=16)
        for a in arrays:
            d.update(self.array(a))
        return d.digest()

    def leaves(self, obj) -> list[bytes | None]:
        """One fingerprint per leaf of a pytree."""
        return [self.array(x) if _is_array(x) else self.value(x)
                for x in jax.tree_util.tree_leaves(obj)]

    def retain(self, values) -> None:
        """Forget arrays that ``values`` no longer reach."""
        live = {id(x) for x in jax.tree_util.tree_leaves(list(values))
                if _is_array(x)}
        self._arrays = {k: v for k, v in self._arrays.items() if k in live}


def _user_names(ns: dict) -> list[str]:
    return [n for n, v in ns.items() if not n.startswith("_")
            and not isinstance(v, types.ModuleType)]


# ---------------------------------------------------------------------------
# checks on the program's own state, at the end of the window
# ---------------------------------------------------------------------------

def restore_bad(ns_a: dict, ns_b: dict, fps: Fingerprints) -> tuple[int, int]:
    """(leaves that differ, leaves compared) over the names both
    namespaces hold."""
    bad = total = 0
    for n in sorted(set(_user_names(ns_a)) & set(_user_names(ns_b))):
        la, lb = fps.leaves(ns_a[n]), fps.leaves(ns_b[n])
        total += max(len(la), len(lb))
        if len(la) != len(lb):
            bad += max(len(la), len(lb))
            continue
        bad += sum(x is None or x != y for x, y in zip(la, lb))
    return bad, total


def kernel_bad(leaf_sample, chunk_sample) -> tuple[int, int]:
    """(digests that differ from the host formulation, digests compared)."""
    bad = total = 0
    if leaf_sample is not None:
        for leaf, digest in zip(*leaf_sample):
            bad += hashref.leaf_digest(host_copy(leaf)) != digest
            total += 1
    for chunk, key in chunk_sample:
        bad += hashref.chunk_key(chunk) != key
        total += 1
    return bad, total


def snapshot(ns: dict, names) -> dict:
    """Host copies of the named values, so the program's state can be
    freed before the reference runs."""
    return {n: jax.tree_util.tree_map(
        lambda x: host_copy(x) if _is_array(x) else x, ns[n])
        for n in names if n in ns}


# ---------------------------------------------------------------------------
# the reference: the same cells in one namespace, no migration
# ---------------------------------------------------------------------------

def replay(sources: list[str], migrations: list[dict], outputs,
           snap_after) -> tuple[list[dict], int, int]:
    """Run ``sources`` in order in one namespace.

    At each logged migration (``pos`` = index of the cell it belongs to,
    ``phase`` = before or after that cell ran) the names it considered are
    fingerprinted; a name whose content differs from what the two sides
    last agreed on had to be sent.  Returns (the outputs after each cell
    in ``snap_after``, names missed, names sent that had not changed)."""
    fps = Fingerprints()
    ns: dict = {}
    agreed: dict[str, bytes | None] = {}
    by_pos: dict[tuple[int, str], list[dict]] = {}
    for m in migrations:
        by_pos.setdefault((m["pos"], m["phase"]), []).append(m)
    missed = extra = 0

    def sync(pos, phase):
        nonlocal missed, extra
        for m in by_pos.get((pos, phase), ()):
            sent = set(m["sent"])
            for n in m["considered"]:
                if n not in ns or isinstance(ns[n], types.ModuleType):
                    continue
                fp = fps.value(ns[n])
                changed = fp is None or agreed.get(n, b"") != fp
                missed += changed and n not in sent
                extra += (not changed) and n in sent
                agreed[n] = fp
        fps.retain(ns.values())

    snap_after = set(snap_after)
    snaps = []
    for pos, src in enumerate(sources):
        sync(pos, "pre")
        exec(compile(src, f"<reference {pos}>", "exec"), ns)  # noqa: S102
        sync(pos, "post")
        if pos in snap_after:
            snaps.append(snapshot(ns, outputs))
    return snaps, missed, extra


def output_bad(program: list[dict], reference: list[dict], names,
               fps: Fingerprints) -> tuple[int, int]:
    """(output leaves that differ, output leaves compared), over the
    snapshots taken after each cycle."""
    if len(program) != len(reference):
        return max(len(program), len(reference)), 0
    bad = total = 0
    for prog, ref in zip(program, reference):
        b, t = _outputs_bad(prog, ref, names, fps)
        bad += b
        total += t
    return bad, total


def _outputs_bad(program: dict, reference: dict, names, fps):
    bad = total = 0
    for n in names:
        if n not in program and n not in reference:
            continue
        if n not in program or n not in reference:
            bad += 1
            total += 1
            continue
        la, lb = fps.leaves(program[n]), fps.leaves(reference[n])
        total += max(len(la), len(lb))
        if len(la) != len(lb):
            bad += max(len(la), len(lb))
            continue
        bad += sum(x is None or x != y for x, y in zip(la, lb))
    return bad, total

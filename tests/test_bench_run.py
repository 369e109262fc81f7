"""The benchmark driver's module list names only modules that exist."""
import importlib

from benchmarks.run import ARTIFACTS, MODULES


def test_every_listed_module_has_a_run_function():
    for name in MODULES:
        mod = importlib.import_module(f"benchmarks.{name}")
        assert callable(getattr(mod, "run", None)), name
    assert set(ARTIFACTS) <= set(MODULES)

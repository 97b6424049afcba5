"""The on-chip benchmark's own tests (``bench/tests``), collected by tier-1.

They run on the CPU with the chip check stood in for (see
``bench/tests/conftest.py``).  Each module of ``bench/tests`` is loaded
under a name of its own, with the benchmark's ``conftest`` visible to it
as ``conftest`` while it loads, and its tests and fixtures are gathered
here, so that they run with the rest of tier-1 under their own names.
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests`` runs the same tests
alone."""
import importlib.util
import os
import sys

from _pytest.fixtures import getfixturemarker

BENCH_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "tests")


def _load(name: str, alias: str):
    spec = importlib.util.spec_from_file_location(
        alias, os.path.join(BENCH_TESTS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[alias] = module
    spec.loader.exec_module(module)
    return module


def _gather():
    own = sys.modules.get("conftest")
    conftest = _load("conftest", "bench_tests_conftest")
    sys.modules["conftest"] = conftest
    try:
        modules = [conftest] + [
            _load(f[:-3], "bench_tests_" + f[:-3])
            for f in sorted(os.listdir(BENCH_TESTS))
            if f.startswith("test_") and f.endswith(".py")]
    finally:
        if own is None:
            sys.modules.pop("conftest", None)
        else:
            sys.modules["conftest"] = own
    found = {}
    for module in modules:
        for key, value in vars(module).items():
            if (key.startswith("test_") and callable(value)
                    or getfixturemarker(value) is not None):
                assert key not in found, f"two bench tests named {key}"
                found[key] = value
    return found


globals().update(_gather())

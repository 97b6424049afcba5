"""Fixtures of the benchmark's own tests (run them from the checkout's root:
``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``).

``tiny_root`` is a copy of the benchmark with one more cell, ``tiny3d.repart``
(the cavity at 10³ in 10 slabs under the ``repart`` traffic), one more
configuration file and one more metric reader, ``test.steps``: added as
files and entries only, the way a later change adds them."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.env import enable_x64  # noqa: E402

enable_x64()

TINY = "tiny3d.repart"


def make_root(path, n: int = 10, config: str = "cavity3d") -> str:
    """A checkout-like directory: ``BENCHMARK.json`` and a copy of ``bench``
    with the tiny cell added, and ``src`` linked to this checkout's."""
    root = str(path)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("out", "scratch", "tests",
                                                  "__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    bench = os.path.join(root, "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny3d", "source": "test",
                            "file": "bench/configs/tiny3d.json",
                            "reduced": ["n"], "why": "test"})
    spec["workloads"].append({"name": TINY, "config": "tiny3d",
                              "traffic": "repart", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "test.steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "device", "moves": "step_s",
                              "workloads": [TINY]})
    for m in spec["per_layer"]:
        m["workloads"] = sorted(set(m["workloads"]) | {TINY})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    cfg["n"] = n
    with open(os.path.join(bench, "configs", "tiny3d.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(bench, "limits", "cavity3d.repart.json"),
                os.path.join(bench, "limits", TINY + ".json"))
    with open(os.path.join(bench, "metrics", "test.steps.py"), "w") as f:
        f.write("def read(run):\n    return len(run['window']['ends'])\n")
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture
def drive(monkeypatch, capsys):
    """Run ``run.main`` on a root with the chip check skipped (the CPU
    stands in); returns the parsed last line of standard output and the
    text of standard error."""
    import jax

    import run

    def go(root, *argv):
        # run.main points JAX's cache into the root; restored afterwards
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", root)
        monkeypatch.setattr(run, "ROOT", root)
        monkeypatch.setattr(run, "BENCH", os.path.join(root, "bench"))
        monkeypatch.setattr(run, "require_chips",
                            lambda jax_, chips: jax.devices())
        assert run.main(list(argv)) == 0
        out, err = capsys.readouterr()
        return json.loads(out.strip().splitlines()[-1]), err

    return go

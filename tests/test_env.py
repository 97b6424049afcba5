"""repro.env: process-level XLA tuning — flag hygiene, idempotence, the
after-init guard.  The merge tests run in subprocesses so the parent's
initialized JAX backend (and its XLA_FLAGS) never interferes.
"""
import json
import os
import subprocess
import sys

from repro.env import GPU_XLA_FLAGS

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def run_py(code: str, **env) -> subprocess.CompletedProcess:
    full = {**os.environ, "PYTHONPATH": SRC, **env}
    full.pop("XLA_FLAGS", None)
    full.update({k: v for k, v in env.items()})
    return subprocess.run([sys.executable, "-c", code], env=full,
                          capture_output=True, text=True, timeout=120)


def test_gpu_flags_well_formed():
    """Every tuning flag is a --name=value token, names unique."""
    names = []
    for f in GPU_XLA_FLAGS:
        assert f.startswith("--xla_"), f
        assert "=" in f and " " not in f, f
        names.append(f.split("=", 1)[0])
    assert len(set(names)) == len(names)


def test_configure_merges_and_is_idempotent():
    out = run_py(
        "import os, json\n"
        "from repro.env import configure_platform, GPU_XLA_FLAGS\n"
        "s1 = configure_platform()\n"
        "s2 = configure_platform()\n"
        "print(json.dumps({'s1': s1, 's2': s2,\n"
        "                  'env': os.environ['XLA_FLAGS']}))\n",
        JAX_PLATFORMS="gpu")
    assert out.returncode == 0, out.stderr
    r = json.loads(out.stdout)
    assert r["s1"] == r["s2"] == r["env"] == " ".join(GPU_XLA_FLAGS)


def test_configure_preserves_user_overrides():
    """A flag the user already set (even to the opposite value) wins; the
    rest are appended."""
    out = run_py(
        "import os\n"
        "from repro.env import configure_platform\n"
        "print(configure_platform())\n",
        JAX_PLATFORMS="gpu",
        XLA_FLAGS="--xla_gpu_enable_latency_hiding_scheduler=false")
    assert out.returncode == 0, out.stderr
    toks = out.stdout.strip().splitlines()[-1].split()
    assert toks[0] == "--xla_gpu_enable_latency_hiding_scheduler=false"
    assert len([t for t in toks
                if t.startswith("--xla_gpu_enable_latency_hiding")]) == 1
    assert len(toks) == len(GPU_XLA_FLAGS)


def test_configure_is_noop_off_gpu():
    """On a CPU platform (or none declared) the GPU flag set must NOT be
    applied: XLA aborts the process on flags its build does not register."""
    for env in ({"JAX_PLATFORMS": "cpu"}, {"JAX_PLATFORMS": ""}):
        out = run_py(
            "from repro.env import configure_platform\n"
            "print(repr(configure_platform()))\n",
            **env)
        assert out.returncode == 0, out.stderr
        assert out.stdout.splitlines()[0] == "''"


def test_configure_raises_after_jax_init():
    """Once a backend exists, XLA_FLAGS edits are silently ignored by XLA
    — the helper must refuse loudly instead."""
    out = run_py(
        "import jax\n"
        "jax.numpy.zeros(1).block_until_ready()\n"
        "from repro.env import configure_platform\n"
        "try:\n"
        "    configure_platform('gpu')\n"
        "except RuntimeError as e:\n"
        "    print('RAISED:', str(e)[:40])\n",
        JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr
    assert out.stdout.startswith("RAISED:"), out.stdout


def test_platform_pin_is_soft():
    out = run_py(
        "import os\n"
        "from repro.env import configure_platform\n"
        "configure_platform('gpu')\n"
        "print(os.environ['JAX_PLATFORMS'])\n",
        JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr
    # explicit user env wins over the pin
    assert out.stdout.strip() == "cpu"


def test_compile_cache_honours_env_var(tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins as it stands; no other directory is
    set in code."""
    out = run_py(
        "import jax\n"
        "from repro.env import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n",
        JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_compile_cache_defaults_to_checkout_dir():
    """Without the env var the cache sits at a fixed ``.jax_cache`` of the
    checkout — never a temporary, per-process or per-run path."""
    from repro.env import COMPILE_CACHE_DIR

    out = run_py(
        "import os\n"
        "os.environ.pop('JAX_COMPILATION_CACHE_DIR', None)\n"
        "import jax\n"
        "from repro.env import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n",
        JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [COMPILE_CACHE_DIR, COMPILE_CACHE_DIR]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert COMPILE_CACHE_DIR == os.path.join(root, ".jax_cache")

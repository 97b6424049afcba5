"""The harness: cells, configurations and metrics found by name, the chip
check, the result line, the trace reduction and the frozen cost contracts.

Run from the checkout's root: ``JAX_PLATFORMS=cpu python -m pytest -q
bench/tests``."""
import json
import os

import pytest

import spec
from conftest import ROOT, TINY

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_finds_its_files():
    b = _benchmark()
    for w in b["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"step_s", "setup_s"}
        assert cell.per_layer
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_added_cell_and_metric_need_no_harness_edit(tiny_root):
    bench = os.path.join(tiny_root, "bench")
    cell = spec.load_cell(tiny_root, TINY, bench=bench)
    assert cell.config["n"] == 10
    assert "test.steps" in [m["name"] for m in cell.per_layer]
    read = spec.load_reader("test.steps", bench=bench)
    assert read({"window": {"ends": [0.1, 0.2, 0.3]}}) == 3
    with pytest.raises(KeyError):
        spec.load_cell(tiny_root, "no.such.cell", bench=bench)


def test_run_refuses_a_host_without_a_tpu(capsys, monkeypatch):
    import run

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", ROOT)
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "cavity3d.repart", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_last_line_has_the_contract_keys(tiny_root, drive):
    line, _ = drive(tiny_root, "--workload", TINY, "--seed",
                    str(2**31 + 12345), "--seconds", "0.2", "--trace", "0")
    assert list(line) == CONTRACT_KEYS + ["checks"]
    assert set(line["metrics"]) == {"step_s", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] >= 1


SPANS = ("bench.dispatch", "bench.wait", "bench.readback")
TRACE = os.path.join(ROOT, "bench", "testdata",
                     "cavity20_fused_step.xplane.pb.xz")


@pytest.fixture(scope="module")
def recorded():
    """The reduction of one traced PISO step of the 20³ cavity (10 slabs,
    alpha 2, f32_ir, fused kernels) recorded on a TPU v5e."""
    import lzma
    import tempfile

    import xplane

    with tempfile.TemporaryDirectory() as d:
        with lzma.open(TRACE) as f, \
                open(os.path.join(d, "step.xplane.pb"), "wb") as g:
            g.write(f.read())
        return xplane.reduce(d, SPANS)


def test_recorded_trace_reduces(recorded):
    r = recorded
    assert r["devices"] == 1 and r["steps"] == 1
    assert r["window_s"] == pytest.approx(0.113894802, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.102790449, abs=1e-9)
    assert r["xla_s"] == pytest.approx(0.102370502, abs=1e-9)
    # one spmv_dot and one axpy_precond call per inner CG iteration
    # (the step's p_iters were [214, 216]), on the (5, 2048/128, 128)
    # stacked vectors of the coarse pressure system
    assert r["kernels"]["spmv_dot"]["5x16x128"]["calls"] == 430
    assert r["kernels"]["axpy_precond"]["5x16x128"]["calls"] == 430
    assert r["kernels"]["spmv_dot"]["5x16x128"]["seconds"] == \
        pytest.approx(282.257e-6, abs=1e-9)
    assert r["idle_gaps"][0][0] == "bench.readback"
    assert r["idle_gaps"][0][1] == pytest.approx(0.011101222, abs=1e-9)
    assert r["top_ops"][0][0].startswith(
        "%fusion.255 = (f32[8000], f32[8000]) fusion(")
    assert len(r["top_ops"]) == len(r["idle_gaps"]) == 10


def test_kernel_time_readers_on_the_recorded_trace(recorded):
    cell = spec.load_cell(ROOT, "cavity3d.repart")
    run = {"cell": cell, "trace": recorded}
    spmv = spec.load_reader("kernel.spmv_dot_us")(run)
    assert spmv == pytest.approx(1e6 * 282.257e-6 / 430, rel=1e-6)
    assert spec.load_reader("kernel.axpy_precond_us")(run) > 0
    run["trace"] = dict(recorded, kernels={})
    assert spec.load_reader("kernel.spmv_dot_us")(run) is None


def test_self_time_leaves_out_nested_events():
    import xplane

    events = [(0, 100, "while"), (10, 20, "a"), (50, 70, "b")]
    assert xplane.self_times(events) == [70, 10, 20]
    assert xplane.union([(10, 20), (15, 30), (50, 70)]) == [[10, 30],
                                                           [50, 70]]

"""The per-phase reduction (``bench/phases.py``) on a synthetic executable
and trace: a scan's while loop holding a fusion and a scoped inner while,
a copy the compiler made, and one executable more in the window."""
from types import SimpleNamespace as NS

import pytest

import phases
import xplane

NAMES = ("assemble_mom", "solve_p", "correct")
SPANS = ("bench.dispatch", "bench.wait", "bench.readback")

HLO = """\
HloModule jit_rolled, entry_computation_layout={(f64[8]{0})->f64[8]{0}}

%fused_computation (param_0: f64[8]) -> f64[8] {
  %param_0 = f64[8]{0} parameter(0)
  %multiply.1 = f64[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(rolled)/while/body/assemble_mom/mul"}
  ROOT %negate.1 = f64[8]{0} negate(%multiply.1), metadata={op_name="jit(rolled)/while/body/correct/neg"}
  %abs.1 = f64[8]{0} abs(%param_0), metadata={op_name="jit(rolled)/while/body/assemble_mom/abs"}
}

%inner_body (arg.1: (f64[8], s32[])) -> (f64[8], s32[]) {
  %arg.1 = (f64[8]{0}, s32[]) parameter(0)
  %get-tuple-element.1 = f64[8]{0} get-tuple-element(%arg.1), index=0
  %copy.1 = f64[8]{0} copy(%get-tuple-element.1)
  %sine.1 = f64[8]{0} sine(%copy.1), metadata={op_name="jit(rolled)/while/body/solve_p/while/body/sin"}
  %cosine.1 = f64[8]{0} cosine(%sine.1), metadata={op_name="jit(rolled)/while/body/solve_p/while/body/correct/cos"}
  %get-tuple-element.2 = s32[] get-tuple-element(%arg.1), index=1
  ROOT %tuple.1 = (f64[8]{0}, s32[]) tuple(%cosine.1, %get-tuple-element.2)
}

%inner_cond (arg.2: (f64[8], s32[])) -> pred[] {
  %arg.2 = (f64[8]{0}, s32[]) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element(%arg.2), index=1
  %constant.1 = s32[] constant(3)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.3, %constant.1), direction=LT
}

%outer_body (arg.3: (f64[8], s32[])) -> (f64[8], s32[]) {
  %arg.3 = (f64[8]{0}, s32[]) parameter(0)
  %get-tuple-element.4 = f64[8]{0} get-tuple-element(%arg.3), index=0
  %fusion.1 = f64[8]{0} fusion(%get-tuple-element.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(rolled)/while/body"}
  %while.2 = (f64[8]{0}, s32[]) while(%arg.3), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(rolled)/while/body/solve_p/while"}
  %get-tuple-element.5 = f64[8]{0} get-tuple-element(%while.2), index=0
  %copy.3 = f64[8]{0} copy(%get-tuple-element.5)
  ROOT %tuple.2 = (f64[8]{0}, s32[]) tuple(%copy.3, %get-tuple-element.2)
}

%outer_cond (arg.4: (f64[8], s32[])) -> pred[] {
  %arg.4 = (f64[8]{0}, s32[]) parameter(0)
  ROOT %constant.2 = pred[] constant(true)
}

ENTRY %main.9 (x: f64[8]) -> f64[8] {
  %x = f64[8]{0} parameter(0), metadata={op_name="x"}
  %tuple.3 = (f64[8]{0}, s32[]) tuple(%x, %x)
  %while.9 = (f64[8]{0}, s32[]) while(%tuple.3), condition=%outer_cond, body=%outer_body, metadata={op_name="jit(rolled)/while"}
  ROOT %get-tuple-element.9 = f64[8]{0} get-tuple-element(%while.9), index=0
}
"""


def test_scope_map_rules():
    m = phases.scope_map(HLO, NAMES)
    assert phases.module_name(HLO) == "jit_rolled"
    # the fusion names no phase: two of its three fused ops are assemble_mom
    assert m["fusion.1"] == "assemble_mom"
    # a nested while: its body's ops keep their own scope, and an unscoped
    # op inside takes the scope of the while that calls its body
    assert m["while.2"] == "solve_p"
    assert m["sine.1"] == "solve_p" and m["cosine.1"] == "solve_p"
    assert m["copy.1"] == "solve_p" and m["compare.1"] == "solve_p"
    # an unscoped op in the unscoped scan loop stays unscoped
    assert m["copy.3"] is None and m["while.9"] is None
    # fused instructions are no top-level ops
    assert "multiply.1" not in m


def _ev(start, end, name):
    return NS(start_ns=start, end_ns=end, name=name)


def _trace():
    ops = [
        _ev(10, 990, "%while.9 = (f64[8]{0}, s32[]) while(%tuple.3)"),
        _ev(20, 200, "%fusion.1 = f64[8]{0} fusion(%get-tuple-element.4)"),
        _ev(200, 900, "%while.2 = (f64[8]{0}, s32[]) while(%arg.3)"),
        _ev(210, 300, "%copy.1 = f64[8]{0} copy(%get-tuple-element.1)"),
        _ev(300, 880, "%sine.1 = f64[8]{0} sine(%copy.1)"),
        _ev(900, 980, "%copy.3 = f64[8]{0} copy(%get-tuple-element.5)"),
        _ev(992, 995, "%mystery.7 = f64[8]{0} add(%x, %x)"),
        # the snapshot's executable: the same instruction name, no phase
        _ev(1010, 1090, "%copy.3 = f64[8]{0} copy(%p)"),
        # after the window
        _ev(1200, 1300, "%sine.1 = f64[8]{0} sine(%copy.1)"),
    ]
    modules = [_ev(0, 1000, "jit_rolled(123)"),
               _ev(1000, 1100, "jit__lambda_(7)"),
               _ev(1150, 1350, "jit_rolled(123)")]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev(0, 600, "bench.dispatch"), _ev(600, 1100, "bench.wait")])])
    return NS(planes=[device, host])


def test_phases_add_up_to_busy_time():
    data = _trace()
    scopes = phases.scope_map(HLO, NAMES)
    r = phases.reduce_data(data, scopes, SPANS, module="jit_rolled")
    ns = 1e-9
    assert r["phase_s"] == pytest.approx({"assemble_mom": 180 * ns,
                                          "solve_p": 700 * ns})
    # the scan loop's own time, its unscoped copy, the op missing from the
    # map and the other executable's copy
    assert r["other_s"] == pytest.approx((20 + 80 + 3 + 80) * ns)
    assert r["unmatched"] == 1 and r["steps"] == 1
    busy = xplane.reduce_data(data, SPANS)["busy_s"]
    assert sum(r["phase_s"].values()) + r["other_s"] == pytest.approx(busy)
    assert r["other_ops"][0][1] == pytest.approx(80 * ns)
    # without the module, every op is looked up: the snapshot's copy.3
    # then reads as the step's
    r = phases.reduce_data(data, scopes, SPANS)
    assert r["other_s"] == pytest.approx((20 + 80 + 3 + 80) * ns)

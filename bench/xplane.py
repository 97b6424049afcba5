"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers of a
traced window.

On a TPU the ``XLA Ops`` line of each ``/device:TPU:N`` plane holds one
event per executed HLO instruction, named by the instruction's HLO text;
container instructions (``while``, ``conditional``) span the events of
their bodies, which nest inside them.  The host plane holds the
benchmark's spans (``jax.profiler.TraceAnnotation``) on the same clock.

* window: from the start of the first host span of the window to the end
  of the last (``run.SPANS``);
* busy: the union of the intervals of all events, clipped to the window
  and averaged over the devices;
* kernels: the Pallas calls, found by ``custom_call_target=
  "tpu_custom_call"``.  The trace carries no kernel name (a vmapped
  ``pallas_call`` is named ``vmap__``), so a call is told by its outputs:
  one array is a plain band SpMV (``spmv``), two are the band SpMV with
  its ``p . Ap`` partials (``spmv_dot``), five the axpy pair with the
  Jacobi apply and two partials (``axpy_precond``).  Calls and device
  seconds are kept per kind and per first-output shape;
* xla: busy time outside every Pallas call;
* top ops: self time (duration less nested events) per instruction;
* idle gaps: the stretches of the window with no event, each named by the
  host span that covers most of it (``"none"`` where none does).
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
DEVICE = re.compile(r"^/device:TPU:\d+$")
PALLAS = 'custom_call_target="tpu_custom_call"'
KINDS = {1: "spmv", 2: "spmv_dot", 5: "axpy_precond"}
TOP = 10
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def outputs(hlo: str) -> list:
    """``(dtype, dims)`` of each output of an HLO instruction's text."""
    head = hlo.split(" = ", 1)[1]
    opcode = re.search(r"\)? ?[a-z][a-z\-]*\(", head)
    result = head[:opcode.start() + 1] if opcode else head
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(result)]


def pallas_call(hlo: str):
    """``(kind, first output dims)`` of a Pallas call, or None."""
    if PALLAS not in hlo:
        return None
    outs = outputs(hlo)
    return KINDS.get(len(outs), f"pallas{len(outs)}"), outs[0][1]


def short(hlo: str, width: int = 160) -> str:
    """An instruction's text without layouts and operand names."""
    s = hlo
    while True:
        t = re.sub(r"\{[^{}]*\}", "", s)
        if t == s:
            break
        s = t
    s = re.sub(r" %[\w.\-]+", "", s)
    s = s.split(", calls=")[0].split(", operand_layout")[0]
    return s[:width]


def load(trace_dir: str):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{trace_dir}, found {len(paths)}")
    return ProfileData.from_file(paths[0])


def self_times(events):
    """Duration less the nested events' of each event of one line
    (``(start, end, name)``), where a container spans its body."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = [e - s for s, e, _ in events]
    stack = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return self_t


def reduce(trace_dir: str, spans) -> dict:
    """The window's device numbers (seconds) from the one trace under
    ``trace_dir``; ``spans`` are the host span names of the window, the
    first of them opening each step."""
    return reduce_data(load(trace_dir), spans)


def reduce_data(data, spans) -> dict:
    host, devices = [], []
    for plane in data.planes:
        is_device = bool(DEVICE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                devices.append([(e.start_ns, e.end_ns, e.name)
                                for e in line.events])
            elif not is_device:
                host += [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name in spans]
    if not host:
        raise ValueError("the trace holds none of the window's host spans")
    lo = min(s for s, _, _ in host)
    hi = max(e for _, e, _ in host)
    busy = pallas = 0.0
    kernels, ops, gaps, seen = {}, {}, [], {}
    for events in devices:
        events = [(max(s, lo), min(e, hi), n) for s, e, n in events
                  if e > lo and s < hi]
        calls = []
        for (s, e, name), t in zip(events, self_times(events)):
            ops[name] = ops.get(name, 0.0) + t
            if name not in seen:
                seen[name] = pallas_call(name)
            call = seen[name]
            if call is not None:
                calls.append((s, e))
                k = kernels.setdefault(call[0], {}).setdefault(
                    "x".join(map(str, call[1])), {"calls": 0, "seconds": 0.0})
                k["calls"] += 1
                k["seconds"] += (e - s) * 1e-9
        merged = union((s, e) for s, e, _ in events)
        busy += _length(merged)
        pallas += _length(union(calls))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(devices), 1)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:TOP]:
        cover = {}
        for hs, he, name in host:
            overlap = min(e, he) - max(s, hs)
            if overlap > 0:
                cover[name] = cover.get(name, 0) + overlap
        named.append([max(cover, key=cover.get) if cover else "none",
                      (e - s) * 1e-9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n_dev,
        "xla_s": (busy - pallas) * 1e-9 / n_dev,
        "steps": sum(1 for _, _, n in host if n == spans[0]),
        "devices": len(devices),
        "kernels": kernels,
        "top_ops": [[short(name), t * 1e-9 / n_dev] for name, t in top],
        "idle_gaps": named,
    }

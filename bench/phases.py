"""Device time per phase of the PISO step, from a profiler trace and the
compiled window's HLO.

The program runs each phase of its step under ``jax.named_scope(<phase>)``
(``fvm/step_program.py``), so the instructions of the compiled window carry
their phase as a component of their ``op_name`` metadata.  The trace's
``XLA Ops`` events are named by their instruction's HLO text and carry no
metadata, so the phase comes from the HLO of the executable that ran
(``compiled.as_text()``), keyed by instruction name: ``%fusion.3437`` in
the trace is ``fusion.3437`` in the HLO.  :func:`scope_map` gives every
instruction of the entry computation and of the computations it calls
(while bodies and conditions, conditional branches, calls):

* the first component of its ``op_name`` that is a phase name;
* for a fusion whose own ``op_name`` names no phase, the phase that most of
  its fused instructions carry;
* for an instruction with no phase of its own inside a called computation,
  the phase of the instruction that calls it;
* otherwise ``None`` (unscoped: compiler-made copies, the program's seed
  and finalize, the splits of f64 parameters).

:func:`reduce` sums the device self time of each op
(:func:`xplane.self_times`) per phase over the window :mod:`xplane`
reduces; unscoped ops, ops missing from the map and the ops of any other
executable go to ``other_s``, so the phases and ``other_s`` add up to the
device's busy time.
"""
from __future__ import annotations

import bisect
import collections
import re

import xplane

MODULES_LINE = "XLA Modules"
TOP = 10
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED_ONE = re.compile(
    r"\b(?:calls|condition|body|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALLED_MANY = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_EVENT = re.compile(r"^(?:ROOT )?%?([\w.\-]+) = ")


def instruction(event_name: str) -> str | None:
    """The instruction name of a trace event named by its HLO text."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else None


def module_name(hlo_text: str) -> str | None:
    """The ``HloModule`` name of an executable's HLO text."""
    m = re.match(r"HloModule ([^\s,]+)", hlo_text)
    return m.group(1) if m else None


def _parse(hlo_text: str):
    """``{computation: [(name, opcode, own op_name, called)]}`` and the
    entry computation's name."""
    comps, entry, body = {}, None, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            body = comps.setdefault(head.group(1), [])
            if line.startswith("ENTRY"):
                entry = head.group(1)
            continue
        ins = _INSTRUCTION.match(line) if body is not None else None
        if not ins:
            continue
        rest = ins.group(2)
        opcode = _OPCODE.search(" " + rest)
        op_name = _OP_NAME.search(rest)
        called = _CALLED_ONE.findall(rest)
        for group in _CALLED_MANY.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        body.append((ins.group(1), opcode.group(1) if opcode else "",
                     op_name.group(1) if op_name else "", called))
    return comps, entry


def scope_map(hlo_text: str, names) -> dict:
    """``{instruction: phase or None}`` for the instructions of the entry
    computation of ``hlo_text`` and of the computations it calls (see the
    module docstring); ``names`` are the phase names."""
    names = frozenset(names)
    comps, entry = _parse(hlo_text)

    def own(op_name):
        return next((p for p in op_name.split("/") if p in names), None)

    def majority(comp):
        votes = collections.Counter(
            s for s in (own(op) for _, _, op, _ in comps.get(comp, ()))
            if s)
        return votes.most_common(1)[0][0] if votes else None

    out, done = {}, set()
    todo = [(entry, None)] if entry is not None else []
    while todo:
        comp, inherited = todo.pop()
        if (comp, inherited) in done:
            continue
        done.add((comp, inherited))
        for name, opcode, op_name, called in comps.get(comp, ()):
            scope = own(op_name)
            if scope is None and opcode == "fusion" and called:
                scope = majority(called[0])
            scope = scope or inherited
            out[name] = scope
            if opcode != "fusion":
                todo += [(c, scope) for c in called]
    return out


def reduce(trace_dir: str, scopes: dict, spans,
           module: str | None = None) -> dict:
    """Device self time (seconds) per phase over the traced window of the
    one trace under ``trace_dir`` (:func:`xplane.load`; the window of
    :func:`xplane.reduce`).  ``scopes`` is a :func:`scope_map`; with
    ``module`` (:func:`module_name`) only the ops of that executable are
    looked up in it, told by the trace's ``XLA Modules`` line."""
    return reduce_data(xplane.load(trace_dir), scopes, spans, module)


def reduce_data(data, scopes: dict, spans, module: str | None = None):
    host, devices = [], []
    for plane in data.planes:
        is_device = bool(xplane.DEVICE.match(plane.name))
        ops, mods = [], []
        for line in plane.lines:
            if is_device and line.name == xplane.OPS_LINE:
                ops = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            elif is_device and line.name == MODULES_LINE:
                mods = sorted((e.start_ns, e.end_ns, e.name.split("(")[0])
                              for e in line.events)
            elif not is_device:
                host += [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name in spans]
        if is_device:
            devices.append((ops, mods))
    if not host:
        raise ValueError("the trace holds none of the window's host spans")
    lo = min(s for s, _, _ in host)
    hi = max(e for _, e, _ in host)
    phase = collections.defaultdict(float)
    other, unscoped = 0.0, collections.defaultdict(float)
    unmatched = 0
    for ops, mods in devices:
        starts = [m[0] for m in mods]
        kept = [(s, e, n) for s, e, n in ops if e > lo and s < hi]
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in kept]
        for (s, e, name), t in zip(kept, xplane.self_times(clipped)):
            if module is not None and mods:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= mods[i][1] or mods[i][2] != module:
                    other += t
                    unscoped[f"[{mods[i][2] if i >= 0 else '?'}]"] += t
                    continue
            ins = instruction(name)
            if ins not in scopes:
                unmatched += 1
            if scopes.get(ins):
                phase[scopes[ins]] += t
            else:
                other += t
                unscoped[name] += t
    n_dev = max(len(devices), 1)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "phase_s": {k: v * 1e-9 / n_dev for k, v in sorted(phase.items())},
        "other_s": other * 1e-9 / n_dev,
        "steps": sum(1 for _, _, n in host if n == spans[0]),
        "unmatched": unmatched,
        "other_ops": [[xplane.short(n), t * 1e-9 / n_dev] for n, t in top],
    }

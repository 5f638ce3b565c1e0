"""Per-phase device time of the train step, read from the program's own
names.

The program names each phase of its step with ``jax.named_scope``
(``fwd_bwd``, ``attack``, ``defense``, ``optimizer``, ``telemetry``), with
sub-scopes inside them (``accumulate``, ``distance``, ``filter``,
``aggregate`` in the safeguard; ``embed``, ``mixer``, ``mlp``, ``ssd``,
``head`` in the models), and its host loop with profiler spans
(``repro.step``, ``repro.batch``, ``repro.dispatch``, ``repro.log``).

A scope reaches the compiled module's HLO text as the ``op_name`` of each
instruction (``jit(step_fn)/fwd_bwd/.../mixer/ssd/...``); the trace's
``XLA Ops`` events carry only the instruction's name.  :func:`op_scopes`
maps one to the other from the compiled step's ``as_text()``.  A fusion
carries the ``op_name`` of its root: where XLA fuses ops of two phases,
the whole fusion counts under the root's.

:func:`reduce_planes` adds to ``trace_reduce.reduce_planes``'s reduction
the device self time of the step's module per phase and per sub-scope.
An operation's self time is its time minus the time its nested children
cover, so a ``while`` keeps only its loop control and its body's
operations count under their own scopes.  Times are seconds, device
means, like ``ops``.  Idle gaps are then named by the innermost host span
(the benchmark's ``chipbench.*`` or the program's ``repro.*``) over most
of each gap.
"""

from __future__ import annotations

import bisect
import collections
import gzip
import re

from chipbench import trace_reduce

PHASES = ("fwd_bwd", "attack", "defense", "optimizer", "telemetry")
MODEL_SCOPES = ("embed", "mixer", "mlp", "ssd", "head")
SUB_SCOPES = ("accumulate", "distance", "filter", "aggregate") + MODEL_SCOPES
HOST_PREFIXES = ("chipbench.", "repro.")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s")
_CALLS = re.compile(r"\bcalls=%?([A-Za-z0-9_.\-]+)")
_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()*([^()]*)\)*$")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([A-Za-z0-9_.\-]+)")
_OPERAND = re.compile(r"%([A-Za-z0-9_.\-]+)")


def _parse(hlo_text: str):
    """Per instruction its own ``op_name`` (``""`` without one), the
    computation it fuses (``calls=``), its operands and its computation;
    per computation its instructions in text order (the root last) and
    the instruction that runs it (a loop's body, a fusion's)."""
    own, calls, operands, where, comps, caller = {}, {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            name = m.group(1)
            op = _OP_NAME.search(line)
            own[name] = op.group(1) if op else ""
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
            for c in _CALLED.findall(line):
                caller.setdefault(c, name)
            operands[name] = _OPERAND.findall(line.split(" = ", 1)[1])
            where[name] = comp
            comps.setdefault(comp, []).append(name)
        elif line.endswith("{") and not line.startswith(" "):
            head = _COMP.match(line)
            comp = head.group(1) if head else None
    return own, calls, operands, where, comps, caller


def op_scopes(hlo_text: str) -> dict:
    """``{instruction: op_name}`` for every instruction of every
    computation of an HLO module's text (loop bodies and fusions
    included).  An instruction the compiler made without one takes, in
    this order, that of its fused computation's root (or of the op nearest
    that root that has one: a multi-output fusion's root is a tuple), else
    that of its first operand that has one (a layout copy belongs to what
    it copies), else that of the instruction that runs its computation (a
    loop the compiler built belongs to the loop's scope); ``""`` where
    none has one."""
    own, calls, operands, where, comps, caller = _parse(hlo_text)
    resolved = {}

    def resolve(name):
        if name in resolved:
            return resolved[name]
        resolved[name] = ""                  # a cycle resolves to nothing
        inner = reversed(comps.get(calls.get(name), []))
        args = (o for o in operands[name] if o in own)
        outer = [caller[where[name]]] if where[name] in caller else []
        got = own[name] or next(
            (r for part in (inner, args, outer) for r in map(resolve, part)
             if r), "")
        resolved[name] = got
        return got

    return {name: resolve(name) for name in own}


def fused_phases(hlo_text: str) -> dict:
    """``{fusion: phases of the ops fused into it}``: where XLA fuses ops
    of several phases, the fusion's time counts under its root's phase
    alone, and this says which others ride in it."""
    own, calls, _, _, comps, _ = _parse(hlo_text)

    def phases(comp, seen=()):
        out = set()
        for name in comps.get(comp, []):
            out.add(phase_of(own[name]))
            if name in calls and calls[name] not in seen:
                out |= phases(calls[name], seen + (comp,))
        return out

    return {name: sorted(p for p in phases(c) if p)
            for name, c in calls.items()}


def module_name(hlo_text: str):
    """The module's name (``jit_step_fn``), as the trace's ``XLA Modules``
    line names its runs (``jit_step_fn(<fingerprint>)``)."""
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else None


def read_hlo(path) -> str:
    """An HLO text file, gzipped where its name ends in ``.gz``."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def _split(op_name: str):
    """``(phase, [sub-scopes under it])``.  A scope at the top of a
    transformed function is printed inside the transformation
    (``vmap(jvp(embed))``, ``transpose(jvp(head))``).  A path with no
    phase but a model scope is the forward/backward's: the compiler
    hoists constants of the model (a causal mask) out of the step's
    scopes."""
    parts = [(m.group(1) if m else p) for p, m in
             ((p, _WRAPPED.match(p)) for p in op_name.split("/"))]
    for i, part in enumerate(parts):
        if part in PHASES:
            return part, list(dict.fromkeys(
                p for p in parts[i + 1:] if p in SUB_SCOPES))
    model = [p for p in parts if p in MODEL_SCOPES]
    return ("fwd_bwd", list(dict.fromkeys(model))) if model else (None, [])


def phase_of(op_name: str):
    """The first path component of ``op_name`` that is a phase, else
    ``fwd_bwd`` where a model scope is in the path, else ``None``."""
    return _split(op_name)[0]


def scopes_of(op_name: str) -> list:
    """``phase/sub`` for each named sub-scope under the phase: an op of
    ``fwd_bwd/.../mixer/ssd`` counts for ``fwd_bwd/mixer`` and
    ``fwd_bwd/ssd``."""
    phase, subs = _split(op_name)
    return [f"{phase}/{s}" for s in subs]


def _region(intervals, lo, hi):
    """Sorted, disjoint ``[s, e)`` of ``intervals`` clipped to
    ``[lo, hi)``."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(region, starts, s, e):
    """Length of ``[s, e)`` inside ``region`` (``starts`` its starts)."""
    total = 0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(region) and region[i][0] < e:
        total += max(0, min(e, region[i][1]) - max(s, region[i][0]))
        i += 1
    return total


def self_times(events, region):
    """``[(name, self_ns)]`` for the ``(name, start, end)`` events of one
    ``XLA Ops`` line, inside ``region``: each event's time there minus
    that of its direct children (events nested in it)."""
    starts = [r[0] for r in region]
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    own, stack = [0] * len(ordered), []
    for idx, (_, s, e) in enumerate(ordered):
        while stack and stack[-1][1] <= s:
            stack.pop()
        t = _overlap(region, starts, s, e)
        if stack:
            parent, end = stack[-1]
            own[parent] -= t if e <= end else _overlap(region, starts, s, end)
        own[idx] = t
        stack.append((idx, e))
    return [(ev[0], t) for ev, t in zip(ordered, own) if t]


def _events(planes):
    """Host spans ``(name, start, end)`` of both prefixes and, per device,
    the ``XLA Ops`` and ``XLA Modules`` events."""
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device:"):
            lines = {line.name: [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            if trace_reduce.OPS_LINE in lines:
                devices.append((lines[trace_reduce.OPS_LINE],
                                lines.get("XLA Modules", [])))
    return spans, devices


def name_gap(gap, spans):
    """The innermost host span over most of ``gap`` (``[start, end)``):
    at each instant the span that started last among those running, and
    ``host`` where none runs."""
    inside = [s for s in spans if s[1] < gap[1] and s[2] > gap[0]]
    cuts = sorted({gap[0], gap[1]} | {t for _, s, e in inside
                                      for t in (s, e)
                                      if gap[0] < t < gap[1]})
    cover = collections.Counter()
    for a, b in zip(cuts, cuts[1:]):
        running = [s for s in inside if s[1] <= a and s[2] >= b]
        name = (max(running, key=lambda s: (s[1], -s[2]))[0]
                if running else "host")
        cover[name] += b - a
    return cover.most_common(1)[0][0] if cover else "host"


def attribute(op_self_s: dict, scopes: dict, fused: dict) -> dict:
    """Self times per op (``{op: seconds}``) summed by the map of
    :func:`op_scopes`: ``phase_s`` and ``scope_s`` (per phase and per
    ``phase/sub``; ``0.0`` for one the program has but no traced op is
    rooted in), ``unscoped_s`` (no phase), ``unmapped`` (``{op: seconds}``
    of names absent from the map) and ``carried_s``: per phase the time
    of fusions rooted elsewhere that carry its ops (``fused``, the map of
    :func:`fused_phases`)."""
    names = set(scopes.values())
    phase = dict.fromkeys(filter(None, map(phase_of, names)), 0.0)
    scope = dict.fromkeys((k for n in names for k in scopes_of(n)), 0.0)
    unscoped, unmapped = 0.0, {}
    carried = collections.Counter()
    for op, t in op_self_s.items():
        if op not in scopes:
            unmapped[op] = t
            continue
        p = phase_of(scopes[op])
        for other in fused.get(op, ()):
            if other != p:
                carried[other] += t
        if p is None:
            unscoped += t
            continue
        phase[p] += t
        for k in scopes_of(scopes[op]):
            scope[k] += t
    return {"phase_s": phase, "scope_s": scope, "unscoped_s": unscoped,
            "unmapped": unmapped, "carried_s": dict(carried)}


def reduce_planes(planes, scopes: dict, module: str, fused: dict) -> dict:
    """``trace_reduce.reduce_planes(planes)`` with :func:`attribute`'s keys
    (given ``scopes``, the map of :func:`op_scopes`, and ``fused``, that
    of :func:`fused_phases`), ``op_self_s`` (each op's self time in the
    runs of the step's module, named ``module`` as :func:`module_name`
    gives it), ``step_busy_s`` (union of those ops) and the gaps named by
    :func:`name_gap`."""
    planes = list(planes)
    red = trace_reduce.reduce_planes(planes)
    spans, devices = _events(planes)
    _, lo, hi = next(s for s in spans if s[0] == trace_reduce.WINDOW)
    own, busy = collections.Counter(), 0
    for ops, modules in devices:
        runs = [(s, e) for name, s, e in modules
                if name.split("(")[0] == module]
        region = _region(runs, lo, hi)
        starts = [r[0] for r in region]
        for name, s, e in trace_reduce._top_level(ops, lo, hi):
            busy += _overlap(region, starts, s, e)
        for event, t in self_times(ops, region):
            own[trace_reduce.op_name(event)] += t
    n = len(devices)
    op_self_s = {k: v / n / 1e9 for k, v in own.items()}
    inner = [s for s in spans if s[0] != trace_reduce.WINDOW]
    red.update(attribute(op_self_s, scopes, fused))
    red.update({
        "op_self_s": op_self_s, "step_busy_s": busy / n / 1e9,
        "gaps": [(name_gap((lo + at * 1e9, lo + (at + dur) * 1e9), inner),
                  dur, at) for _, dur, at in red["gaps"]]})
    return red


def reduce(path, hlo_text: str) -> dict:
    """The scoped reduction of one ``.xplane.pb`` file, with the map,
    module and fusions of the compiled step's HLO text."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes,
                         op_scopes(hlo_text), module_name(hlo_text),
                         fused_phases(hlo_text))


def label(op: str, op_name: str) -> str:
    """``while.123 fwd_bwd/…/mixer``: an op with its phase and innermost
    named sub-scope."""
    p = phase_of(op_name)
    if p is None:
        return op
    subs = scopes_of(op_name)
    return f"{op} {p}/…/{subs[-1].split('/')[1]}" if subs else f"{op} {p}"


def breakdown(red: dict, scopes: dict, top: int = 10) -> dict:
    """``trace_reduce.breakdown``, each op named with its phase and scope
    (same times)."""
    out = trace_reduce.breakdown(red, top)
    out["device_ops"] = [[label(k, scopes.get(k, "")), v]
                         for k, v in out["device_ops"]]
    return out


def layer_metrics(red: dict, steps: int) -> dict:
    """The per-phase per-layer metrics of one traced window, each ``None``
    where its scope is absent: ms per step under each phase, under
    ``fwd_bwd/mixer`` and ``defense/accumulate``, and the step module's
    share of self time under no phase, in %."""
    per_step = lambda s: None if s is None else 1e3 * s / steps
    out = {f"{p}_ms": per_step(red["phase_s"].get(p)) for p in PHASES}
    out["mixer_ms"] = per_step(red["scope_s"].get("fwd_bwd/mixer"))
    out["sg_accumulate_ms"] = per_step(
        red["scope_s"].get("defense/accumulate"))
    out["unscoped_share"] = (100.0 * red["unscoped_s"] / red["step_busy_s"]
                             if red["step_busy_s"] else None)
    return out

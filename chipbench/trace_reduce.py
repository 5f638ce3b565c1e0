"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the traced window, the device's busy time in it, the device time of
each operation, and the idle gaps with what the host was doing in each
and where in the window each starts.

The window is the host span ``chipbench.window`` that the benchmark writes
around its measured loop; the host spans ``chipbench.batch``,
``chipbench.dispatch`` and ``chipbench.block`` name what the host was
doing.  A device is a ``/device:...`` plane with an ``XLA Ops`` line.
Operations nest on that line (a loop's body inside the loop): an
operation's time is that of the outermost one, so the times of all
operations add up to the busy time.  Times are in seconds; device figures
are the mean over the devices traced; ``ops_by_device`` keeps each
device's own, for a metric that takes the worst chip.
"""

from __future__ import annotations

import collections

WINDOW = "chipbench.window"
HOST_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[4]{0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _top_level(events, lo, hi):
    """Outermost events, clipped to ``[lo, hi)``: ``(name, start, end)``."""
    out, cur_end = [], None
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        if cur_end is not None and start < cur_end:
            continue
        cur_end = end
        s, e = max(start, lo), min(end, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _gaps(intervals, lo, hi):
    gaps, t = [], lo
    for _, s, e in intervals:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _label(gap, spans):
    """The host span that overlaps ``gap`` most, or ``host`` (the loop
    between the benchmark's spans)."""
    best, best_ov = "host", 0
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce_planes(planes) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines``, each line
    with ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``),
    as ``jax.profiler.ProfileData`` gives them."""
    spans, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(op_name(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events])
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1 or not devices:
        raise ValueError(f"trace has {len(windows)} {WINDOW!r} spans and "
                         f"{len(devices)} devices with an {OPS_LINE!r} line")
    _, lo, hi = windows[0]
    inner = [s for s in spans if s[0] != WINDOW]
    ops = collections.Counter()
    by_device = []
    busy = 0
    gaps = []
    for events in devices:
        top = _top_level(events, lo, hi)
        own = collections.Counter()
        for name, s, e in top:
            own[name] += e - s
        ops.update(own)
        by_device.append({k: v / 1e9 for k, v in own.items()})
        busy += sum(e - s for _, s, e in top)
        gaps += [(_label(g, inner), g[1] - g[0], g[0] - lo)
                 for g in _gaps(top, lo, hi)]
    n = len(devices)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n / 1e9,
            "devices": n,
            "ops": {k: v / n / 1e9 for k, v in ops.items()},
            "ops_by_device": by_device,
            "gaps": sorted(((k, v / 1e9, at / 1e9) for k, v, at in gaps),
                           key=lambda g: -g[1])}


def reduce(path) -> dict:
    """The reduction of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the operations that took most
    device time, and the longest idle gaps named by the host's span and
    their start in the window, each with its seconds."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"{k} at {at:.3f} s", v]
                          for k, v, at in red["gaps"][:top]]}

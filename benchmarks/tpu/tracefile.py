"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote under a
directory: for each TPU device plane the events of its "XLA Ops" and
"XLA Modules" lines, and the host's events (the harness's own
``TraceAnnotation`` spans among them), all on one clock in nanoseconds.
``summarize`` reduces the part of it inside the host span that marks the
window.  Everything is plain interval arithmetic:

* busy: the union of a device's op intervals inside the window, leaving
  out control-flow ops (``CONTAINERS``) whose events span their bodies;
* collective: the union of its collective ops (told by HLO opcode, see
  ``COLLECTIVES``), and the exposed part of it, in which no other op runs;
* top ops: device seconds by HLO op, named by a short form of its text;
* idle gaps: the holes in the first device's busy union, each named by
  what the host was doing then (``_host_label``).
"""
from __future__ import annotations

import glob
import os
import re

COLLECTIVES = ("all-reduce", "collective-permute", "all-gather", "all-to-all",
               "reduce-scatter", "collective-broadcast", "ragged-all-to-all")
# ops whose event spans the ops of their body on the same line
CONTAINERS = ("while", "conditional", "call")
TOP = 10  # entries kept in each breakdown list


def op_name(text: str) -> str:
    """HLO op name of an event: ``%fusion.60 = f32[...] fusion(...)`` -> ``fusion.60``."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    return m.group(1) if m else text


def label(text: str) -> str:
    """A short readable form of an op's HLO text, layouts and attributes cut."""
    short = re.sub(r"\{[^{}]*\}", "", text).split(", kind=")[0].split(", calls=")[0]
    return short.lstrip("%")[:160]


def opcode(name: str) -> str:
    """HLO opcode of an op name: ``all-reduce-start.12`` -> ``all-reduce-start``."""
    return re.sub(r"\.\d+$", "", op_name(name))


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVES)


def is_container(name: str) -> bool:
    return opcode(name) in CONTAINERS


def load(trace_dir: str) -> dict:
    """Device and host events of the trace under ``trace_dir``.

    Returns ``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host": [...]}`` with events as ``(name, start_ns, end_ns)``.
    """
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in lines[lname].events] if lname in lines else []
                for key, lname in (("ops", "XLA Ops"), ("modules", "XLA Modules"))
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    return {"devices": dict(sorted(devices.items(), key=lambda kv: _ordinal(kv[0]))),
            "host": host}


def _ordinal(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sorted disjoint union of ``(start, end)`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def measure(merged) -> float:
    return sum(e - s for s, e in merged)


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def span(trace: dict, name: str) -> tuple[float, float]:
    """Start and end of the longest host span called ``name``."""
    hits = [(e - s, s, e) for n, s, e in trace["host"] if n == name]
    if not hits:
        raise ValueError(f"no host span {name!r} in the trace")
    _, s, e = max(hits)
    return s, e


def summarize(trace: dict, window: str) -> dict:
    """Device numbers of the part of ``trace`` inside host span ``window``.

    Times are in seconds; busy and collective times are means over the
    traced devices.
    """
    lo, hi = span(trace, window)
    devs = list(trace["devices"].values())
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    busy = coll = exposed = 0.0
    by_name: dict[str, float] = {}
    gaps_of_first = []
    for k, dev in enumerate(devs):
        ops = [op for op in dev["ops"] if not is_container(op[0])]
        merged = merge([(s, e) for _, s, e in ops], lo, hi)
        c = merge([(s, e) for n, s, e in ops if is_collective(n)], lo, hi)
        other = merge([(s, e) for n, s, e in ops if not is_collective(n)], lo, hi)
        busy += measure(merged)
        coll += measure(c)
        exposed += measure(c) - measure(intersect(c, other))
        for n, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_name[n] = by_name.get(n, 0.0) + d
        if k == 0:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            gaps_of_first = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]]
    nd = len(devs)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(gaps_of_first, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / nd * 1e-9,
        "collective_s": coll / nd * 1e-9,
        "collective_exposed_s": exposed / nd * 1e-9,
        "devices": nd,
        "top_ops": [[label(n), t / nd * 1e-9] for n, t in top],
        "idle_gaps": [[_host_label(trace["host"], s, e, window), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def _host_label(host, s: float, e: float, window: str) -> str:
    """What the host was doing in the gap [s, e].

    The shortest host span that covers at least half of the gap, else the
    one that overlaps it most; the window's own span does not count.
    """
    covering, best, label = None, 0.0, "host: no traced span"
    for n, hs, he in host:
        if n == window:
            continue
        o = min(he, e) - max(hs, s)
        if o >= 0.5 * (e - s) and (covering is None or he - hs < covering[0]):
            covering = (he - hs, n)
        if o > best:
            best, label = o, n
    return covering[1] if covering else label


def module_time_per_call(trace: dict, calls: int, window: str = "probe") -> float:
    """Mean device seconds per program execution inside host span ``window``.

    Reads the first device's "XLA Modules" events; where the plane has
    none, the union of its ops.
    """
    lo, hi = span(trace, window)
    dev = next(iter(trace["devices"].values()))
    events = dev["modules"] or dev["ops"]
    return measure(merge([(s, e) for _, s, e in events], lo, hi)) * 1e-9 / calls

"""Attribute a chip trace's device time to the program's named scopes.

The program wraps each layer of the solve in a ``jax.named_scope`` whose
name starts with ``PREFIX`` (``repro.obs``).  XLA keeps the JAX name stack
of every op as its ``tf_op``, a stat on the op's event metadata in the
profiler's XSpace, for example
``jit(f)/while/body/closed_call/hb.cg.iteration/hb.cg.operator/hb.op.gather/scatter-add``.
``jax.profiler.ProfileData`` does not expose event metadata stats, so
``load`` reads the raw ``.xplane.pb`` with a short protobuf wire-format
reader (no TensorFlow, no generated protobuf classes).

``summarize(trace_dir, window)`` gives, inside the host span ``window``
and as a mean over the traced devices, for every program scope:

* ``inclusive_s``: the union of the intervals of the ops under the scope;
* ``self_s``: the union of the intervals of the ops whose innermost
  program scope it is;

and ``none_s``, the busy time in which no op under a program scope runs.
Control-flow container ops are left out as ``tracefile.summarize`` leaves
them out, so the union of all scoped ops plus ``none_s`` is its
``busy_s``.  Ops without a ``tf_op`` (XLA's own asynchronous
``copy-start``/``copy-done``) belong to no scope.

This module keeps its own copy of the prefix: a scope renamed in the
program then reads as a missing scope here, not as a moved number.

    python3 benchmarks/tpu/scopes.py <trace_dir> [--window window]

prints the table of one trace.
"""
from __future__ import annotations

import glob
import json
import os
import re
import struct
import sys

import tracefile

PREFIX = "hb."  # the program's scope prefix (repro.obs.PREFIX), copied


# ------------------------------------------------------------ wire format
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """Yield ``(field number, wire type, value)`` of one message.

    Length-delimited values come as ``bytes``, varints as ``int``, fixed64
    as the raw 8 bytes.
    """
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, val


def _signed(v: int) -> int:
    """An int64 sent as a varint (two's complement in 64 bits)."""
    return v - (1 << 64) if v >= 1 << 63 else v


# XStat: metadata_id 1; double 2, uint64 3, int64 4, str 5, bytes 6, ref 7
def _stat(buf: bytes, stat_names: dict) -> tuple[str, object]:
    mid, value = 0, None
    for num, wt, val in _fields(buf):
        if num == 1:
            mid = val
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num in (3, 4):
            value = _signed(val) if num == 4 else val
        elif num in (5, 6):
            value = val.decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, "")
    return stat_names.get(mid, ""), value


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _plane(buf: bytes) -> dict:
    """One XPlane: its name, lines, event metadata and stat metadata."""
    name, lines, ev_meta, stat_names = "", [], {}, {}
    for num, _, val in _fields(buf):
        if num == 2:
            name = val.decode("utf-8", "replace")
        elif num == 3:
            lines.append(val)
        elif num == 4:
            mid, raw = _map_entry(val)
            ev_meta[mid] = raw
        elif num == 5:
            sid, raw = _map_entry(val)
            for n2, _, v2 in _fields(raw):
                if n2 == 2:
                    stat_names[sid] = v2.decode("utf-8", "replace")
    return {"name": name, "lines": lines, "ev_meta": ev_meta, "stat_names": stat_names}


def _event_metadata(raw: bytes, stat_names: dict) -> tuple[str, dict]:
    """XEventMetadata: name 2, display_name 4, stats 5."""
    name, stats = "", {}
    for num, _, val in _fields(raw):
        if num == 2:
            name = val.decode("utf-8", "replace")
        elif num == 5:
            k, v = _stat(val, stat_names)
            stats[k] = v
    return name, stats


def _line(buf: bytes) -> tuple[str, int, list[tuple[int, int, int]]]:
    """XLine: name, timestamp_ns and events as (metadata id, offset_ps, duration_ps)."""
    name, ts, events = "", 0, []
    for num, _, val in _fields(buf):
        if num == 2:
            name = val.decode("utf-8", "replace")
        elif num == 3:
            ts = _signed(val)
        elif num == 4:
            mid = off = dur = 0
            for n2, _, v2 in _fields(val):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    off = _signed(v2)
                elif n2 == 3:
                    dur = _signed(v2)
            events.append((mid, off, dur))
    return name, ts, events


# ------------------------------------------------------------ loading
def load(trace_dir: str) -> dict:
    """The device ops, with their ``tf_op``, and the host spans of a trace.

    Returns ``{"devices": {plane: [(name, tf_op, start_ns, end_ns)]},
    "host": [(name, start_ns, end_ns)]}`` for the newest ``.xplane.pb``
    under ``trace_dir``; device ops are the events of each TPU plane's
    "XLA Ops" line, each joined to its event metadata by metadata id.
    """
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        space = f.read()
    devices, host = {}, []
    for num, _, val in _fields(space):
        if num != 1:
            continue
        plane = _plane(val)
        pname = plane["name"]
        is_device = re.fullmatch(r"/device:TPU:\d+", pname) is not None
        if not (is_device or pname.startswith("/host:")):
            continue
        meta = {}
        ops = []
        for raw_line in plane["lines"]:
            lname, ts, events = _line(raw_line)
            if is_device and lname != "XLA Ops":
                continue
            for mid, off, dur in events:
                if mid not in meta:
                    meta[mid] = _event_metadata(plane["ev_meta"].get(mid, b""),
                                                plane["stat_names"])
                name, stats = meta[mid]
                # whole nanoseconds, as jax.profiler.ProfileData gives them
                start, dur_ns = ts + off // 1000, dur // 1000
                if is_device:
                    ops.append((name, str(stats.get("tf_op", "") or ""), start,
                                start + dur_ns))
                elif dur_ns > 0:
                    host.append((name, start, start + dur_ns))
        if is_device:
            devices[pname] = ops
    return {"devices": dict(sorted(devices.items(), key=lambda kv: _ordinal(kv[0]))),
            "host": host}


def _ordinal(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


# ------------------------------------------------------------ reduction
def program_scopes(tf_op: str) -> list[str]:
    """The program scopes on an op's name stack, outermost first.

    ``jit(f)/while/body/hb.cg.iteration/hb.op.gather/scatter-add`` ->
    ``["hb.cg.iteration", "hb.op.gather"]``.
    """
    path = tf_op.rsplit(":", 1)[0]  # the op type follows the last colon
    return [p for p in path.split("/") if p.startswith(PREFIX)]


def summarize(trace_dir: str, window: str) -> dict:
    """``reduce`` of the trace under ``trace_dir``."""
    return reduce(load(trace_dir), window)


def reduce(trace: dict, window: str) -> dict:
    """Device time per program scope inside host span ``window``.

    ``trace`` is what ``load`` returns.  Returns ``{"window_s", "busy_s",
    "none_s", "devices", "scopes": {scope: {"inclusive_s", "self_s"}}}``,
    times in seconds and means over the traced devices; scopes by
    inclusive time, largest first.
    """
    devs = list(trace["devices"].values())
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    lo, hi = tracefile.span({"host": trace["host"]}, window)
    busy = none = 0.0
    inclusive: dict[str, float] = {}
    self_t: dict[str, float] = {}
    for ops in devs:
        ops = [op for op in ops if not tracefile.is_container(op[0])]
        dev_busy = tracefile.measure(
            tracefile.merge([(s, e) for _, _, s, e in ops], lo, hi))
        under: dict[str, list] = {}
        innermost: dict[str, list] = {}
        scoped = []
        for _, tf_op, s, e in ops:
            stack = program_scopes(tf_op)
            if not stack:
                continue
            scoped.append((s, e))
            for sc in set(stack):
                under.setdefault(sc, []).append((s, e))
            innermost.setdefault(stack[-1], []).append((s, e))
        for table, ivs_of in ((inclusive, under), (self_t, innermost)):
            for sc, ivs in ivs_of.items():
                table[sc] = table.get(sc, 0.0) + tracefile.measure(
                    tracefile.merge(ivs, lo, hi))
        busy += dev_busy
        none += dev_busy - tracefile.measure(tracefile.merge(scoped, lo, hi))
    nd = len(devs)
    names = sorted(set(inclusive), key=lambda sc: -inclusive[sc])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / nd * 1e-9,
        "none_s": none / nd * 1e-9,
        "devices": nd,
        "scopes": {sc: {"inclusive_s": inclusive[sc] / nd * 1e-9,
                        "self_s": self_t.get(sc, 0.0) / nd * 1e-9} for sc in names},
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--window", default="window",
                    help="host span that bounds the reduction (default: window)")
    args = ap.parse_args(argv)
    s = summarize(args.trace_dir, args.window)
    print(f"window {s['window_s']:.6f} s  busy {s['busy_s']:.6f} s  "
          f"none {s['none_s']:.6f} s  devices {s['devices']}")
    print(f"{'scope':<32} {'inclusive_s':>14} {'self_s':>14}")
    for sc, v in s["scopes"].items():
        print(f"{sc:<32} {v['inclusive_s']:>14.6f} {v['self_s']:>14.6f}")
    print(json.dumps(s))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""CPU tests of the scope reduction (``scopes.py``) and the span readers.

    PYTHONPATH=src python -m pytest -q benchmarks/tpu/tests

No TPU is needed: the reduction reads recorded chip traces and traces
written here with a small protobuf encoder.
"""
import os
import struct
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(CHECKOUT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import scopes  # noqa: E402
import tracefile  # noqa: E402

TESTDATA = os.path.join(HERE, "testdata")
MS = 1_000_000  # ns


# ------------------------------------------------------------ a trace written here
def _varint(v: int) -> bytes:
    v &= (1 << 64) - 1
    out = b""
    while True:
        b, v = v & 0x7F, v >> 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _plane(name, lines, event_meta, stat_meta):
    """XPlane bytes; ``event_meta``: {id: (name, [(stat id, field, value)])}."""
    body = [(2, name)]
    body += [(3, _msg((2, lname), (3, ts), *[(4, _msg((1, mid), (2, off), (3, dur)))
                                             for mid, off, dur in events]))
             for lname, ts, events in lines]
    for mid, (ename, stats) in event_meta.items():
        meta = _msg((1, mid), (2, ename), *[(5, _msg((1, sid), (f, v))) for sid, f, v in stats])
        body.append((4, _msg((1, mid), (2, meta))))
    body += [(5, _msg((1, sid), (2, _msg((1, sid), (2, sname)))))
             for sid, sname in stat_meta.items()]
    return _msg(*body)


def write_trace(root, device_ops, host_spans):
    """An ``.xplane.pb`` under ``root``: one TPU plane, one host plane.

    ``device_ops``: (HLO name, tf_op or None, start ms, end ms); the same
    name with two tf_ops gets two metadata entries.  The tf_op stat is
    stored as a string on even metadata ids and by reference on odd ones.
    """
    stat_meta = {1: "tf_op", 2: "flops"}
    metas, ids, events, ref = {}, {}, [], 100
    for name, tf_op, s, e in device_ops:
        if (name, tf_op) not in ids:
            mid = len(ids) + 1
            stats = [(2, 3, 7)]
            if tf_op is not None and mid % 2:
                stat_meta[ref] = tf_op
                stats.append((1, 7, ref))
                ref += 1
            elif tf_op is not None:
                stats.append((1, 5, tf_op))
            metas[mid] = (name, stats)
            ids[name, tf_op] = mid
        events.append((ids[name, tf_op], int(s * MS * 1000), int((e - s) * MS * 1000)))
    dev = _plane("/device:TPU:0", [("XLA Ops", 5 * MS, events), ("Steps", 5 * MS, [])],
                 metas, stat_meta)
    host_meta = {i + 1: (n, []) for i, (n, _, _) in enumerate(host_spans)}
    host = _plane("/host:CPU", [("python", 5 * MS, [
        (i + 1, int(s * MS * 1000), int((e - s) * MS * 1000))
        for i, (_, s, e) in enumerate(host_spans)])], host_meta, {})
    path = root / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(_msg((1, dev), (1, host)))
    return str(root)


SYNTH = [
    # name, tf_op, start ms, end ms
    ("%fusion.1 = f32[9] fusion(x)", "jit(s)/while/body/hb.cg.iteration/hb.cg.operator/hb.op.gather/scatter-add:", 0, 10),
    ("%fusion.2 = f32[9] fusion(x)", "jit(s)/while/body/hb.cg.iteration/hb.cg.operator/hb.op.scatter/jit(_take)/gather:", 10, 14),
    ("%fusion.3 = f32[] fusion(x)", "jit(s)/while/body/hb.cg.iteration/dot_general:", 14, 15),
    # the same HLO name under another scope: joined by metadata id, not name
    ("%fusion.3 = f32[] fusion(x)", "jit(s)/hb.cg.init/dot_general:", 15, 17),
    ("%copy-start.1 = (f32[9]) copy-start(x)", None, 16, 18),
    ("%while.4 = (f32[9]) while(x)", "jit(s)/while:", 0, 30),
    ("%fusion.5 = f32[9] fusion(x)", "jit(rhs)/threefry2x32:", 20, 22),
    ("%fusion.6 = f32[9] fusion(x)", "jit(s)/hb.cg.iteration/add:", 40, 50),  # outside
]


def test_reduction_of_a_written_trace(tmp_path):
    d = write_trace(tmp_path, SYNTH, [("window", 0, 30), ("wait", 1, 29)])
    trace = scopes.load(d)
    ops = trace["devices"]["/device:TPU:0"]
    assert [op[1] for op in ops[2:5]] == [SYNTH[2][1], SYNTH[3][1], ""]
    assert ops[0][2:] == (5 * MS, 15 * MS)
    s = scopes.reduce(trace, "window")
    sc = {k: (round(v["inclusive_s"] * 1e3, 9), round(v["self_s"] * 1e3, 9))
          for k, v in s["scopes"].items()}
    assert sc == {"hb.cg.iteration": (15, 1), "hb.cg.operator": (14, 0),
                  "hb.op.gather": (10, 10), "hb.op.scatter": (4, 4), "hb.cg.init": (2, 2)}
    assert list(s["scopes"])[0] == "hb.cg.iteration"  # largest inclusive first
    # busy 0-18 and 20-22 (the while left out); scoped 0-17; none 17-18, 20-22
    assert s["busy_s"] == pytest.approx(0.020) and s["none_s"] == pytest.approx(0.003)
    assert s["window_s"] == pytest.approx(0.030)
    # the same events through jax's own reader: the same busy time
    assert tracefile.summarize(tracefile.load(d), "window")["busy_s"] == s["busy_s"]


def test_program_scopes_of_a_name_stack():
    assert scopes.program_scopes(SYNTH[1][1]) == ["hb.cg.iteration", "hb.cg.operator",
                                                 "hb.op.scatter"]
    assert scopes.program_scopes("jit(apply)/jit(_take)/gather:") == []
    assert scopes.program_scopes("jit(f)/hb.pmg.l0:") == ["hb.pmg.l0"]
    assert scopes.program_scopes("") == []


# ------------------------------------------------------------ recorded chip traces
def test_recorded_trace_without_scopes_is_all_none():
    """``small_operator`` predates the scopes: every busy second is none."""
    d = os.path.join(TESTDATA, "small_operator")
    trace = scopes.load(d)
    ref = tracefile.load(d)
    (plane,) = trace["devices"]
    ops = trace["devices"][plane]
    assert sorted((n, s, e) for n, _, s, e in ops) == sorted(ref["devices"][plane]["ops"])
    assert sorted(trace["host"]) == sorted(ref["host"])
    tf_ops = [tf for _, tf, _, _ in ops]
    assert "jit(apply)/jit(_take)/gather:" in tf_ops
    # every op event carries its name stack, but XLA's own async copies
    assert all(tf.startswith("jit(apply)/") or tracefile.opcode(n) in ("copy-start", "copy-done")
               for n, tf, _, _ in ops)
    s = scopes.summarize(d, "window")
    assert s["scopes"] == {}
    assert s["none_s"] == s["busy_s"] == tracefile.summarize(ref, "window")["busy_s"]


def test_recorded_trace_of_a_scoped_solve():
    """``small_scoped_solve``: 5 plain CG iterations at N=7 on 2^3 elements,
    twice, on one v5e chip, with the program's scopes."""
    d = os.path.join(TESTDATA, "small_scoped_solve")
    s = scopes.summarize(d, "window")
    busy = tracefile.summarize(tracefile.load(d), "window")["busy_s"]
    assert s["busy_s"] == busy
    sc = s["scopes"]
    assert set(sc) == {"hb.cg.init", "hb.cg.iteration", "hb.cg.operator",
                       "hb.op.scatter", "hb.op.local", "hb.op.gather"}
    # the scopes partition the busy time: the union of every op under a
    # scope is the iteration's and the init's; none is what is left
    union = sc["hb.cg.iteration"]["inclusive_s"] + sc["hb.cg.init"]["inclusive_s"]
    assert union + s["none_s"] == pytest.approx(busy, rel=1e-9)
    assert 0 < s["none_s"] < 0.1 * busy
    # nesting: iteration > operator > each operator stage; self times add up
    ops = ("hb.op.scatter", "hb.op.local", "hb.op.gather")
    assert sc["hb.cg.iteration"]["inclusive_s"] > sc["hb.cg.operator"]["inclusive_s"]
    assert sc["hb.cg.operator"]["inclusive_s"] == pytest.approx(
        sum(sc[o]["inclusive_s"] for o in ops), rel=1e-9)
    assert all(sc[o]["self_s"] == sc[o]["inclusive_s"] > 0 for o in ops)
    assert sc["hb.cg.operator"]["self_s"] == 0
    # the gather (segment sum) is the slowest stage, as in the cells
    assert max(ops, key=lambda o: sc[o]["self_s"]) == "hb.op.gather"


def test_existing_reduction_reads_as_before():
    """tracefile and the readers that use it give their former values."""
    t = tracefile.summarize(tracefile.load(os.path.join(TESTDATA, "small_operator")), "window")
    assert (t["window_s"], t["busy_s"], t["collective_s"]) == (0.005984570000000001,
                                                               0.0005371, 0.0)
    rec = run.types.SimpleNamespace(trace=t)
    idle = run.Bench().metric("device_idle_pct.fixed100").read(rec)
    assert idle == pytest.approx(100 * (1 - 0.0005371 / 0.005984570000000001), rel=1e-12)


# ------------------------------------------------------------ span readers
@pytest.mark.parametrize("metric, span", [("setup_build_s", "setup.build_problem"),
                                          ("setup_precond_s.tol", "setup.precond")])
def test_span_readers(metric, span, monkeypatch):
    from repro import obs

    reader = run.Bench().metric(metric)
    obs.reset()
    assert reader.read(None) is None
    with obs.span(span):
        pass
    with obs.span(span):
        pass
    last = obs.spans(span)[-1]
    assert reader.read(None) == (last.end_ns - last.start_ns) * 1e-9
    # a program without repro.obs reads nothing
    import repro

    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader.read(None) is None

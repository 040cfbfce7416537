"""The stage split of a profiler trace, on traces recorded on a TPU v5e
and kept under ``data/``, and on hand-made planes. CPU only."""
import pathlib

import numpy as np
import pytest

from perfbench import stage_trace as st
from perfbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
STAGES = ("lane.plant", "lane.decide", "lane.forecast")


def _planes(host, *devices):
    """Decoded planes standing in for a trace: host events and the ops
    of each device, each (name, start_s, end_s[, tf_op]); events of one
    name and tf_op share their metadata, as one HLO op's do."""
    def plane(name, line_name, events):
        ops = {}
        for n, _, _, *tf in events:
            ops.setdefault((n, tf[0] if tf else ""), len(ops))
        meta = [ops[(n, tf[0] if tf else "")] for n, _, _, *tf in events]
        line = st.Line(line_name, np.asarray(meta, np.int64),
                       np.asarray([e[1] for e in events], float),
                       np.asarray([e[2] for e in events], float))
        return st.Plane(name, [line], {i: k for k, i in ops.items()})
    return [plane("/host:CPU", "python", host)] + [
        plane(f"/device:TPU:{i}", trace.OPS_LINE, ops)
        for i, ops in enumerate(devices)]


def test_the_tf_op_of_a_recorded_op_is_decoded():
    """``jax.profiler.ProfileData`` gives no event metadata; the file's
    own decode gives each op its ``tf_op`` op-name path."""
    planes = st.decode((DATA / "one_chip_scan.xplane.pb").read_bytes())
    tpu, = [p for p in planes if p.name == "/device:TPU:0"]
    tf_ops = {trace.short_name(n): t for n, t in tpu.ops.values() if t}
    assert tf_ops == {"%sine_reduce_fusion.2":
                      "jit(<lambda>)/while/body/closed_call/reduce_sum:"}


def test_the_decode_reads_the_window_as_the_reduction_does():
    """The same file through the profiler's reader (``trace.load``) and
    through the decode: one window, one busy time, the same gaps."""
    path = DATA / "one_chip_scan.xplane.pb"
    s, ref = st.load(path, ("closed_call",)), trace.load(path)
    assert s.window_s == pytest.approx(ref.window_s, rel=1e-6)
    assert s.busy_s[0] == pytest.approx(ref.busy_s, rel=1e-3)
    assert s.stage_seconds() == {"closed_call": s.busy_s[0],
                                 st.UNSCOPED: 0.0}
    assert [g for g, _ in s.idle_gaps(3)] == \
        [g for g, _ in ref.idle_gaps(3)]


def test_stage_time_and_runs_of_hand_made_ops():
    """Each leaf op counts under the innermost stage of its ``tf_op``;
    a stage's runs are its least-executed op's events; the loop that
    holds the kernels counts under none (it is no leaf)."""
    host = [("window", 0.0, 10.0), ("dispatch", 0.0, 0.1),
            ("block", 0.1, 10.0)]
    plant, fc = "jit(f)/while/body/lane.plant/add", \
        "jit(f)/while/body/lane.decide/vmap(lane.forecast)/gather"
    ops = [("%while.1", 1.0, 9.0, "jit(f)/while")]
    for k in range(4):                       # 4 iterations of 2 s
        t = 1.0 + 2.0 * k
        ops += [("%fusion.1", t, t + 0.5, plant),
                ("%fusion.2", t + 0.5, t + 0.7, plant),
                ("%copy.3", t + 0.8, t + 0.9, "jit(f)/while/body/copy")]
        if k % 2 == 0:
            ops.append(("%gather.4", t + 1.0, t + 1.6, fc))
    s = st.split(_planes(host, ops), STAGES)
    got = s.stage_seconds()
    assert list(got) == list(STAGES) + [st.UNSCOPED]
    assert got["lane.plant"] == pytest.approx(4 * 0.7)
    assert got["lane.forecast"] == pytest.approx(2 * 0.6)
    assert got["lane.decide"] == 0.0
    assert got[st.UNSCOPED] == pytest.approx(4 * 0.1)
    assert sum(got.values()) == pytest.approx(s.busy_s[0])
    assert s.runs("lane.plant") == 4
    assert s.runs("lane.forecast") == 2
    assert s.runs("lane.decide") is None
    # without stage names every op is unscoped
    bare = st.split(_planes(host, ops))
    assert bare.stage_seconds() == {st.UNSCOPED: pytest.approx(s.busy_s[0])}


def test_stage_time_is_the_mean_over_the_devices():
    host = [("window", 0.0, 4.0)]
    one = [("%f", 0.0, 1.0, "jit(f)/lane.plant/add")]
    two = [("%f", 0.0, 3.0, "jit(f)/lane.plant/add"),
           ("%f", 3.0, 3.5, "jit(f)/lane.plant/add")]
    s = st.split(_planes(host, one, two), STAGES)
    assert s.seconds("lane.plant") == pytest.approx((1.0 + 3.5) / 2)
    assert s.runs("lane.plant") == pytest.approx((1 + 2) / 2)
    assert s.busy_s == pytest.approx([1.0, 3.5])


def test_a_gap_is_named_by_the_innermost_host_span():
    """Gaps inside the benchmark's ``feed`` read the program's span that
    overlaps them there; a gap under no span reads ``other``."""
    host = [("window", 0.0, 10.0),
            ("feed", 0.0, 4.0), ("fleet.put", 0.1, 1.0),
            ("fleet.fold", 1.0, 3.9), ("block", 5.0, 10.0)]
    ops = [("%a", 0.0, 0.5), ("%b", 0.9, 2.0), ("%c", 2.5, 4.4),
           ("%d", 4.6, 6.0), ("%e", 8.0, 9.0)]
    s = st.split(_planes(host, ops), (), ("fleet.put", "fleet.fold"))
    assert s.idle_gaps(5) == [["block", pytest.approx(2.0)],
                              ["fleet.fold", pytest.approx(0.5)],
                              ["fleet.put", pytest.approx(0.4)],
                              ["other", pytest.approx(0.2)]]
    assert s.span_durations("fleet.put") == [pytest.approx(0.9)]
    # without the program's names the same gaps read the outer span
    outer = st.split(_planes(host, ops))
    assert [g for g, _ in outer.idle_gaps(5)] == ["block", "feed", "feed",
                                                   "other"]


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError, match="window"):
        st.split(_planes([("feed", 0.0, 1.0)], []))

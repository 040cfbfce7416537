"""The trace reduction, on small traces recorded on a TPU v5e and kept
under ``data/``, and on hand-made intervals. CPU only."""
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest

from perfbench import harness, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_reports_every_gap():
    s = np.array([0.0, 0.5, 2.0, 2.2, 5.0])
    e = np.array([1.0, 1.5, 2.5, 2.3, 6.0])
    busy, (gs, gl) = trace.union_s(s, e, -1.0, 7.0)
    assert busy == pytest.approx(1.5 + 0.5 + 1.0)
    np.testing.assert_allclose(gs, [-1.0, 1.5, 2.5, 6.0])
    np.testing.assert_allclose(gl, [1.0, 0.5, 2.5, 1.0])
    assert busy + gl.sum() == pytest.approx(8.0)


def test_leaves_drop_the_loops_that_contain_kernels():
    # a loop [0, 10) holding two kernels, then a lone kernel
    s = np.array([0.0, 1.0, 4.0, 12.0])
    e = np.array([10.0, 3.0, 9.0, 13.0])
    np.testing.assert_array_equal(trace.leaves(s, e),
                                  [False, True, True, True])


def test_collective_ops_are_recognised_by_name():
    names = ["%all-reduce.3", "%all-reduce-start.1", "%all-gather.2",
             "%reduce-scatter", "%collective-permute-done.4",
             "%fusion.12", "%copy.3", "%reduce.5", "%while.288"]
    hits = [bool(trace.COLLECTIVE.search(n)) for n in names]
    assert hits == [True] * 5 + [False] * 4


@pytest.fixture(scope="module")
def one_chip():
    """A jitted lax.scan of 64 steps over [128, 128] blocks, dispatched
    three times inside the harness's annotations, on one TPU v5e."""
    return trace.load(DATA / "one_chip_scan.xplane.pb")


def test_one_chip_window_busy_and_idle(one_chip):
    s = one_chip
    assert [d.name for d in s.devices] == ["/device:TPU:0"]
    assert s.window_s == pytest.approx(0.034495288)
    # the kernels of the three dispatches; the 10 ms host sleeps between
    # the dispatches are idle
    assert 0.0 < s.busy_s < 0.002
    # idle on the chip's own clock: its span runs from the first kernel
    # of the first dispatch to the last kernel of the third
    idle = harness.layer("device_idle_share").read(
        harness.Run("c", None, None, s, 3, 0.0))
    assert idle == pytest.approx(100.0 * (1 - s.busy_s / s.span_s))
    assert 0.02 < s.span_s <= s.window_s
    assert idle > 90.0
    # the gaps and the kernels cover the span
    starts, lengths = s.devices[0].gaps
    assert s.busy_s + lengths.sum() == pytest.approx(s.span_s)
    # on this recording the profiler puts the kernels about 1 ms
    # outside the host window
    assert 0.0 < s.offset_s < 0.002


def test_one_chip_module_time_and_ops(one_chip):
    s = one_chip
    (module, secs), = s.devices[0].module_s.items()
    assert module.startswith("jit__lambda")
    assert s.module_s("jit__lambda") == pytest.approx(secs)
    # the loop contains the kernels, so it outlasts their sum
    assert secs >= s.busy_s
    top = s.top_ops(3)
    assert top[0][0].startswith("%") and " = " not in top[0][0]
    assert sum(t for _, t in s.top_ops(100)) == pytest.approx(s.busy_s)
    assert s.collective_s() == 0.0


def test_one_chip_host_spans_label_the_gaps(one_chip):
    names = [n for n, _, _ in one_chip.spans]
    assert names.count("dispatch") == 3 and names.count("block") == 3
    gaps = one_chip.idle_gaps(3)
    assert len(gaps) == 3
    assert all(label in ("feed", "dispatch", "block", "other")
               for label, _ in gaps)
    # the two host sleeps between the three dispatches; the span
    # ends at the last kernel, so no gap follows the third
    assert gaps[0][1] >= gaps[1][1] > 0.005 > gaps[2][1]


def test_scan_iter_reader(one_chip):
    class Driver:
        module, iterations = "jit__lambda", 64
    run = harness.Run("c", Driver(), None, one_chip, 3, 0.0)
    us = harness.layer("scan_iter_us").read(run)
    assert us == pytest.approx(1e6 * one_chip.module_s("jit__lambda")
                               / (64 * 3))


@pytest.fixture(scope="module")
def four_chips():
    """A jitted scan whose every step sums rows sharded over a 4-chip dp
    mesh (an all-reduce per step), dispatched twice, on a 2x2 v5e host."""
    return trace.load(DATA / "four_chip_allreduce.xplane.pb")


def test_four_chips_collectives_and_per_chip_means(four_chips):
    s = four_chips
    assert [d.name for d in s.devices] == [f"/device:TPU:{i}"
                                           for i in range(4)]
    assert all(any(trace.COLLECTIVE.search(op) for op in d.op_s)
               for d in s.devices)
    assert s.collective_s() == pytest.approx(
        np.mean([d.collective_s for d in s.devices]))
    assert 0.0 < s.collective_s() <= s.busy_s
    assert s.busy_s == pytest.approx(np.mean([d.busy_s
                                              for d in s.devices]))
    share = harness.layer("collective_share").read(
        harness.Run("c", None, None, s, 2, 0.0))
    assert share == pytest.approx(100.0 * s.collective_s() / s.busy_s)
    assert 0.0 < share < 100.0
    # one chip's trace alone reports no collective share
    one = trace.Summary(s.window_s, s.devices[:1], s.spans)
    assert harness.layer("collective_share").read(
        harness.Run("c", None, None, one, 2, 0.0)) is None


def _profile(host, device):
    """A stand-in for ``jax.profiler.ProfileData``: host events and the
    ops of one device, each (name, start_s, end_s)."""
    def line(name, events):
        return NS(name=name, events=[
            NS(name=n, start_ns=int(a * 1e9), end_ns=int(b * 1e9))
            for n, a, b in events])
    return NS(planes=[
        NS(name="/host:CPU", lines=[line("python", host)]),
        NS(name="/device:TPU:0", lines=[line(trace.OPS_LINE, device)])])


@pytest.mark.parametrize("offset", [0.0, 0.4, -3.0, 50.0])
def test_idle_share_does_not_depend_on_the_clock_offset(offset):
    """The device's kernels are read on the device's clock: however far
    the profiler puts them from the host window, the reading is the
    same, and the offset is reported."""
    host = [("window", 10.0, 11.0), ("dispatch", 10.0, 10.1),
            ("block", 10.1, 11.0)]
    ops = [("%while.1", 10.2, 10.8), ("%fusion.1", 10.2, 10.4),
           ("%fusion.2", 10.5, 10.6), ("%fusion.3", 10.7, 10.8)]
    ops = [(n, a + offset, b + offset) for n, a, b in ops]
    s = trace.summarize(_profile(host, ops))
    assert s.window_s == pytest.approx(1.0)
    assert s.busy_s == pytest.approx(0.4)
    assert s.span_s == pytest.approx(0.6)
    idle = harness.layer("device_idle_share").read(
        harness.Run("c", None, None, s, 1, 0.0))
    assert idle == pytest.approx(100.0 / 3.0)
    outside = max(10.0 - (10.2 + offset), 0.0) + max(10.8 + offset - 11.0,
                                                     0.0)
    assert s.offset_s == pytest.approx(outside, abs=1e-9)
    assert [g for _, g in s.idle_gaps(5)] == pytest.approx([0.1, 0.1])


def test_a_trace_without_kernels_reads_no_idle_share():
    s = trace.summarize(_profile([("window", 0.0, 1.0)], []))
    assert s.busy_s == 0.0 and s.idle_gaps() == []
    assert harness.layer("device_idle_share").read(
        harness.Run("c", None, None, s, 1, 0.0)) is None

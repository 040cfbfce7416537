"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: device busy intervals, device time per XLA
module and per op, collective-op time, idle gaps, and the benchmark's
own host spans in the traced window.

The window is the host annotation named ``WINDOW`` that the harness
opens around the traced dispatches; host spans are its other
annotations (``feed``, ``dispatch``, ``block``). The trace is started
just before the window and stopped just after it, so every device event
in it belongs to the window's dispatches. Device planes are those
named ``/device:<platform>:<n>``; on a TPU their ``XLA Ops`` line holds
one event per executed op and their ``XLA Modules`` line one event per
executed program.

Host and device events sit on two clocks that the profiler aligns only
roughly. So a device's busy share is taken on the device's clock alone:
the union of its kernels over its span, from its first kernel's start
to its last kernel's end. ``offset_s`` records how far the device's
kernels lie outside the host window, which is how far the two clocks
disagree (the trace holds only the window's dispatches).
"""
from __future__ import annotations

import collections
import dataclasses
import re

import numpy as np

WINDOW = "window"
HOST_SPANS = ("feed", "dispatch", "block")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"collective-broadcast", re.IGNORECASE)


@dataclasses.dataclass
class Device:
    """One device's events inside the window (times in seconds); busy
    time, op time and gaps are of the leaf ops (kernels)."""
    name: str
    busy_s: float
    span_s: float             # first kernel's start to last kernel's end
    offset_s: float           # kernel time outside the host window
    op_s: dict                # short op name -> summed leaf duration
    module_s: dict            # module name -> summed duration
    collective_s: float
    gaps: tuple               # (starts, lengths) of the window's idle gaps


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: list
    spans: list               # (name, start_s, end_s) host spans

    def _mean(self, values) -> float:
        return float(np.mean(values)) if self.devices else 0.0

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return self._mean([d.busy_s for d in self.devices])

    @property
    def span_s(self) -> float:
        """Device spans (first to last kernel) averaged over the
        devices."""
        return self._mean([d.span_s for d in self.devices])

    def idle_share(self) -> float | None:
        """1 - busy / span on each device's own clock, averaged over the
        devices that ran a kernel; None where none did."""
        shares = [1.0 - d.busy_s / d.span_s for d in self.devices
                  if d.span_s > 0]
        return float(np.mean(shares)) if shares else None

    @property
    def offset_s(self) -> float:
        """The most by which a device's kernels lie outside the host
        window."""
        return max((d.offset_s for d in self.devices), default=0.0)

    def module_s(self, needle: str) -> float:
        """Device seconds in modules whose name contains `needle`,
        averaged over the devices."""
        return self._mean([sum(v for k, v in d.module_s.items()
                               if needle in k) for d in self.devices])

    def collective_s(self) -> float:
        return self._mean([d.collective_s for d in self.devices])

    def top_ops(self, k: int = 10) -> list:
        """The k op names with the most device time (mean over devices)."""
        tot = collections.Counter()
        for d in self.devices:
            tot.update(d.op_s)
        n = len(self.devices)
        return [[name, s / n] for name, s in tot.most_common(k)]

    def idle_gaps(self, k: int = 10) -> list:
        """The k longest idle gaps of the first device, each named by the
        host span that overlaps it most ("other" when none does)."""
        if not self.devices:
            return []
        starts, lengths = self.devices[0].gaps
        out = []
        for i in np.argsort(-lengths, kind="stable")[:k]:
            start, length = float(starts[i]), float(lengths[i])
            best, label = 0.0, "other"
            for name, s0, s1 in self.spans:
                ov = min(s1, start + length) - max(s0, start)
                if ov > best:
                    best, label = ov, name
            out.append([label, length])
        return out


def union_s(starts: np.ndarray, ends: np.ndarray, lo: float,
            hi: float) -> tuple[float, tuple]:
    """Length of the union of [start, end) intervals, and the idle gaps
    around its pieces inside [lo, hi] as arrays (starts, lengths)."""
    if len(starts) == 0:
        keep = np.array([hi > lo])
        return 0.0, (np.array([lo])[keep], np.array([hi - lo])[keep])
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    piece_start = s[new]
    piece_end = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    busy = float(np.sum(piece_end - piece_start))
    a = np.append(lo, piece_end)
    b = np.append(piece_start, hi)
    keep = b > a
    return busy, (a[keep], (b - a)[keep])


def short_name(op: str) -> str:
    """The HLO instruction's name (``%fusion.12``) without its text."""
    return op.split(" = ", 1)[0]


def leaves(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Mask of the events that contain no other event. On a TPU an XLA
    loop is itself an event on the ops line that spans every op it runs;
    the leaves are the kernels, and the gaps between them are the time
    in which no kernel ran."""
    order = np.lexsort((-ends, starts))
    s, e = starts[order], ends[order]
    leaf = np.ones(len(s), bool)
    leaf[:-1] = s[1:] >= e[:-1]
    out = np.empty(len(s), bool)
    out[order] = leaf
    return out


def _events(line):
    """(names, starts, ends) of a line's events, in seconds."""
    names, st, en = [], [], []
    for ev in line.events:
        names.append(ev.name)
        st.append(ev.start_ns * 1e-9)
        en.append(ev.end_ns * 1e-9)
    return names, np.asarray(st, np.float64), np.asarray(en, np.float64)


def summarize(profile) -> Summary:
    """`jax.profiler.ProfileData` -> Summary of the traced window."""
    host = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names, st, en = _events(line)
                host += [(n, a, b) for n, a, b in zip(names, st, en)
                         if n == WINDOW or n in HOST_SPANS]
    wins = [ev for ev in host if ev[0] == WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {WINDOW!r} host annotation")
    _, w0, w1 = wins[0]
    spans = [ev for ev in host if ev[0] in HOST_SPANS and ev[2] > w0
             and ev[1] < w1]
    devices = []
    for plane in profile.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        # the trace holds only the window's dispatches, so a device's
        # events are not clipped to the host window; busy time, span and
        # gaps are on the device's clock
        names, st, en = _events(lines[OPS_LINE])
        leaf = leaves(st, en)
        if leaf.any():
            lo, hi = st[leaf].min(), en[leaf].max()
            offset = max(w0 - lo, 0.0) + max(hi - w1, 0.0)
        else:
            lo, hi, offset = w0, w0, 0.0
        busy, gaps = union_s(st[leaf], en[leaf], lo, hi)
        op_s = collections.Counter()
        for name, d in zip((n for n, k in zip(names, leaf) if k),
                           (en - st)[leaf]):
            op_s[short_name(name)] += d
        coll = sum(v for k, v in op_s.items() if COLLECTIVE.search(k))
        module_s = collections.Counter()
        if MODULES_LINE in lines:
            mnames, ms, me = _events(lines[MODULES_LINE])
            for name, d in zip(mnames, me - ms):
                module_s[name] += d
        devices.append(Device(plane.name, busy, float(hi - lo),
                              float(offset), dict(op_s), dict(module_s),
                              float(coll), gaps))
    return Summary(w1 - w0, devices, spans)


def load(path) -> Summary:
    """Summary of the ``.xplane.pb`` file at `path`."""
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(path)))

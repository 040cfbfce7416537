"""Device time of a JAX profiler trace (``.xplane.pb``) split by the
program's stages, and the program's own host spans in the traced window.

A stage is a ``jax.named_scope`` in the program. On a TPU each op's
event metadata carries a ``tf_op`` stat, the op-name path of the HLO op
(``jit(run)/while/body/lane.decide/vmap(lane.forecast)/gather``); a
leaf op (kernel) belongs to the innermost stage name in that path, or
to ``unscoped`` where none occurs. XLA gives a fusion the metadata of
its root op, so a fusion that spans two stages counts under its root's.
A stage's executions are the event count of its least-executed leaf op.

``jax.profiler.ProfileData`` does not expose event metadata, so the file
is decoded here with a message descriptor of the public ``xplane.proto``
(the fields read) written in code. The window, the leaves and the busy
union are those of ``perfbench.trace``. The stage and host span names
come from the caller: this module imports nothing of the program.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import pathlib
import re

import numpy as np

from perfbench import trace

UNSCOPED = "unscoped"
TF_OP = "tf_op"


@dataclasses.dataclass
class Line:
    """One line of a trace plane: its events' metadata ids and their
    start and end in seconds on the plane's clock."""
    name: str
    meta: np.ndarray
    start: np.ndarray
    end: np.ndarray


@dataclasses.dataclass
class Plane:
    """One trace plane; `ops` maps an event metadata id to (name,
    tf_op), tf_op "" where the event carries none."""
    name: str
    lines: list
    ops: dict


@functools.lru_cache(maxsize=None)
def _xspace():
    """The message class of ``XSpace`` (tensorflow/tsl
    ``profiler/protobuf/xplane.proto``), the fields read here, built in
    a pool of its own. The proto's maps are read as their repeated
    entry messages, which is how they are encoded."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    i64, u64, text = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING
    many = F.LABEL_REPEATED
    msgs = {
        "XStat": [("metadata_id", 1, i64), ("str_value", 5, text),
                  ("ref_value", 7, u64)],
        "XEvent": [("metadata_id", 1, i64), ("offset_ps", 2, i64),
                   ("duration_ps", 3, i64)],
        "XLine": [("name", 2, text), ("timestamp_ns", 3, i64),
                  ("events", 4, "XEvent", many)],
        "XEventMetadata": [("name", 2, text), ("stats", 5, "XStat", many)],
        "XStatMetadata": [("name", 2, text)],
        "EventMetadataEntry": [("key", 1, i64),
                               ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64),
                              ("value", 2, "XStatMetadata")],
        "XPlane": [("name", 2, text), ("lines", 3, "XLine", many),
                   ("event_metadata", 4, "EventMetadataEntry", many),
                   ("stat_metadata", 5, "StatMetadataEntry", many)],
        "XSpace": [("planes", 1, "XPlane", many)],
    }
    fdp = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane.proto", package="perfbench_xplane",
        syntax="proto2")
    for name, fields in msgs.items():
        msg = fdp.message_type.add(name=name)
        for fname, number, kind, *label in fields:
            field = msg.field.add(name=fname, number=number,
                                  label=label[0] if label
                                  else F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                field.type = F.TYPE_MESSAGE
                field.type_name = f".perfbench_xplane.{kind}"
            else:
                field.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def _stat_text(stat, stat_names: dict) -> str:
    """A string stat's value, held inline or as a reference to the stat
    metadata whose name is the string."""
    if stat.HasField("ref_value"):
        return stat_names.get(stat.ref_value, "")
    return stat.str_value


def decode(data: bytes) -> list[Plane]:
    """The planes of a serialized ``XSpace``."""
    planes = []
    for pl in _xspace().FromString(data).planes:
        stat_names = {e.key: e.value.name for e in pl.stat_metadata}
        ops = {}
        for e in pl.event_metadata:
            tf_op = next((_stat_text(st, stat_names) for st in e.value.stats
                          if stat_names.get(st.metadata_id) == TF_OP), "")
            ops[e.key] = (e.value.name, tf_op)
        lines = []
        for ln in pl.lines:
            n = len(ln.events)
            meta = np.fromiter((ev.metadata_id for ev in ln.events),
                               np.int64, n)
            off = np.fromiter((ev.offset_ps for ev in ln.events),
                              np.int64, n)
            dur = np.fromiter((ev.duration_ps for ev in ln.events),
                              np.int64, n)
            start = (ln.timestamp_ns * 1000 + off) * 1e-12
            lines.append(Line(ln.name, meta, start, start + dur * 1e-12))
        planes.append(Plane(pl.name, lines, ops))
    return planes


def stage_pattern(stages):
    """A regex that finds the stage names as whole path components (a
    component may be wrapped by a transform, ``vmap(lane.plant)``)."""
    if not stages:
        return None
    names = "|".join(re.escape(s) for s in sorted(stages, key=len,
                                                  reverse=True))
    return re.compile(rf"(?<![\w.])({names})(?![\w.])")


def stage_of(tf_op: str, pattern) -> str:
    """The innermost stage in an op-name path: the last match of
    `pattern` (`stage_pattern`), else `UNSCOPED`."""
    found = pattern.findall(tf_op) if pattern is not None else []
    return found[-1] if found else UNSCOPED


@dataclasses.dataclass
class Split:
    """A traced window split by stage. Per device (a list entry each):
    leaf seconds and executions per stage, busy seconds and the idle
    gaps (starts, lengths) on the device's clock; the host spans named
    by the caller, (name, start_s, end_s), on the host's clock."""
    window_s: float
    stages: tuple
    stage_s: list
    stage_runs: list
    busy_s: list
    gaps: list
    spans: list

    def seconds(self, stage: str) -> float:
        """Leaf-op device seconds in `stage` (or `UNSCOPED`), averaged
        over the devices."""
        return float(np.mean([d.get(stage, 0.0) for d in self.stage_s])
                     ) if self.stage_s else 0.0

    def stage_seconds(self) -> dict:
        """Device seconds of every stage and of `UNSCOPED`."""
        return {s: self.seconds(s) for s in self.stages + (UNSCOPED,)}

    def runs(self, stage: str) -> float | None:
        """Executions of `stage`, averaged over the devices; None where
        it ran no op."""
        runs = [d[stage] for d in self.stage_runs if stage in d]
        return float(np.mean(runs)) if runs else None

    def span_durations(self, name: str) -> list[float]:
        """Seconds of each host span called `name` in the window."""
        return [b - a for n, a, b in self.spans if n == name]

    def idle_gaps(self, k: int = 10) -> list:
        """The k longest idle gaps of the first device, each named by the
        innermost host span that overlaps it ("other" when none does):
        of the overlapping spans, the one inside the most others, and of
        those the one that overlaps the gap most."""
        if not self.gaps:
            return []
        starts, lengths = self.gaps[0]
        depth = [sum(a <= s0 and s1 <= b for _, a, b in self.spans) - 1
                 for _, s0, s1 in self.spans]
        out = []
        for i in np.argsort(-lengths, kind="stable")[:k]:
            start, length = float(starts[i]), float(lengths[i])
            best, label = (-1, 0.0), "other"
            for (name, s0, s1), dep in zip(self.spans, depth):
                ov = min(s1, start + length) - max(s0, start)
                if ov > 0 and (dep, ov) > best:
                    best, label = (dep, ov), name
            out.append([label, length])
        return out


def split(planes: list[Plane], stages: tuple = (),
          host_spans: tuple = ()) -> Split:
    """Planes of a trace -> Split of the traced window. `stages` are the
    program's stage names, `host_spans` the host span names kept beside
    the benchmark's own ``trace.HOST_SPANS``."""
    kept = set(trace.HOST_SPANS) | set(host_spans)
    host = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names = [plane.ops.get(m, ("", ""))[0] for m in line.meta]
                host += [(n, float(a), float(b)) for n, a, b
                         in zip(names, line.start, line.end)
                         if n == trace.WINDOW or n in kept]
    wins = [ev for ev in host if ev[0] == trace.WINDOW]
    if not wins:
        raise ValueError(f"the trace has no {trace.WINDOW!r} host "
                         "annotation")
    _, w0, w1 = wins[0]
    spans = [ev for ev in host if ev[0] in kept and ev[2] > w0
             and ev[1] < w1]
    pattern = stage_pattern(stages)
    out = Split(w1 - w0, tuple(stages), [], [], [], [], spans)
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if (not trace._DEVICE_PLANE.match(plane.name)
                or trace.OPS_LINE not in lines):
            continue
        ops = lines[trace.OPS_LINE]
        st, en = ops.start, ops.end
        leaf = trace.leaves(st, en)
        lo, hi = (st[leaf].min(), en[leaf].max()) if leaf.any() else (0, 0)
        busy, gaps = trace.union_s(st[leaf], en[leaf], lo, hi)
        ids, inv = np.unique(ops.meta[leaf], return_inverse=True)
        secs = np.bincount(inv, weights=(en - st)[leaf],
                           minlength=len(ids))
        counts = np.bincount(inv, minlength=len(ids))
        stage_s, stage_runs = collections.Counter(), {}
        for m, d, c in zip(ids, secs, counts):
            stage = stage_of(plane.ops.get(int(m), ("", ""))[1], pattern)
            stage_s[stage] += float(d)
            stage_runs[stage] = min(stage_runs.get(stage, int(c)), int(c))
        out.stage_s.append(dict(stage_s))
        out.stage_runs.append(stage_runs)
        out.busy_s.append(busy)
        out.gaps.append(gaps)
    return out


def load(path, stages: tuple = (), host_spans: tuple = ()) -> Split:
    """Split of the ``.xplane.pb`` file at `path`."""
    return split(decode(pathlib.Path(path).read_bytes()), stages,
                 host_spans)

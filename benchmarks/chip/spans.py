"""What ``trace_reduce`` leaves out of a run's trace: the stats of each host
event, and the name scope of each device op.

``table(run)`` re-opens the run's ``.xplane.pb`` (under
``.bench_out/trace/<workload>``, where ``run_cell.py`` has the profiler
write it) and returns a :class:`Table` on the profiler's clock, in
nanoseconds, as ``trace_reduce`` has it:

- ``host``: every event of the host's threads, with its stats: the
  system's own spans among them, with the numbers they carry
  (``decode_step`` with ``step_num`` and ``batch``, ...);
- ``ops``: every op of each device's ``XLA Ops`` line, with its scope: the
  op's ``tf_op`` stat, which holds the ``op_name`` metadata XLA kept from
  JAX's name stack (``jit(_decode_fn)/while/body/closed_call/
  paged_attention/dot_general:``), or ``None``.

``jax.profiler.ProfileData`` gives the stats of an event but not those of
its metadata, and ``tf_op`` is a stat of the metadata.  So the file is read
with protobuf, by a schema declared here for the fields of
``xplane.proto`` (``tsl/profiler/protobuf``) that this module reads.
"""

from __future__ import annotations

import bisect
import functools
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Tuple

import manifest
from trace_reduce import DEVICE_PLANE, HOST_PLANE, find_xplane

TRACES = manifest.ROOT / ".bench_out" / "trace"  # run_cell.py writes here
SCOPE_STAT = "tf_op"


class HostSpan(NamedTuple):
    start: float
    end: float
    name: str
    thread: int  # the profiler's line id: one per host thread
    stats: Dict[str, object]


class Op(NamedTuple):
    start: float
    end: float
    device: str
    scope: Optional[str]


@dataclass
class Table:
    host: List[HostSpan]              # sorted by start
    ops: Dict[str, List[Op]]          # device plane → its ops, by start
    _leaves: Dict[str, List[Op]] = field(default_factory=dict, repr=False)

    def spans(self, name: str) -> List[HostSpan]:
        """Host events named ``name``, by start."""
        return [h for h in self.host if h.name == name]

    def leaf_ops(self, device: str) -> List[Op]:
        """The device's ops that hold no other op (a layer loop's ``while``
        holds its body's ops: its time is theirs), by start."""
        if device not in self._leaves:
            ops = sorted(self.ops.get(device, ()), key=lambda o: (o.start, -o.end))
            self._leaves[device] = [o for i, o in enumerate(ops)
                                    if not (i + 1 < len(ops)
                                            and ops[i + 1].start < o.end)]
        return self._leaves[device]

    def ops_between(self, device: str, start: float, end: float) -> List[Op]:
        """Leaf ops of ``device`` that start in ``[start, end)``."""
        ops = self.leaf_ops(device)
        starts = [o.start for o in ops]
        return ops[bisect.bisect_left(starts, start):bisect.bisect_left(starts, end)]


def in_scope(op: Op, scope: str) -> bool:
    """Whether ``scope`` is one of the names on the op's name stack."""
    return op.scope is not None and scope in re.split(r"[/:]", op.scope)


def table(run) -> Optional[Table]:
    """The table of the run's trace; ``None`` for a run without one."""
    if getattr(run, "trace", None) is None:
        return None
    try:
        path = find_xplane(str(TRACES / run.workload))
    except FileNotFoundError:
        return None
    return load(path)


@functools.lru_cache(maxsize=2)
def load(path: str) -> Table:
    space = _xspace()()
    space.ParseFromString(Path(path).read_bytes())
    host: List[HostSpan] = []
    ops: Dict[str, List[Op]] = {}
    for plane in space.planes:
        name = plane.name.decode(errors="replace")
        if name == HOST_PLANE:
            stat_names = {m.key: m.value.name.decode(errors="replace")
                          for m in plane.stat_metadata}
            names = {m.key: m.value.name.decode(errors="replace")
                     for m in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    s, e = _span(line, ev)
                    host.append(HostSpan(s, e, names.get(ev.metadata_id, "?"),
                                         line.id, _stats(ev.stats, stat_names)))
        elif DEVICE_PLANE.match(name):
            stat_names = {m.key: m.value.name.decode(errors="replace")
                          for m in plane.stat_metadata}
            scopes = {m.key: _stats(m.value.stats, stat_names).get(SCOPE_STAT)
                      for m in plane.event_metadata}
            for line in plane.lines:
                if line.name.decode(errors="replace") != "XLA Ops":
                    continue
                ops[name] = sorted(
                    (Op(*_span(line, ev), name, scopes.get(ev.metadata_id))
                     for ev in line.events), key=lambda o: (o.start, -o.end))
    host.sort(key=lambda h: (h.start, -h.end))
    return Table(host, ops)


def _span(line, ev) -> Tuple[float, float]:
    start = line.timestamp_ns + ev.offset_ps / 1e3
    return start, start + ev.duration_ps / 1e3


def _stats(stats, stat_names: Dict[int, str]) -> Dict[str, object]:
    out = {}
    for st in stats:
        if st.HasField("ref_value"):
            v = stat_names.get(st.ref_value)
        elif st.HasField("str_value"):
            v = st.str_value.decode(errors="replace")
        elif st.HasField("int64_value"):
            v = st.int64_value
        elif st.HasField("uint64_value"):
            v = st.uint64_value
        elif st.HasField("double_value"):
            v = st.double_value
        else:
            continue
        out[stat_names.get(st.metadata_id, str(st.metadata_id))] = v
    return out


# Fields of xplane.proto that this module reads, by their numbers there.
# Strings are read as bytes: the profiler does not promise valid UTF-8.
_FIELDS = {
    "XSpace": [("planes", 1, "XPlane")],
    "XPlane": [("name", 2, "bytes"), ("lines", 3, "XLine"),
               ("event_metadata", 4, "EventMetadataEntry"),
               ("stat_metadata", 5, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
    "XLine": [("id", 1, "int64"), ("name", 2, "bytes"),
              ("timestamp_ns", 3, "int64"), ("events", 4, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "XStat")],
    "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "bytes"), ("ref_value", 7, "uint64")],
    "XEventMetadata": [("name", 2, "bytes"), ("stats", 5, "XStat")],
    "XStatMetadata": [("name", 2, "bytes")],
}
_REPEATED = {("XSpace", "planes"), ("XPlane", "lines"),
             ("XPlane", "event_metadata"), ("XPlane", "stat_metadata"),
             ("XLine", "events"), ("XEvent", "stats"),
             ("XEventMetadata", "stats")}


@functools.lru_cache(maxsize=1)
def _xspace() -> type:
    """The message class of ``XSpace`` by the fields above (proto2 on the
    wire is proto3's; proto2 tells which of a stat's values is set)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "double": F.TYPE_DOUBLE, "bytes": F.TYPE_BYTES}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_chip_xplane.proto", package="bench_chip_xplane",
        syntax="proto2")
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for fname, number, ftype in fields:
            f = m.field.add(name=fname, number=number)
            f.label = (F.LABEL_REPEATED if (msg, fname) in _REPEATED
                       else F.LABEL_OPTIONAL)
            if ftype in scalar:
                f.type = scalar[ftype]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".bench_chip_xplane.{ftype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_chip_xplane.XSpace"))

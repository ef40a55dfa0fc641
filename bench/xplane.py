"""Read a profiler trace (``.xplane.pb``) with its event metadata.

``jax.profiler.ProfileData`` gives each event's name, times and own
stats, but not the stats of its metadata, where the TPU profiler keeps
an operation's op name path (``tf_op``: ``jit(step)/while/body/
attention/...``), its HLO category, FLOPs and bytes.  This parses the
file with the ``XSpace`` schema of the profiler (``tsl/profiler/
protobuf/xplane.proto``, the fields read here), built at run time, so
it needs only the ``protobuf`` package.

``events(path)`` yields, per plane and line, every event with its start
and end on the trace's clock (line ``timestamp_ns`` plus the event's
offset) and its metadata's stats by name.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, NamedTuple

# (field, number, type, repeated, message type); types from descriptor.proto
_INT64, _UINT64, _DOUBLE, _STRING, _BYTES, _MESSAGE = 3, 4, 1, 9, 12, 11
_SCHEMA = {
    "XSpace": [("planes", 1, _MESSAGE, True, "XPlane")],
    "XPlane": [("id", 1, _INT64, False, None),
               ("name", 2, _STRING, False, None),
               ("lines", 3, _MESSAGE, True, "XLine"),
               ("event_metadata", 4, _MESSAGE, True,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _MESSAGE, True,
                "XPlane.StatMetadataEntry"),
               ("stats", 6, _MESSAGE, True, "XStat")],
    "XLine": [("id", 1, _INT64, False, None),
              ("name", 2, _STRING, False, None),
              ("timestamp_ns", 3, _INT64, False, None),
              ("events", 4, _MESSAGE, True, "XEvent")],
    "XEvent": [("metadata_id", 1, _INT64, False, None),
               ("offset_ps", 2, _INT64, False, None),
               ("duration_ps", 3, _INT64, False, None),
               ("stats", 4, _MESSAGE, True, "XStat")],
    "XStat": [("metadata_id", 1, _INT64, False, None),
              ("double_value", 2, _DOUBLE, False, None),
              ("uint64_value", 3, _UINT64, False, None),
              ("int64_value", 4, _INT64, False, None),
              ("str_value", 5, _STRING, False, None),
              ("bytes_value", 6, _BYTES, False, None),
              ("ref_value", 7, _UINT64, False, None)],
    "XEventMetadata": [("id", 1, _INT64, False, None),
                       ("name", 2, _STRING, False, None),
                       ("display_name", 4, _STRING, False, None),
                       ("stats", 5, _MESSAGE, True, "XStat")],
    "XStatMetadata": [("id", 1, _INT64, False, None),
                      ("name", 2, _STRING, False, None)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
_PACKAGE = "bench_xplane"


@functools.lru_cache(maxsize=None)
def _space_class():
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PACKAGE, syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto

    def add_fields(msg, fields):
        for name, num, typ, repeated, ref in fields:
            f = msg.field.add(name=name, number=num, type=typ,
                              label=(F.LABEL_REPEATED if repeated
                                     else F.LABEL_OPTIONAL))
            if ref:
                f.type_name = f".{_PACKAGE}.{ref}"

    for name, fields in _SCHEMA.items():
        msg = fdp.message_type.add(name=name)
        add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                sub = msg.nested_type.add(name=entry)
                sub.options.map_entry = True
                add_fields(sub, [("key", 1, _INT64, False, None),
                                 ("value", 2, _MESSAGE, False, value)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace"))


def read(path: str):
    """The trace's ``XSpace`` message."""
    with open(path, "rb") as f:
        return _space_class().FromString(f.read())


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: int
    end_ns: int
    meta: Dict[str, object]      # the metadata's stats by name


def _value(stat, names):
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return names.get(stat.ref_value, stat.ref_value)
    return stat.int64_value or stat.uint64_value or stat.double_value


def events(path: str, keep=lambda plane, line: True) -> Iterator[Event]:
    """Every event of the lines ``keep(plane name, line name)`` admits."""
    for plane in read(path).planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        metas = {}
        for line in plane.lines:
            if not keep(plane.name, line.name):
                continue
            base_ps = line.timestamp_ns * 1000
            for e in line.events:
                mid = e.metadata_id
                if mid not in metas:
                    md = plane.event_metadata[mid]
                    metas[mid] = (md.name, {names.get(s.metadata_id, ""):
                                            _value(s, names)
                                            for s in md.stats})
                name, meta = metas[mid]
                start = base_ps + e.offset_ps
                yield Event(plane.name, line.name, name, start // 1000,
                            (start + e.duration_ps) // 1000, meta)

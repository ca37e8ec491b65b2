"""A reader of the profiler's ``*.xplane.pb`` files (the XSpace protobuf of
``tsl/profiler/protobuf/xplane.proto``) that keeps what
``jax.profiler.ProfileData`` leaves out: the stats of an event's
metadata. A TPU's ``XLA Ops`` events carry the op's metadata (its
``tf_op``, the ``jax.named_scope`` path of the op) there, and not on the
event.

Only the fields the benchmark reads are declared; protobuf skips the
rest. Times follow ``ProfileData``: an event starts at its line's
``timestamp_ns`` plus its ``offset_ps``.
"""
from __future__ import annotations

from pathlib import Path

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
_I64, _STR = _F.TYPE_INT64, _F.TYPE_STRING
# message -> [(field, number, scalar type or message name, repeated)]
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, _STR, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)],
    "XLine": [("name", 2, _STR, False), ("timestamp_ns", 3, _I64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _I64, False),
               ("offset_ps", 2, _I64, False),
               ("duration_ps", 3, _I64, False)],
    "XStat": [("metadata_id", 1, _I64, False), ("str_value", 5, _STR, False)],
    "XEventMetadata": [("name", 2, _STR, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, _STR, False)],
}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


def _message_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")
    for name, fields in _SCHEMA.items():
        m = fd.message_type.add(name=name)
        for field, number, kind, repeated in fields:
            f = m.field.add(name=field, number=number,
                            label=_F.LABEL_REPEATED if repeated
                            else _F.LABEL_OPTIONAL)
            if isinstance(kind, str):
                f.type, f.type_name = _F.TYPE_MESSAGE, f".bench_xspace.{kind}"
            else:
                f.type = kind
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = m.nested_type.add(name=entry)
                e.options.map_entry = True
                e.field.add(name="key", number=1, type=_I64,
                            label=_F.LABEL_OPTIONAL)
                e.field.add(name="value", number=2, type=_F.TYPE_MESSAGE,
                            label=_F.LABEL_OPTIONAL,
                            type_name=f".bench_xspace.{value}")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


XSpace = _message_class()


def read(path: Path):
    """The XSpace message of one ``*.xplane.pb`` file."""
    return XSpace.FromString(Path(path).read_bytes())


def metadata_stat(plane, stat: str) -> dict:
    """{event metadata id: string value of its ``stat``} of a plane."""
    ids = {k for k, v in plane.stat_metadata.items() if v.name == stat}
    out = {}
    for key, md in plane.event_metadata.items():
        for st in md.stats:
            if st.metadata_id in ids:
                out[key] = st.str_value
    return out


def events(line, names: dict):
    """(name, start_ns, duration_ns, metadata id) of each event of a
    line; ``names`` is the plane's {metadata id: name}."""
    t0 = line.timestamp_ns
    for e in line.events:
        yield (names.get(e.metadata_id, ""), t0 + e.offset_ps / 1000,
               e.duration_ps / 1000, e.metadata_id)

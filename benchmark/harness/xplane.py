"""Read an ``.xplane.pb`` with its event metadata.

``jax.profiler.ProfileData`` gives planes, lines and events, and leaves out
what the profiler stores once per distinct operation, in the plane's event
metadata: the scoped framework name (``tf_op``, e.g.
``jit(fused)/.../policy_core/dot_general``), the HLO category, the short
name. The reduction needs those, so this module parses the file itself with
``google.protobuf`` (which the program already depends on), from a
descriptor of the few fields it reads. Field numbers are those of
``tsl/profiler/protobuf/xplane.proto``; a map is read as the repeated
key/value entries it is on the wire; unknown fields are skipped.
"""

from __future__ import annotations

from typing import Any, Dict

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_PACKAGE = "benchmark_xplane"
_T = descriptor_pb2.FieldDescriptorProto
_SCALAR = {
    "int64": _T.TYPE_INT64, "uint64": _T.TYPE_UINT64, "double": _T.TYPE_DOUBLE,
    "string": _T.TYPE_STRING, "bytes": _T.TYPE_BYTES,
}
# message -> [(field, number, type, repeated)]
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [
        ("name", 2, "string", False), ("lines", 3, "XLine", True),
        ("event_metadata", 4, "EventMetadataEntry", True),
        ("stat_metadata", 5, "StatMetadataEntry", True),
    ],
    "XLine": [
        ("name", 2, "string", False), ("timestamp_ns", 3, "int64", False),
        ("events", 4, "XEvent", True),
    ],
    "XEvent": [
        ("metadata_id", 1, "int64", False), ("offset_ps", 2, "int64", False),
        ("duration_ps", 3, "int64", False), ("stats", 4, "XStat", True),
    ],
    "XStat": [
        ("metadata_id", 1, "int64", False), ("double_value", 2, "double", False),
        ("uint64_value", 3, "uint64", False), ("int64_value", 4, "int64", False),
        ("str_value", 5, "string", False), ("ref_value", 7, "uint64", False),
    ],
    "XEventMetadata": [
        ("id", 1, "int64", False), ("name", 2, "string", False),
        ("display_name", 4, "string", False), ("stats", 5, "XStat", True),
    ],
    "XStatMetadata": [("id", 1, "int64", False), ("name", 2, "string", False)],
    "EventMetadataEntry": [("key", 1, "int64", False), ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False), ("value", 2, "XStatMetadata", False)],
}


def _space_class():
    f = descriptor_pb2.FileDescriptorProto(
        name=f"{_PACKAGE}.proto", package=_PACKAGE, syntax="proto3"
    )
    for message, fields in _SCHEMA.items():
        m = f.message_type.add(name=message)
        for name, number, kind, repeated in fields:
            field = m.field.add(
                name=name, number=number,
                label=_T.LABEL_REPEATED if repeated else _T.LABEL_OPTIONAL,
            )
            if kind in _SCALAR:
                field.type = _SCALAR[kind]
            else:
                field.type = _T.TYPE_MESSAGE
                field.type_name = f".{_PACKAGE}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.XSpace")
    )


_XSpace = _space_class()


def read(path: str) -> Any:
    """The parsed ``XSpace`` of ``path``."""
    space = _XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_names(plane: Any) -> Dict[int, str]:
    return {e.key: e.value.name for e in plane.stat_metadata}


def stat_value(stat: Any, names: Dict[int, str]) -> Any:
    """A stat's value; a reference is resolved to the string it refers to."""
    if stat.ref_value:
        return names.get(stat.ref_value, "")
    if stat.str_value:
        return stat.str_value
    return stat.int64_value or stat.uint64_value or stat.double_value


def event_metadata(plane: Any) -> Dict[int, Dict[str, Any]]:
    """metadata id -> {"name", "display_name", and every stat by name}."""
    names = stat_names(plane)
    out: Dict[int, Dict[str, Any]] = {}
    for entry in plane.event_metadata:
        md = entry.value
        row = {"name": md.name, "display_name": md.display_name}
        for st in md.stats:
            row[names.get(st.metadata_id, str(st.metadata_id))] = stat_value(st, names)
        out[entry.key] = row
    return out

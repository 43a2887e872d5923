"""The ``op_name`` of every HLO instruction of every program in a
profiler trace.

A TPU trace names a device op by its HLO instruction (the ``XLA Ops``
events carry no ``op_name``), and a program by its module and program id
(``XLA Modules`` events named ``jit_run_plan_batch(<program id>)``).
The trace's ``/host:metadata`` plane holds each program's ``HloProto``
in an event metadata entry of the same name, whose instructions carry
the ``op_name`` metadata that ``jax.named_scope`` writes.
``jax.profiler.ProfileData`` does not expose a plane's metadata, so this
module reads the ``.xplane.pb`` itself, with just the fields it needs of
the XSpace and HLO messages (field numbers of
``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``);
the others are skipped.
"""

from __future__ import annotations

import functools

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"

# (message, [(field, number, type, label, message type)])
_STR, _BYTES, _INT64, _MSG = 9, 12, 3, 11
_ONE, _MANY = 1, 3
_MESSAGES = {
    "XSpace": [("planes", 1, _MSG, _MANY, "XPlane")],
    "XPlane": [("name", 2, _STR, _ONE, None),
               ("event_metadata", 4, _MSG, _MANY,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, _MANY,
                "XPlane.StatMetadataEntry")],
    "XPlane.EventMetadataEntry": [("key", 1, _INT64, _ONE, None),
                                  ("value", 2, _MSG, _ONE, "XEventMetadata")],
    "XPlane.StatMetadataEntry": [("key", 1, _INT64, _ONE, None),
                                 ("value", 2, _MSG, _ONE, "XStatMetadata")],
    "XEventMetadata": [("id", 1, _INT64, _ONE, None),
                       ("name", 2, _STR, _ONE, None),
                       ("stats", 5, _MSG, _MANY, "XStat")],
    "XStat": [("metadata_id", 1, _INT64, _ONE, None),
              ("bytes_value", 6, _BYTES, _ONE, None)],
    "XStatMetadata": [("id", 1, _INT64, _ONE, None),
                      ("name", 2, _STR, _ONE, None)],
    "HloProto": [("hlo_module", 1, _MSG, _ONE, "HloModuleProto")],
    "HloModuleProto": [("name", 1, _STR, _ONE, None),
                       ("computations", 3, _MSG, _MANY,
                        "HloComputationProto")],
    "HloComputationProto": [("instructions", 2, _MSG, _MANY,
                             "HloInstructionProto")],
    "HloInstructionProto": [("name", 1, _STR, _ONE, None),
                            ("metadata", 7, _MSG, _ONE, "OpMetadata")],
    "OpMetadata": [("op_name", 2, _STR, _ONE, None)],
}


@functools.cache
def _classes() -> dict:
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                  message_factory)

    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_opnames.proto", package="chipbench", syntax="proto3")
    protos = {}
    for full, fields in _MESSAGES.items():
        outer, _, inner = full.partition(".")
        if inner:
            msg = protos[outer].nested_type.add(name=inner)
            msg.options.map_entry = True
        else:
            msg = protos[full] = fdp.message_type.add(name=full)
        for name, number, type_, label, ref in fields:
            f = msg.field.add(name=name, number=number, type=type_,
                              label=label)
            if ref:
                f.type_name = ".chipbench." + ref
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(
                pool.FindMessageTypeByName("chipbench." + name))
            for name in ("XSpace", "HloProto")}


def program_op_names(xspace: bytes) -> dict:
    """``{program: {instruction name: op_name}}`` from a serialized
    XSpace's metadata plane, a program named as its ``XLA Modules``
    events are (empty where the trace has no such plane)."""
    cls = _classes()
    space = cls["XSpace"].FromString(xspace)
    out: dict = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        hlo_stat = {k for k, v in plane.stat_metadata.items()
                    if v.name == HLO_PROTO_STAT}
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if stat.metadata_id in hlo_stat:
                    out[meta.name] = instruction_op_names(stat.bytes_value)
    return out


def instruction_op_names(hlo_proto: bytes) -> dict:
    """``{instruction name: op_name}`` of a serialized ``HloProto``."""
    module = _classes()["HloProto"].FromString(hlo_proto).hlo_module
    return {inst.name: inst.metadata.op_name
            for comp in module.computations for inst in comp.instructions}

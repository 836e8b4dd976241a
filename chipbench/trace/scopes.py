"""Attribute a device trace's ops to the model's scopes.

A ``jax.named_scope`` (and every flax module's name) ends up in the
``op_name`` the compiler keeps for each instruction of the program
(``jit(train_step)/.../block_0/moe/experts/...``). The trace's events carry
only the instruction's name (``fusion.319``), but the profiler also stores
each program it saw, whole, as an ``HloProto`` in the plane
``/host:metadata`` of the same ``.xplane.pb``. ``jax.profiler.ProfileData``
does not expose that plane's event metadata, so this module reads the two
protocol buffers itself: just the wire format (varints and length-delimited
fields) and the handful of field numbers below, nothing but the standard
library.

``op_names(xplane)`` gives instruction name -> op_name over every program of
the trace; ``seconds_under(run, needle)`` sums the busy seconds of the ops
whose op_name holds ``needle`` (a scope such as ``/moe/``; seen on the chip:
``.../TransformerLM/block_0/attn/cond/branch_0_fun/rdt_flash_fwd/pallas_call``,
``.../lm_head_loss/while/body/closed_call/checkpoint/dot_general``, and plain
``ragged-dot-none`` for the compiler's grouped matmul). A fusion carries
the op_name of its root instruction, so an op that fuses work of two scopes
counts for one of them. A trace with no stored program, or a program compiled
without the scope, gives nothing: the reader then says nothing.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, Optional, Tuple

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STATS = ("hlo proto", "hlo_proto")   # as the profiler spells it
# field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto)
XSPACE_PLANES = 1
XPLANE_NAME, XPLANE_EVENT_METADATA, XPLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
XSTATMETA_NAME = 2
XEVENTMETA_STATS = 5
XSTAT_METADATA_ID, XSTAT_BYTES = 1, 6
HLOPROTO_MODULE, MODULE_COMPUTATIONS, COMPUTATION_INSTRUCTIONS = 1, 3, 2
INSTRUCTION_NAME, INSTRUCTION_METADATA, OPMETADATA_OP_NAME = 1, 7, 2


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one serialized message: an int for a varint
    or fixed field, the bytes of a length-delimited one."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 1:
            value, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == 5:
            value, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, value


def _all(buf: bytes, number: int) -> Iterator[bytes]:
    return (v for n, v in fields(buf) if n == number)


def _first(buf: bytes, number: int, default=None):
    return next(_all(buf, number), default)


def hlo_protos(xplane_path: str) -> Iterator[bytes]:
    """Every serialized ``HloProto`` the trace's metadata plane holds."""
    with open(xplane_path, "rb") as fh:
        space = fh.read()
    for plane in _all(space, XSPACE_PLANES):
        if _first(plane, XPLANE_NAME, b"").decode() != METADATA_PLANE:
            continue
        stat_ids = {
            _first(entry, MAP_KEY)
            for entry in _all(plane, XPLANE_STAT_METADATA)
            if _first(_first(entry, MAP_VALUE, b""), XSTATMETA_NAME,
                      b"").decode().lower() in HLO_PROTO_STATS}
        for entry in _all(plane, XPLANE_EVENT_METADATA):
            for stat in _all(_first(entry, MAP_VALUE, b""), XEVENTMETA_STATS):
                if _first(stat, XSTAT_METADATA_ID) in stat_ids:
                    yield _first(stat, XSTAT_BYTES, b"")


def op_names(xplane_path: str) -> Dict[str, str]:
    """Instruction name -> op_name, over every program stored in the trace
    (every computation of each: a loop's body, a fusion's too)."""
    out: Dict[str, str] = {}
    for proto in hlo_protos(xplane_path):
        module = _first(proto, HLOPROTO_MODULE, b"")
        for computation in _all(module, MODULE_COMPUTATIONS):
            for instruction in _all(computation, COMPUTATION_INSTRUCTIONS):
                name = _first(instruction, INSTRUCTION_NAME, b"").decode()
                meta = _first(instruction, INSTRUCTION_METADATA, b"")
                op_name = _first(meta, OPMETADATA_OP_NAME, b"").decode()
                if name and op_name:
                    out.setdefault(name, op_name)
    return out


def seconds_under(run: dict, needle: str,
                  kernels: Optional[str] = None) -> Optional[float]:
    """Busy seconds (a chip) of the traced ops whose op_name holds
    ``needle``, and of the ops whose own name matches ``kernels`` (a regular
    expression: a kernel the compiler brings, such as its grouped matmul,
    keeps no op_name of the model's). None where the trace stores no program
    that names the scope."""
    trace = run.get("trace")
    if not trace or not run.get("xplane"):
        return None
    under = {op for op, scope in op_names(run["xplane"]).items()
             if needle in scope}
    if not under:
        return None
    named = re.compile(kernels) if kernels else None
    return sum(sec for op, sec in trace["op_seconds"].items()
               if op in under or (named and named.search(op)))

"""Protobuf wire-format decoding, stdlib only.

Just the wire layer (https://protobuf.dev/programming-guides/encoding/):
a message is a sequence of (field_number, wire_type, payload) records;
nested messages are length-delimited payloads decoded recursively by
whoever knows the schema (``tracing/xplane.py``). No proto compiler, no
``protobuf`` package — the XSpace schema is small and frozen enough
that hand-walking it beats a build-time dependency, and it keeps
``tools/trace_report.py`` runnable on machines with nothing but a
Python (the jaxlint contract).

Wire types handled: 0 varint, 1 fixed64, 2 length-delimited, 5 fixed32.
Groups (3/4) are obsolete and absent from xplane protos; hitting one
raises ``ProtoError`` rather than desyncing silently.
"""

from __future__ import annotations

import struct
from typing import Iterator, Tuple, Union

WIRE_VARINT = 0
WIRE_FIXED64 = 1
WIRE_LEN = 2
WIRE_FIXED32 = 5

FieldValue = Union[int, bytes]


class ProtoError(ValueError):
    """Malformed wire data (truncated varint, unknown wire type, ...)."""


def read_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    """Decode one base-128 varint at ``pos``; returns (value, new_pos)."""
    result = 0
    shift = 0
    n = len(buf)
    while True:
        if pos >= n:
            raise ProtoError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ProtoError("varint longer than 10 bytes")


def fields(buf: bytes) -> Iterator[Tuple[int, int, FieldValue]]:
    """Iterate (field_number, wire_type, value) over one message's bytes.

    Varints come back as unsigned ints (see ``to_signed`` for int64
    fields), fixed64/fixed32/length-delimited as raw ``bytes`` — the
    schema layer knows whether a length-delimited field is a string, a
    sub-message, or packed scalars.
    """
    pos = 0
    n = len(buf)
    while pos < n:
        tag, pos = read_varint(buf, pos)
        field_num, wire_type = tag >> 3, tag & 7
        if field_num == 0:
            raise ProtoError(f"field number 0 at byte {pos}")
        if wire_type == WIRE_VARINT:
            value, pos = read_varint(buf, pos)
        elif wire_type == WIRE_FIXED64:
            value = buf[pos:pos + 8]
            pos += 8
        elif wire_type == WIRE_LEN:
            length, pos = read_varint(buf, pos)
            value = buf[pos:pos + length]
            pos += length
            if len(value) != length:
                raise ProtoError("truncated length-delimited field")
        elif wire_type == WIRE_FIXED32:
            value = buf[pos:pos + 4]
            pos += 4
        else:
            raise ProtoError(f"unsupported wire type {wire_type} "
                             f"(field {field_num})")
        if pos > n:
            raise ProtoError("field overruns buffer")
        yield field_num, wire_type, value


def to_signed(value: int) -> int:
    """Reinterpret a varint as two's-complement int64 (proto ``int64``
    fields encode negatives as 10-byte varints, not zigzag)."""
    return value - (1 << 64) if value >= (1 << 63) else value


def to_double(raw: bytes) -> float:
    return struct.unpack("<d", raw)[0]


def to_text(raw: bytes) -> str:
    """Proto strings are UTF-8; tolerate the occasional garbage byte in
    tool-emitted names rather than failing a whole trace."""
    return raw.decode("utf-8", "replace")

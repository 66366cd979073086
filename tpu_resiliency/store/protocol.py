"""Wire protocol for the tpurx KV store.

Fixed binary framing, designed to be trivially implementable in C++:

Request frame:
    u8  opcode
    u32 nargs                (little-endian)
    repeated nargs times:
        u32 len
        len bytes

Response frame:
    u8  status               (0=OK, 1=KEY_MISS, 2=TIMEOUT, 3=ERROR, 4=CAS_FAIL)
    u32 nargs
    repeated args as above

All integers (ADD amounts/results) travel as ASCII decimal bytes so the
store itself stays type-agnostic (same choice the reference's TCPStore makes).
"""

from __future__ import annotations

import struct
from enum import IntEnum


class Op(IntEnum):
    SET = 1
    GET = 2          # blocking get: waits for key (args: key, timeout_ms)
    TRY_GET = 3      # immediate get; KEY_MISS if absent
    ADD = 4          # atomic add (args: key, amount) -> new value
    APPEND = 5       # append bytes to key (creates if absent) -> new length
    COMPARE_SET = 6  # args: key, expected, desired -> actual value after op.
                     # expected=="" means "set only if absent" (TCPStore semantics)
    WAIT = 7         # args: timeout_ms, key... ; blocks until all exist
    CHECK = 8        # args: key... -> b"1"/b"0"
    DELETE = 9       # args: key -> b"1" if removed
    NUM_KEYS = 10
    PING = 11
    LIST_KEYS = 12   # args: prefix -> all keys with that prefix
    MULTI_SET = 13   # args: k1, v1, k2, v2, ...
    MULTI_GET = 14   # immediate; args: key... -> value per key (KEY_MISS if any absent)
    MULTI_TRY_GET = 15  # immediate; args: key... -> (b"1", value) per present
                        # key, (b"0", b"") per absent one — per-key misses
                        # instead of MULTI_GET's all-or-nothing KEY_MISS
    # One-RTT protocol rounds: the ops below fold a whole arrival (append +
    # completion check, or counter bump + record write) into one round trip,
    # so a barrier/rendezvous round costs O(rounds) trips instead of
    # O(ops x ranks).  All keys an op touches MUST live on one shard — the
    # sharded client's affinity groups guarantee that.
    APPEND_CHECK = 16   # args: key, value, done_key, done_value, required,
                        # token... ; append value to key, then decode the log
                        # as comma-separated tokens and set done_key when the
                        # population is complete: tokens given -> all of them
                        # present; none given -> >= `required` DISTINCT tokens
                        # (duplicates from re-entry collapse).  ->
                        # (new_len, b"1" if done was set by anyone else b"0")
    ADD_SET = 17        # args: add_key, amount, set_key, set_value ; atomic
                        # ADD then SET in one trip.  The first ADD_SLOT marker
                        # in set_value is replaced by the post-add counter
                        # (ASCII decimal) — protocols embed the arrival number
                        # only the server knows.  -> new counter value
    WAIT_GE = 18        # args: key, threshold, timeout_ms ; block until the
                        # key holds an integer >= threshold (missing key
                        # counts as 0).  The event-driven "wait for the next
                        # arrival" primitive that replaces per-count marker
                        # keys.  -> current value (or TIMEOUT status)
    # 19 is retired (it was an envelope opcode), never reused


# Spliced by the server into ADD_SET's set_value (first occurrence only):
# the post-add counter as ASCII decimal.  Chosen to never collide with JSON
# payloads the protocols store (no '%' keys in any record schema).
ADD_SLOT = b"%TPURX_N%"


class Status(IntEnum):
    OK = 0
    KEY_MISS = 1
    TIMEOUT = 2
    ERROR = 3
    CAS_FAIL = 4


_U32 = struct.Struct("<I")


def encode_frame(code: int, args: list[bytes]) -> bytes:
    parts = [bytes([code]), _U32.pack(len(args))]
    for a in args:
        parts.append(_U32.pack(len(a)))
        parts.append(a)
    return b"".join(parts)


def encode_request(op: Op, *args: bytes) -> bytes:
    return encode_frame(int(op), list(args))


def encode_response(status: Status, *args: bytes) -> bytes:
    return encode_frame(int(status), list(args))


def itob(value: int) -> bytes:
    return str(int(value)).encode()


def btoi(value: bytes) -> int:
    return int(value.decode())


# -- single-source op table ---------------------------------------------------
# The native server's accepted-op range guard once rejected any op added only
# on the Python side (silently: the C++ side dropped the connection).  The
# C++ enum is now GENERATED from this module between the markers below, and a
# parity test asserts the generated block appears verbatim in the source, so
# the two servers cannot drift.

CPP_OP_TABLE_BEGIN = "// BEGIN GENERATED OP TABLE"
CPP_OP_TABLE_END = "// END GENERATED OP TABLE"


def render_cpp_op_enum() -> str:
    """The C++ ``enum Op`` block for ``native/store_server.cpp``.

    ``OP__LAST`` is the range-guard sentinel: the frame parser accepts
    ``OP_SET..OP__LAST``, so a new Python-side op is refused (``ERROR``) by
    the native server until this block is regenerated — which the parity
    test turns into a failure of the suite, not of a job.
    """
    lines = [
        f"{CPP_OP_TABLE_BEGIN} "
        "(source: tpu_resiliency/store/protocol.py;",
        "// regenerate: python -m tpu_resiliency.store.protocol --cpp)",
        "enum Op : uint8_t {",
    ]
    for op in Op:
        lines.append(f"  OP_{op.name} = {int(op)},")
    lines.append(f"  OP__LAST = {max(int(op) for op in Op)},")
    lines.append("};")
    lines.append(CPP_OP_TABLE_END)
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    if "--cpp" in sys.argv:
        # tpurx: disable=TPURX001 -- CLI entry point, stdout is the generated table
        print(render_cpp_op_enum())
    else:
        for _op in Op:
            # tpurx: disable=TPURX001 -- CLI entry point, stdout is the op listing
            print(f"{int(_op):3d}  {_op.name}")

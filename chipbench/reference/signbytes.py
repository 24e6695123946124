"""Canonical precommit sign-bytes, written from the wire description
(proto/tendermint/types/canonical.proto; types/vote.go MarshalDelimited):

    CanonicalVote{type=1 varint, height=2 sfixed64, round=3 sfixed64,
                  block_id=4 {hash=1, part_set_header=2 {total=1, hash=2}},
                  timestamp=5 {seconds=1, nanos=2} (always present),
                  chain_id=6}, varint-length-delimited.

The harness signs over THESE bytes; the program verifies over the bytes
its own (native, batched) assembly produces.  A divergence between the
two shows as a rejected honest row, i.e. as `correct` false.
"""

from __future__ import annotations

import struct

PRECOMMIT = 2
NS = 1_000_000_000


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field_bytes(field: int, payload: bytes) -> bytes:
    return _uvarint(field << 3 | 2) + _uvarint(len(payload)) + payload


def _field_varint(field: int, value: int) -> bytes:
    return _uvarint(field << 3) + _uvarint(value & (2**64 - 1))


def _field_sfixed64(field: int, value: int) -> bytes:
    return _uvarint(field << 3 | 1) + struct.pack("<q", value)


class PrecommitTemplate:
    """Sign-bytes of precommits FOR one block (non-zero block id) at one
    height and round: everything but the timestamp is shared by the
    validators of a commit, so it is encoded once."""

    def __init__(self, chain_id: str, height: int, round_: int,
                 block_hash: bytes, parts_total: int, parts_hash: bytes):
        psh = _field_varint(1, parts_total) + _field_bytes(2, parts_hash)
        block_id = _field_bytes(1, block_hash) + _field_bytes(2, psh)
        head = _field_varint(1, PRECOMMIT)
        if height:
            head += _field_sfixed64(2, height)
        if round_:
            head += _field_sfixed64(3, round_)
        self._head = head + _field_bytes(4, block_id)
        self._tail = _field_bytes(6, chain_id.encode())

    def sign_bytes(self, timestamp_ns: int) -> bytes:
        seconds, nanos = divmod(timestamp_ns, NS)
        ts = ((_field_varint(1, seconds) if seconds else b"")
              + (_field_varint(2, nanos) if nanos else b""))
        msg = self._head + _field_bytes(5, ts) + self._tail
        return _uvarint(len(msg)) + msg


def precommit_sign_bytes(chain_id: str, height: int, round_: int,
                         block_hash: bytes, parts_total: int,
                         parts_hash: bytes, timestamp_ns: int) -> bytes:
    return PrecommitTemplate(chain_id, height, round_, block_hash,
                             parts_total, parts_hash).sign_bytes(timestamp_ns)

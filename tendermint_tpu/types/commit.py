"""Commit and CommitSig: the aggregated precommit evidence for a block.

Parity: reference types/block.go:583-870 (CommitSig :603, VoteSignBytes
:815, CommitToVoteSet in vote_set.py), wire form types.proto Commit{1..4},
CommitSig{1..4}.

Verification of a commit's signatures (ValidatorSet.verify_commit and
the batched multi-commit surface, types/validator.batch_verify_commits)
routes through the async verification service since round 6: the
sign-bytes assembled here feed crypto.async_verify, where a replayed
commit's (pub, msg, sig) triples hit the verified-signature cache and
never reach host or device again.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from tendermint_tpu.crypto import merkle
from tendermint_tpu.wire.proto import (
    ProtoWriter,
    encode_uvarint,
    fields_to_dict,
)

from .basic import (
    BlockID,
    BlockIDFlag,
    GO_ZERO_TIME_NS,
    SignedMsgType,
    decode_timestamp,
    encode_timestamp,
)
from .canonical import _canonical_block_id, vote_sign_bytes_raw


# the one absent commit row and its wire form (filled in right after
# the class body; None disarms the fast paths while it bootstraps)
_ABSENT_SIG = None
_ABSENT_SIG_ENC = None


@dataclass
class CommitSig:
    block_id_flag: BlockIDFlag
    validator_address: bytes = b""
    timestamp_ns: int = GO_ZERO_TIME_NS
    signature: bytes = b""

    @classmethod
    def absent_sig(cls) -> "CommitSig":
        return cls(block_id_flag=BlockIDFlag.ABSENT)

    def absent(self) -> bool:
        return self.block_id_flag == BlockIDFlag.ABSENT

    def for_block(self) -> bool:
        return self.block_id_flag == BlockIDFlag.COMMIT

    def vote_block_id(self, commit_block_id: BlockID) -> BlockID:
        """The BlockID this signature signed over (reference block.go
        CommitSig.BlockID): COMMIT → the commit's, NIL/ABSENT → zero."""
        if self.block_id_flag == BlockIDFlag.COMMIT:
            return commit_block_id
        return BlockID()

    def validate_basic(self) -> None:
        if self.block_id_flag not in (
            BlockIDFlag.ABSENT,
            BlockIDFlag.COMMIT,
            BlockIDFlag.NIL,
        ):
            raise ValueError(f"unknown BlockIDFlag {self.block_id_flag}")
        if self.absent():
            if self.validator_address or self.signature:
                raise ValueError("absent CommitSig must be empty")
        else:
            if len(self.validator_address) != 20:
                raise ValueError("validator address must be 20 bytes")
            if not self.signature or len(self.signature) > 64:
                raise ValueError("signature missing or too big")

    def encode(self) -> bytes:
        """Hand-rolled, byte-identical to the ProtoWriter form
        (differential-tested): encoded once per signature per block save
        — the single hottest encoder during replay."""
        if (_ABSENT_SIG_ENC is not None
                and self.block_id_flag == BlockIDFlag.ABSENT
                and not self.validator_address and not self.signature
                and self.timestamp_ns == GO_ZERO_TIME_NS):
            # thousand-slot validator sets are mostly passive: their
            # commit rows are ALL this one absent value, encoded once
            return _ABSENT_SIG_ENC
        ts = encode_timestamp(self.timestamp_ns)
        out = bytearray()
        if self.block_id_flag:
            out += b"\x08" + encode_uvarint(int(self.block_id_flag))
        if self.validator_address:
            out += b"\x12" + encode_uvarint(len(self.validator_address))
            out += self.validator_address
        out += b"\x1a" + encode_uvarint(len(ts)) + ts
        if self.signature:
            out += b"\x22" + encode_uvarint(len(self.signature)) + self.signature
        return bytes(out)

    @classmethod
    def decode(cls, data: bytes) -> "CommitSig":
        if data == _ABSENT_SIG_ENC:
            # value object: every absent row decodes to ONE shared
            # instance (the encode fast path's mirror — a 1000-slot
            # commit is ~90% this row, decoded per node per save)
            return _ABSENT_SIG
        f = fields_to_dict(data)
        ts = f.get(3, [None])[0]
        return cls(
            block_id_flag=BlockIDFlag(f.get(1, [1])[0]),
            validator_address=f.get(2, [b""])[0],
            timestamp_ns=decode_timestamp(ts) if ts is not None else GO_ZERO_TIME_NS,
            signature=f.get(4, [b""])[0],
        )


# arm the absent-row fast paths: the canonical instance and its wire
# form (computed through the slow path above while the cell was None,
# so the bytes are the encoder's own)
_ABSENT_SIG = CommitSig.absent_sig()
_ABSENT_SIG_ENC = _ABSENT_SIG.encode()


@dataclass
class Commit:
    height: int
    round: int
    block_id: BlockID
    signatures: list[CommitSig] = field(default_factory=list)

    def _sign_bytes_templates(self, chain_id: str):
        """Within one commit the canonical vote bytes differ per signature
        only by BlockID flavor (COMMIT vs NIL/ABSENT) and timestamp, so
        fields 1-4 and field 6 are built once and reused.  This runs per
        signature on every commit-verification surface (fast-sync windows,
        light ranges, VerifyCommit) — at 200 validators x 10k blocks the
        per-call ProtoWriter cost dominated replay (BENCH r2: 0.86x).
        Byte-identity with vote_sign_bytes_raw is differential-tested
        (tests/test_wire.py)."""
        # ADVICE r3: key on every field the prefix bytes depend on, not
        # just chain_id, so a mutated Commit can never serve stale bytes
        key = (
            chain_id,
            self.height,
            self.round,
            self.block_id.hash,
            self.block_id.part_set_header.total,
            self.block_id.part_set_header.hash,
        )
        tpl = getattr(self, "_sb_tpl", None)
        if tpl is not None and tpl[0] == key:
            return tpl[1]

        def prefix(block_id: BlockID) -> bytes:
            return (
                ProtoWriter()
                .varint(1, int(SignedMsgType.PRECOMMIT))
                .sfixed64(2, self.height)
                .sfixed64(3, self.round)
                .message(4, _canonical_block_id(block_id))
                .bytes_out()
            )

        out = (
            prefix(self.block_id),
            prefix(BlockID()),
            ProtoWriter().string(6, chain_id).bytes_out(),
        )
        self._sb_tpl = (key, out)
        return out

    def vote_sign_bytes(self, chain_id: str, idx: int) -> bytes:
        """Reconstruct validator idx's canonical precommit bytes
        (reference block.go:815)."""
        return self._row_sign_bytes(chain_id, self.signatures[idx])

    def _row_sign_bytes(self, chain_id: str, cs: CommitSig) -> bytes:
        pre_block, pre_nil, suffix = self._sign_bytes_templates(chain_id)
        pre = pre_block if cs.block_id_flag == BlockIDFlag.COMMIT else pre_nil
        ts = encode_timestamp(cs.timestamp_ns)
        body = pre + b"\x2a" + encode_uvarint(len(ts)) + ts + suffix
        return encode_uvarint(len(body)) + body

    def vote_sign_bytes_batch(self, chain_id: str, idxs) -> list[bytes]:
        """Every selected validator's canonical precommit bytes, assembled
        by the native kernel in one C call when available (the per-row
        Python path costs ~4 µs — 40 ms for a 10k commit, 20× the
        BASELINE 2 ms end-to-end target).  Byte-identical to
        vote_sign_bytes per index (differential-tested)."""
        sigs = self.signatures
        return self.sign_bytes_of(chain_id, [sigs[i] for i in idxs])[0]

    def sign_bytes_of(self, chain_id: str,
                      rows: list[CommitSig]) -> tuple[list[bytes], str]:
        """The canonical precommit bytes of `rows` (rows of this commit,
        in any selection) and the path that built them, for the caller's
        span: `native`, `native_exact_ts` (a timestamp outside int64: the
        split into seconds and nanos ran row by row) or `template`
        (under 64 rows, or no native library).  From 64 rows on nothing
        is called per row: flags and timestamps are read by one
        comprehension each and the buffer is sliced from Python ints."""
        if len(rows) >= 64:
            from tendermint_tpu.crypto import signbytes_native

            pre_block, pre_nil, suffix = self._sign_bytes_templates(chain_id)
            commit_flag = BlockIDFlag.COMMIT
            packed = signbytes_native.batch_sign_bytes(
                pre_block, pre_nil, suffix,
                [cs.block_id_flag == commit_flag for cs in rows],
                [cs.timestamp_ns for cs in rows],
            )
            if packed is not None:
                buf, offsets, exact_ts = packed
                return ([buf[a:b] for a, b in zip(offsets, offsets[1:])],
                        "native_exact_ts" if exact_ts else "native")
        return [self._row_sign_bytes(chain_id, cs) for cs in rows], "template"

    def hash(self) -> bytes:
        """Merkle root over proto-encoded CommitSigs (reference block.go
        Commit.Hash).  Memoized like encode(): the root covers every
        signature row — O(validator slots) — and block validation
        recomputes it at each surface that sees the block."""
        h = getattr(self, "_hash_memo", None)
        if h is None:
            h = merkle.hash_from_byte_slices(
                [cs.encode() for cs in self.signatures])
            self._hash_memo = h
        return h

    def size(self) -> int:
        return len(self.signatures)

    def validate_basic(self) -> None:
        from .vote_set import MAX_VOTES_COUNT

        if self.height < 0:
            raise ValueError("negative height")
        if self.round < 0:
            raise ValueError("negative round")
        if len(self.signatures) > MAX_VOTES_COUNT:
            raise ValueError(f"too many signatures: max {MAX_VOTES_COUNT}")
        if self.height >= 1:
            if self.block_id.is_zero():
                raise ValueError("commit cannot be for nil block")
            if not self.signatures:
                raise ValueError("no signatures in commit")
            for cs in self.signatures:
                cs.validate_basic()

    def encode(self) -> bytes:
        # memoized on the instance: a stored commit is re-encoded for
        # every block save / WAL record / catchup frame that carries it,
        # and each encode walks EVERY CommitSig — O(validator slots).
        # Commits are append-frozen after construction (MakeCommit /
        # decode build the signature list once); the memo is as safe as
        # the _sb_tpl template cache above and saved whole seconds per
        # thousand-slot simnet run.
        enc = getattr(self, "_enc_memo", None)
        if enc is not None:
            return enc
        w = (
            ProtoWriter()
            .varint(1, self.height)
            .varint(2, self.round)
            .message(3, self.block_id.encode(), always=True)
        )
        for cs in self.signatures:
            w.message(4, cs.encode(), always=True)
        enc = w.bytes_out()
        self._enc_memo = enc
        return enc

    @classmethod
    def decode(cls, data: bytes) -> "Commit":
        from tendermint_tpu.wire.proto import to_int64

        f = fields_to_dict(data)
        bid = f.get(3, [None])[0]
        return cls(
            height=to_int64(f.get(1, [0])[0]),
            round=to_int64(f.get(2, [0])[0]),
            block_id=BlockID.decode(bid) if bid is not None else BlockID(),
            signatures=[CommitSig.decode(b) for b in f.get(4, [])],
        )

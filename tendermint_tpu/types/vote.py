"""Vote: the signed consensus message (prevote/precommit).

Parity: reference types/vote.go (sign-bytes :93-101, Verify :147-156),
wire form proto/tendermint/types/types.proto Vote{1..8}.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from tendermint_tpu.crypto.keys import PubKey
from tendermint_tpu.utils.metrics import Counter
from tendermint_tpu.wire.proto import ProtoWriter, fields_to_dict

from .basic import (
    BlockID,
    BlockIDFlag,
    GO_ZERO_TIME_NS,
    SignedMsgType,
    decode_timestamp,
    encode_timestamp,
)
from .canonical import vote_sign_bytes_raw

MAX_VOTE_BYTES = 223  # reference types/vote.go MaxVoteBytes

# Vote signatures verified one at a time (`Vote.verify` reaching
# `verify_one`): a vote the consensus tick precheck gave a verdict is
# never among them.  node/metrics.py registers it beside the precheck's
# own counters (consensus/state.py VOTE_VERIFY_COUNTERS).
SINGLE_VERIFIES_TOTAL = Counter(
    "vote_single_verifies_total",
    "Vote signatures verified one at a time, outside the tick precheck",
    namespace="tendermint", subsystem="consensus")


@dataclass
class Vote:
    type: SignedMsgType
    height: int
    round: int
    block_id: BlockID
    timestamp_ns: int = GO_ZERO_TIME_NS
    validator_address: bytes = b""
    validator_index: int = -1
    signature: bytes = b""

    def sign_bytes(self, chain_id: str) -> bytes:
        # memoized per chain and timestamp: every verify surface
        # (precheck slices, single-vote admission, the service cache
        # key) recomputes the canonical bytes, and the decode memo
        # shares one Vote instance across all in-process receivers — so
        # one encode serves them all.  A signer that finds the same
        # vote saved under another timestamp rewrites `timestamp_ns`
        # (privval/file_pv, grpc_pv, socket_pv) AFTER asking for the
        # bytes, hence the timestamp in the key; `signature` is not
        # covered by sign-bytes, and type, height, round and block_id
        # (a frozen dataclass) are set at construction and assigned
        # nowhere.
        key = (chain_id, self.timestamp_ns)
        memo = getattr(self, "_sb_memo", None)
        if memo is not None and memo[0] == key:
            return memo[1]
        sb = vote_sign_bytes_raw(
            chain_id, self.type, self.height, self.round, self.block_id, self.timestamp_ns
        )
        self._sb_memo = (key, sb)
        return sb

    def _precheck_digest(self, chain_id: str, pub_key: PubKey) -> bytes:
        from tendermint_tpu.crypto import tmhash

        return tmhash.sum_sha256(
            chain_id.encode() + b"\x00" + pub_key.bytes_()
            + self.sign_bytes(chain_id) + self.signature
        )

    def verify(self, chain_id: str, pub_key: PubKey) -> None:
        """Address check + signature check (reference vote.go:147-156)."""
        if pub_key.address() != self.validator_address:
            raise ValueError("invalid validator address")
        marker = getattr(self, "_sig_prechecked", None)
        if marker is not None and marker[1] == self._precheck_digest(chain_id, pub_key):
            # this exact content+signature was batch-verified: its
            # verdict stands, valid or refused, and is not computed again
            if marker[0]:
                return
            raise ValueError("invalid signature")
        # probe + fill the shared verified-sig cache around the
        # scalar-mult: N callers re-checking one wire vote (every node
        # of an in-process net) become lookups (crypto/async_verify)
        from tendermint_tpu.crypto.async_verify import verify_one

        SINGLE_VERIFIES_TOTAL.inc()
        if not verify_one(pub_key, self.sign_bytes(chain_id),
                          self.signature):
            raise ValueError("invalid signature")

    def mark_sig_verdict(self, chain_id: str, pub_key: PubKey, ok: bool) -> None:
        """Record a batched precheck's verdict on the signature
        (consensus tick batching, SURVEY §7 stage 6) — verify() then
        returns (valid) or raises (refused) without verifying again.  The
        marker binds the FULL verified content (chain, key, sign-bytes,
        signature), so mutating the vote after marking can never carry a
        verdict over to unchecked bytes — it just falls back to a real
        verification."""
        self._sig_prechecked = (bool(ok), self._precheck_digest(chain_id, pub_key))

    def is_nil(self) -> bool:
        return self.block_id.is_zero()

    def commit_sig(self):
        """Convert to CommitSig (reference block.go CommitSig/NewCommitSigForBlock)."""
        from .commit import CommitSig

        if self.block_id.is_zero():
            flag = BlockIDFlag.NIL
        else:
            flag = BlockIDFlag.COMMIT
        return CommitSig(
            block_id_flag=flag,
            validator_address=self.validator_address,
            timestamp_ns=self.timestamp_ns,
            signature=self.signature,
        )

    def validate_basic(self) -> None:
        if self.type not in (SignedMsgType.PREVOTE, SignedMsgType.PRECOMMIT):
            raise ValueError("invalid vote type")
        if self.height < 0:
            raise ValueError("negative height")
        if self.round < 0:
            raise ValueError("negative round")
        self.block_id.validate_basic()
        if not self.block_id.is_zero() and not self.block_id.is_complete():
            raise ValueError("blockID must be either empty or complete")
        if len(self.validator_address) != 20:
            raise ValueError("validator address must be 20 bytes")
        if self.validator_index < 0:
            raise ValueError("negative validator index")
        if not self.signature:
            raise ValueError("signature is missing")
        if len(self.signature) > 64:
            raise ValueError("signature too big")

    @staticmethod
    def decode_sign_bytes_timestamp(sign_bytes: bytes) -> tuple[int, tuple] | None:
        """(timestamp_ns, non-timestamp fields) of canonical sign-bytes
        (CanonicalVote timestamp = field 5); None if unparseable."""
        from .canonical import split_canonical_timestamp

        return split_canonical_timestamp(sign_bytes, 5)

    # -- wire (gossip) encoding ---------------------------------------
    def encode(self) -> bytes:
        # memoized per instance: one vote is encoded once per SEND, and
        # gossip fans a vote out over every mesh link — at 100 nodes the
        # re-encodes dominated the wire layer.  Keyed on the signature
        # object so a vote encoded before signing (or re-signed by a
        # maverick) can never serve stale bytes; every other field is
        # set at construction.
        memo = getattr(self, "_enc_memo", None)
        if memo is not None and memo[0] is self.signature:
            return memo[1]
        enc = (
            ProtoWriter()
            .varint(1, int(self.type))
            .varint(2, self.height)
            .varint(3, self.round)
            .message(4, self.block_id.encode(), always=True)
            .message(5, encode_timestamp(self.timestamp_ns), always=True)
            .bytes_(6, self.validator_address)
            .varint(7, self.validator_index)
            .bytes_(8, self.signature)
            .bytes_out()
        )
        self._enc_memo = (self.signature, enc)
        return enc

    @classmethod
    def decode(cls, data: bytes) -> "Vote":
        from tendermint_tpu.wire.proto import to_int64

        f = fields_to_dict(data)

        def get(n, default):
            return f.get(n, [default])[0]

        bid = get(4, None)
        ts = get(5, None)
        return cls(
            type=SignedMsgType(get(1, 0)),
            height=to_int64(get(2, 0)),
            round=to_int64(get(3, 0)),
            block_id=BlockID.decode(bid) if bid is not None else BlockID(),
            timestamp_ns=decode_timestamp(ts) if ts is not None else GO_ZERO_TIME_NS,
            validator_address=get(6, b""),
            validator_index=to_int64(get(7, 0)),
            signature=get(8, b""),
        )


def batch_verify_votes(chain_id: str, pairs: list[tuple["Vote", PubKey]]) -> list[bool]:
    """ONE batched signature verification over (vote, pub_key) pairs;
    returns a verdict per pair.  The single shared crypto path for every
    vote-slice verifier: VoteSet.add_votes and the consensus tick
    precheck (state._precheck_vote_sigs) — admission rules differ per
    caller, the batched crypto must not.

    Routed through the async verification service (crypto.async_verify)
    by default: concurrent slices from independent callers (gossip
    ticks, blocksync, replay) coalesce into one device batch, and
    re-gossiped duplicates resolve from the verified-signature cache
    without touching host or device.  TM_TPU_ASYNC_VERIFY=0 restores a
    per-caller BatchVerifier."""
    from tendermint_tpu.crypto.async_verify import new_service_batch_verifier

    bv = new_service_batch_verifier()
    for v, pk in pairs:
        bv.add(pk, v.sign_bytes(chain_id), v.signature)
    _, oks = bv.verify()
    return oks

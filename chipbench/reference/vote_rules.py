"""What a full node following a chain through the consensus receive loop
must admit, commit and refuse, height by height (reference: consensus/
state.go receiveRoutine -> handleMsg -> tryAddVote -> addVote, and
types/vote_set.go AddVote, which verifies each vote's signature once and
refuses a vote whose signature fails without changing anything).

A height delivers its proposal, 128 prevotes and 128 precommits for the
proposed block, one vote of each kind from every validator of the set.
A call's rows are the votes in that order: row i < n is validator i's
prevote, row n + i its precommit.

  admission  a delivered vote is admitted exactly when its signature is
             valid (ZIP-215)
  commit     the height commits the proposed block once the valid
             precommits for it carry more than 2/3 of the power; the
             successor block's LastCommit holds exactly those precommits
  forged     a forged vote is never admitted, and never stops the commit
             while the remaining valid power exceeds 2/3
  once       each delivered vote's signature reaches a verifier once a
             height: the receive loop's tick verifies a burst's votes as
             one batch, and no vote is verified again alone

The proposed block's LastCommit is verified wherever upstream validates
the block — doPrevote when the block is there before the node prevotes,
enterPrecommit when it locks on it, finalizeCommit, ApplyBlock — and every
row of it was verified as a precommit at the height before: so each
validation is answered from the verified-signature cache, and no other
row is (`cache_hits`).

Sign-bytes are written here from the wire description, beside the
precommit form of `signbytes.py`: the prevote (CanonicalVote type 1) and
the proposal (CanonicalProposal, type 32: height, round, pol_round,
block_id, timestamp, chain_id).

Imports nothing of the program.
"""

from __future__ import annotations

from chipbench.reference.signbytes import (
    NS,
    _field_bytes,
    _field_sfixed64,
    _field_varint,
    _uvarint,
)

PREVOTE, PRECOMMIT, PROPOSAL = 1, 2, 32


def _block_id(block_hash: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    psh = _field_varint(1, parts_total) + _field_bytes(2, parts_hash)
    return _field_bytes(1, block_hash) + _field_bytes(2, psh)


def _timestamp(timestamp_ns: int) -> bytes:
    seconds, nanos = divmod(timestamp_ns, NS)
    return ((_field_varint(1, seconds) if seconds else b"")
            + (_field_varint(2, nanos) if nanos else b""))


def _delimited(msg: bytes) -> bytes:
    return _uvarint(len(msg)) + msg


class VoteTemplate:
    """Sign-bytes of votes of one type FOR one block at one height and
    round: only the timestamp differs between validators."""

    def __init__(self, vote_type: int, chain_id: str, height: int, round_: int,
                 block_hash: bytes, parts_total: int, parts_hash: bytes):
        head = _field_varint(1, vote_type)
        if height:
            head += _field_sfixed64(2, height)
        if round_:
            head += _field_sfixed64(3, round_)
        self._head = head + _field_bytes(4, _block_id(block_hash, parts_total, parts_hash))
        self._tail = _field_bytes(6, chain_id.encode())

    def sign_bytes(self, timestamp_ns: int) -> bytes:
        return _delimited(self._head + _field_bytes(5, _timestamp(timestamp_ns))
                          + self._tail)


def proposal_sign_bytes(chain_id: str, height: int, round_: int, pol_round: int,
                        block_hash: bytes, parts_total: int, parts_hash: bytes,
                        timestamp_ns: int) -> bytes:
    msg = _field_varint(1, PROPOSAL)
    if height:
        msg += _field_sfixed64(2, height)
    if round_:
        msg += _field_sfixed64(3, round_)
    if pol_round:
        msg += _field_varint(4, pol_round)
    msg += _field_bytes(5, _block_id(block_hash, parts_total, parts_hash))
    msg += _field_bytes(6, _timestamp(timestamp_ns))
    msg += _field_bytes(7, chain_id.encode())
    return _delimited(msg)


def outcome(height: int, powers: list[int], row_ok) -> tuple:
    """What the node answers for one height, from the reference's verdict
    on each row (`row_ok(i)`): ("committed", (height, prevotes admitted,
    precommits admitted, the rows refused)) once the valid precommits
    carry more than 2/3 of the power, else ("not_committed", (...))."""
    n = len(powers)
    ok = [bool(row_ok(i)) for i in range(2 * n)]
    refused = tuple(i for i in range(2 * n) if not ok[i])
    committed = 3 * sum(p for p, v in zip(powers, ok[n:]) if v) > 2 * sum(powers)
    return ("committed" if committed else "not_committed",
            (height, sum(ok[:n]), sum(ok[n:]), refused))


def last_commit(powers: list[int], row_ok) -> list[bool]:
    """The rows of the successor block's LastCommit: validator i's
    precommit exactly when it is valid."""
    n = len(powers)
    return [bool(row_ok(n + i)) for i in range(n)]


def validations(block_before_prevote: bool) -> int:
    """ValidateBlock calls a height makes: enterPrecommit's (it locks on
    the block its prevotes carry), finalizeCommit's and ApplyBlock's, and
    doPrevote's when the block arrived before the node prevoted — not when
    the propose timeout made it prevote first (a node left idle longer
    than the timeout)."""
    return 3 + bool(block_before_prevote)


def cache_hits(last_commit_rows: int, block_before_prevote: bool) -> int:
    """Rows a height answers from the verified-signature cache: its
    block's LastCommit, once for each validation of the block."""
    return validations(block_before_prevote) * last_commit_rows


def implied(said: tuple, row: int) -> bool | None:
    """A committed height says each row it did not refuse was valid."""
    if said is None or said[0] not in ("committed", "not_committed"):
        return None
    return row not in said[1][3]

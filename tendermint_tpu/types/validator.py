"""Validator and ValidatorSet: proposer rotation, set updates, and the
batched commit-verification surface.

Semantics parity targets (reference types/validator_set.go):
  * a-priori weighted round-robin proposer selection via ProposerPriority
    (IncrementProposerPriority :116, rescale window 2*total :27-30,
    centering :226, tie-break by address in CompareProposerPriority).
  * validators sorted by (voting power desc, address asc) (:904-918).
  * Hash = merkle root over SimpleValidator{pub_key, voting_power} proto
    bytes (:347, validator.go:117).
  * VerifyCommit / VerifyCommitLight / VerifyCommitLightTrusting
    (:662, :720, :776) — re-designed here as ONE BatchVerifier device call
    while preserving the reference's exact accept/reject semantics,
    including the in-order early-exit behaviour of the Light variants
    (an invalid signature positioned after the +2/3 cutoff must not cause
    rejection, because the reference never looks at it).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from fractions import Fraction
from itertools import compress

from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.keys import PubKey
from tendermint_tpu.utils import trace as _trace
from tendermint_tpu.wire.proto import (
    ProtoWriter,
    encode_uvarint,
    encode_varint_signed,
)

from .basic import BlockID, BlockIDFlag

MAX_TOTAL_VOTING_POWER = (1 << 63) - 1 >> 3  # reference: MaxTotalVotingPower int64/8
PRIORITY_WINDOW_SIZE_FACTOR = 2

_I64_MAX = (1 << 63) - 1
_I64_MIN = -(1 << 63)


def _clip(v: int) -> int:
    return max(_I64_MIN, min(_I64_MAX, v))


class ErrNotEnoughVotingPowerSigned(ValueError):
    """The signatures a commit check consulted were all valid and carry
    too little power (reference types/errors.go
    ErrNotEnoughVotingPowerSigned).  A class of its own because the light
    client's skipping verification answers THIS failure with a bisection
    pivot and every other one (a wrong signature, a double vote) with a
    failed verification.  `rows`: the signatures verified on the way."""

    def __init__(self, msg: str, got: int, needed: int, rows: int):
        super().__init__(msg)
        self.got, self.needed, self.rows = got, needed, rows


_PK_PROTO_CACHE: dict[bytes, bytes] = {}


def pub_key_proto_bytes(pub_key: PubKey) -> bytes:
    """tendermint.crypto.PublicKey{oneof sum: ed25519=1, secp256k1=2}
    (keys.proto; dispatch in crypto/encoding.py).  Memoized by key
    bytes: encoded for every validator row of every state save / wire
    message, keys are immutable, and the two key types have distinct
    lengths so raw bytes are a sufficient cache key."""
    from tendermint_tpu.crypto.encoding import pub_key_proto_field

    field, raw = pub_key_proto_field(pub_key)
    enc = _PK_PROTO_CACHE.get(raw)
    if enc is None:
        enc = ProtoWriter().bytes_(field, raw, omit_empty=False).bytes_out()
        if len(_PK_PROTO_CACHE) < 65536:  # bound: ~100B/entry
            _PK_PROTO_CACHE[raw] = enc
    return enc


def simple_validator_bytes(pub_key: PubKey, voting_power: int) -> bytes:
    """SimpleValidator{pub_key=1, voting_power=2} — the Hash() leaf."""
    return (
        ProtoWriter()
        .message(1, pub_key_proto_bytes(pub_key))
        .varint(2, voting_power)
        .bytes_out()
    )


@dataclass
class Validator:
    pub_key: PubKey
    voting_power: int
    proposer_priority: int = 0
    address: bytes = b""

    def __post_init__(self):
        if not self.address:
            self.address = self.pub_key.address()

    def copy(self) -> "Validator":
        # positional construction, not dataclasses.replace(): set copies
        # run this once per row per proposer rotation, and replace()'s
        # kwargs/machinery showed up as whole seconds on thousand-slot
        # simnet runs
        return Validator(self.pub_key, self.voting_power,
                         self.proposer_priority, self.address)

    def bytes_(self) -> bytes:
        return simple_validator_bytes(self.pub_key, self.voting_power)

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties broken by lower address."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare validators with same address")

    def validate_basic(self) -> None:
        if self.voting_power < 0:
            raise ValueError("validator has negative voting power")
        if len(self.address) != 20:
            raise ValueError("validator address must be 20 bytes")

    def encode(self) -> bytes:
        """validator.proto Validator{address=1, pub_key=2, voting_power=3,
        proposer_priority=4}.  Hand-rolled (byte-identical to the
        ProtoWriter form — differential-tested): this runs per validator
        row per state save, the hottest encoder after CommitSig."""
        pk = pub_key_proto_bytes(self.pub_key)
        # proto3 omit-empty: an empty address (possible on adversarially
        # decoded input that never passed validate_basic) must not emit
        # field 1, or re-encoding diverges from the canonical form
        out = b""
        if self.address:
            out += b"\x0a" + encode_uvarint(len(self.address)) + self.address
        out += b"\x12" + encode_uvarint(len(pk)) + pk
        if self.voting_power:
            out += b"\x18" + encode_varint_signed(self.voting_power)
        if self.proposer_priority:
            out += b"\x20" + encode_varint_signed(self.proposer_priority)
        return out

    @classmethod
    def decode(cls, data: bytes) -> "Validator":
        from tendermint_tpu.wire.proto import fields_to_dict

        from tendermint_tpu.crypto.encoding import pub_key_from_proto_fields

        f = fields_to_dict(data)
        pk = fields_to_dict(f.get(2, [b""])[0])
        prio = f.get(4, [0])[0]
        if prio >= 1 << 63:
            prio -= 1 << 64
        return cls(
            pub_key=pub_key_from_proto_fields(pk),
            voting_power=f.get(3, [0])[0],
            proposer_priority=prio,
            address=f.get(1, [b""])[0],
        )


def _sort_by_voting_power(vals: list[Validator]) -> list[Validator]:
    return sorted(vals, key=lambda v: (-v.voting_power, v.address))


class ValidatorSet:
    """Mutable validator set (copy() before mutating shared instances)."""

    def __init__(self, validators: list[Validator], proposer: Validator | None = None):
        self.validators = _sort_by_voting_power([v.copy() for v in validators])
        self._total_voting_power = 0
        self._update_total_voting_power()
        self._reindex()
        self.proposer = proposer
        if validators and proposer is None:
            self.increment_proposer_priority(1)

    def _reindex(self) -> None:
        # address → index; keeps get_by_address O(1) at 10k-validator scale
        self._by_address = {v.address: i for i, v in enumerate(self.validators)}
        # membership/power changed ⇒ the memoized hash is stale.  Priority
        # churn (increment_proposer_priority) deliberately does NOT come
        # through here: the hash covers (pub_key, power) only
        # (simple_validator_bytes), so it survives rotation.
        self._hash: bytes | None = None
        # the memoized wire form IS priority-sensitive, so it is also
        # invalidated at every mutator (rotation, updates, get_proposer)
        self._enc: bytes | None = None
        # the verify columns cover (pub_key, power) like the hash, and are
        # dropped here and nowhere else: the proposer-priority methods
        # change neither a key nor a power, so they keep them
        self._cols: tuple[list[bytes], list[int]] | None = None

    # -- bookkeeping ---------------------------------------------------
    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total += v.voting_power
            if total > MAX_TOTAL_VOTING_POWER:
                raise ValueError("total voting power exceeds maximum")
        self._total_voting_power = total

    def total_voting_power(self) -> int:
        return self._total_voting_power

    def __len__(self) -> int:
        return len(self.validators)

    def size(self) -> int:
        return len(self.validators)

    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def copy(self) -> "ValidatorSet":
        c = ValidatorSet.__new__(ValidatorSet)
        c.validators = [v.copy() for v in self.validators]
        c._total_voting_power = self._total_voting_power
        c._reindex()
        c._hash = self._hash  # same membership ⇒ same hash
        c._enc = self._enc    # row-for-row copy ⇒ same wire form; the
        #                       copy's own mutators re-invalidate it.
        #                       This is what lets a state save encode
        #                       each thousand-slot set once per rotation
        #                       instead of once per save that sees it
        #                       (validators/next/last share lineage).
        c._cols = self._cols  # same keys and powers; never edited in place
        c.proposer = self.proposer.copy() if self.proposer else None
        return c

    def has_address(self, address: bytes) -> bool:
        return address in self._by_address

    def get_by_address(self, address: bytes) -> tuple[int, Validator | None]:
        i = self._by_address.get(address)
        if i is None:
            return -1, None
        return i, self.validators[i]

    def get_by_index(self, index: int) -> Validator | None:
        if 0 <= index < len(self.validators):
            return self.validators[index]
        return None

    # -- proposer rotation --------------------------------------------
    def increment_proposer_priority(self, times: int) -> None:
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("times must be positive")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self._rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority_once()
        self.proposer = proposer
        self._enc = None   # priorities/proposer are in the wire form

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    def _increment_proposer_priority_once(self) -> Validator:
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority + v.voting_power)
        mostest = self._val_with_most_priority()
        mostest.proposer_priority = _clip(
            mostest.proposer_priority - self.total_voting_power()
        )
        return mostest

    def _val_with_most_priority(self) -> Validator:
        res = self.validators[0]
        for v in self.validators[1:]:
            res = res.compare_proposer_priority(v)
        return res

    def _rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        if diff > diff_max:
            # integer division toward zero, mirroring Go int64 semantics
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                p = v.proposer_priority
                v.proposer_priority = -(-p // ratio) if p < 0 else p // ratio

    def _shift_by_avg_proposer_priority(self) -> None:
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        # floor division matches big.Int.Div (Euclidean for positive divisor)
        avg = total // n
        for v in self.validators:
            v.proposer_priority = _clip(v.proposer_priority - avg)

    def get_proposer(self) -> Validator:
        if not self.validators:
            raise ValueError("empty validator set")
        if self.proposer is None:
            self.proposer = self._val_with_most_priority()
            self._enc = None   # proposer rides the wire form (field 2)
        return self.proposer

    # -- hashing -------------------------------------------------------
    def hash(self) -> bytes:
        """Merkle root over (pub_key, power) rows; memoized — consensus
        recomputes it for every header validation and the membership
        changes only at validator-update heights."""
        if self._hash is None:
            self._hash = merkle.hash_from_byte_slices(
                [v.bytes_() for v in self.validators]
            )
        return self._hash

    def verify_columns(self) -> tuple[list[bytes], list[int]]:
        """(public-key bytes, voting powers) by validator index: what a
        commit check reads of the set, built by one pass each and
        memoised like hash() — a 10,000-validator set is verified
        against every block and changes at validator-update heights.
        Callers must not edit the lists."""
        cols = self._cols
        if cols is None:
            vals = self.validators
            cols = self._cols = ([v.pub_key.bytes_() for v in vals],
                                 [v.voting_power for v in vals])
        return cols

    # -- validator-set updates (ABCI EndBlock) -------------------------
    def update_with_change_set(self, changes: list[Validator]) -> None:
        """Apply updates/removals (voting_power 0 = remove), then recompute
        priorities for new entrants (reference updateWithChangeSet :587:
        new validators start at -1.125*total)."""
        if not changes:
            return
        by_addr = {v.address: v for v in changes}
        if len(by_addr) != len(changes):
            raise ValueError("duplicate addresses in change set")
        removals = {a for a, v in by_addr.items() if v.voting_power == 0}
        for a in removals:
            if not self.has_address(a):
                raise ValueError(f"cannot remove unknown validator {a.hex()}")
        kept = [v for v in self.validators if v.address not in removals]
        current = {v.address: v for v in kept}
        # compute the updated total before assigning new-entrant priority
        new_total = sum(
            by_addr[a].voting_power if a in by_addr else current[a].voting_power
            for a in current
        ) + sum(
            v.voting_power
            for a, v in by_addr.items()
            if a not in current and a not in removals
        )
        if new_total == 0:
            raise ValueError("applying the validator changes would result in empty set")
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power exceeds maximum")
        out = []
        for v in kept:
            upd = by_addr.get(v.address)
            if upd is not None and upd.voting_power != 0:
                nv = v.copy()
                nv.voting_power = upd.voting_power
                nv.pub_key = upd.pub_key
                out.append(nv)
            else:
                out.append(v)
        for a, v in by_addr.items():
            if a not in current and a not in removals:
                nv = v.copy()
                nv.proposer_priority = -(new_total + (new_total >> 3))
                out.append(nv)
        self.validators = _sort_by_voting_power(out)
        self._update_total_voting_power()
        self._reindex()
        self._rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()

    # -- commit verification (batched; the north-star surface) ---------
    def verify_commit(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """All non-absent signatures must be valid; ForBlock power > 2/3.
        One device call for the whole commit.  Raises ValueError on failure.
        (reference :662-712)"""
        batch_verify_commits(
            [CommitVerifyJob(self, chain_id, block_id, height, commit, mode="full")]
        )

    def verify_commit_light(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        """ForBlock signatures verified until cumulative power > 2/3,
        preserving the reference's in-order early exit (:720-766):
        signatures after the cutoff index are never consulted."""
        batch_verify_commits(
            [CommitVerifyJob(self, chain_id, block_id, height, commit, mode="light")]
        )

    def verify_commit_light_trusting(self, chain_id: str, commit, trust_level: Fraction) -> int:
        """Address-matched verification to trust_level of this set's power
        (light-client skipping verification, reference :776-830).  Returns
        the number of signatures it verified; raises
        ErrNotEnoughVotingPowerSigned when they were valid and too few."""
        if trust_level.denominator == 0:
            raise ValueError("trustLevel has zero denominator")
        if commit is None:
            raise ValueError("nil commit")
        needed = self.total_voting_power() * trust_level.numerator // trust_level.denominator
        from tendermint_tpu.crypto.async_verify import new_service_batch_verifier

        bv = new_service_batch_verifier()
        pubs, powers = self.verify_columns()
        by_address = self._by_address
        for_block = BlockIDFlag.COMMIT
        sel, matched = [], []  # the commit's rows selected, their validators
        seen: dict[int, int] = {}
        running = 0
        double_vote = None
        # the same commit.* spans as batch_verify_commits, one per phase
        with _trace.span("commit.select", mode="trusting") as sp:
            for idx, cs in enumerate(commit.signatures):
                if cs.block_id_flag != for_block:
                    continue
                val_idx = by_address.get(cs.validator_address)
                if val_idx is None:
                    continue
                if val_idx in seen:
                    # the reference verifies row by row, so a wrong
                    # signature BEFORE the second vote is what it reports:
                    # the rows selected so far are verified first
                    double_vote = (val_idx, seen[val_idx], idx)
                    break
                seen[val_idx] = idx
                sel.append(idx)
                matched.append(val_idx)
                running += powers[val_idx]
                if running > needed:
                    break
            rows = [commit.signatures[i] for i in sel]
            sp.set(n_sigs=len(commit.signatures), selected=len(rows))
        # assemble all selected sign-bytes in one (native) call, same as
        # batch_verify_commits
        with _trace.span("commit.sign_bytes", n=len(rows)) as sp:
            msgs, path = commit.sign_bytes_of(chain_id, rows)
            sp.set(path=path)
        with _trace.span("commit.add", n=len(rows), bulk=1):
            bv.add_many([pubs[i] for i in matched], msgs,
                        [cs.signature for cs in rows])
        with _trace.span("commit.verify", n=len(rows)):
            _, oks = bv.verify()
        with _trace.span("commit.tally", n=len(rows)):
            if not all(oks):
                # the walk stopped at the row that crossed `needed`, so a
                # row-by-row tally would meet the first bad row before it
                # could accept
                raise ValueError(f"wrong signature (#{sel[_first_false(oks)]})")
            if running > needed:
                return len(rows)
            if double_vote is not None:
                raise ValueError(
                    "double vote from validator %d (%d and %d)" % double_vote
                )
            raise ErrNotEnoughVotingPowerSigned(
                f"insufficient voting power: got {running}, needed >{needed}",
                running, needed, len(rows),
            )

    def _check_commit_basics(self, chain_id: str, block_id: BlockID, height: int, commit) -> None:
        if commit is None:
            raise ValueError("nil commit")
        if self.size() != len(commit.signatures):
            raise ValueError(
                f"invalid commit: {self.size()} vals, {len(commit.signatures)} sigs"
            )
        if height != commit.height:
            raise ValueError(f"invalid commit height: want {height}, got {commit.height}")
        if block_id != commit.block_id:
            raise ValueError("invalid commit: wrong block ID")

    def validate_basic(self) -> None:
        if self.is_nil_or_empty():
            raise ValueError("validator set is empty")
        for v in self.validators:
            v.validate_basic()
        addrs = {v.address for v in self.validators}
        if len(addrs) != len(self.validators):
            raise ValueError("duplicate validator address")

    # -- wire (persistence / light blocks) ----------------------------
    def encode(self) -> bytes:
        """validator.proto ValidatorSet{validators=1, proposer=2,
        total_voting_power=3}.  Memoized like hash(), but invalidated by
        EVERY mutator (rotation, updates, proposer resolution — the wire
        form covers priorities): a state save encodes up to three
        thousand-slot sets per height, several times each."""
        if self._enc is not None:
            return self._enc
        w = ProtoWriter()
        for v in self.validators:
            w.message(1, v.encode(), always=True)
        if self.proposer is not None:
            w.message(2, self.proposer.encode())
        w.varint(3, self._total_voting_power)
        enc = w.bytes_out()
        self._enc = enc
        return enc

    @classmethod
    def decode(cls, data: bytes) -> "ValidatorSet":
        from tendermint_tpu.wire.proto import fields_to_dict

        f = fields_to_dict(data)
        vals = [Validator.decode(b) for b in f.get(1, [])]
        vs = cls.__new__(cls)
        vs.validators = vals
        vs._total_voting_power = 0
        vs._update_total_voting_power()
        vs._reindex()
        prop = f.get(2, [None])[0]
        vs.proposer = Validator.decode(prop) if prop else None
        return vs


# ---------------------------------------------------------------------------
# Cross-commit batching — the fast-sync / light-client pipeline surface
# ---------------------------------------------------------------------------


@dataclass
class CommitVerifyJob:
    """One commit to verify as part of a multi-commit device batch.

    mode='full'  → VerifyCommit semantics (every non-absent signature must
                   be valid; ForBlock power > 2/3)          (reference :662)
    mode='light' → VerifyCommitLight semantics (ForBlock signatures in
                   order until cumulative power > 2/3; later signatures
                   never consulted)                         (reference :720)
    """

    val_set: "ValidatorSet"
    chain_id: str
    block_id: BlockID
    height: int
    commit: object
    mode: str = "full"  # 'full' | 'light'


def commit_job_outcomes(jobs: list[CommitVerifyJob]) -> list[Exception | None]:
    """Verify many commits as ONE batched device call and answer for
    EVERY job: `None` where the job's commit verifies, else the exception
    the job would have raised alone — `ValueError("wrong signature (#row)
    in commit for height h")`, `ErrNotEnoughVotingPowerSigned`, or what
    `_check_commit_basics` raises (such a job adds no row to the batch and
    costs the others nothing).  In job order.

    The TPU-native redesign of the reference's per-block sequential
    verify loops (blockchain/v0/reactor.go:517 fast sync,
    light/verifier.go:81,141): a whole pipeline window of block commits
    — thousands of signatures — is shipped to the device as a single
    XLA program invocation instead of one host call per commit.
    Accept/reject semantics per commit are identical to calling
    verify_commit / verify_commit_light individually.

    Submits through the async verification service (crypto.async_verify)
    by default, so a blocksync window, a light-client range, and a
    consensus VerifyCommit arriving concurrently coalesce into one
    device dispatch, and replayed commits resolve from the
    verified-signature cache.

    `batch_verify_commits` raises the first of these; the gateway's
    coalescer (gateway/coalescer.py) hands each client its own.
    """
    from tendermint_tpu.crypto.async_verify import new_service_batch_verifier

    bv = new_service_batch_verifier()
    outcomes: list[Exception | None] = [None] * len(jobs)
    plans = []  # (job's index, start in the batch, row count, sel or None = every row, power, needed)
    n = 0
    # spans (utils/trace): one per phase per job, never inside a per-row
    # loop — where a call's host time goes around the service's own
    # verify.* spans (docs/observability.md).  Between a commit's
    # signatures and the service's queue the rows travel as three columns
    # built by one bulk pass each: nothing is called per row.
    for j, job in enumerate(jobs):
        vs, commit = job.val_set, job.commit
        with _trace.span("commit.select", mode=job.mode) as sp:
            try:
                vs._check_commit_basics(job.chain_id, job.block_id, job.height, commit)
            except ValueError as err:
                outcomes[j] = err
                continue
            needed = vs.total_voting_power() * 2 // 3
            pubs, powers = vs.verify_columns()
            sigs = commit.signatures
            sel, power = _select_rows(sigs, powers, needed, job.mode == "light")
            rows = sigs if sel is None else [sigs[i] for i in sel]
            sp.set(n_sigs=len(sigs), selected=len(rows))
        # all sign-bytes of a job in one native call (the per-row Python
        # path is ~4 µs — 40 ms on a 10k commit, 20x the BASELINE
        # end-to-end budget)
        with _trace.span("commit.sign_bytes", n=len(rows)) as sp:
            msgs, path = commit.sign_bytes_of(job.chain_id, rows)
            sp.set(path=path)
        with _trace.span("commit.add", n=len(rows), bulk=1):
            bv.add_many(pubs if sel is None else [pubs[i] for i in sel],
                        msgs, [cs.signature for cs in rows])
        plans.append((j, n, len(rows), sel, power, needed))
        n += len(rows)
    with _trace.span("commit.verify", n=n):
        _, oks = bv.verify() if n else (True, [])
    for j, start, k, sel, power, needed in plans:
        with _trace.span("commit.tally", n=k):
            mine = oks[start:start + k]
            if not all(mine):
                bad = _first_false(mine)
                outcomes[j] = ValueError(
                    f"wrong signature (#{bad if sel is None else sel[bad]}) "
                    f"in commit for height {jobs[j].height}"
                )
            elif power <= needed:
                outcomes[j] = ErrNotEnoughVotingPowerSigned(
                    f"insufficient voting power for height {jobs[j].height}: "
                    f"got {power}, needed >{needed}",
                    power, needed, k,
                )
    return outcomes


def batch_verify_commits(jobs: list[CommitVerifyJob]) -> None:
    """`commit_job_outcomes` with the raise-only contract blocksync, the
    light verifier and state validation use: one batched device call,
    then the first failing job's exception (a ValueError naming its
    height), in job order."""
    for err in commit_job_outcomes(jobs):
        if err is not None:
            raise err


def _first_false(oks) -> int:
    """The first row, in order, that a verify path refused."""
    return next(i for i, ok in enumerate(oks) if not ok)


def _select_rows(sigs, powers, needed: int, light: bool):
    """Which rows of a commit a check consults, and the ForBlock power
    they carry if every one of them verifies: `(sel, power)`, `sel` the
    row indices in order or None for every row.  The flags are read by
    one comprehension and counted in bulk; nothing is called per row.

    full:  every non-absent row; power = the ForBlock rows'.
    light: the ForBlock rows in order until the running power EXCEEDS
           `needed` — rows past that cut are not selected, so a bad
           signature there is never seen (reference :720-766); power =
           the running power at the cut (all of them when it is never
           reached)."""
    for_block = BlockIDFlag.COMMIT
    flags = [cs.block_id_flag for cs in sigs]
    n_for_block = flags.count(for_block)
    if light:
        if n_for_block == len(flags):
            sel, mine = None, powers
        else:
            sel = [i for i, f in enumerate(flags) if f == for_block]
            mine = [powers[i] for i in sel]
        running = 0
        for k, p in enumerate(mine, 1):
            running += p
            if running > needed:
                if k < len(flags):
                    sel = list(range(k)) if sel is None else sel[:k]
                break
        return sel, running
    if n_for_block == len(flags):
        return None, sum(powers)
    absent = BlockIDFlag.ABSENT
    sel = ([i for i, f in enumerate(flags) if f != absent]
           if absent in flags else None)
    return sel, sum(compress(powers, [f == for_block for f in flags]))

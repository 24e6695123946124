"""Data from --seed for the entries whose call takes ONE commit of ONE
validator set (chipbench/entries/verify_commit*.py), and the helpers any
entry builds its own data from: keys, a commit, a corrupted row, the
pool-size rule.  The same seed gives the same bytes; every seed gives the
same SIZES (validators, pool length, special rows) at other positions.

The program's own types (`ValidatorSet`, `Commit`, `CommitSig`) are built
here because they are what its entry points take; keys, sign-bytes and
signatures are made with `cryptography` (OpenSSL) and the reference's
encoder, not with the program's.

Special rows — what lets a run tell a right verifier from a wrong one:
  * `small_order` validators: their public key is an encoding of an
    8-torsion point and their "signature" is (torsion point, s = 0),
    which the cofactored ZIP-215 equation accepts for any message and a
    strict RFC 8032 verifier refuses.  They sit in EVERY commit.
  * `bad_commits`: a few commits of the pool carry one corrupted row each
    (a flipped signature bit, or a timestamp changed after signing) at a
    position drawn from a stated range of the commit; the call must
    refuse them naming that row, or — where the row lies beyond the
    light cut-off — accept, never having consulted it.
"""

from __future__ import annotations

import hashlib
import math
import random
from dataclasses import dataclass, field

from cryptography.hazmat.primitives import serialization as _ser
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference.commit_rules import consulted_rows
from chipbench.reference.signbytes import PrecommitTemplate

CHAIN_ID = "chipbench"
T0_NS = 1_700_000_000 * 10**9
POWER = 10
WARMUP_HEIGHT = 1_000_000  # warm-up commits: heights no pool commit has


def pool_size(cache_capacity: int, consulted: int, *, min_commits: int,
              cache_factor: float) -> int:
    """Commits a cyclic walk needs so that it never meets the verified-
    signature LRU: at least `min_commits`, and at least `cache_factor`
    times the cache's capacity in consulted signatures."""
    return max(min_commits, math.ceil(cache_factor * cache_capacity / consulted))


@dataclass
class PoolCommit:
    """One commit, and as an item of a pool what one call takes: it knows
    the rows that call consults (`n_rows`, `row(i)`, `suspects`)."""

    height: int
    block_id: object            # the program's BlockID
    commit: object              # the program's Commit
    template: PrecommitTemplate
    timestamps: list[int]       # as carried by the commit, per row
    signatures: list[bytes]     # as carried by the commit, per row
    pubs: list[bytes]           # the validator set's keys, in its order (shared)
    n_rows: int                 # leading rows a call on this commit consults
    suspects: dict[int, str] = field(default_factory=dict)  # row -> kind

    def row(self, i: int) -> tuple[bytes, bytes, bytes]:
        """(pub, message, signature) of row i as the commit claims them."""
        return self.pubs[i], self.template.sign_bytes(self.timestamps[i]), self.signatures[i]


@dataclass
class CellData:
    mode: str                   # "full" | "light"
    vset: object                # the program's ValidatorSet
    pubs: list[bytes]           # in the set's order
    powers: list[int]
    consulted: int
    pool: list[PoolCommit]
    warmup: list[PoolCommit]


@dataclass
class Signers:
    """A validator set with what signs for it."""

    vset: object                # the program's ValidatorSet
    pubs: list[bytes]           # in the set's order
    keys: list                  # [(address, signing key | None)], None: a small-order key
    small_order: dict[int, bytes]   # row -> small-order key
    encs: list[bytes]           # the small-order encodings their "signatures" are drawn from


def priv(seed: int, name: str, i: int) -> Ed25519PrivateKey:
    return Ed25519PrivateKey.from_private_bytes(
        hashlib.sha256(b"%d|%s|val|%d" % (seed, name.encode(), i)).digest())


def build_commit(seed: int, name: str, rng: random.Random, height: int,
                 who: Signers, n_rows: int) -> PoolCommit:
    """A complete commit of `who`'s set at `height`, of which a call
    consults the leading `n_rows` rows."""
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig

    tag = b"%d|%s|block|%d" % (seed, name.encode(), height)
    block_hash = hashlib.sha256(tag).digest()
    parts_hash = hashlib.sha256(tag + b"|parts").digest()
    block_id = BlockID(hash=block_hash,
                       part_set_header=PartSetHeader(total=1, hash=parts_hash))
    tpl = PrecommitTemplate(CHAIN_ID, height, 0, block_hash, 1, parts_hash)
    base = T0_NS + height * 10**9
    timestamps, signatures = [], []
    for i, (addr, key) in enumerate(who.keys):
        ts = base + i + 1  # every validator signs its own timestamp
        timestamps.append(ts)
        if key is None:
            signatures.append(rng.choice(who.encs) + bytes(32))
        else:
            signatures.append(key.sign(tpl.sign_bytes(ts)))
    suspects = {i: "small_order" for i in who.small_order}
    commit = Commit(height=height, round=0, block_id=block_id, signatures=[
        CommitSig(block_id_flag=BlockIDFlag.COMMIT, validator_address=addr,
                  timestamp_ns=ts, signature=sig)
        for (addr, _), ts, sig in zip(who.keys, timestamps, signatures)])
    return PoolCommit(height, block_id, commit, tpl, timestamps, signatures,
                      who.pubs, n_rows, suspects)


def corrupt(pc: PoolCommit, row: int, kind: str) -> None:
    cs = pc.commit.signatures[row]
    if kind == "sig_bit":
        sig = pc.signatures[row]
        sig = sig[:-1] + bytes([sig[-1] ^ 1])
        pc.signatures[row] = cs.signature = sig
    elif kind == "timestamp":
        pc.timestamps[row] = cs.timestamp_ns = pc.timestamps[row] + 7
    else:
        raise ValueError(f"unknown corruption {kind!r}")
    pc.suspects[row] = kind


def validator_set(seed: int, name: str, rng: random.Random, n: int, n_edge: int) -> Signers:
    """A set of `n` equal-power validators of which `n_edge` hold a
    small-order key."""
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.types.validator import Validator, ValidatorSet

    encs = ref.small_order_encodings()
    entries = [(pub, None) for pub in rng.sample(encs, n_edge)]
    for i in range(n - n_edge):
        key = priv(seed, name, i)
        entries.append((key.public_key().public_bytes(
            _ser.Encoding.Raw, _ser.PublicFormat.Raw), key))
    # the set's own order: equal power, so by address = SHA-256(pub)[:20]
    entries.sort(key=lambda e: hashlib.sha256(e[0]).digest()[:20])
    pubs = [pub for pub, _ in entries]
    vset = ValidatorSet([Validator(pub_key=PubKey(pub), voting_power=POWER)
                         for pub in pubs])
    if [v.pub_key.bytes_() for v in vset.validators] != pubs:
        raise RuntimeError("the validator set's order is not the harness's")
    return Signers(vset, pubs,
                   [(v.address, key) for v, (_, key) in zip(vset.validators, entries)],
                   {i: pub for i, (pub, key) in enumerate(entries) if key is None}, encs)


def build(seed: int, name: str, sizes: dict, adversarial: dict, mode: str,
          cache_capacity: int, pool_rule: dict, warmup_commits: int) -> CellData:
    """`name`: the configuration's, so that two configurations never share
    a key or a block under one seed; `sizes`: {"validators": n}; `adversarial`: {"small_order_validators":
    e, "bad_commits": [[lo, hi, kind], ...]} with lo/hi as shares of the
    commit's rows; `pool_rule`: {"min_commits", "cache_factor"}."""
    rng = random.Random(seed)
    n = sizes["validators"]
    who = validator_set(seed, name, rng, n, adversarial["small_order_validators"])
    powers = [POWER] * n
    consulted = consulted_rows(mode, powers)

    count = pool_size(cache_capacity, consulted, **pool_rule)
    pool = [build_commit(seed, name, rng, h, who, consulted) for h in range(1, count + 1)]
    bad = adversarial["bad_commits"]
    for pc, (lo, hi, kind) in zip(rng.sample(pool, len(bad)), bad):
        honest = [i for i in range(int(lo * n), max(int(lo * n) + 1, int(hi * n)))
                  if i not in who.small_order]
        corrupt(pc, rng.choice(honest), kind)
    warmup = [build_commit(seed, name, rng, WARMUP_HEIGHT + j, who, consulted)
              for j in range(warmup_commits)]
    return CellData(mode, who.vset, who.pubs, powers, consulted, pool, warmup)

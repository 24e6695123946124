"""Light client suite: verifier rules, batched range verification,
sequential + skipping client modes, backwards verify, divergence
detection.  Scenario model: reference light/verifier_test.go and
light/client_test.go."""

from __future__ import annotations

from fractions import Fraction

import pytest

from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.light import (
    Client,
    ErrInvalidHeader,
    ErrLightClientAttack,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
    LightBlockStore,
    MemoryProvider,
    SEQUENTIAL,
    SKIPPING,
    TrustOptions,
    verify_adjacent,
    verify_adjacent_range,
    verify_non_adjacent,
)
from tendermint_tpu.light.errors import LightClientError
from tendermint_tpu.types.basic import BlockID, PartSetHeader
from tendermint_tpu.types.block import Header
from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.light import LightBlock, SignedHeader
from tendermint_tpu.types.validator import Validator, ValidatorSet
from tendermint_tpu.types.vote import SignedMsgType, vote_sign_bytes_raw

CHAIN_ID = "light-chain"
T0 = 1_700_000_000 * 10**9
SEC = 10**9
PERIOD = 3600 * SEC
DRIFT = 10 * SEC


def _keys(seeds):
    return [priv_key_from_seed(bytes([s]) * 32) for s in seeds]


def _valset(keys, power=10):
    return ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=power) for k in keys])


class LightChain:
    """Synthetic signed-header chain with controllable validator rotation
    and forking — the light-client equivalent of the reference's
    genLightBlocksWithKeys (light/helpers_test.go)."""

    def __init__(self, keys=None, chain_id=CHAIN_ID):
        self.chain_id = chain_id
        self.keys = keys if keys is not None else _keys([1, 2, 3, 4])
        self.blocks: dict[int, LightBlock] = {}
        self.last_block_id = BlockID()

    def height(self):
        return max(self.blocks) if self.blocks else 0

    def extend(self, n=1, next_keys=None, app_hash=b"\x01" * 32):
        """Append n blocks; if next_keys is given, the set rotates to it
        effective at the NEXT height (as validator updates do)."""
        for _ in range(n):
            h = self.height() + 1
            cur = _valset(self.keys)
            nxt_keys = next_keys if next_keys is not None else self.keys
            nxt = _valset(nxt_keys)
            header = Header(
                chain_id=self.chain_id,
                height=h,
                time_ns=T0 + h * SEC,
                last_block_id=self.last_block_id,
                validators_hash=cur.hash(),
                next_validators_hash=nxt.hash(),
                consensus_hash=b"\x02" * 32,
                app_hash=app_hash,
                proposer_address=cur.get_proposer().address,
            )
            block_id = BlockID(
                hash=header.hash(),
                part_set_header=PartSetHeader(total=1, hash=b"\x03" * 32),
            )
            sigs = []
            key_by_addr = {k.pub_key().address(): k for k in self.keys}
            for v in cur.validators:
                sb = vote_sign_bytes_raw(
                    self.chain_id, SignedMsgType.PRECOMMIT, h, 0, block_id,
                    T0 + h * SEC + SEC // 2,
                )
                sigs.append(
                    CommitSig(
                        block_id_flag=BlockIDFlag.COMMIT,
                        validator_address=v.address,
                        timestamp_ns=T0 + h * SEC + SEC // 2,
                        signature=key_by_addr[v.address].sign(sb),
                    )
                )
            commit = Commit(height=h, round=0, block_id=block_id, signatures=sigs)
            self.blocks[h] = LightBlock(
                signed_header=SignedHeader(header=header, commit=commit),
                validator_set=cur,
            )
            self.last_block_id = block_id
            self.keys = nxt_keys
        return self

    def fork(self):
        """A copy sharing all existing blocks (divergence point = now)."""
        other = LightChain(keys=list(self.keys), chain_id=self.chain_id)
        other.blocks = dict(self.blocks)
        other.last_block_id = self.last_block_id
        return other

    def provider(self):
        return MemoryProvider(self.chain_id, dict(self.blocks))


@pytest.fixture
def chain():
    return LightChain().extend(12)


def now_at(h):
    return T0 + h * SEC + 5 * SEC


# -- types ---------------------------------------------------------------


def test_light_block_roundtrip_and_validate(chain):
    lb = chain.blocks[3]
    lb.validate_basic(CHAIN_ID)
    rt = LightBlock.decode(lb.encode())
    assert rt.height == 3
    assert rt.hash() == lb.hash()
    assert rt.validator_set.hash() == lb.validator_set.hash()
    rt.validate_basic(CHAIN_ID)
    with pytest.raises(ValueError, match="another chain"):
        lb.validate_basic("other-chain")


def test_signed_header_commit_mismatch(chain):
    lb2, lb3 = chain.blocks[2], chain.blocks[3]
    bad = SignedHeader(header=lb2.header, commit=lb3.commit)
    with pytest.raises(ValueError):
        bad.validate_basic(CHAIN_ID)


# -- verifier ------------------------------------------------------------


def test_verify_adjacent_ok(chain):
    verify_adjacent(
        chain.blocks[1].signed_header,
        chain.blocks[2].signed_header,
        chain.blocks[2].validator_set,
        PERIOD, now_at(2), DRIFT,
    )


def test_verify_adjacent_rejects_gap(chain):
    with pytest.raises(ValueError, match="adjacent"):
        verify_adjacent(
            chain.blocks[1].signed_header,
            chain.blocks[3].signed_header,
            chain.blocks[3].validator_set,
            PERIOD, now_at(3), DRIFT,
        )


def test_verify_adjacent_expired_trusted(chain):
    with pytest.raises(ErrOldHeaderExpired):
        verify_adjacent(
            chain.blocks[1].signed_header,
            chain.blocks[2].signed_header,
            chain.blocks[2].validator_set,
            3 * SEC,  # trusting period shorter than the gap to `now`
            now_at(9), DRIFT,
        )


def test_verify_adjacent_next_vals_mismatch():
    a = LightChain().extend(1)
    # rotate the set at height 2 without announcing it in header 1
    a.keys = _keys([7, 8, 9, 10])
    a.extend(1)
    with pytest.raises(ErrInvalidHeader, match="next validators"):
        verify_adjacent(
            a.blocks[1].signed_header,
            a.blocks[2].signed_header,
            a.blocks[2].validator_set,
            PERIOD, now_at(2), DRIFT,
        )


def test_verify_non_adjacent_ok(chain):
    verify_non_adjacent(
        chain.blocks[1].signed_header,
        chain.blocks[1].validator_set,
        chain.blocks[9].signed_header,
        chain.blocks[9].validator_set,
        PERIOD, now_at(9), DRIFT,
    )


def test_verify_non_adjacent_valset_cant_be_trusted():
    c = LightChain().extend(3)
    c.extend(1, next_keys=_keys([21, 22, 23, 24]))  # announce full rotation
    c.extend(5)  # new set signs from height 5
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(
            c.blocks[1].signed_header,
            c.blocks[1].validator_set,
            c.blocks[8].signed_header,
            c.blocks[8].validator_set,
            PERIOD, now_at(8), DRIFT,
        )


def test_verify_non_adjacent_future_time(chain):
    with pytest.raises(ErrInvalidHeader, match="future"):
        verify_non_adjacent(
            chain.blocks[1].signed_header,
            chain.blocks[1].validator_set,
            chain.blocks[9].signed_header,
            chain.blocks[9].validator_set,
            PERIOD, now_at(9) - 20 * SEC, DRIFT,
        )


def test_verify_adjacent_range_batched(chain):
    blocks = [chain.blocks[h] for h in range(2, 11)]
    verify_adjacent_range(chain.blocks[1], blocks, PERIOD, now_at(10), DRIFT)


def test_verify_adjacent_range_detects_bad_signature(chain):
    blocks = [chain.blocks[h] for h in range(2, 11)]
    victim = blocks[4]
    sigs = [
        CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp_ns,
                  b"\x05" * 64 if cs.for_block() else cs.signature)
        for cs in victim.commit.signatures
    ]
    bad_commit = Commit(
        height=victim.commit.height, round=victim.commit.round,
        block_id=victim.commit.block_id, signatures=sigs,
    )
    blocks[4] = LightBlock(
        signed_header=SignedHeader(header=victim.header, commit=bad_commit),
        validator_set=victim.validator_set,
    )
    with pytest.raises(ErrInvalidHeader):
        verify_adjacent_range(chain.blocks[1], blocks, PERIOD, now_at(10), DRIFT)


# -- client --------------------------------------------------------------


def _client(chain, mode=SKIPPING, witnesses=(), height=1, store=None, now=None):
    return Client(
        CHAIN_ID,
        TrustOptions(period_ns=PERIOD, height=height, hash=chain.blocks[height].hash()),
        chain.provider(),
        list(witnesses),
        trusted_store=store,
        mode=mode,
        now_fn=(lambda: now) if now else (lambda: now_at(chain.height())),
    )


def test_client_sequential_verifies_to_head(chain):
    c = _client(chain, mode=SEQUENTIAL)
    lb = c.verify_light_block_at_height(12, now_at(12))
    assert lb.hash() == chain.blocks[12].hash()
    assert c.last_trusted_height() == 12
    # intermediates were stored by the batched range path
    assert c.trusted_light_block(7) is not None


def test_client_skipping_verifies_to_head(chain):
    c = _client(chain, mode=SKIPPING)
    lb = c.verify_light_block_at_height(12, now_at(12))
    assert lb.hash() == chain.blocks[12].hash()


def test_client_skipping_bisects_through_rotation():
    c = LightChain().extend(3)
    c.extend(1, next_keys=_keys([21, 22, 23, 24]))
    c.extend(8)
    cl = _client(c, mode=SKIPPING)
    lb = cl.verify_light_block_at_height(12, now_at(12))
    assert lb.hash() == c.blocks[12].hash()


def test_client_start_is_one_span_a_client(chain):
    """`light.client_start`: once a client, the root of trust fetched
    (no trusted block decoded), or read back from a store that holds it
    (decoded twice: the latest block, then the one at the option's
    height)."""
    from tendermint_tpu.utils import trace

    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    try:
        store = LightBlockStore()
        _client(chain, height=2, store=store)
        _client(chain, height=2, store=store)
    finally:
        trace.set_enabled(was)
    spans = trace.spans()
    trace.clear()
    starts = [s for s in spans if s["name"] == "light.client_start"]
    assert [s["attrs"] for s in starts] == [{"height": 2, "decodes": 0},
                                           {"height": 2, "decodes": 2}]
    fetch = [s for s in spans if s["name"] == "light.fetch"]
    assert len(fetch) == 1 and fetch[0]["parent"] == starts[0]["id"]
    assert all(s["parent"] is None for s in starts)


def test_client_init_bad_hash(chain):
    with pytest.raises(LightClientError, match="hash"):
        Client(
            CHAIN_ID,
            TrustOptions(period_ns=PERIOD, height=1, hash=b"\x09" * 32),
            chain.provider(),
            [],
        )


def test_client_backwards_verification(chain):
    c = _client(chain, height=10)
    lb = c.verify_light_block_at_height(4, now_at(12))
    assert lb.hash() == chain.blocks[4].hash()


def test_client_trust_level_validation(chain):
    with pytest.raises(ValueError, match="trustLevel"):
        Client(
            CHAIN_ID,
            TrustOptions(period_ns=PERIOD, height=1, hash=chain.blocks[1].hash()),
            chain.provider(),
            [],
            trust_level=Fraction(1, 4),
        )


def test_client_pruning(chain):
    store = LightBlockStore()
    c = Client(
        CHAIN_ID,
        TrustOptions(period_ns=PERIOD, height=1, hash=chain.blocks[1].hash()),
        chain.provider(),
        [],
        trusted_store=store,
        mode=SEQUENTIAL,
        pruning_size=5,
        now_fn=lambda: now_at(12),
    )
    c.verify_light_block_at_height(12, now_at(12))
    assert store.size() <= 5


def test_client_witness_agreement_ok(chain):
    w = chain.provider()
    c = _client(chain, witnesses=[w])
    c.verify_light_block_at_height(12, now_at(12))


def test_client_detects_forked_witness(chain):
    evil = chain.fork()
    evil.blocks = {h: lb for h, lb in evil.blocks.items() if h <= 6}
    evil.last_block_id = evil.blocks[6].commit.block_id
    evil.extend(6, app_hash=b"\x66" * 32)  # same signers, different app hash
    w = evil.provider()
    c = _client(chain, witnesses=[w])
    with pytest.raises(ErrLightClientAttack):
        c.verify_light_block_at_height(12, now_at(12))
    # evidence was reported to the witness (against the primary's block)
    assert w.evidence, "witness should have received attack evidence"
    ev = w.evidence[0]
    assert ev.common_height <= 6


def test_client_promotes_witness_when_primary_dies(chain):
    dead = MemoryProvider(CHAIN_ID, {1: chain.blocks[1]})
    dead.fail = False
    c = Client(
        CHAIN_ID,
        TrustOptions(period_ns=PERIOD, height=1, hash=chain.blocks[1].hash()),
        dead,
        [chain.provider()],
        now_fn=lambda: now_at(12),
    )
    dead.fail = True
    lb = c.verify_light_block_at_height(12, now_at(12))
    assert lb.hash() == chain.blocks[12].hash()


def test_store_prune_and_lookup(chain):
    s = LightBlockStore()
    for h in (3, 5, 7, 9):
        s.save_light_block(chain.blocks[h])
    assert s.size() == 4
    assert s.first_light_block().height == 3
    assert s.latest_light_block().height == 9
    assert s.light_block_before(7).height == 5
    s.prune(2)
    assert s.size() == 2
    assert s.first_light_block().height == 7


def test_client_store_clean_after_detected_attack(chain):
    """A detected divergence must leave NO forged blocks in the trusted
    store — otherwise the next call would serve the attacker's header
    from cache without any witness cross-check."""
    evil = chain.fork()
    evil.blocks = {h: lb for h, lb in evil.blocks.items() if h <= 6}
    evil.last_block_id = evil.blocks[6].commit.block_id
    evil.extend(6, app_hash=b"\x66" * 32)
    store = LightBlockStore()
    c = Client(
        CHAIN_ID,
        TrustOptions(period_ns=PERIOD, height=1, hash=chain.blocks[1].hash()),
        evil.provider(),  # primary is the attacker
        [chain.provider()],
        trusted_store=store,
        mode=SKIPPING,
        now_fn=lambda: now_at(12),
    )
    with pytest.raises(ErrLightClientAttack):
        c.verify_light_block_at_height(12, now_at(12))
    for h in range(7, 13):
        stored = store.light_block(h)
        assert stored is None or stored.hash() == chain.blocks[h].hash(), (
            f"forged block at height {h} persisted to trusted store"
        )
    assert c.last_trusted_height() == 1


def test_backwards_returns_requested_height_with_lower_trusted_blocks(chain):
    """Regression: _backwards must anchor on the closest trusted block
    ABOVE the target.  With blocks both below and above the target in the
    store (root of trust at 1, verified head at 12), asking for an
    unstored intermediate height must return THAT height, hash-verified —
    not the nearest lower stored block."""
    c = _client(chain, mode=SKIPPING, height=1)
    c.verify_light_block_at_height(12, now_at(12))  # store now holds 1, pivots, 12
    lb = c.verify_light_block_at_height(4, now_at(12))
    assert lb.height == 4
    assert lb.hash() == chain.blocks[4].hash()


def test_detector_reports_forged_block_to_honest_chain(chain):
    """Regression: the witness must receive evidence packaging the
    PRIMARY's conflicting header, and the primary the witness's
    (detector.go:120-147) — not their own blocks back."""
    evil = chain.fork()
    evil.blocks = {h: lb for h, lb in evil.blocks.items() if h <= 6}
    evil.last_block_id = evil.blocks[6].commit.block_id
    evil.extend(6, app_hash=b"\x66" * 32)
    w = evil.provider()
    primary = chain.provider()
    c = Client(
        CHAIN_ID,
        TrustOptions(period_ns=PERIOD, height=1, hash=chain.blocks[1].hash()),
        primary,
        [w],
        now_fn=lambda: now_at(12),
    )
    with pytest.raises(ErrLightClientAttack):
        c.verify_light_block_at_height(12, now_at(12))
    assert w.evidence and primary.evidence
    # witness got the primary's block as the conflict proof
    assert w.evidence[0].conflicting_header_hash == chain.blocks[12].hash()
    # primary got the witness's forged block
    assert primary.evidence[0].conflicting_header_hash == evil.blocks[12].hash()


def test_promoted_primary_is_dropped_from_rotation(chain):
    """Regression: a replaced primary must leave the provider pool —
    re-adding it lets two bad providers swap places forever."""
    dead = MemoryProvider(CHAIN_ID, {1: chain.blocks[1]})
    witness = chain.provider()
    c = Client(
        CHAIN_ID,
        TrustOptions(period_ns=PERIOD, height=1, hash=chain.blocks[1].hash()),
        dead,
        [witness],
        now_fn=lambda: now_at(12),
    )
    dead.fail = True
    c.verify_light_block_at_height(12, now_at(12))
    assert c.primary is witness
    assert dead not in c.witnesses


# -- restore from trusted store (reference TestClientRestoresTrustedHeader
# AfterStartup1/2/3 + TestClient_NewClientFromTrustedStore + TestClient_Update)


def test_client_restores_trusted_state_from_store(chain):
    """A restarted client with a populated trusted store resumes from it
    without re-fetching the root of trust."""
    store = LightBlockStore()
    c1 = _client(chain, store=store)
    c1.verify_light_block_at_height(8, now_at(8))
    assert store.latest_light_block().height == 8

    # restart: same store, same trust options — must adopt stored state
    c2 = _client(chain, store=store)
    assert c2.last_trusted_height() == 8
    lb = c2.verify_light_block_at_height(12, now_at(12))
    assert lb.hash() == chain.blocks[12].hash()


def test_client_rejects_store_conflicting_with_trust_options(chain):
    """Startup must fail loudly when the stored header at the trust
    height disagrees with the user-pinned hash (poisoned store)."""
    store = LightBlockStore()
    c1 = _client(chain, store=store)
    c1.verify_light_block_at_height(5, now_at(5))

    other = LightChain(keys=_keys([31, 32, 33, 34])).extend(2)  # different chain
    with pytest.raises(LightClientError, match="purge"):
        Client(
            CHAIN_ID,
            TrustOptions(period_ns=PERIOD, height=1, hash=other.blocks[1].hash()),
            chain.provider(),
            [],
            trusted_store=store,
            now_fn=lambda: now_at(chain.height()),
        )


def test_client_from_store_with_options_height_not_stored(chain):
    """Trust options pinned at a height the store never saved: existing
    trusted state wins (reference NewClientFromTrustedStore semantics —
    no conflict means proceed)."""
    store = LightBlockStore()
    c1 = _client(chain, store=store)
    c1.verify_light_block_at_height(6, now_at(6))
    store.delete_light_block(1)  # the options height is gone

    c2 = _client(chain, store=store)
    assert c2.last_trusted_height() == 6


def test_client_update_advances_to_primary_head(chain):
    """update() fetches the primary's latest header and verifies up to it
    (reference TestClient_Update); a second update with no new header
    returns None."""
    c = _client(chain)
    lb = c.update(now_at(chain.height()))
    assert lb is not None and lb.height == chain.height()
    assert c.last_trusted_height() == chain.height()
    assert c.update(now_at(chain.height())) is None


# -- attack classification (reference types/evidence.go:233-279
# GetByzantineValidators: lunatic / equivocation / amnesia) ---------------


def _attack_evidence(chain, conflicting_lb, common_h=1):
    from tendermint_tpu.types.evidence import LightClientAttackEvidence

    common = chain.blocks[common_h]
    return LightClientAttackEvidence(
        conflicting_block_bytes=conflicting_lb.encode(),
        common_height=common.height,
        total_voting_power=common.validator_set.total_voting_power(),
        timestamp_ns=common.time_ns,
        conflicting_header_hash=conflicting_lb.hash(),
    )


def test_byzantine_validators_lunatic(chain):
    """A conflicting header with a forged app hash is a lunatic attack:
    byzantine = common-set validators who signed the conflicting commit."""
    fork = chain.fork()
    del fork.blocks[6]
    for h in (7, 8, 9, 10, 11, 12):
        del fork.blocks[h]
    fork.last_block_id = chain.blocks[5].signed_header.commit.block_id
    fork.extend(1, app_hash=b"\xEE" * 32)  # invalid state transition at 6
    evil = fork.blocks[6]

    ev = _attack_evidence(chain, evil, common_h=5)
    trusted = chain.blocks[6].signed_header
    assert ev.conflicting_header_is_invalid(trusted.header)
    byz = ev.get_byzantine_validators(chain.blocks[5].validator_set, trusted)
    signers = {cs.validator_address for cs in evil.commit.signatures
               if cs.for_block()}
    assert byz and {v.address for v in byz} <= signers


def test_byzantine_validators_equivocation(chain):
    """Same height, same round, valid header fields, different block:
    equivocation — byzantine = validators who signed BOTH commits."""
    real = chain.blocks[6]
    # forge a sibling block at height 6 with identical deterministic
    # fields but a different data hash → different block hash
    from tendermint_tpu.types.block import Header

    h6 = real.header
    evil_header = Header(
        chain_id=h6.chain_id, height=h6.height, time_ns=h6.time_ns,
        last_block_id=h6.last_block_id, validators_hash=h6.validators_hash,
        next_validators_hash=h6.next_validators_hash,
        consensus_hash=h6.consensus_hash, app_hash=h6.app_hash,
        last_results_hash=h6.last_results_hash,
        data_hash=b"\x77" * 32,
        proposer_address=h6.proposer_address,
    )
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
    from tendermint_tpu.types.light import LightBlock, SignedHeader
    from tendermint_tpu.types.vote import SignedMsgType, vote_sign_bytes_raw

    bid = BlockID(hash=evil_header.hash(),
                  part_set_header=PartSetHeader(total=1, hash=b"\x03" * 32))
    key_by_addr = {k.pub_key().address(): k for k in chain.keys}
    sigs = []
    for v in real.validator_set.validators:
        sb = vote_sign_bytes_raw(chain.chain_id, SignedMsgType.PRECOMMIT,
                                 6, 0, bid, real.commit.signatures[0].timestamp_ns)
        sigs.append(CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                              validator_address=v.address,
                              timestamp_ns=real.commit.signatures[0].timestamp_ns,
                              signature=key_by_addr[v.address].sign(sb)))
    evil = LightBlock(
        signed_header=SignedHeader(
            header=evil_header,
            commit=Commit(height=6, round=0, block_id=bid, signatures=sigs),
        ),
        validator_set=real.validator_set,
    )

    ev = _attack_evidence(chain, evil, common_h=5)
    trusted = real.signed_header
    assert not ev.conflicting_header_is_invalid(trusted.header)
    byz = ev.get_byzantine_validators(chain.blocks[5].validator_set, trusted)
    # every validator double-signed → all are byzantine
    assert {v.address for v in byz} == {
        v.address for v in real.validator_set.validators
    }


def test_byzantine_validators_amnesia_not_attributable(chain):
    """Valid header, different round: amnesia — no validator is provably
    malicious from the evidence alone."""
    real = chain.blocks[6]
    from tendermint_tpu.types.commit import Commit
    from tendermint_tpu.types.light import LightBlock, SignedHeader

    evil = LightBlock(
        signed_header=SignedHeader(
            header=real.header,
            commit=Commit(height=6, round=1,  # different round
                          block_id=real.commit.block_id,
                          signatures=list(real.commit.signatures)),
        ),
        validator_set=real.validator_set,
    )
    ev = _attack_evidence(chain, evil, common_h=5)
    byz = ev.get_byzantine_validators(
        chain.blocks[5].validator_set, real.signed_header
    )
    assert byz == []


# -- the error mapping of skipping verification (reference verifier.go:73-80,
#    client.go verifySkipping) ---------------------------------------------


class _CountingProvider(MemoryProvider):
    """Records the heights it was asked for: a pivot shows as a fetch."""

    def __init__(self, chain_id, blocks):
        super().__init__(chain_id, blocks)
        self.asked = []

    def light_block(self, height):
        self.asked.append(height)
        return super().light_block(height)


def _with_commit_rows(lb, rows):
    """`lb` with its commit's rows replaced (the header, and so the block
    id the rows sign, kept)."""
    commit = Commit(height=lb.commit.height, round=lb.commit.round,
                    block_id=lb.commit.block_id, signatures=rows)
    return LightBlock(signed_header=SignedHeader(header=lb.header, commit=commit),
                      validator_set=lb.validator_set)


def _skipping_client_on(blocks, target):
    provider = _CountingProvider(CHAIN_ID, blocks)
    client = Client(
        CHAIN_ID, TrustOptions(period_ns=PERIOD, height=1, hash=blocks[1].hash()),
        provider, [], mode=SKIPPING, now_fn=lambda: now_at(target))
    provider.asked.clear()
    return client, provider


def test_too_little_trusted_power_is_a_pivot():
    from tendermint_tpu.types.validator import ErrNotEnoughVotingPowerSigned

    c = LightChain().extend(3)
    c.extend(1, next_keys=_keys([21, 22, 23, 24]))  # a full rotation at height 5
    c.extend(5)
    with pytest.raises(ErrNotEnoughVotingPowerSigned) as e:
        c.blocks[1].validator_set.verify_commit_light_trusting(
            CHAIN_ID, c.blocks[8].commit, Fraction(1, 3))
    assert isinstance(e.value, ValueError)
    assert (e.value.got, e.value.needed, e.value.rows) == (0, 13, 0)
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(
            c.blocks[1].signed_header, c.blocks[1].validator_set,
            c.blocks[8].signed_header, c.blocks[8].validator_set,
            PERIOD, now_at(8), DRIFT)
    client, provider = _skipping_client_on(c.blocks, 8)
    assert client.verify_light_block_at_height(8, now_at(8)).hash() == c.blocks[8].hash()
    assert provider.asked[0] == 8 and len(provider.asked) > 1     # it pivoted


def test_wrong_signature_in_the_trusting_check_fails_and_does_not_pivot(chain):
    from tendermint_tpu.light import ErrVerificationFailed

    rows = list(chain.blocks[9].commit.signatures)
    rows[0] = CommitSig(rows[0].block_id_flag, rows[0].validator_address,
                        rows[0].timestamp_ns, b"\x05" * 64)
    blocks = {**chain.blocks, 9: _with_commit_rows(chain.blocks[9], rows)}
    with pytest.raises(ValueError, match=r"wrong signature \(#0\)") as e:
        verify_non_adjacent(
            blocks[1].signed_header, blocks[1].validator_set,
            blocks[9].signed_header, blocks[9].validator_set,
            PERIOD, now_at(9), DRIFT)
    assert not isinstance(e.value, LightClientError)
    client, provider = _skipping_client_on(blocks, 9)
    with pytest.raises(ErrVerificationFailed) as failed:
        client.verify_light_block_at_height(9, now_at(9))
    assert (failed.value.from_height, failed.value.to_height) == (1, 9)
    assert "wrong signature (#0)" in str(failed.value.reason)
    assert provider.asked == [9]                  # the target, and no pivot
    assert client.last_trusted_height() == 1


def test_double_vote_in_the_trusting_check_fails_and_does_not_pivot(chain):
    from tendermint_tpu.light import ErrVerificationFailed

    rows = list(chain.blocks[9].commit.signatures)
    rows[1] = rows[0]                             # validator 0 votes twice
    blocks = {**chain.blocks, 9: _with_commit_rows(chain.blocks[9], rows)}
    with pytest.raises(ValueError, match=r"double vote from validator 0 \(0 and 1\)") as e:
        verify_non_adjacent(
            blocks[1].signed_header, blocks[1].validator_set,
            blocks[9].signed_header, blocks[9].validator_set,
            PERIOD, now_at(9), DRIFT)
    assert not isinstance(e.value, LightClientError)
    client, provider = _skipping_client_on(blocks, 9)
    with pytest.raises(ErrVerificationFailed) as failed:
        client.verify_light_block_at_height(9, now_at(9))
    assert "double vote" in str(failed.value.reason) and provider.asked == [9]
    # a wrong signature BEFORE the second vote is what the reference reports
    rows[0] = CommitSig(rows[0].block_id_flag, rows[0].validator_address,
                        rows[0].timestamp_ns, b"\x05" * 64)
    rows[1] = rows[0]
    with pytest.raises(ValueError, match=r"wrong signature \(#0\)"):
        blocks[1].validator_set.verify_commit_light_trusting(
            CHAIN_ID, _with_commit_rows(chain.blocks[9], rows).commit, Fraction(1, 3))

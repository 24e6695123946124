"""Skipping verification against the plain reference's rule
(chipbench/reference/skipping_rules.py, which imports nothing of the
program): the trusting check on seeded random commits, and the light
client's bisection — the heights it visits, its answer, its spans and
counters — on seeded chains whose set changes a little every block.

Host path, small sizes: every flush here is under the 64-row floor, so
nothing compiles.
"""

from __future__ import annotations

import hashlib
import random
import re
from fractions import Fraction

import pytest

from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference import skipping_rules as rules
from chipbench.reference.signbytes import precommit_sign_bytes
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.light import (
    Client,
    ErrVerificationFailed,
    LightBlockStore,
    SKIPPING,
    TrustOptions,
)
from tendermint_tpu.light import client as light_client
from tendermint_tpu.light.errors import ErrLightBlockNotFound
from tendermint_tpu.types.basic import BlockID, PartSetHeader
from tendermint_tpu.types.block import Header
from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.light import LightBlock, SignedHeader
from tendermint_tpu.types.validator import (
    ErrNotEnoughVotingPowerSigned,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.utils import trace

CHAIN_ID = "skip-chain"
T0 = 1_700_000_000 * 10**9
SEC = 10**9
FLAGS = {"commit": BlockIDFlag.COMMIT, "nil": BlockIDFlag.NIL, "absent": BlockIDFlag.ABSENT}


@pytest.fixture(autouse=True)
def cpu_backend():
    set_default_backend("cpu")
    yield
    set_default_backend("auto")


def _key(seed: int, i: int):
    return priv_key_from_seed(hashlib.sha256(b"%d|skip|%d" % (seed, i)).digest())


def _block_id(tag: bytes) -> BlockID:
    return BlockID(hash=hashlib.sha256(tag).digest(), part_set_header=PartSetHeader(
        total=1, hash=hashlib.sha256(tag + b"|parts").digest()))


def _sign(key, height: int, block_id: BlockID, ts: int) -> bytes:
    psh = block_id.part_set_header
    return key.sign(precommit_sign_bytes(CHAIN_ID, height, 0, block_id.hash,
                                         psh.total, psh.hash, ts))


def _flip(sig: bytes) -> bytes:
    return sig[:-1] + bytes([sig[-1] ^ 1])


# ---------------------------------------------------------------------------
# the trusting check on seeded random commits
# ---------------------------------------------------------------------------

SCENARIOS = ("plain", "bad_before_cut", "bad_after_cut", "double_vote",
             "bad_before_double_vote")
LEVELS = (Fraction(1, 3), Fraction(1, 2), Fraction(2, 3))


def _trusting_case(seed: int, level: Fraction, scenario: str):
    """A trusted set of skewed power and a commit of ANOTHER set that
    shares some of its validators: absent and nil rows, unknown addresses;
    per scenario a corrupted row or a second vote, placed by the rule."""
    rng = random.Random(seed)
    n = rng.randrange(24, 61)
    keys = [_key(seed, i) for i in range(2 * n)]
    trusted = ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=rng.choice(
        (1, 1, 2, 5, 10, 40))) for k in keys[:n]])
    by_addr = {k.pub_key().address(): k for k in keys}
    # the commit's signers: most of the trusted set, and strangers
    signers = rng.sample(keys[:n], rng.randrange(n // 2, n + 1)) + keys[n:n + n // 3]
    rng.shuffle(signers)
    flags = [rng.choices(("commit", "nil", "absent"), (8, 1, 1))[0] for _ in signers]
    plain = [(f, k.pub_key().address()) for f, k in zip(flags, signers)]
    num, den = level.numerator, level.denominator
    trusted_plain = [(v.address, v.voting_power) for v in trusted.validators]
    rows, ended = rules.trusting_select(plain, trusted_plain, num, den)
    bad = set()
    if scenario in ("double_vote", "bad_before_double_vote") and len(rows) >= 2:
        # a validator the walk has met votes again, before the walk would end
        at = rng.randrange(rows[len(rows) // 2], rows[-1] + 1)
        first = rng.choice([r for r in rows if r < at])
        plain.insert(at, ("commit", plain[first][1]))
        if scenario == "bad_before_double_vote":
            bad.add(rng.choice([r for r in rows if r < at]))
    elif scenario == "bad_before_cut" and rows:
        bad.add(rng.choice(rows))
    elif scenario == "bad_after_cut":
        past = [i for i in range(rows[-1] + 1 if rows else 0, len(plain))
                if plain[i][0] == "commit"]
        if past:
            bad.add(rng.choice(past))
    height, block_id = 77, _block_id(b"%d|trusting" % seed)
    sigs = []
    for i, (flag, addr) in enumerate(plain):
        ts = T0 + i + 1
        sig = b"" if flag == "absent" else _sign(
            by_addr[addr], height, block_id if flag == "commit" else BlockID(), ts)
        if i in bad:
            sig = _flip(sig)
        sigs.append(CommitSig(block_id_flag=FLAGS[flag],
                              validator_address=b"" if flag == "absent" else addr,
                              timestamp_ns=0 if flag == "absent" else ts, signature=sig))
    plain = [(f, a if f != "absent" else b"") for f, a in plain]
    commit = Commit(height=height, round=0, block_id=block_id, signatures=sigs)
    pubs = {a: k.pub_key().bytes_() for a, k in by_addr.items()}

    def ok(i):
        """The plain reference's verdict where a row may fail, and on the
        walk's first row; every other row was signed honestly above."""
        if i not in bad and i != (rows[0] if rows else -1):
            return True
        psh = block_id.part_set_header
        msg = precommit_sign_bytes(CHAIN_ID, height, 0, block_id.hash, psh.total,
                                   psh.hash, sigs[i].timestamp_ns)
        return ref.verify(pubs[plain[i][1]], msg, sigs[i].signature)

    return trusted, trusted_plain, commit, plain, ok, bad


def _said(trusted, commit, level):
    """(the program's answer in the rule's shape, rows it verified | None)."""
    try:
        n = trusted.verify_commit_light_trusting(CHAIN_ID, commit, level)
    except ErrNotEnoughVotingPowerSigned as e:
        return ("not_enough", (e.got, e.needed)), e.rows
    except ValueError as e:
        m = re.search(r"wrong signature \(#(\d+)\)", str(e))
        if m:
            return ("wrong_signature", int(m.group(1))), None
        m = re.search(r"double vote from validator (\d+) \((\d+) and (\d+)\)", str(e))
        assert m, str(e)
        return ("double_vote", tuple(int(g) for g in m.groups())), None
    return ("enough", None), n


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("level", LEVELS, ids=lambda f: f"{f.numerator}of{f.denominator}")
@pytest.mark.parametrize("seed", (11, 2**31 + 12))
def test_trusting_check_answers_what_the_rule_gives(seed, level, scenario):
    trusted, trusted_plain, commit, plain, ok, bad = _trusting_case(seed, level, scenario)
    want = rules.trusting_answer(plain, trusted_plain, level.numerator,
                                 level.denominator, ok)
    rows, _ = rules.trusting_select(plain, trusted_plain, level.numerator,
                                    level.denominator)
    got, n_rows = _said(trusted, commit, level)
    assert got == want
    if n_rows is not None:
        assert n_rows == len(rows)      # the rows it verified are the rule's
    # the scenario bit: what was planted decides the answer as the rule says
    if scenario == "bad_before_cut" and bad:
        assert want == ("wrong_signature", min(bad))
    if scenario == "bad_after_cut":
        assert want[0] in ("enough", "not_enough")
    if scenario == "bad_before_double_vote" and bad:
        assert want[0] == "wrong_signature"


def test_trusting_check_scenarios_cover_every_answer():
    seen = set()
    for scenario in SCENARIOS:
        for level in LEVELS:
            for seed in (11, 2**31 + 12):
                _, tp, _, plain, ok, _ = _trusting_case(seed, level, scenario)
                seen.add(rules.trusting_answer(plain, tp, level.numerator,
                                               level.denominator, ok)[0])
    assert seen == {"enough", "not_enough", "wrong_signature", "double_vote"}


# ---------------------------------------------------------------------------
# the client's bisection on seeded chains with churn
# ---------------------------------------------------------------------------


class ChurnChain:
    """One sequence of keys; the set of height h is keys[churn*(h-1):][:n]
    (upstream's ChangeKeys), equal power.  Light blocks are built when
    asked for, and the provider records the heights asked."""

    def __init__(self, seed: int, n: int, churn: int, bad=None):
        self.seed, self.n, self.churn = seed, n, churn
        self.bad = bad or {}            # (height, row) -> "sig_bit"
        self.blocks: dict[int, LightBlock] = {}
        self.asked: list[int] = []

    def _set(self, h: int):
        keys = [_key(self.seed, j) for j in range(self.churn * (h - 1),
                                                 self.churn * (h - 1) + self.n)]
        vset = ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=10) for k in keys])
        by_addr = {k.pub_key().address(): k for k in keys}
        return vset, [by_addr[v.address] for v in vset.validators]

    def plain(self, h: int):
        vset, _ = self._set(h)
        s = [(v.address, v.voting_power) for v in vset.validators]
        return s, [("commit", a) for a, _ in s]

    def block(self, h: int) -> LightBlock:
        if h not in self.blocks:
            vset, keys = self._set(h)
            header = Header(
                chain_id=CHAIN_ID, height=h, time_ns=T0 + h * 60 * SEC,
                last_block_id=_block_id(b"%d|last|%d" % (self.seed, h)),
                validators_hash=vset.hash(), next_validators_hash=self._set(h + 1)[0].hash(),
                consensus_hash=b"\x02" * 32, app_hash=b"%032d" % self.seed,
                proposer_address=vset.get_proposer().address)
            block_id = BlockID(hash=header.hash(), part_set_header=PartSetHeader(
                total=1, hash=hashlib.sha256(b"%d|parts|%d" % (self.seed, h)).digest()))
            rows = []
            for i, (v, k) in enumerate(zip(vset.validators, keys)):
                ts = header.time_ns + i + 1
                sig = _sign(k, h, block_id, ts)
                if (h, i) in self.bad:
                    sig = _flip(sig)
                rows.append(CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                                      validator_address=v.address, timestamp_ns=ts,
                                      signature=sig))
            commit = Commit(height=h, round=0, block_id=block_id, signatures=rows)
            self.blocks[h] = LightBlock(
                signed_header=SignedHeader(header=header, commit=commit), validator_set=vset)
        return self.blocks[h]

    # -- the provider ---------------------------------------------------
    def chain_id(self):
        return CHAIN_ID

    def light_block(self, height: int) -> LightBlock:
        if height <= 0:
            raise ErrLightBlockNotFound("no latest")
        self.asked.append(height)
        return LightBlock.decode(self.block(height).encode())

    def report_evidence(self, ev):
        raise AssertionError("no witness")


def _walk(chain: ChurnChain, target: int):
    """The client on `chain` from height 1 to `target`, traced: (answer,
    heights asked, spans, counter deltas)."""
    store = LightBlockStore()
    store.save_light_block(chain.block(1))
    client = Client(
        CHAIN_ID, TrustOptions(period_ns=24 * 3600 * SEC, height=1, hash=chain.block(1).hash()),
        chain, [], trusted_store=store, mode=SKIPPING,
        now_fn=lambda: T0 + target * 60 * SEC + 30 * SEC)
    before = {(c.name, tuple(sorted(lb.items()))): v for c in light_client.LIGHT_COUNTERS
              for _, lb, v in c.samples()}
    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    try:
        try:
            client.verify_light_block_at_height(target)
            answer = ("accept", tuple(
                h for h in range(2, target + 1) if store.light_block(h) is not None))
        except ErrVerificationFailed as e:
            answer = ("failed", e.from_height, e.to_height, str(e.reason))
    finally:
        trace.set_enabled(was)
    spans = trace.spans()
    trace.clear()
    after = {(c.name, tuple(sorted(lb.items()))): v for c in light_client.LIGHT_COUNTERS
             for _, lb, v in c.samples()}
    delta = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
    return answer, chain.asked, spans, delta


# (validators, churn a block, target): fast churn refuses jumps with no
# shared validator, slow churn with some; 2 -> adjacent steps at the end
CHAINS = [(24, 1, 40), (30, 2, 33), (36, 3, 50), (48, 2, 70), (60, 4, 64),
          (27, 1, 90), (45, 5, 22), (33, 3, 2), (24, 6, 30), (60, 1, 200)]


@pytest.mark.parametrize("n,churn,target", CHAINS)
def test_client_bisects_as_the_rule_schedules(n, churn, target):
    seed = 1000 * n + target
    chain = ChurnChain(seed, n, churn)
    want = rules.walk(chain.plain, 1, target, 1, 3, lambda h, r: True)
    answer, asked, spans, delta = _walk(chain, target)
    assert answer == want.answer and want.answer[0] == "accept"
    assert asked == want.fetched
    hops = [s for s in spans if s["name"] == "light.hop"]
    assert [(s["attrs"]["trusted"], s["attrs"]["candidate"], s["attrs"]["outcome"])
            for s in hops] == want.attempts
    accepted = sum(1 for a in want.attempts if a[2] == "accepted")
    refused = len(want.attempts) - accepted
    (top,) = [s for s in spans if s["name"] == "light.verify_to_height"]
    assert top["attrs"] == {"mode": SKIPPING, "from": 1, "target": target,
                            "hops": accepted, "refused": refused,
                            "fetched": len(want.fetched)}
    # every span of the walk hangs under it; one fetch a height asked, one store
    fetches = [s for s in spans if s["name"] == "light.fetch"]
    assert [s["attrs"]["height"] for s in fetches] == want.fetched
    (saved,) = [s for s in spans if s["name"] == "light.store"]
    assert saved["attrs"] == {"blocks": accepted}
    assert {s["parent"] for s in hops + fetches + [saved]} == {top["id"]}
    # the commit.* spans of a jump's checks hang under its light.hop, the
    # trusting check first
    by_hop = {h["id"]: [s["attrs"]["mode"] for s in spans
                        if s["name"] == "commit.select" and s["parent"] == h["id"]]
              for h in hops}
    for h, (a, b, outcome) in zip(hops, want.attempts):
        modes = (["trusting"] if b != a + 1 else []) + (["light"] if outcome == "accepted" else [])
        assert by_hop[h["id"]] == modes
    # counters: hops by outcome, fetches, and the trusting checks' rows
    trusting = [(c, a[2]) for c, a in _trusting_checks(want)]
    assert delta.get(("tendermint_light_hops_total", (("outcome", "accepted"),)), 0) == accepted
    assert delta.get(("tendermint_light_hops_total", (("outcome", "refused"),)), 0) == refused
    assert delta.get(("tendermint_light_fetches_total", ()), 0) == len(want.fetched)
    assert delta.get(("tendermint_light_trusting_rows_total", ()), 0) == sum(
        len(c.rows) for c, out in trusting if out == "accepted")
    assert delta.get(("tendermint_light_refused_rows_total", ()), 0) == sum(
        len(c.rows) for c, out in trusting if out == "refused")


def _trusting_checks(want):
    """Each trusting check of the walk with the attempt it belongs to."""
    checks = iter(want.checks)
    out = []
    for attempt in want.attempts:
        a, b, outcome = attempt
        if b != a + 1:
            out.append((next(checks), attempt))
        if outcome == "accepted":
            next(checks)            # its light check
    return out


def test_slow_churn_refuses_jumps_that_verified_rows():
    """A chain that changes slowly: a refused jump shares validators, so it
    verifies rows before it runs out of power, and the rule counts them."""
    want = rules.walk(ChurnChain(60200, 60, 1).plain, 1, 200, 1, 3, lambda h, r: True)
    refused = [c for c, a in _trusting_checks(want) if a[2] == "refused"]
    assert refused and any(c.rows for c in refused)


@pytest.mark.parametrize("where", ["trusting", "light_only", "past_cut"])
def test_a_corrupted_row_gets_the_answer_the_rule_gives(where):
    n, churn, target = 48, 2, 70
    honest = rules.walk(ChurnChain(5, n, churn).plain, 1, target, 1, 3, lambda h, r: True)
    trusting, light = (c.rows for c in honest.checks[-2:])
    row = {"trusting": trusting[3],
           "light_only": sorted(set(light) - set(trusting))[2],
           "past_cut": sorted(set(range(n)) - set(light) - set(trusting))[0]}[where]
    chain = ChurnChain(5, n, churn, bad={(target, row): "sig_bit"})
    want = rules.walk(chain.plain, 1, target, 1, 3, lambda h, r: (h, r) != (target, row))
    answer, asked, spans, delta = _walk(chain, target)
    # a failure never pivots: nothing is fetched after it (with slow churn
    # an EARLIER, refused jump to the target may be the one that meets the row)
    assert asked == want.fetched and len(asked) <= len(honest.fetched)
    if where == "past_cut":
        assert answer == want.answer == honest.answer
        return
    kind, a, b, reason = want.answer
    assert (kind, reason) == ("failed", ("wrong_signature", row))
    assert answer[:3] == ("failed", a, b) and f"wrong signature (#{row})" in answer[3]
    assert delta.get(("tendermint_light_hops_total", (("outcome", "failed"),)), 0) == 1
    last = [s for s in spans if s["name"] == "light.hop"][-1]
    assert last["attrs"] == {"trusted": a, "candidate": b, "outcome": "failed"}
    # the trusted store holds what was verified before the failure, no more
    assert not [s for s in spans if s["name"] == "light.store"]


def test_a_pivot_rule_off_by_one_is_not_the_rule():
    """The rule's schedule is its own: with another pivot the heights differ."""
    chain = ChurnChain(36050, 36, 3)
    want = rules.walk(chain.plain, 1, 50, 1, 3, lambda h, r: True)
    off = rules.walk(chain.plain, 1, 50, 1, 3, lambda h, r: True,
                     pivot_of=lambda a, b: (a + b) // 2 + 1)
    assert off.fetched != want.fetched

"""Entry point `light.Client.verify_light_block_at_height` in the default
SKIPPING mode: a light client restarted from its trusted store verifies
the head of a chain whose validator set changes a little every block, by
bisection.  A call is handed a chain it has never seen: the trusted store
as the client left it (the stored bytes of the trusted block) and a
provider that serves, per fetch, a light block decoded from its wire bytes;
it builds a fresh `Client` over them and asks for the target height.

The chain follows upstream's `genMockNode` / `ChangeKeys`: one sequence of
keys, of which a block's set is a window that moves `churn` keys a block.
Only the heights a bisection fetches are built and signed; which they are,
the rows each check consults, the rows two checks share and the flushes
are chipbench/reference/skipping_rules.py's.  Headers are the program's own
`Header`s (the set's `validators_hash`, the next set's, a hash the commit
signs); signatures are made through the reference's encoder.
"""

import hashlib
import random
import re
import sys
from dataclasses import dataclass

from chipbench import correct, data
from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference import skipping_rules as rules
from chipbench.reference.signbytes import PrecommitTemplate

WRONG = re.compile(r"wrong signature \(#(\d+)\)")
DOUBLE = re.compile(r"double vote from validator (\d+) \((\d+) and (\d+)\)")
SEC = 10**9
SMALL_ORDER_A_JOIN = 4      # small-order keys that join at each stated height

# what the rule says the calls since the last `path` should have cost the
# service: each call adds its item's numbers, `path` compares and clears
_expected = {"fresh": 0, "shared": 0, "flushes": 0}


def _require_skipping_path():
    """A program whose skipping path still turns every failure of the
    trusting check into a pivot (the parent of the PR that brought this
    entry) cannot give this deployment's answers: say so and leave as a
    failed set-up does, before anything is compiled."""
    try:
        from tendermint_tpu.light.client import LIGHT_COUNTERS  # noqa: F401
        from tendermint_tpu.types.validator import ErrNotEnoughVotingPowerSigned  # noqa: F401
    except ImportError as e:
        print(f"chipbench: stage 'build' failed (exit 4): the program's light client "
              f"lacks the skipping path this entry binds ({e})", file=sys.stderr, flush=True)
        raise SystemExit(4)


@dataclass
class Chain:
    """What one call is handed, and the rows it consults: those of its
    checks in the order they are made (`n_rows`, `row(i)`, `suspects`)."""

    stored: list            # [(key, bytes)]: the trusted store as the client left it
    trusted_hash: bytes
    wire: dict              # height -> the light block's wire bytes
    now_ns: int
    sets: dict              # height -> [(address, power)], for the rule
    commits: dict           # height -> data.PoolCommit
    kinds: dict             # (height, row) -> kind, the rows that may fail
    walk: object            # rules.Walk under the verdicts the builder planted
    expect: dict            # what it should cost the service: fresh, shared, flushes
    flat: list              # walk.consulted()
    first: dict             # (height, row) -> its first flat row
    n_rows: int
    suspects: dict          # flat row -> kind
    warm: bool

    def row(self, i: int):
        h, r = self.flat[i]
        return self.commits[h].row(r)

    def plain(self, h: int):
        """(set, commit) of height `h` as the rule takes them."""
        return self.sets[h], [(rules.FOR_BLOCK, addr) for addr, _ in self.sets[h]]


@dataclass
class SkipData:
    trusted_height: int
    target: int
    period_ns: int
    trust: tuple            # (numerator, denominator)
    pool: list
    warmup: list


class WireProvider:
    """A primary that answers from wire bytes: every fetch decodes, as
    light/store.py and light/http_provider.py do, so nothing a light block
    memoizes (hashes, wire forms) is carried from one call to the next."""

    def __init__(self, chain_id, wire, decode):
        self._chain_id, self._wire, self._decode = chain_id, wire, decode
        self.fetched = []

    def chain_id(self):
        return self._chain_id

    def light_block(self, height):
        from tendermint_tpu.light.errors import ErrLightBlockNotFound

        self.fetched.append(height)
        raw = self._wire.get(height)
        if raw is None:
            raise ErrLightBlockNotFound(f"no light block at height {height}")
        return self._decode(raw)

    def report_evidence(self, ev):
        raise AssertionError("no witness, so no evidence")


# -- the chain ---------------------------------------------------------------


def _key_sequence(seed, name, rng, n_keys, joins, n, churn):
    """[(pub, signing key | None)]: the chain's keys in the order they join;
    a block's set is keys[churn * (h - 1):][:n].  `joins`: the heights at
    which SMALL_ORDER_A_JOIN small-order keys join."""
    from cryptography.hazmat.primitives import serialization as ser

    encs = ref.small_order_encodings()
    small = iter(rng.sample(encs, SMALL_ORDER_A_JOIN * len(joins)))
    at = {n + churn * (h - 2) + t: next(small)
          for h in joins for t in range(SMALL_ORDER_A_JOIN)}
    keys = []
    for j in range(n_keys):
        if j in at:
            keys.append((at[j], None))
        else:
            key = data.priv(seed, name, j)
            keys.append((key.public_key().public_bytes(ser.Encoding.Raw, ser.PublicFormat.Raw),
                         key))
    return keys, encs


def _signers(keys, encs):
    """`data.Signers` of one block's set: the program's ValidatorSet in its
    own order (equal power: by address)."""
    from tendermint_tpu.crypto.keys import PubKey
    from tendermint_tpu.types.validator import Validator, ValidatorSet

    entries = sorted(keys, key=lambda e: hashlib.sha256(e[0]).digest()[:20])
    pubs = [pub for pub, _ in entries]
    vset = ValidatorSet([Validator(pub_key=PubKey(pub), voting_power=data.POWER)
                         for pub in pubs])
    if [v.pub_key.bytes_() for v in vset.validators] != pubs:
        raise RuntimeError("the validator set's order is not the harness's")
    return data.Signers(vset, pubs,
                        [(v.address, key) for v, (_, key) in zip(vset.validators, entries)],
                        {i: pub for i, (pub, key) in enumerate(entries) if key is None}, encs)


def _header(tag, height, time_ns, who, next_hash):
    from tendermint_tpu.types.basic import BlockID, PartSetHeader
    from tendermint_tpu.types.block import Header

    def h(what):
        return hashlib.sha256(tag + b"|%d|" % height + what).digest()

    header = Header(
        chain_id=data.CHAIN_ID, height=height, time_ns=time_ns,
        last_block_id=BlockID(hash=h(b"last"), part_set_header=PartSetHeader(
            total=1, hash=h(b"last-parts"))),
        last_commit_hash=h(b"last-commit"), data_hash=h(b"data"),
        validators_hash=who.vset.hash(), next_validators_hash=next_hash,
        consensus_hash=h(b"consensus"), app_hash=h(b"app"),
        proposer_address=who.vset.get_proposer().address)
    return header, BlockID(hash=header.hash(),
                           part_set_header=PartSetHeader(total=1, hash=h(b"parts")))


def _commit(rng, who, height, time_ns, block_id):
    """A complete commit of `who`'s set over `block_id`: every validator
    precommits, each with a timestamp of its own."""
    from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig

    psh = block_id.part_set_header
    tpl = PrecommitTemplate(data.CHAIN_ID, height, 0, block_id.hash, psh.total, psh.hash)
    timestamps, signatures = [], []
    for i, (_, key) in enumerate(who.keys):
        ts = time_ns + i + 1
        timestamps.append(ts)
        signatures.append(rng.choice(who.encs) + bytes(32) if key is None
                          else key.sign(tpl.sign_bytes(ts)))
    commit = Commit(height=height, round=0, block_id=block_id, signatures=[
        CommitSig(block_id_flag=BlockIDFlag.COMMIT, validator_address=addr,
                  timestamp_ns=ts, signature=sig)
        for (addr, _), ts, sig in zip(who.keys, timestamps, signatures)])
    return data.PoolCommit(height, block_id, commit, tpl, timestamps, signatures,
                           who.pubs, len(signatures),
                           {i: "small_order" for i in who.small_order})


def _chain(seed, name, rng, sizes, k, bad, warm):
    """Chain `k` of the pool: its keys, the heights the rule's walk visits,
    their light blocks, and (`bad` = (place, kind) or None) one corrupted
    row, placed by what the rule consults."""
    from tendermint_tpu.light import LightBlockStore
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.types.light import LightBlock, SignedHeader

    n, churn = sizes["validators"], sizes["churn"]
    trusted_h, target = sizes["trusted_height"], sizes["trusted_height"] + sizes["chain_gap"]
    num, den = sizes["trust_level"]
    block_ns = sizes["block_seconds"] * SEC
    cname = f"{name}|chain|{'w' if warm else 'p'}{k}"
    tag = b"%d|%s" % (seed, cname.encode())
    keys, encs = _key_sequence(seed, cname, rng, n + churn * target,
                               sizes["small_order_joins"], n, churn)
    who = {}

    def signers(h):
        if h not in who:
            who[h] = _signers(keys[churn * (h - 1):][:n], encs)
        return who[h]

    def plain(h):
        s = [(addr, data.POWER) for addr, _ in signers(h).keys]
        return s, [(rules.FOR_BLOCK, addr) for addr, _ in s]

    honest = rules.walk(plain, trusted_h, target, num, den, lambda h, r: True)
    corrupted = {}
    if bad is not None:
        place, kind = bad
        trusting, light = (c.rows for c in honest.checks[-2:])   # the last hop's checks
        rows = {"light_only": sorted(set(light) - set(trusting)),
                "last_trusting": trusting,
                "past_cut": sorted(set(range(n)) - set(light) - set(trusting))}[place]
        corrupted[target, rng.choice([r for r in rows
                                      if r not in signers(target).small_order])] = kind

    commits, blocks = {}, {}
    for h in sorted({trusted_h, *honest.fetched}):
        time_ns = data.T0_NS + h * block_ns
        header, block_id = _header(tag, h, time_ns, signers(h), signers(h + 1).vset.hash())
        pc = commits[h] = _commit(rng, signers(h), h, time_ns, block_id)
        for (hh, r), kind in corrupted.items():
            if hh == h:
                data.corrupt(pc, r, kind)
        blocks[h] = LightBlock(signed_header=SignedHeader(header=header, commit=pc.commit),
                               validator_set=signers(h).vset)
    kinds = {**{(h, r): "small_order" for h in commits for r in signers(h).small_order},
             **corrupted}
    store = LightBlockStore(MemDB())
    store.save_light_block(blocks[trusted_h])

    planted = rules.walk(plain, trusted_h, target, num, den,
                         lambda h, r: kinds.get((h, r), "small_order") == "small_order")
    flat = planted.consulted()
    first = {}
    for i, hr in enumerate(flat):
        first.setdefault(hr, i)
    return Chain(
        stored=list(store.db.iterate()), trusted_hash=blocks[trusted_h].hash(),
        wire={h: blocks[h].encode() for h in honest.fetched},
        now_ns=data.T0_NS + target * block_ns + 30 * SEC,
        sets={h: plain(h)[0] for h in commits}, commits=commits, kinds=kinds,
        walk=planted, expect={"fresh": planted.fresh(), "shared": planted.shared(),
                              "flushes": planted.flushes},
        flat=flat, first=first, n_rows=len(flat),
        suspects={i: kinds[hr] for i, hr in enumerate(flat) if hr in kinds}, warm=warm)


def build(seed, cfg, sizes, cache_capacity, pool_rule, warmup_commits):
    _require_skipping_path()
    name = cfg["name"]
    rng = random.Random(seed)
    probe = _chain(seed, name, rng, sizes, 0, None, True)
    count = data.pool_size(cache_capacity, probe.expect["fresh"], **pool_rule)
    bad_chains = cfg["adversarial"]["bad_chains"]
    bad = dict(zip(rng.sample(range(count), len(bad_chains)), map(tuple, bad_chains)))
    pool = [_chain(seed, name, rng, sizes, k, bad.get(k), False) for k in range(count)]
    # the rule was fed ONE chain's unique rows; a chain whose walk fails
    # early has fewer, so top the pool up until a lap holds what the rule wants
    while sum(ch.expect["fresh"] for ch in pool) < pool_rule["cache_factor"] * cache_capacity:
        pool.append(_chain(seed, name, rng, sizes, len(pool), None, False))
    warmup = [probe] + [_chain(seed, name, rng, sizes, j, None, True)
                        for j in range(1, warmup_commits)]
    return SkipData(sizes["trusted_height"], sizes["trusted_height"] + sizes["chain_gap"],
                    sizes["trusting_period_hours"] * 3600 * SEC,
                    tuple(sizes["trust_level"]), pool, warmup[:warmup_commits])


# -- the call, the rule, the path --------------------------------------------


def _reason(e, to_height):
    text = str(e)
    m = WRONG.search(text)
    if m:
        return ("wrong_signature", int(m.group(1)))
    m = DOUBLE.search(text)
    if m:
        return ("double_vote", tuple(int(g) for g in m.groups()))
    if "insufficient voting power" in text:
        return ("insufficient_power", to_height)
    if "bisection exhausted" in text:
        return ("exhausted", None)
    return ("error", f"{type(e).__name__}: {text}"[:200])


def bind(d):
    from fractions import Fraction

    _require_skipping_path()
    from tendermint_tpu.light import Client, ErrVerificationFailed, LightBlockStore, TrustOptions
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.types.light import LightBlock

    trust = Fraction(*d.trust)

    def call(item):
        for name, n in item.expect.items():
            _expected[name] += n
        db = MemDB()
        for key, raw in item.stored:
            db.set(key, raw)
        primary = WireProvider(data.CHAIN_ID, item.wire, LightBlock.decode)
        client = Client(data.CHAIN_ID,
                        TrustOptions(period_ns=d.period_ns, height=d.trusted_height,
                                     hash=item.trusted_hash),
                        primary, [], trusted_store=LightBlockStore(db), trust_level=trust,
                        now_fn=lambda: item.now_ns)
        try:
            client.verify_light_block_at_height(d.target, item.now_ns)
        except ErrVerificationFailed as e:
            return (("failed", e.from_height, e.to_height, _reason(e.reason, e.to_height)),
                    tuple(primary.fetched))
        # the heights the client now trusts, off the keys of its store
        trusted = tuple(int.from_bytes(key[-8:], "big") for key, _ in db.iterate())[1:]
        said = (("accept", trusted), tuple(primary.fetched))
        if item.warm and said == (item.walk.answer, tuple(item.walk.fetched)):
            return None     # what the harness's warm-up asks of an accepted item
        return said

    return call


def expected(d, item, row_ok):
    def ok(h, r):
        """The reference's verdict on a row that may fail; a row the
        builder's walk never reached has no flat row to ask about and keeps
        the verdict it was planted with."""
        if (h, r) in item.first and (h, r) in item.kinds:
            return row_ok(item.first[h, r])
        return item.kinds.get((h, r), "small_order") == "small_order"

    w = rules.walk(item.plain, d.trusted_height, d.target, *d.trust, ok)
    return (w.answer, tuple(w.fetched))


def implied(outcome, row):
    """An accepted walk says every consulted row is valid; a failure names
    two heights and a row of a commit, which a flat row alone cannot be
    held against."""
    return True if outcome[0][0] == "accept" else None


def path(before, after, calls, compiles, route, chips):
    """`correct.device_path`'s host_flushes, device_errors,
    compiles_in_window and route_other, and from the rule: the fresh rows
    it expects minus those the device resolved, the cache hits minus the
    rows it says two checks share, the flushes minus those it counts."""
    want = dict(_expected)
    for name in _expected:
        _expected[name] = 0
    base = correct.device_path(before, after, calls, compiles, route, chips)
    return {
        "rows_off_device": want["fresh"] - (after["resolved_on_device"]
                                            - before["resolved_on_device"]),
        "host_flushes": base["host_flushes"],
        "device_errors": base["device_errors"],
        "cache_hits_off": after["cache_hits"] - before["cache_hits"] - want["shared"],
        "flushes_off": after["flushes"] - before["flushes"] - want["flushes"],
        "compiles_in_window": base["compiles_in_window"],
        "route_other": base["route_other"],
    }

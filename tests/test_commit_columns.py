"""A commit's rows reach the verifier as three columns built by bulk
passes (ISSUE 33): the bulk forms answer exactly what the row-by-row forms
they replace answered.

(a) the sign-bytes of a batch, byte for byte, timestamps outside int64
    included, and which path built them;
(b) `batch_verify_commits` and `verify_commit_light_trusting` against a
    row-by-row rule written here (select, verify each row with
    crypto/ed25519.py, tally): same acceptance, same exception type, text
    and fields;
(c) `add` and `add_many` interleaved keep the verdicts' order;
(d) no `add` a row on the commit surfaces, and the counter that says so.

Host path, nothing compiles.
"""

from __future__ import annotations

import hashlib
import random
from fractions import Fraction

import pytest

from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519, signbytes_native
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.types.basic import (
    GO_ZERO_TIME_NS,
    BlockID,
    BlockIDFlag,
    PartSetHeader,
)
from tendermint_tpu.types.commit import Commit, CommitSig
from tendermint_tpu.types.validator import (
    CommitVerifyJob,
    ErrNotEnoughVotingPowerSigned,
    Validator,
    ValidatorSet,
    batch_verify_commits,
    commit_job_outcomes,
)
from tendermint_tpu.types.vote import SignedMsgType, vote_sign_bytes_raw
from tendermint_tpu.utils import trace

CHAIN_ID = "columns-chain"
T0 = 1_700_000_000 * 10**9
COMMIT, NIL, ABSENT = BlockIDFlag.COMMIT, BlockIDFlag.NIL, BlockIDFlag.ABSENT
NATIVE = signbytes_native._load() is not None


@pytest.fixture(autouse=True)
def host_path(monkeypatch):
    # every flush on the host, and no threshold measurement started: the
    # rows here are enough (64 and more) to reach for the device
    monkeypatch.setenv("TM_TPU_CPU_THRESHOLD", "1000000")
    cbatch.set_default_backend("cpu")
    yield
    cbatch.set_default_backend("auto")


def _block_id(tag: bytes) -> BlockID:
    return BlockID(hash=hashlib.sha256(tag).digest(), part_set_header=PartSetHeader(
        total=1, hash=hashlib.sha256(tag + b"|parts").digest()))


def _traced(fn):
    """(what fn raised | None, the spans it left)."""
    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    try:
        try:
            fn()
            raised = None
        except ValueError as e:
            raised = e
    finally:
        trace.set_enabled(was)
    spans = trace.spans()
    trace.clear()
    return raised, spans


# ---------------------------------------------------------------------------
# (a) sign-bytes: the batch against a row at a time
# ---------------------------------------------------------------------------

I64_MAX, I64_MIN = 2**63 - 1, -(2**63)
TS_IN_INT64 = (0, 1, -1, 10**9 - 1, 10**9, -(10**9) - 1, T0, I64_MAX, I64_MIN)
# Go's zero time is about -6.2e19 ns; seconds 2^63 - 1 with nanos >= 10^9 is
# what an adversarial wire timestamp decodes to
TS_PAST_INT64 = (GO_ZERO_TIME_NS, I64_MAX + 1, I64_MIN - 1,
                 (2**63 - 1) * 10**9 + 2 * 10**9)
MIXES = {"commit": (COMMIT,), "commit_nil": (COMMIT, NIL), "all_three": (COMMIT, NIL, ABSENT)}


@pytest.mark.parametrize("ts_kind", ("in_int64", "past_int64"))
@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("n", (63, 64, 65, 1000))
def test_sign_bytes_batch_is_the_row_form_byte_for_byte(n, mix, ts_kind):
    rng = random.Random(f"{n}|{mix}|{ts_kind}")
    pool = TS_IN_INT64 + (TS_PAST_INT64 if ts_kind == "past_int64" else ())
    sigs = [CommitSig(block_id_flag=rng.choice(MIXES[mix]),
                      validator_address=bytes([i % 256]) * 20,
                      timestamp_ns=pool[i] if i < len(pool) else rng.choice(
                          pool + (T0 + rng.randrange(10**12), -rng.randrange(1, 10**15))),
                      signature=b"s" * 64) for i in range(n)]
    commit = Commit(height=rng.randrange(1, 2**62), round=rng.randrange(2**31 - 1),
                    block_id=_block_id(b"a|%d" % n), signatures=sigs)
    want = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(n)]
    # the row form is the canonical encoder's
    for i in rng.sample(range(n), 8):
        assert want[i] == vote_sign_bytes_raw(
            CHAIN_ID, SignedMsgType.PRECOMMIT, commit.height, commit.round,
            sigs[i].vote_block_id(commit.block_id), sigs[i].timestamp_ns)
    got, path = commit.sign_bytes_of(CHAIN_ID, sigs)
    assert got == want
    assert all(type(m) is bytes for m in got)
    if n < 64 or not NATIVE:
        assert path == "template"
    else:
        assert path == ("native_exact_ts" if ts_kind == "past_int64" else "native")
    # a selection in another order, and an iterator of indices
    idxs = rng.sample(range(n), n - 3)
    assert commit.vote_sign_bytes_batch(CHAIN_ID, iter(idxs)) == [want[i] for i in idxs]
    assert commit.vote_sign_bytes_batch(CHAIN_ID, range(3)) == want[:3]


# ---------------------------------------------------------------------------
# (b) the commit checks against a row-by-row rule
# ---------------------------------------------------------------------------


class Case:
    """A validator set of unequal powers and a commit over it (full and
    light), or over ANOTHER set that shares validators with it (trusting);
    `bad` rows carry a flipped signature bit."""

    def __init__(self, seed, n=150, height=7, flags=None, powers=None):
        rng = self.rng = random.Random(repr(seed))
        self.height, self.block_id = height, _block_id(b"b|%r|%d" % (seed, height))
        keys = [priv_key_from_seed(hashlib.sha256(b"col|%r|%d" % (seed, i)).digest())
                for i in range(n)]
        powers = powers or [rng.choice((6, 8, 10, 12, 15)) for _ in keys]
        self.vset = ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=p)
                                  for k, p in zip(keys, powers)])
        by_addr = {k.pub_key().address(): k for k in keys}
        self.keys = [by_addr[v.address] for v in self.vset.validators]
        self.flags = flags or [rng.choices((COMMIT, NIL, ABSENT), (10, 1, 1))[0]
                               for _ in keys]
        self.signers = list(self.keys)

    def commit(self, bad=()):
        rows = []
        for i, (k, flag) in enumerate(zip(self.signers, self.flags)):
            if flag == ABSENT:
                rows.append(CommitSig.absent_sig())
                continue
            ts = T0 + 1000 * self.height + i
            sig = k.sign(vote_sign_bytes_raw(
                CHAIN_ID, SignedMsgType.PRECOMMIT, self.height, 0,
                self.block_id if flag == COMMIT else BlockID(), ts))
            if i in bad:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            rows.append(CommitSig(block_id_flag=flag, timestamp_ns=ts, signature=sig,
                                  validator_address=k.pub_key().address()))
        return Commit(height=self.height, round=0, block_id=self.block_id, signatures=rows)


def _row_ok(pub: bytes, commit: Commit, idx: int) -> bool:
    cs = commit.signatures[idx]
    msg = vote_sign_bytes_raw(CHAIN_ID, SignedMsgType.PRECOMMIT, commit.height,
                              commit.round, cs.vote_block_id(commit.block_id),
                              cs.timestamp_ns)
    return ed25519.verify_fast(pub, msg, cs.signature)


def _rule_select(mode, vset, commit):
    """The rows a full or light check consults, in order."""
    needed = vset.total_voting_power() * 2 // 3
    rows, running = [], 0
    for idx, cs in enumerate(commit.signatures):
        if mode == "light":
            if not cs.for_block():
                continue
        elif cs.absent():
            continue
        rows.append(idx)
        if mode == "light":
            running += vset.validators[idx].voting_power
            if running > needed:
                break
    return rows, needed


def _rule(mode, vset, commit):
    """None, or (exception type, text, (got, needed, rows) | None): one
    row at a time, the first failure in order."""
    rows, needed = _rule_select(mode, vset, commit)
    tallied = 0
    for idx in rows:
        val = vset.validators[idx]
        if not _row_ok(val.pub_key.bytes_(), commit, idx):
            return (ValueError, f"wrong signature (#{idx}) in commit for height "
                                f"{commit.height}", None)
        if mode == "light" or commit.signatures[idx].for_block():
            tallied += val.voting_power
    if tallied <= needed:
        return (ErrNotEnoughVotingPowerSigned,
                f"insufficient voting power for height {commit.height}: "
                f"got {tallied}, needed >{needed}", (tallied, needed, len(rows)))
    return None


def _rule_trusting(trusted, commit, level):
    needed = trusted.total_voting_power() * level.numerator // level.denominator
    seen, rows, tallied = {}, 0, 0
    for idx, cs in enumerate(commit.signatures):
        if not cs.for_block():
            continue
        val_idx, val = trusted.get_by_address(cs.validator_address)
        if val is None:
            continue
        if val_idx in seen:
            return (ValueError, "double vote from validator %d (%d and %d)"
                    % (val_idx, seen[val_idx], idx), None), rows
        seen[val_idx] = idx
        rows += 1
        if not _row_ok(val.pub_key.bytes_(), commit, idx):
            return (ValueError, f"wrong signature (#{idx})", None), rows
        tallied += val.voting_power
        if tallied > needed:
            return None, rows
    return (ErrNotEnoughVotingPowerSigned,
            f"insufficient voting power: got {tallied}, needed >{needed}",
            (tallied, needed, rows)), rows


def _answer(raised):
    if raised is None:
        return None
    fields = ((raised.got, raised.needed, raised.rows)
              if isinstance(raised, ErrNotEnoughVotingPowerSigned) else None)
    return (type(raised), str(raised), fields)


def _commit_spans(spans):
    return [s for s in spans if s["name"].startswith("commit.")]


def _check_spans(spans, jobs, selected):
    """One of each commit.* span a job, in order, with their attributes."""
    names = [s["name"] for s in _commit_spans(spans)]
    assert names[:3 * jobs] == ["commit.select", "commit.sign_bytes", "commit.add"] * jobs
    assert names[3 * jobs] == "commit.verify"
    assert set(names[3 * jobs + 1:]) <= {"commit.tally"} and len(names) <= 4 * jobs + 1
    by_name = {}
    for s in _commit_spans(spans):
        by_name.setdefault(s["name"], []).append(s["attrs"])
    assert [a["selected"] for a in by_name["commit.select"]] == selected
    assert [a["n"] for a in by_name["commit.sign_bytes"]] == selected
    assert [a["path"] for a in by_name["commit.sign_bytes"]] == [
        "native" if k >= 64 and NATIVE else "template" for k in selected]
    assert [(a["n"], a["bulk"]) for a in by_name["commit.add"]] == [(k, 1) for k in selected]
    assert by_name["commit.verify"] == [{"n": sum(selected)}]


SCENARIOS = ("plain", "bad_first", "bad_middle", "bad_last", "bad_past_cut", "short_power")


# a full check has no cut, so nothing lies past it
MODE_SCENARIOS = [(m, s) for m in ("full", "light") for s in SCENARIOS
                  if (m, s) != ("full", "bad_past_cut")]


@pytest.mark.parametrize("mode,scenario", MODE_SCENARIOS)
@pytest.mark.parametrize("seed", (5, 2**31 + 6))
def test_commit_check_answers_what_the_row_rule_gives(seed, mode, scenario):
    flags = None
    if scenario == "short_power":
        # all valid, too few for the block
        flags = [COMMIT if i % 2 else NIL for i in range(150)]
    case = Case((seed, scenario), flags=flags)
    rows, _ = _rule_select(mode, case.vset, case.commit())
    assert len(rows) >= 64                     # the native sign-bytes path
    if mode == "light" and scenario != "short_power":
        assert rows[-1] < 140                  # the cut lies inside the commit
    bad = {"bad_first": rows[:1], "bad_middle": rows[len(rows) // 2:][:1],
           "bad_last": rows[-1:],
           "bad_past_cut": [i for i in range(rows[-1] + 1, 150)
                            if case.flags[i] == COMMIT][:1]}.get(scenario, [])
    commit = case.commit(bad)
    want = _rule(mode, case.vset, commit)
    raised, spans = _traced(lambda: batch_verify_commits(
        [CommitVerifyJob(case.vset, CHAIN_ID, case.block_id, case.height, commit, mode=mode)]))
    assert _answer(raised) == want
    assert (want is None) == (scenario in ("plain", "bad_past_cut"))
    if scenario.startswith("bad_") and want is not None:
        assert want[0] is ValueError and f"(#{bad[0]})" in want[1]
    if scenario == "short_power":
        assert want[0] is ErrNotEnoughVotingPowerSigned
    _check_spans(spans, 1, [len(rows)])
    # the set's own methods are the same call
    method = case.vset.verify_commit if mode == "full" else case.vset.verify_commit_light
    raised2, _ = _traced(lambda: method(CHAIN_ID, case.block_id, case.height, commit))
    assert _answer(raised2) == want


@pytest.mark.parametrize("second", ("bad_row", "short_power"))
def test_a_failing_job_second_of_three_is_the_one_named(second):
    cases = [Case(("jobs", h), n=70, height=h,
                  flags=[NIL] * 40 + [COMMIT] * 30 if (h == 21 and second == "short_power")
                  else None) for h in (20, 21, 22)]
    bads = [(), (5,) if second == "bad_row" else (), (3,)]   # the third fails too
    jobs, selected = [], []
    for case, bad, mode in zip(cases, bads, ("full", "light", "full")):
        if second == "short_power" and case.height == 21:
            mode = "full"
        elif case.height == 21:
            # row 5 must be one the light check consults
            case.flags = [COMMIT] * 70
        commit = case.commit(bad)
        jobs.append(CommitVerifyJob(case.vset, CHAIN_ID, case.block_id, case.height,
                                    commit, mode=mode))
        selected.append(len(_rule_select(mode, case.vset, commit)[0]))
    wants = [_rule(j.mode, j.val_set, j.commit) for j in jobs]
    assert wants[0] is None and wants[1] is not None and wants[2] is not None
    raised, spans = _traced(lambda: batch_verify_commits(jobs))
    assert _answer(raised) == wants[1]
    assert "height 21" in str(raised)
    _check_spans(spans, 3, selected)
    # every job is tallied (each has an outcome of its own, ISSUE 34:
    # `commit_job_outcomes`); what is raised is the first of them
    assert [s["attrs"]["n"] for s in _commit_spans(spans)
            if s["name"] == "commit.tally"] == selected
    assert [_answer(e) for e in commit_job_outcomes(jobs)] == wants


TRUSTING = ("plain", "bad_first", "bad_middle", "bad_last", "bad_past_cut",
            "short_power", "double_vote_after_bad_row", "double_vote")


@pytest.mark.parametrize("scenario", TRUSTING)
@pytest.mark.parametrize("seed", (7, 2**31 + 8))
def test_trusting_check_answers_what_the_row_rule_gives(seed, scenario):
    level = Fraction(1, 3)
    case = Case((seed, "trusting"), n=300, flags=[COMMIT] * 300)
    trusted = case.vset
    # the commit is another set's: strangers first, then the trusted
    # set's validators in another order, a few of them nil
    rng = case.rng
    strangers = [priv_key_from_seed(hashlib.sha256(b"stranger|%d|%d" % (seed, i)).digest())
                 for i in range(20)]
    case.signers = strangers[:10] + rng.sample(case.keys, 270) + strangers[10:]
    case.flags = [COMMIT if rng.random() < 0.9 else NIL for _ in case.signers]
    if scenario == "short_power":
        case.flags = [COMMIT if i < 30 else NIL for i in range(len(case.signers))]
    (_, consulted) = _rule_trusting(trusted, case.commit(), level)
    matched = [i for i, (k, f) in enumerate(zip(case.signers, case.flags))
               if f == COMMIT and trusted.has_address(k.pub_key().address())]
    rows = matched[:consulted]
    bad = {"bad_first": rows[:1], "bad_middle": rows[len(rows) // 2:][:1],
           "bad_last": rows[-1:], "bad_past_cut": matched[consulted:][:1],
           "double_vote_after_bad_row": rows[3:4]}.get(scenario, [])
    if scenario.startswith("double_vote"):
        # a validator the walk has met votes again before the walk would end
        at = rows[len(rows) // 2]
        case.signers.insert(at, case.signers[rows[1]])
        case.flags.insert(at, COMMIT)
    commit = case.commit(bad)
    want, consulted = _rule_trusting(trusted, commit, level)
    got_rows = []
    raised, spans = _traced(lambda: got_rows.append(
        trusted.verify_commit_light_trusting(CHAIN_ID, commit, level)))
    assert _answer(raised) == want
    expect = {"plain": None, "bad_past_cut": None, "short_power": ErrNotEnoughVotingPowerSigned,
              "double_vote": ValueError}.get(scenario, ValueError)
    assert (want and want[0]) == expect
    if scenario == "double_vote":
        assert "double vote" in want[1]
    if scenario in ("double_vote_after_bad_row", "bad_first", "bad_middle", "bad_last"):
        assert want[1] == f"wrong signature (#{bad[0]})"
    if want is None:
        assert got_rows == [consulted] and consulted >= 64
    # the spans: the walk, then the bulk forms
    select, sign, add = _commit_spans(spans)[:3]
    assert select["name"] == "commit.select" and select["attrs"]["mode"] == "trusting"
    k = select["attrs"]["selected"]
    assert sign["attrs"] == {"n": k, "path": "native" if k >= 64 and NATIVE else "template"}
    assert add["attrs"] == {"n": k, "bulk": 1}


def test_a_timestamp_outside_int64_takes_the_exact_split_and_says_so():
    case = Case("exact_ts", n=80, flags=[COMMIT] * 80)
    commit = case.commit()
    # row 5 carries what an adversarial wire timestamp decodes to; its
    # signature is then wrong, and the check names it
    commit.signatures[5].timestamp_ns = (2**63 - 1) * 10**9 + 2 * 10**9
    raised, spans = _traced(lambda: case.vset.verify_commit(
        CHAIN_ID, case.block_id, case.height, commit))
    assert str(raised) == "wrong signature (#5) in commit for height 7"
    (sign,) = [s for s in spans if s["name"] == "commit.sign_bytes"]
    assert sign["attrs"] == {"n": 80, "path": "native_exact_ts" if NATIVE else "template"}


# ---------------------------------------------------------------------------
# (c) add and add_many interleaved
# ---------------------------------------------------------------------------


def _triples(n, bad=()):
    out = []
    for i in range(n):
        k = priv_key_from_seed(hashlib.sha256(b"triple|%d" % i).digest())
        msg = b"interleaved-%d" % i
        sig = k.sign(msg)
        if i in bad:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        out.append((k.pub_key(), msg, sig))
    return out


def _verifier(which):
    """(a batch verifier, what closes it)."""
    if which == "service":
        svc = av.VerifyService(linger_ms=0.2, cache_size=64)
        return av.ServiceBatchVerifier(svc), svc.close
    if which == "cpu":
        return cbatch.CPUBatchVerifier(), lambda: None
    return cbatch.JAXBatchVerifier(cpu_threshold=10**6), lambda: None


@pytest.mark.parametrize("which", ("service", "cpu", "jax"))
def test_add_and_add_many_interleaved_keep_the_verdicts_in_row_order(which):
    bv, close = _verifier(which)
    try:
        rows = _triples(12, bad=(0, 4, 7, 11))
        want = [i not in (0, 4, 7, 11) for i in range(12)]
        before = _rows_added()

        def many(part, as_bytes):
            pubs = [p.bytes_() if as_bytes else p for p, _, _ in part]
            bv.add_many(pubs, [bytearray(m) for _, m, _ in part], [s for _, _, s in part])

        bv.add(*rows[0])
        many(rows[1:5], as_bytes=True)
        bv.add(*rows[5])
        bv.add(*rows[6])
        many(rows[7:11], as_bytes=False)     # key objects and bytearrays are coerced
        many([], as_bytes=True)
        bv.add(*rows[11])
        assert bv.count() == 12
        assert bv.verify() == (False, want)
        assert bv.count() == 0 and bv.verify() == (False, [])
        after = _rows_added()
        assert after["bulk"] - before["bulk"] == 8 and after["row"] - before["row"] == 4
        with pytest.raises(ValueError, match="unequal length"):
            bv.add_many([rows[0][0]], [b"m", b"n"], [rows[0][2]])
        assert bv.count() == 0
    finally:
        close()


def test_the_service_takes_columns_as_it_takes_items():
    svc = av.VerifyService(linger_ms=0.2, cache_size=64)
    try:
        rows = _triples(9, bad=(2, 8))
        want = [i not in (2, 8) for i in range(9)]
        items = [(p.bytes_(), m, s) for p, m, s in rows]
        cols = list(zip(*rows))            # key objects: the type test coerces
        assert svc.verify_columns(*cols) == want
        assert svc.submit_columns(*cols).result(timeout=30) == want   # the valid rows from the cache
        assert svc.verify_many(items) == want
        assert svc.cache.hits == 14        # 7 valid rows, twice; a bad row is never stored
        assert svc.verify_columns([], [], []) == []
        with pytest.raises(ValueError, match="unequal length"):
            svc.verify_columns(cols[0], cols[1][:-1], cols[2])
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# (d) nothing is called per row, and the counter says so
# ---------------------------------------------------------------------------


def _rows_added():
    got = {lb["how"]: v for _, lb, v in cbatch.ROWS_ADDED_TOTAL.samples()}
    return {"bulk": got.get("bulk", 0), "row": got.get("row", 0)}


@pytest.mark.parametrize("mode", ("full", "light", "trusting"))
def test_a_thousand_row_commit_goes_in_by_one_add_many_a_job(monkeypatch, mode):
    case = Case(("thousand", mode), n=1000, flags=[COMMIT] * 1000, powers=[10] * 1000)
    commit = case.commit()
    calls = {"add": 0, "add_many": []}
    real_many = av.ServiceBatchVerifier.add_many

    def add(self, *a):
        calls["add"] += 1

    def add_many(self, pubs, msgs, sigs):
        calls["add_many"].append(len(pubs))
        real_many(self, pubs, msgs, sigs)

    monkeypatch.setattr(av.ServiceBatchVerifier, "add", add)
    monkeypatch.setattr(av.ServiceBatchVerifier, "add_many", add_many)
    before = _rows_added()
    if mode == "trusting":
        want = 334                  # the first rows that carry more than a third
        assert case.vset.verify_commit_light_trusting(CHAIN_ID, commit, Fraction(1, 3)) == want
    else:
        want = 1000 if mode == "full" else 667
        job = CommitVerifyJob(case.vset, CHAIN_ID, case.block_id, case.height, commit, mode=mode)
        other = Case(("thousand", mode, 2), n=70, height=9, flags=[COMMIT] * 70)
        batch_verify_commits([job, CommitVerifyJob(
            other.vset, CHAIN_ID, other.block_id, 9, other.commit(), mode="full")])
    assert calls["add"] == 0
    assert calls["add_many"] == ([want] if mode == "trusting" else [want, 70])
    after = _rows_added()
    assert after["bulk"] - before["bulk"] == sum(calls["add_many"])
    assert after["row"] == before["row"]

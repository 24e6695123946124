"""Many light clients behind one gateway against the plain reference's
rule (chipbench/reference/fanin_rules.py, which imports nothing of the
program): what each client is told (isolation) and what serving them
costs the verify service (once), on small seeded chains.

Host path: a header here consults 9 rows, far under the 64-row floor, so
nothing compiles.  A flush is made deterministic by a linger no test ever
waits out: the clients submit, then `close()` cuts what gathered.
"""

from __future__ import annotations

import hashlib
import random
import re
import threading
import time

import pytest

from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference import fanin_rules as rules
from chipbench.reference.signbytes import precommit_sign_bytes
from tendermint_tpu.crypto import async_verify as av
from tendermint_tpu.crypto.batch import set_default_backend
from tendermint_tpu.crypto.keys import PubKey, priv_key_from_seed
from tendermint_tpu.gateway import coalescer as gwc
from tendermint_tpu.gateway.coalescer import VerifyCoalescer
from tendermint_tpu.gateway.service import Gateway
from tendermint_tpu.types.basic import BlockID, PartSetHeader
from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.validator import (
    CommitVerifyJob,
    ErrNotEnoughVotingPowerSigned,
    Validator,
    ValidatorSet,
    batch_verify_commits,
    commit_job_outcomes,
)
from tendermint_tpu.utils import trace

CHAIN_ID = "fanin-chain"
T0 = 1_700_000_000 * 10**9
N_VALS, N_SMALL_ORDER, POWER = 12, 2, 10
POWERS = [POWER] * N_VALS
CONSULTED = rules.consulted(POWERS)          # 9 of 12
NEVER_MS = 60_000.0                          # a linger nobody waits out
WRONG = re.compile(r"wrong signature \(#(\d+)\) in commit for height (\d+)")


@pytest.fixture(autouse=True)
def fresh_host_service():
    set_default_backend("cpu")
    av.clear_service()
    yield
    av.clear_service()
    set_default_backend("auto")


class Chain:
    """One set of 12 equal validators, 2 of them small-order keys (whose
    "signature" cofactored ZIP-215 accepts and a strict verifier refuses),
    and complete commits over it."""

    def __init__(self, seed: int):
        self.seed = seed
        rng = random.Random(seed)
        self.encs = ref.small_order_encodings()
        keys = {}
        for i in range(N_VALS - N_SMALL_ORDER):
            k = priv_key_from_seed(hashlib.sha256(b"%d|fanin|%d" % (seed, i)).digest())
            keys[k.pub_key().bytes_()] = k
        for enc in rng.sample(self.encs, N_SMALL_ORDER):
            keys[enc] = None
        self.vset = ValidatorSet([Validator(pub_key=PubKey(pub), voting_power=POWER)
                                  for pub in keys])
        self.pubs = [v.pub_key.bytes_() for v in self.vset.validators]
        self.keys = [keys[pub] for pub in self.pubs]
        self.small_order = {i for i, k in enumerate(self.keys) if k is None}

    def header(self, height: int, bad: dict | None = None):
        """(job, rows, suspects): `bad` = {row: "sig_bit" | "timestamp"}."""
        tag = b"%d|fanin|block|%d" % (self.seed, height)
        block_id = BlockID(hash=hashlib.sha256(tag).digest(), part_set_header=PartSetHeader(
            total=1, hash=hashlib.sha256(tag + b"|parts").digest()))
        rng = random.Random(self.seed * 1000 + height)
        sigs, rows = [], []
        for i, (v, key) in enumerate(zip(self.vset.validators, self.keys)):
            ts = T0 + height * 10**9 + i + 1
            msg = precommit_sign_bytes(CHAIN_ID, height, 0, block_id.hash, 1,
                                       block_id.part_set_header.hash, ts)
            sig = rng.choice(self.encs) + bytes(32) if key is None else key.sign(msg)
            kind = (bad or {}).get(i)
            if kind == "sig_bit":
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            elif kind == "timestamp":
                ts += 7
                msg = precommit_sign_bytes(CHAIN_ID, height, 0, block_id.hash, 1,
                                           block_id.part_set_header.hash, ts)
            sigs.append(CommitSig(block_id_flag=BlockIDFlag.COMMIT, timestamp_ns=ts,
                                  validator_address=v.address, signature=sig))
            rows.append((self.pubs[i], msg, sig))
        commit = Commit(height=height, round=0, block_id=block_id, signatures=sigs)
        job = CommitVerifyJob(self.vset, CHAIN_ID, block_id, height, commit, mode="light")
        suspects = {**{i: "small_order" for i in self.small_order}, **(bad or {})}
        return job, rows, suspects

    def honest_row(self, rng, lo: int, hi: int) -> int:
        return rng.choice([i for i in range(lo, hi) if i not in self.small_order])


def _said(err) -> tuple:
    """A client's answer in the rule's words."""
    if err is None:
        return ("accept", None)
    m = WRONG.search(str(err)) if type(err) is ValueError else None
    return ("wrong_signature", int(m.group(1))) if m else ("error", repr(err))


def _expected(rows, suspects) -> tuple:
    return rules.expected_alone(POWERS, suspects, lambda i: ref.verify(*rows[i]))


def _serve(gw: Gateway, clients: list[list]) -> list:
    """Each client (a thread) hands the gateway its jobs and waits; once
    all are queued the gateway is closed, which cuts ONE flush of them.
    What each was told: None or the exception."""
    told = [None] * len(clients)

    def client(k):
        try:
            gw.verify_commits(clients[k])
        except Exception as e:  # noqa: BLE001 — the client's answer
            told[k] = e

    threads = [threading.Thread(target=client, args=(k,)) for k in range(len(clients))]
    for th in threads:
        th.start()
    total = sum(len(c) for c in clients)
    deadline = time.monotonic() + 30
    while gw.coalescer.stats_snapshot()["verify_jobs"] < total:
        assert time.monotonic() < deadline
        time.sleep(0.0005)
    gw.close()
    for th in threads:
        th.join(30)
        assert not th.is_alive()
    return told


def _moved(gw: Gateway, before: dict) -> dict:
    """What the rule's `once` reads, on the host path: the rows the
    service took stand where the chip's resolved rows do."""
    after, st = av.service_stats(), gw.coalescer.stats_snapshot()
    return {
        "rows_resolved_on_device": after["submitted"] - before["submitted"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "service_flushes": after["flushes"] - before["flushes"],
        "gateway_flushes": st["verify_flushes"],
        "gateway_jobs_flushed": st["verify_flushed_jobs"],
        "gateway_coalesced": st["verify_coalesced"],
        "gateway_shed": st["shed"],
    }


def _gateway(**kw) -> Gateway:
    return Gateway(coalescer=VerifyCoalescer(linger_ms=NEVER_MS, **kw))


def _headers(chain, k, refused, seed):
    """k headers at distinct heights; `refused` of them carry a corrupted
    consulted row, one more (where there is room) a corrupted row past the
    cut-off, which must not fail it."""
    rng = random.Random(seed)
    which = rng.sample(range(k), refused)
    past = next((j for j in range(k) if j not in which), None)
    out = []
    for j in range(k):
        bad = None
        if j in which:
            bad = {chain.honest_row(rng, 0, CONSULTED): rng.choice(("sig_bit", "timestamp"))}
        elif j == past:
            bad = {chain.honest_row(rng, CONSULTED, N_VALS): "sig_bit"}
        out.append(chain.header(100 + j, bad))
    return out, which


# ---------------------------------------------------------------------------
# isolation and once, for 1 to 6 clients with 0, 1 and 2 refused headers
# ---------------------------------------------------------------------------

CASES = [(k, r) for k in range(1, 7) for r in (0, 1, 2) if r <= k]


@pytest.mark.parametrize("k,refused", CASES)
def test_each_client_is_told_what_its_header_alone_deserves(k, refused):
    chain = Chain(3400 + 10 * k + refused)
    headers, which = _headers(chain, k, refused, seed=k * 7 + refused)
    gw = _gateway()
    before = av.service_stats()
    told = _serve(gw, [[job] for job, _, _ in headers])
    for j, (job, rows, suspects) in enumerate(headers):
        want = _expected(rows, suspects)
        assert _said(told[j]) == want, (j, told[j])
        assert (want[0] == "wrong_signature") == (j in which)
        if told[j] is not None:
            # the row AND the height of the client's own header
            assert WRONG.search(str(told[j])).group(2) == str(job.height)
    # once: ONE service flush for the gateway's one, refused header or not,
    # and every consulted row taken by the service exactly once
    moved = _moved(gw, before)
    assert moved["gateway_flushes"] == 1 and moved["service_flushes"] == 1
    assert rules.once(k, k * CONSULTED, moved) == {
        "rows_off_device": 0, "cache_hits": 0, "flushes_off": 0, "jobs_off": 0,
        "coalesced": 0, "shed": 0}


@pytest.mark.parametrize("fault", ("height", "block_id", "size"))
def test_a_structurally_bad_commit_refuses_its_own_job_only(fault):
    chain = Chain(3450)
    headers = [chain.header(200 + j) for j in range(4)]
    job = headers[2][0]
    if fault == "height":
        job.height += 1
        text = "invalid commit height: want 203, got 202"
    elif fault == "block_id":
        job.block_id = headers[3][0].block_id
        text = "invalid commit: wrong block ID"
    else:
        job.commit.signatures.pop()
        text = "invalid commit: 12 vals, 11 sigs"
    gw = _gateway()
    before = av.service_stats()
    told = _serve(gw, [[j] for j, _, _ in headers])
    assert [type(t) for t in told] == [type(None), type(None), ValueError, type(None)]
    assert str(told[2]) == text
    moved = _moved(gw, before)
    # the refused job added no row to the flush and cost the others nothing
    assert moved["service_flushes"] == 1 and moved["cache_hits"] == 0
    assert moved["rows_resolved_on_device"] == 3 * CONSULTED


def test_a_client_with_several_headers_is_told_its_first_failure():
    chain = Chain(3460)
    a = [chain.header(300 + j, {1: "sig_bit"} if j == 1 else None) for j in range(3)]
    b = [chain.header(310 + j) for j in range(2)]
    gw = _gateway()
    told = _serve(gw, [[j for j, _, _ in a], [j for j, _, _ in b]])
    assert _said(told[0]) == ("wrong_signature", 1) and "height 301" in str(told[0])
    assert told[1] is None
    assert gw.coalescer.stats_snapshot()["verify_flushes"] == 1


# ---------------------------------------------------------------------------
# planted faults: the path numbers read > 0
# ---------------------------------------------------------------------------

def test_planted_reverify_of_a_refused_flush_shows_in_flushes_off_and_cache_hits():
    """The raise-only adapter (what `_flush_individually` was) over the
    program's own verifier: a refused flush is verified again job by job,
    the proven rows come back from the verified-signature cache."""
    chain = Chain(3470)
    headers, _ = _headers(chain, 4, 1, seed=9)
    gw = _gateway(verify_fn=batch_verify_commits)
    before = av.service_stats()
    told = _serve(gw, [[job] for job, _, _ in headers])
    assert [_said(t) for t in told] == [_expected(r, s) for _, r, s in headers]
    off = rules.once(4, 4 * CONSULTED, _moved(gw, before))
    assert off["flushes_off"] >= 1 and off["cache_hits"] >= 3 * CONSULTED
    assert off["jobs_off"] == 0 and off["coalesced"] == 0


def test_planted_duplicate_header_shows_in_coalesced():
    chain = Chain(3480)
    job, _, _ = chain.header(400)
    twin = CommitVerifyJob(job.val_set, job.chain_id, job.block_id, job.height,
                           job.commit, mode="light")
    gw = _gateway()
    before = av.service_stats()
    told = _serve(gw, [[job], [twin]])
    assert told == [None, None]
    off = rules.once(2, 2 * CONSULTED, _moved(gw, before))
    assert off["coalesced"] == 1 and off["jobs_off"] == -1
    assert off["rows_off_device"] == CONSULTED


# ---------------------------------------------------------------------------
# batch_verify_commits: the first failure, the same text
# ---------------------------------------------------------------------------

def _batch(chain, plan):
    """Jobs at heights 500…; `plan[j]`: None | ("row", i) | "height"."""
    jobs, wants = [], []
    for j, what in enumerate(plan):
        h = 500 + j
        job, _, _ = chain.header(h, {what[1]: "sig_bit"} if isinstance(what, tuple) else None)
        want = None
        if isinstance(what, tuple):
            want = (ValueError, f"wrong signature (#{what[1]}) in commit for height {h}")
        elif what == "height":
            job.height = h + 50
            want = (ValueError, f"invalid commit height: want {h + 50}, got {h}")
        jobs.append(job)
        wants.append(want)
    return jobs, wants


@pytest.mark.parametrize("plan", (
    (None, None, None),
    (None, ("row", 3), None, ("row", 0)),
    (("row", 8), None, "height"),
    (None, "height", ("row", 2)),
    ("height",),
), ids=lambda p: "-".join("ok" if w is None else w if isinstance(w, str) else f"row{w[1]}"
                          for w in p))
def test_batch_verify_commits_raises_the_first_of_the_outcomes(plan):
    chain = Chain(3490)
    jobs, wants = _batch(chain, plan)
    outcomes = commit_job_outcomes(jobs)
    assert [None if e is None else (type(e), str(e)) for e in outcomes] == wants
    first = next((w for w in wants if w is not None), None)
    if first is None:
        assert batch_verify_commits(jobs) is None
    else:
        with pytest.raises(first[0]) as exc:
            batch_verify_commits(jobs)
        assert (type(exc.value), str(exc.value)) == first


def test_too_little_power_is_its_own_jobs_outcome():
    chain = Chain(3491)
    jobs = [chain.header(600 + j)[0] for j in range(3)]
    # 8 of 12 sign for the block, all valid: 80 is not more than 80
    short = chain.header(601)[0]
    for i in (0, 1, 2, 3):
        short.commit.signatures[i] = CommitSig.absent_sig()
    jobs[1] = short
    outcomes = commit_job_outcomes(jobs)
    assert outcomes[0] is None and outcomes[2] is None
    err = outcomes[1]
    assert type(err) is ErrNotEnoughVotingPowerSigned
    assert str(err) == "insufficient voting power for height 601: got 80, needed >80"
    assert (err.got, err.needed, err.rows) == (80, 80, 8)
    with pytest.raises(ErrNotEnoughVotingPowerSigned, match="height 601"):
        batch_verify_commits(jobs)


# ---------------------------------------------------------------------------
# spans and series
# ---------------------------------------------------------------------------

def _refused_jobs() -> float:
    return sum(v for _, _, v in gwc.REFUSED_JOBS_TOTAL.samples())


def test_spans_and_series_of_one_flush_of_three_clients():
    chain = Chain(3500)
    headers, which = _headers(chain, 3, 1, seed=5)
    series = [dict(m.label_stats()) for m in (gwc.FLUSH_JOBS, gwc.JOB_WAIT_SECONDS)]
    refused0 = _refused_jobs()
    gw = _gateway()
    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    try:
        told = _serve(gw, [[job] for job, _, _ in headers])
    finally:
        trace.set_enabled(was)
    spans = trace.spans()
    trace.clear()
    assert sum(t is not None for t in told) == 1

    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    (flush,), (linger,), (resolve,) = by["gateway.flush"], by["gateway.linger"], by["gateway.resolve"]
    seq = flush["attrs"]["flush"]
    assert flush["attrs"]["jobs"] == 3 and flush["attrs"]["rows"] == 3 * N_VALS
    assert flush["attrs"]["wait_sum_ns"] > 0
    assert linger["attrs"] == {"flush": seq, "jobs": 3}
    assert resolve["attrs"] == {"flush": seq, "jobs": 3, "refused": 1}
    assert [s["attrs"] for s in by["gateway.submit"]] == [{"jobs": 1, "joined": 0}] * 3
    assert [s["attrs"] for s in by["gateway.wait"]] == [{"jobs": 1, "flush": seq}] * 3
    # the worker's thread: linger, flush, resolve, in that order; the
    # clients' threads: three others
    assert linger["tid"] == flush["tid"] == resolve["tid"]
    assert linger["t0_ns"] + linger["dur_ns"] <= flush["t0_ns"] + 1000
    assert flush["t0_ns"] + flush["dur_ns"] <= resolve["t0_ns"]
    assert len({s["tid"] for s in by["gateway.wait"]} - {flush["tid"]}) == 3
    # a job's wait for its flush: submit -> the flush's start
    first_submit = min(s["t0_ns"] for s in by["gateway.submit"])
    assert flush["attrs"]["wait_sum_ns"] <= 3 * (flush["t0_ns"] - first_submit) + 3000
    # the flush is the parent of that thread's commit.* and verify.* spans
    for name in ("commit.select", "commit.sign_bytes", "commit.add", "commit.verify",
                 "commit.tally"):
        assert by[name] and all(s["parent"] == flush["id"] for s in by[name]), name
    assert len(by["commit.select"]) == len(by["commit.tally"]) == 3
    (verify,) = by["commit.verify"]
    assert verify["attrs"] == {"n": 3 * CONSULTED}
    assert all(s["parent"] == verify["id"] for s in by["verify.submit"] + by["verify.wait"])
    # the three series beside the tendermint_gateway_* counters
    for metric, was_, (dn, lo, hi) in zip(
            (gwc.FLUSH_JOBS, gwc.JOB_WAIT_SECONDS), series, ((1, 3, 3), (3, 0, 90))):
        n0, s0 = was_[()]
        n1, s1 = metric.label_stats()[()]
        assert n1 - n0 == dn and lo <= s1 - s0 <= hi
    assert _refused_jobs() - refused0 == 1
    names = {m.name for m in gwc.GATEWAY_SERIES}
    assert names == {"tendermint_gateway_flush_jobs", "tendermint_gateway_job_wait_seconds",
                     "tendermint_gateway_refused_jobs_total"}


def test_spans_off_is_no_span():
    chain = Chain(3501)
    gw = _gateway()
    trace.clear()
    was = trace.enabled()
    trace.set_enabled(False)
    try:
        assert _serve(gw, [[chain.header(700)[0]]]) == [None]
    finally:
        trace.set_enabled(was)
    assert trace.spans() == []


# ---------------------------------------------------------------------------
# the cut: a flush is what one flush of the verify service holds, in rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap,sizes,cut", (
    (30, (12, 12, 12, 12, 12), [[0, 1], [2, 3], [4]]),
    (36, (12, 12, 12, 12, 12), [[0, 1, 2], [3, 4]]),
    (10, (12, 12), [[0], [1]]),                 # never fewer than one job
    (16384, (12,) * 6, [[0, 1, 2, 3, 4, 5]]),
))
def test_a_flush_is_cut_by_rows_as_blocksyncs_window_is(monkeypatch, cap, sizes, cut):
    monkeypatch.setattr(av, "MAX_COALESCE", cap)
    chain = Chain(3510)
    jobs = []
    for j, n in enumerate(sizes):
        job = chain.header(800 + j)[0]
        assert len(job.commit.signatures) == n
        jobs.append(job)
    flushes = []
    co = VerifyCoalescer(linger_ms=NEVER_MS,
                         verify_fn=lambda js: flushes.append([j.height - 800 for j in js]))
    futs = co.submit_jobs(jobs)
    co.close()
    assert all(f.result(30) for f in futs)
    assert flushes == cut
    st = co.stats_snapshot()
    assert st["verify_flushes"] == len(cut) and st["verify_flushed_jobs"] == len(sizes)

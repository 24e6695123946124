"""Gateway test/bench kit: a synthetic signed-header chain, a cache-
backed provider, and the `gateway-fanout` measurement harness shared by
tests/test_gateway.py and bench.py (one implementation, so the bench
number and the acceptance test measure the same machinery).
"""

from __future__ import annotations

import time

from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.light.client import Client, SEQUENTIAL, TrustOptions
from tendermint_tpu.light.provider import MemoryProvider
from tendermint_tpu.types.basic import BlockID, PartSetHeader
from tendermint_tpu.types.block import Header
from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.light import LightBlock, SignedHeader
from tendermint_tpu.types.validator import Validator, ValidatorSet
from tendermint_tpu.types.vote import SignedMsgType, vote_sign_bytes_raw

from .cache import ResponseCache
from .client import LightGatewayClient
from .service import Gateway

T0 = 1_700_000_000 * 10**9
SEC = 10**9
PERIOD_NS = 24 * 3600 * SEC


def make_chain(heights: int, validators: int,
               chain_id: str = "gw-chain") -> dict[int, LightBlock]:
    """A fixed-validator signed-header chain 1..heights (the light
    client's provider food; same construction as tests' LightChain)."""
    keys = [priv_key_from_seed(bytes([(i % 250) + 1]) * 32)
            for i in range(validators)]
    vset = ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=10)
                         for k in keys])
    key_by_addr = {k.pub_key().address(): k for k in keys}
    blocks: dict[int, LightBlock] = {}
    last_block_id = BlockID()
    for h in range(1, heights + 1):
        header = Header(
            chain_id=chain_id,
            height=h,
            time_ns=T0 + h * SEC,
            last_block_id=last_block_id,
            validators_hash=vset.hash(),
            next_validators_hash=vset.hash(),
            consensus_hash=b"\x02" * 32,
            app_hash=b"\x01" * 32,
            proposer_address=vset.get_proposer().address,
        )
        block_id = BlockID(hash=header.hash(),
                           part_set_header=PartSetHeader(total=1,
                                                         hash=b"\x03" * 32))
        sigs = []
        for v in vset.validators:
            sb = vote_sign_bytes_raw(chain_id, SignedMsgType.PRECOMMIT, h, 0,
                                     block_id, T0 + h * SEC + SEC // 2)
            sigs.append(CommitSig(
                block_id_flag=BlockIDFlag.COMMIT,
                validator_address=v.address,
                timestamp_ns=T0 + h * SEC + SEC // 2,
                signature=key_by_addr[v.address].sign(sb),
            ))
        commit = Commit(height=h, round=0, block_id=block_id,
                        signatures=sigs)
        blocks[h] = LightBlock(
            signed_header=SignedHeader(header=header, commit=commit),
            validator_set=vset,
        )
        last_block_id = block_id
    return blocks


def chain_now_ns(heights: int) -> int:
    """A `now` safely after every header and inside the trust period."""
    return T0 + (heights + 10) * SEC


def trust_root(blocks: dict[int, LightBlock]) -> TrustOptions:
    return TrustOptions(period_ns=PERIOD_NS, height=1,
                        hash=blocks[1].hash())


class CachedProvider:
    """A provider whose reads route through a gateway ResponseCache —
    the in-process stand-in for N remote clients hitting the front
    end's cached /commit+/validators routes.  Entries below the tip are
    pinned (immutable); the tip itself is tagged."""

    def __init__(self, base: MemoryProvider, cache: ResponseCache,
                 tip_height: int):
        self._base = base
        self._cache = cache
        self._tip = tip_height

    def chain_id(self) -> str:
        return self._base.chain_id()

    def light_block(self, height: int) -> LightBlock:
        doc = self._cache.lookup("light_block", {"height": height},
                                 self._tip)
        if doc is not None:
            return doc
        lb = self._base.light_block(height)
        # size hint: signatures + validators dominate the wire size; a
        # domain object must not pay a serialization just for accounting
        est = 96 + 120 * len(lb.commit.signatures) \
            + 56 * len(lb.validator_set.validators)
        self._cache.store("light_block", {"height": height}, lb,
                          latest_height=self._tip,
                          pinned=0 < lb.height < self._tip, nbytes=est)
        return lb

    def report_evidence(self, ev) -> None:
        self._base.report_evidence(ev)


def _sequential_client_seconds(blocks, chain_id: str, now_ns: int) -> float:
    """One gateway-less client syncing root→tip on a cold verify stack —
    the per-client baseline the fan-out is judged against."""
    tip = max(blocks)
    lc = Client(
        chain_id=chain_id,
        trust_options=trust_root(blocks),
        primary=MemoryProvider(chain_id, dict(blocks)),
        witnesses=[],
        mode=SEQUENTIAL,
        now_fn=lambda: now_ns,
    )
    t0 = time.perf_counter()
    lc.verify_light_block_at_height(tip)
    dt = time.perf_counter() - t0
    assert lc.last_trusted_height() == tip, "baseline client failed to sync"
    return dt


def _reset_verify_stack() -> None:
    """Cold-start the async verify service (drops the verified-sig LRU)
    so baseline and fan-out runs both pay real verification — pinned to
    the HOST verify path: the fan-out harness measures the serving
    architecture (coalescing/caching/shedding), and a window-sized flush
    crossing the device threshold on a cold cache would pay a full XLA
    compile (tens of seconds to minutes per program) instead."""
    from tendermint_tpu.crypto import async_verify as _av

    _av.reset_service(cpu_threshold=1 << 30)


def _restore_verify_stack() -> None:
    """Drop the pinned-threshold service so the NEXT user rebuilds from
    the then-current environment (the PR 3 isolation lesson)."""
    from tendermint_tpu.crypto import async_verify as _av

    _av.clear_service()


def _fanout_once(n_clients: int, heights: int, validators: int,
                 chain_id: str, seq_s: float) -> dict:
    """One fan-out measurement on a FRESH chain (the validate/encode
    memos live on the block objects, so a reused chain would let a
    second run skip work the first paid and flatter its numbers)."""
    blocks = make_chain(heights, validators, chain_id)
    tip = max(blocks)
    now_ns = chain_now_ns(heights)
    _reset_verify_stack()
    gw = Gateway()
    base = MemoryProvider(chain_id, dict(blocks))
    driver = LightGatewayClient(
        gw, chain_id, trust_root(blocks),
        lambda i: CachedProvider(base, gw.cache, tip),
        n_clients=n_clients, now_fn=lambda: now_ns,
    )
    rep = driver.sync_all(target_height=tip)
    gw.close()
    st = rep["gateway"]
    return {
        "clients": n_clients,
        "all_ok": rep["all_ok"],
        "n_ok": rep["n_ok"],
        "fanout_wall_s": rep["wall_s"],
        "clients_synced_per_s": rep["clients_synced_per_s"],
        # N clients served in wall_s vs N x one-client-alone sequentially
        "speedup": round(n_clients * seq_s / rep["wall_s"], 2)
        if rep["wall_s"] > 0 else 0.0,
        "dedup_ratio": st["verify_dedup_ratio"],
        "cache_hit_ratio": st["cache_hit_ratio"],
        "verify_jobs": st["verify_jobs"],
        "verify_flushed_jobs": st["verify_flushed_jobs"],
        "verify_flushes": st["verify_flushes"],
    }


def run_fanout_bench(*, client_counts: tuple = (8, 48), heights: int = 24,
                     validators: int = 32,
                     chain_id: str = "gw-bench-chain",
                     probe_backpressure: bool = True) -> dict:
    """The `gateway-fanout` stage: N concurrent clients through one
    gateway vs the sequential one-client-at-a-time baseline, measured
    at each N in `client_counts` (the acceptance bar reads the dedup
    ratio at N=8 and the throughput at the largest N), plus a
    backpressure round-trip probe.  Every measured run gets a fresh
    chain so object-level memoization cannot leak work between runs."""
    now_ns = chain_now_ns(heights)
    try:
        _reset_verify_stack()
        seq_s = _sequential_client_seconds(
            make_chain(heights, validators, chain_id), chain_id, now_ns)
        runs = {n: _fanout_once(n, heights, validators, chain_id, seq_s)
                for n in client_counts}
    finally:
        _restore_verify_stack()

    headline = runs[max(runs)]
    out = {
        "heights": heights,
        "validators": validators,
        "sequential_client_s": round(seq_s, 4),
        "by_clients": runs,
    }
    out.update(headline)
    out["all_ok"] = all(r["all_ok"] for r in runs.values())
    if min(runs) != max(runs):
        out["n8_dedup_ratio"] = runs[min(runs)]["dedup_ratio"]
    if probe_backpressure:
        out["backpressure_ok"] = _probe_backpressure(
            make_chain(4, 4, chain_id), chain_id, chain_now_ns(4))
    return out


def _probe_backpressure(blocks, chain_id: str, now_ns: int) -> bool:
    """Shed → structured error with a retry hint; clear → clean sync."""
    from .errors import GatewayBackpressureError

    tip = max(blocks)
    level = 1
    gw = Gateway(shed_fn=lambda: level)
    try:
        driver = LightGatewayClient(
            gw, chain_id, trust_root(blocks),
            lambda i: MemoryProvider(chain_id, dict(blocks)),
            n_clients=1, now_fn=lambda: now_ns,
        )
        try:
            driver._build_client(0).verify_light_block_at_height(tip)
            return False   # should have shed
        except GatewayBackpressureError as e:
            if e.retry_after_ms <= 0:
                return False
        level = 0   # detector cleared
        lc = driver._build_client(0)
        lc.verify_light_block_at_height(tip)
        return lc.last_trusted_height() == tip
    finally:
        gw.close()

"""RPC core: Environment + the route handlers.

Parity: reference rpc/core/ (routes.go:10-47 route table; status.go,
blocks.go, mempool.go, consensus.go, abci.go, tx.go, net.go, events.go,
evidence.go, health.go).  Handlers are sync or async callables taking
typed kwargs; the server layers (HTTP POST, URI GET, WebSocket) coerce
params and dispatch here.
"""

from __future__ import annotations

import asyncio
import base64
import itertools

from tendermint_tpu.abci import types as abci
from tendermint_tpu.crypto import tmhash
from tendermint_tpu.pubsub import SubscriptionCancelledError
from tendermint_tpu.utils import health as _health
from tendermint_tpu.utils import txlife as _txlife
from tendermint_tpu.pubsub.query import parse as parse_query
from tendermint_tpu.types import events as tmevents

from . import encoding as enc
from .jsonrpc import INTERNAL_ERROR, INVALID_PARAMS, MEMPOOL_FULL, RPCError


class Environment:
    """Everything the handlers need (reference rpc/core/env.go)."""

    def __init__(
        self,
        *,
        config=None,
        genesis=None,
        block_store=None,
        state_store=None,
        consensus=None,
        consensus_reactor=None,
        mempool=None,
        evidence_pool=None,
        tx_indexer=None,
        event_bus=None,
        app_query_conn=None,
        router=None,
        transport=None,
        add_persistent_peer=None,
        add_private_peer_id=None,
        node_id: str = "",
        moniker: str = "tpu-node",
        version: str = "0.1.0",
        txlife=None,
        health=None,
        remediate=None,
        gateway=None,
        prof=None,
    ):
        self.config = config
        self.genesis = genesis
        self.block_store = block_store
        self.state_store = state_store
        self.consensus = consensus
        self.consensus_reactor = consensus_reactor
        self.mempool = mempool
        self.evidence_pool = evidence_pool
        self.tx_indexer = tx_indexer
        self.event_bus = event_bus
        self.app_query_conn = app_query_conn
        self.router = router
        self.transport = transport
        self.add_persistent_peer = add_persistent_peer
        self.add_private_peer_id = add_private_peer_id
        self.node_id = node_id
        self.moniker = moniker
        self.version = version
        # tx lifecycle store (utils/txlife.py): the broadcast_tx_* routes
        # stamp RPC ingress — the start of the time-to-finality clock
        self.txlife = txlife if txlife is not None else _txlife.NOP
        # health watchdog (utils/health.py): `status` publishes its
        # per-detector block so `tendermint-tpu health` needs one RPC
        self.health = health if health is not None else _health.NOP
        # remediation controller (utils/remediate.py): `status` embeds
        # its block under health.remediation — the explicit backpressure
        # signal (shed level + quarantines) clients poll before retrying
        from tendermint_tpu.utils import remediate as _remediate

        self.remediate = remediate if remediate is not None else _remediate.NOP
        # light-client gateway (tendermint_tpu/gateway): None unless the
        # node runs with TM_TPU_GATEWAY=1 — `status` then publishes the
        # serving block (clients, cache hit ratio, dedup, shed state)
        self.gateway = gateway
        # continuous profiler (utils/profiler.py): `status` publishes
        # its block so `tendermint-tpu top` gets hz/samples/overhead
        # without a second listener; NOP when TM_TPU_PROF=0
        from tendermint_tpu.utils import profiler as _profiler

        self.prof = prof if prof is not None else _profiler.NOP


def _latest_height(env: Environment) -> int:
    return env.block_store.height() if env.block_store else 0


def _normalize_height(env: Environment, height) -> int:
    if height is None or height == 0:
        return _latest_height(env)
    h = int(height)
    if h <= 0:
        raise RPCError(INVALID_PARAMS, f"height must be positive, got {h}")
    if h > _latest_height(env):
        raise RPCError(
            INVALID_PARAMS,
            f"height {h} is ahead of the chain (latest {_latest_height(env)})",
        )
    return h


# ---------------------------------------------------------------------------
# info routes
# ---------------------------------------------------------------------------

def health(env: Environment) -> dict:
    return {}


def _verify_service_status() -> dict:
    """Compact verify-service block for `status`: one RPC answers "is
    the TPU path actually live on this node".  Reads only existing
    snapshots — never instantiates the service or touches a backend."""
    from tendermint_tpu.crypto import async_verify as _av
    from tendermint_tpu.crypto import batch as _cbatch

    st = _av.service_stats()
    lookups = st["cache_hits"] + st["cache_misses"]
    svc = _av._SERVICE
    backend = "unstarted"
    if svc is not None:
        backend = "jax" if svc._jax_bv is not None else "host"
    # what "ready" refers to: XLA-CPU also sets the readiness gate, so
    # the block names the platform/device kind that became ready (None
    # until it has) — a node whose TPU did not come up must not read as
    # healthy
    diag = _cbatch.threshold_diagnostics()
    return {
        "enabled": _av.service_enabled(),
        "backend": backend,
        "device_ready": _cbatch.device_ready(),
        "platform": diag.get("platform"),
        "device_kind": diag.get("device_kind"),
        "queue_depth": enc.i64(st["queue_depth"]),
        "submitted": enc.i64(st["submitted"]),
        "device_batches": enc.i64(st["device_batches"]),
        "device_errors": enc.i64(st["device_errors"]),
        "cache_hit_ratio": round(st["cache_hits"] / lookups, 4)
        if lookups else 0.0,
    }


def _health_status_block(env: Environment) -> dict:
    """The status.health block, with the remediation controller's state
    (admission/shed level, quarantined peers, action counts — the
    backpressure signal) embedded when remediation is on."""
    block = env.health.status_block()
    if env.remediate.enabled:
        block = dict(block)
        block["remediation"] = env.remediate.status_block()
    return block


def status(env: Environment) -> dict:
    latest = _latest_height(env)
    meta = env.block_store.load_block_meta(latest) if latest else None
    earliest = env.block_store.base() if env.block_store else 0
    e_meta = env.block_store.load_block_meta(earliest) if earliest else None
    pub = None
    power = 0
    if env.consensus is not None and env.consensus.priv_validator is not None:
        pub = env.consensus.priv_validator.get_pub_key()
        rs = env.consensus.rs
        if rs.validators is not None:
            _, val = rs.validators.get_by_address(pub.address())
            power = val.voting_power if val else 0
    out = {
        "node_info": {
            "id": env.node_id,
            "moniker": env.moniker,
            "network": env.genesis.chain_id if env.genesis else "",
            "version": env.version,
            "channels": "",
            "listen_addr": getattr(getattr(env.config, "p2p", None), "laddr", ""),
        },
        "sync_info": {
            "latest_block_hash": enc.hexu(meta.header.hash() if meta else b""),
            "latest_app_hash": enc.hexu(meta.header.app_hash if meta else b""),
            "latest_block_height": enc.i64(latest),
            "latest_block_time": enc.rfc3339(meta.header.time_ns) if meta else enc.rfc3339(0),
            "earliest_block_hash": enc.hexu(e_meta.header.hash() if e_meta else b""),
            "earliest_block_height": enc.i64(earliest),
            "catching_up": not getattr(env.consensus, "_task", None) if env.consensus else False,
        },
        "validator_info": {
            "address": enc.hexu(pub.address() if pub else b""),
            "pub_key": (enc.pub_key_json(pub) if pub else
                        {"type": "tendermint/PubKeyEd25519", "value": ""}),
            "voting_power": enc.i64(power),
        },
        "verify_service": _verify_service_status(),
        "health": _health_status_block(env),
    }
    # gateway serving block, only when the node actually runs one —
    # TM_TPU_GATEWAY=0 leaves the status document bit-identical
    gw = getattr(env, "gateway", None)
    if gw is not None:
        out["gateway"] = gw.status_block()
    # profiler block, only when the sampler is on — TM_TPU_PROF=0
    # leaves the status document bit-identical
    prof = getattr(env, "prof", None)
    if prof is not None and prof.enabled:
        out["prof"] = prof.status_block()
    return out


def genesis(env: Environment) -> dict:
    import json as _json

    return {"genesis": _json.loads(env.genesis.to_json())}


def net_info(env: Environment) -> dict:
    """Peer list with per-peer traffic snapshots (reference net.go NetInfo
    → ConnectionStatus): per-channel recv/send bytes and live send-queue
    depths so an operator can see WHICH peer is slow, not just how many
    peers exist."""
    peers = env.router.peer_ids() if env.router else []
    entries = []
    for p in peers:
        entry = {"node_info": {"id": p}, "is_outbound": True}
        snap = env.router.peer_snapshot(p)
        if snap is not None:
            entry["connection_status"] = snap
        entries.append(entry)
    return {
        "listening": True,
        "listeners": [],
        "n_peers": enc.i64(len(peers)),
        "peers": entries,
    }


# ---------------------------------------------------------------------------
# block routes
# ---------------------------------------------------------------------------

def block(env: Environment, height=None) -> dict:
    h = _normalize_height(env, height)
    b = env.block_store.load_block(h)
    meta = env.block_store.load_block_meta(h)
    if b is None or meta is None:
        raise RPCError(INTERNAL_ERROR, f"block at height {h} not found")
    return {"block_id": enc.block_id_json(meta.block_id), "block": enc.block_json(b)}


def block_by_hash(env: Environment, hash=None) -> dict:  # noqa: A002
    if not hash:
        raise RPCError(INVALID_PARAMS, "hash is required")
    b = env.block_store.load_block_by_hash(_bytes_param(hash))
    if b is None:
        return {"block_id": enc.block_id_json(None), "block": None}
    return block(env, b.header.height)


def blockchain(env: Environment, minHeight=None, maxHeight=None) -> dict:
    latest = _latest_height(env)
    base = env.block_store.base()
    max_h = min(int(maxHeight) if maxHeight else latest, latest)
    min_h = max(int(minHeight) if minHeight else base, base, 1)
    # cap 20 results, newest first (reference blocks.go:36-42)
    min_h = max(min_h, max_h - 20 + 1)
    metas = []
    for h in range(max_h, min_h - 1, -1):
        m = env.block_store.load_block_meta(h)
        if m is not None:
            metas.append(enc.block_meta_json(m))
    return {"last_height": enc.i64(latest), "block_metas": metas}


def commit(env: Environment, height=None) -> dict:
    h = _normalize_height(env, height)
    meta = env.block_store.load_block_meta(h)
    if meta is None:
        raise RPCError(INTERNAL_ERROR, f"no block meta at height {h}")
    if h == _latest_height(env):
        c = env.block_store.load_seen_commit(h)
        canonical = False
    else:
        c = env.block_store.load_block_commit(h)
        canonical = True
    return {
        "signed_header": {
            "header": enc.header_json(meta.header),
            "commit": enc.commit_json(c) if c else None,
        },
        "canonical": canonical,
    }


def block_results(env: Environment, height=None) -> dict:
    h = _normalize_height(env, height)
    res = env.state_store.load_abci_responses(h)
    if res is None:
        raise RPCError(INTERNAL_ERROR, f"no results for height {h}")
    eb = res.end_block
    return {
        "height": enc.i64(h),
        "txs_results": [enc.deliver_tx_json(d) for d in res.deliver_txs],
        "begin_block_events": [enc.event_json(e) for e in res.begin_block_events],
        "end_block_events": [enc.event_json(e) for e in (eb.events if eb else [])],
        "validator_updates": [
            {
                "pub_key": enc.pub_key_json(vu.pub_key),
                "power": enc.i64(vu.power),
            }
            for vu in (eb.validator_updates if eb else [])
        ],
        "consensus_param_updates": None,
    }


def validators(env: Environment, height=None, page=None, per_page=None) -> dict:
    h = _normalize_height(env, height)
    vals = env.state_store.load_validators(h)
    if vals is None:
        raise RPCError(INTERNAL_ERROR, f"no validators at height {h}")
    all_vals = vals.validators
    per = min(int(per_page) if per_page else 30, 100)
    pg = max(int(page) if page else 1, 1)
    start = (pg - 1) * per
    return {
        "block_height": enc.i64(h),
        "validators": [enc.validator_json(v) for v in all_vals[start : start + per]],
        "count": enc.i64(len(all_vals[start : start + per])),
        "total": enc.i64(len(all_vals)),
    }


def consensus_params(env: Environment, height=None) -> dict:
    h = _normalize_height(env, height)
    params = env.state_store.load_consensus_params(h)
    if params is None:
        raise RPCError(INTERNAL_ERROR, f"no consensus params at height {h}")
    return {"block_height": enc.i64(h), "consensus_params": enc.consensus_params_json(params)}


def consensus_state(env: Environment) -> dict:
    rs = env.consensus.rs
    return {
        "round_state": {
            "height/round/step": f"{rs.height}/{rs.round}/{int(rs.step)}",
            "height": enc.i64(rs.height),
            "round": rs.round,
            "step": rs.step.name,
            "proposal_block_hash": enc.hexu(
                rs.proposal_block.hash() if rs.proposal_block else b""
            ),
            "locked_block_hash": enc.hexu(
                rs.locked_block.hash() if rs.locked_block else b""
            ),
            "valid_block_hash": enc.hexu(rs.valid_block.hash() if rs.valid_block else b""),
        }
    }


def dump_consensus_state(env: Environment) -> dict:
    rs = env.consensus.rs
    out = consensus_state(env)["round_state"]
    out["validators"] = {
        "validators": [enc.validator_json(v) for v in rs.validators.validators]
        if rs.validators
        else [],
    }
    votes = []
    if rs.votes is not None:
        for r in range(rs.round + 1):
            pv = rs.votes.prevotes(r)
            pc = rs.votes.precommits(r)
            votes.append(
                {
                    "round": r,
                    "prevotes_bit_array": str(pv.bit_array()) if pv else "",
                    "precommits_bit_array": str(pc.bit_array()) if pc else "",
                }
            )
    out["height_vote_set"] = votes
    # per-peer round state (reference consensus.go DumpConsensusState →
    # PeerStateJSON): what each peer CLAIMS about its height/round/step
    # and which votes/parts we believe it already has — the operator-side
    # view the timeline analyzer correlates against
    peers = []
    if env.consensus_reactor is not None:
        for pid, ps in env.consensus_reactor.peers.items():
            peers.append({"node_address": pid, "peer_state": ps.snapshot()})
    out["peers"] = peers
    return {"round_state": out}


# ---------------------------------------------------------------------------
# tx routes
# ---------------------------------------------------------------------------

def _bytes_param(v) -> bytes:
    if isinstance(v, bytes):
        return v
    if isinstance(v, str):
        if v.startswith("0x") or v.startswith("0X"):
            return bytes.fromhex(v[2:])
        try:
            return base64.b64decode(v, validate=True)
        except Exception:
            try:
                return bytes.fromhex(v)
            except ValueError:
                raise RPCError(INVALID_PARAMS, f"cannot decode bytes param {v!r}") from None
    raise RPCError(INVALID_PARAMS, f"cannot decode bytes param {v!r}")


_tx_commit_seq = itertools.count(1)


def _mempool_full_rpc_error(e) -> RPCError:
    """Map a MempoolFullError (capacity) or MempoolBackpressureError
    (admission-control shedding) to the structured MEMPOOL_FULL
    JSON-RPC error — clients distinguish backpressure (retry after the
    hint) from faults by code, not by parsing a message string."""
    data = {
        "code": "mempool_full",
        "num_txs": getattr(e, "num_txs", 0),
        "total_bytes": getattr(e, "total_bytes", 0),
        "retry_after_ms": getattr(e, "retry_after_ms", 0),
    }
    shed_level = getattr(e, "shed_level", 0)
    if shed_level:
        data["code"] = "backpressure"
        data["shed_level"] = shed_level
        data["tx_class"] = getattr(e, "tx_class", "")
    return RPCError(MEMPOOL_FULL, str(e), data=data)


def broadcast_tx_async(env: Environment, tx=None) -> dict:
    from tendermint_tpu.mempool.mempool import MempoolFullError

    data = _bytes_param(tx)
    tx_hash = tmhash.sum_sha256(data)
    if env.txlife.enabled:
        env.txlife.stamp(tx_hash, "rpc")
    # fire-and-forget (reference mempool.go:22-36): CheckTx result is
    # ignored, but a structural rejection still surfaces as the typed
    # error so async submitters see backpressure too
    try:
        env.mempool.check_tx(data)
    except MempoolFullError as e:
        raise _mempool_full_rpc_error(e) from e
    return {"code": 0, "data": "", "log": "", "hash": enc.hexu(tx_hash)}


def broadcast_tx_sync(env: Environment, tx=None) -> dict:
    from tendermint_tpu.mempool.mempool import MempoolFullError

    data = _bytes_param(tx)
    tx_hash = tmhash.sum_sha256(data)
    if env.txlife.enabled:
        env.txlife.stamp(tx_hash, "rpc")
    try:
        res = env.mempool.check_tx(data)
    except MempoolFullError as e:
        raise _mempool_full_rpc_error(e) from e
    except Exception as e:
        raise RPCError(INTERNAL_ERROR, str(e)) from e
    return {
        "code": res.code,
        "data": enc.b64(res.data),
        "log": res.log,
        "codespace": res.codespace,
        "hash": enc.hexu(tx_hash),
    }


async def broadcast_tx_commit(env: Environment, tx=None) -> dict:
    """CheckTx, then wait for the tx to be committed (reference
    rpc/core/mempool.go:55-136, 10s timeout)."""
    from tendermint_tpu.mempool.mempool import MempoolFullError

    data = _bytes_param(tx)
    tx_hash = tmhash.sum_sha256(data)
    if env.txlife.enabled:
        env.txlife.stamp(tx_hash, "rpc")
    if env.event_bus is None:
        raise RPCError(INTERNAL_ERROR, "event bus unavailable")
    # unique per request: two concurrent broadcasts of the SAME tx must not
    # collide on the subscriber id (reference uses the caller's remote addr)
    subscriber = f"tx-commit-{tx_hash.hex()[:16]}-{next(_tx_commit_seq)}"
    query = tmevents.query_for_tx_hash(tx_hash.hex())
    try:
        sub = env.event_bus.subscribe(subscriber, query, capacity=8)
    except ValueError as e:
        raise RPCError(INTERNAL_ERROR, str(e)) from e
    try:
        try:
            check = env.mempool.check_tx(data)
        except MempoolFullError as e:
            raise _mempool_full_rpc_error(e) from e
        if check.code != 0:
            return {
                "check_tx": enc.deliver_tx_json(check),
                "deliver_tx": enc.deliver_tx_json(abci.ResponseDeliverTx()),
                "hash": enc.hexu(tx_hash),
                "height": enc.i64(0),
            }
        timeout_ms = getattr(
            getattr(env.config, "rpc", None), "timeout_broadcast_tx_commit_ms", 10_000
        )
        try:
            msg = await asyncio.wait_for(sub.next(), timeout_ms / 1000.0)
        except asyncio.TimeoutError:
            raise RPCError(
                INTERNAL_ERROR, "timed out waiting for tx to be included in a block"
            ) from None
        except SubscriptionCancelledError as e:
            raise RPCError(INTERNAL_ERROR, f"subscription cancelled: {e}") from e
        tr = msg.data.tx_result
        return {
            "check_tx": enc.deliver_tx_json(check),
            "deliver_tx": enc.deliver_tx_json(tr.result),
            "hash": enc.hexu(tx_hash),
            "height": enc.i64(tr.height),
        }
    finally:
        try:
            env.event_bus.unsubscribe_all(subscriber)
        except KeyError:
            pass


def unconfirmed_txs(env: Environment, limit=None) -> dict:
    # clamp below too: reap_max_txs treats n<0 as "the whole mempool"
    n = max(min(int(limit) if limit else 30, 100), 0)
    txs = env.mempool.reap_max_txs(n)
    return {
        "n_txs": enc.i64(len(txs)),
        "total": enc.i64(env.mempool.size()),
        "total_bytes": enc.i64(env.mempool.tx_bytes()),
        "txs": [enc.b64(t) for t in txs],
    }


def num_unconfirmed_txs(env: Environment) -> dict:
    return {
        "n_txs": enc.i64(env.mempool.size()),
        "total": enc.i64(env.mempool.size()),
        "total_bytes": enc.i64(env.mempool.tx_bytes()),
    }


def check_tx(env: Environment, tx=None) -> dict:  # noqa: A002
    """Run a tx through the app's CheckTx WITHOUT adding it to the mempool
    (reference rpc/core/mempool.go:161-167: goes straight to the mempool
    proxy connection, bypassing the cache and the pool)."""
    data = _bytes_param(tx)
    res = env.mempool.app.check_tx_sync(
        abci.RequestCheckTx(tx=data, type=abci.CheckTxType.NEW)
    )
    return enc.deliver_tx_json(res)


def tx(env: Environment, hash=None, prove=None) -> dict:  # noqa: A002
    if not hash:
        raise RPCError(INVALID_PARAMS, "hash is required")
    r = env.tx_indexer.get(_bytes_param(hash))
    if r is None:
        raise RPCError(INTERNAL_ERROR, f"tx not found: {hash}")
    out = enc.tx_result_json(r)
    if prove:
        b = env.block_store.load_block(r.height)
        if b is not None:
            from tendermint_tpu.crypto.merkle import proofs_from_byte_slices

            root, proofs = proofs_from_byte_slices([bytes(t) for t in b.data.txs])
            p = proofs[r.index]
            out["proof"] = {
                "root_hash": enc.hexu(root),
                "data": enc.b64(r.tx),
                "proof": {
                    "total": enc.i64(p.total),
                    "index": enc.i64(p.index),
                    "leaf_hash": enc.b64(p.leaf_hash),
                    "aunts": [enc.b64(a) for a in p.aunts],
                },
            }
    return out


def tx_search(env: Environment, query=None, prove=None, page=None, per_page=None, order_by=None) -> dict:
    if not query:
        raise RPCError(INVALID_PARAMS, "query is required")
    try:
        q = parse_query(str(query))
    except Exception as e:
        raise RPCError(INVALID_PARAMS, f"bad query: {e}") from e
    try:
        results = env.tx_indexer.search(q)
    except RuntimeError as e:
        raise RPCError(INTERNAL_ERROR, str(e)) from e
    if order_by == "desc":
        results = list(reversed(results))
    per = min(int(per_page) if per_page else 30, 100)
    pg = max(int(page) if page else 1, 1)
    start = (pg - 1) * per
    page_results = results[start : start + per]
    return {
        "txs": [enc.tx_result_json(r) for r in page_results],
        "total_count": enc.i64(len(results)),
    }


# ---------------------------------------------------------------------------
# abci + evidence
# ---------------------------------------------------------------------------

def abci_info(env: Environment) -> dict:
    res = env.app_query_conn.info_sync(abci.RequestInfo())
    return {
        "response": {
            "data": res.data,
            "version": res.version,
            "app_version": enc.i64(res.app_version),
            "last_block_height": enc.i64(res.last_block_height),
            "last_block_app_hash": enc.b64(res.last_block_app_hash),
        }
    }


def abci_query(env: Environment, path=None, data=None, height=None, prove=None) -> dict:
    res = env.app_query_conn.query_sync(
        abci.RequestQuery(
            data=_bytes_param(data) if data else b"",
            path=str(path or ""),
            height=int(height) if height else 0,
            prove=bool(prove),
        )
    )
    return {
        "response": {
            "code": res.code,
            "log": res.log,
            "info": getattr(res, "info", ""),
            "index": enc.i64(getattr(res, "index", 0)),
            "key": enc.b64(res.key),
            "value": enc.b64(res.value),
            "height": enc.i64(res.height),
            "codespace": getattr(res, "codespace", ""),
        }
    }


def broadcast_evidence(env: Environment, evidence=None) -> dict:
    from tendermint_tpu.types.evidence import decode_evidence

    if not evidence:
        raise RPCError(INVALID_PARAMS, "evidence is required")
    try:
        ev = decode_evidence(_bytes_param(evidence))
        env.evidence_pool.add_evidence(ev)
    except Exception as e:
        raise RPCError(INTERNAL_ERROR, f"failed to add evidence: {e}") from e
    return {"hash": enc.hexu(ev.hash())}


# ---------------------------------------------------------------------------
# unsafe control routes (reference rpc/core/routes.go:50-56, net.go:37-77,
# mempool.go UnsafeFlushMempool) — registered only when config.rpc.unsafe
# ---------------------------------------------------------------------------

def _addr_list(v) -> list[str]:
    """Coerce a peers/seeds param to a list of address strings: URI GET
    delivers one comma-separated string, JSON POST a real array."""
    if isinstance(v, str):
        return [a.strip() for a in v.split(",") if a.strip()]
    if isinstance(v, (list, tuple)):
        return [str(a).strip() for a in v if str(a).strip()]
    raise RPCError(INVALID_PARAMS, f"expected address list or string, got {v!r}")


def _validated_addrs(env: Environment, addrs: list[str]) -> list[tuple[str, str]]:
    """Parse every id@host:port address BEFORE any side effect (the
    reference validates the whole list via NewNetAddressStrings first);
    returns [(peer_id, addr)]."""
    from tendermint_tpu.p2p.tcp import parse_net_address

    if env.router is None or env.transport is None or not hasattr(
        env.transport, "add_peer_address"
    ):
        raise RPCError(INTERNAL_ERROR, "p2p layer unavailable")
    out = []
    for addr in addrs:
        try:
            pid, _, _ = parse_net_address(addr)
        except ValueError as e:
            raise RPCError(INVALID_PARAMS, f"bad peer address {addr!r}: {e}") from e
        out.append((pid, addr))
    return out


def _dial_addrs(env: Environment, pairs: list[tuple[str, str]]) -> None:
    """Register pre-validated addresses and kick off background dials
    (reference DialPeersAsync); outcome is observable via /net_info."""
    loop = asyncio.get_running_loop()
    for pid, addr in pairs:
        env.transport.add_peer_address(addr)
        if pid not in env.router.peers:
            task = loop.create_task(env.router.dial(pid))
            task.add_done_callback(lambda t: t.cancelled() or t.exception())


async def dial_seeds(env: Environment, seeds=None) -> dict:
    if not seeds:
        raise RPCError(INVALID_PARAMS, "no seeds provided")
    _dial_addrs(env, _validated_addrs(env, _addr_list(seeds)))
    return {"log": "Dialing seeds in progress. See /net_info for details"}


async def dial_peers(env: Environment, peers=None, persistent=None,
                     unconditional=None, private=None) -> dict:
    """Reference UnsafeDialPeers (net.go:50-85): persistent peers get
    keep-connected backoff dialing, private ids are withheld from PEX
    gossip.  `unconditional` (peer-count-cap exemption) is accepted but
    a no-op: this framework does not hard-cap connected peers."""
    if not peers:
        raise RPCError(INVALID_PARAMS, "no peers provided")
    pairs = _validated_addrs(env, _addr_list(peers))
    if persistent and env.add_persistent_peer is not None:
        for _, addr in pairs:
            env.add_persistent_peer(addr)
    if private and env.add_private_peer_id is not None:
        for pid, _ in pairs:
            env.add_private_peer_id(pid)
    _dial_addrs(env, pairs)
    return {"log": "Dialing peers in progress. See /net_info for details"}


def unsafe_flush_mempool(env: Environment) -> dict:
    env.mempool.flush()
    return {}


# ---------------------------------------------------------------------------
# route table (reference rpc/core/routes.go:10-47)
# ---------------------------------------------------------------------------

ROUTES: dict[str, object] = {
    "health": health,
    "status": status,
    "net_info": net_info,
    "genesis": genesis,
    "blockchain": blockchain,
    "block": block,
    "block_by_hash": block_by_hash,
    "block_results": block_results,
    "commit": commit,
    "check_tx": check_tx,
    "validators": validators,
    "consensus_params": consensus_params,
    "consensus_state": consensus_state,
    "dump_consensus_state": dump_consensus_state,
    "broadcast_tx_async": broadcast_tx_async,
    "broadcast_tx_sync": broadcast_tx_sync,
    "broadcast_tx_commit": broadcast_tx_commit,
    "unconfirmed_txs": unconfirmed_txs,
    "num_unconfirmed_txs": num_unconfirmed_txs,
    "tx": tx,
    "tx_search": tx_search,
    "abci_info": abci_info,
    "abci_query": abci_query,
    "broadcast_evidence": broadcast_evidence,
}

# merged into the served table when config.rpc.unsafe is set
# (reference rpc/core/routes.go:50-56 AddUnsafeRoutes)
UNSAFE_ROUTES: dict[str, object] = {
    "dial_seeds": dial_seeds,
    "dial_peers": dial_peers,
    "unsafe_flush_mempool": unsafe_flush_mempool,
}

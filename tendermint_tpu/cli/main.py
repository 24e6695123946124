"""CLI entry points.

Parity: reference cmd/tendermint/commands/ — init.go, run_node.go,
testnet.go, gen_validator.go, gen_node_key.go, show_node_id.go,
show_validator.go, reset_priv_validator.go, version.go.  cobra/viper
become argparse + the TOML config loader; flags override file values
the same way (flag > config.toml > default).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import signal
import sys
import time

VERSION = "0.1.0"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _home(args) -> str:
    return os.path.expanduser(args.home)


def _load_config(args):
    from tendermint_tpu.config import load_config

    cfg = load_config(_home(args))
    # flag overrides (reference run_node.go flag binding)
    for flag, (section, key) in _FLAG_MAP.items():
        v = getattr(args, flag, None)
        if v is not None:
            setattr(getattr(cfg, section), key, v)
    return cfg


_FLAG_MAP = {
    "moniker": ("base", "moniker"),
    "proxy_app": ("base", "proxy_app"),
    "abci": ("base", "abci"),
    "fast_sync": ("base", "fast_sync"),
    "db_backend": ("base", "db_backend"),
    "log_level": ("base", "log_level"),
    "rpc_laddr": ("rpc", "laddr"),
    "p2p_laddr": ("p2p", "laddr"),
    "p2p_persistent_peers": ("p2p", "persistent_peers"),
    "p2p_seeds": ("p2p", "seeds"),
    "consensus_create_empty_blocks": ("consensus", "create_empty_blocks"),
}


def _add_node_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--moniker", help="node name")
    p.add_argument("--proxy-app", dest="proxy_app",
                   help="ABCI app (builtin name or socket address)")
    p.add_argument("--abci", choices=["builtin", "socket", "grpc"],
                   help="ABCI transport")
    p.add_argument("--fast-sync", dest="fast_sync", action="store_true", default=None)
    p.add_argument("--no-fast-sync", dest="fast_sync", action="store_false")
    p.add_argument("--db-backend", dest="db_backend")
    p.add_argument("--log-level", dest="log_level")
    p.add_argument("--rpc.laddr", dest="rpc_laddr", help="RPC listen address")
    p.add_argument("--p2p.laddr", dest="p2p_laddr", help="p2p listen address")
    p.add_argument("--p2p.persistent-peers", dest="p2p_persistent_peers",
                   help="comma-separated id@host:port")
    p.add_argument("--p2p.seeds", dest="p2p_seeds")
    p.add_argument("--consensus.create-empty-blocks",
                   dest="consensus_create_empty_blocks",
                   type=lambda s: s.lower() == "true", default=None)


# ---------------------------------------------------------------------------
# commands
# ---------------------------------------------------------------------------

def cmd_init(args) -> int:
    """reference cmd/tendermint/commands/init.go"""
    from tendermint_tpu.config import default_config, write_config
    from tendermint_tpu.node.node_key import load_or_gen_node_key
    from tendermint_tpu.privval.file_pv import load_or_gen_file_pv
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    home = _home(args)
    cfg = default_config(home)
    cfg.ensure_dirs()

    if os.path.exists(cfg.config_file):
        print(f"found config file at {cfg.config_file}; not overwriting")
    else:
        write_config(cfg)
        print(f"wrote config to {cfg.config_file}")

    key_type = getattr(args, "key_type", "ed25519")
    pv = load_or_gen_file_pv(cfg.priv_validator_key_file,
                             cfg.priv_validator_state_file, key_type=key_type)
    nk = load_or_gen_node_key(cfg.node_key_file)

    if os.path.exists(cfg.genesis_file):
        print(f"found genesis file at {cfg.genesis_file}; not overwriting")
    else:
        chain_id = args.chain_id or f"test-chain-{os.urandom(3).hex()}"
        gen = GenesisDoc(
            chain_id=chain_id,
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pub_key=pv.get_pub_key(), power=10)],
        )
        if key_type != "ed25519":
            gen.consensus_params.validator.pub_key_types = ["ed25519", key_type]
        with open(cfg.genesis_file, "w") as fh:
            fh.write(gen.to_json())
        print(f"wrote genesis (chain {chain_id}) to {cfg.genesis_file}")
    print(f"node id: {nk.node_id}")
    return 0


def cmd_start(args) -> int:
    """reference cmd/tendermint/commands/run_node.go"""
    from tendermint_tpu.node import Node
    from tendermint_tpu.utils.log import new_logger

    cfg = _load_config(args)
    cfg.validate_basic()
    logger = new_logger(level=cfg.base.log_level)
    node = Node(cfg, logger=logger)

    # TM_TPU_PROFILE=<path>: cProfile the whole node process, dumped on
    # clean shutdown — the measurement tool behind docs/performance.md's
    # localnet throughput analysis (pstats format; inspect with snakeviz
    # or pstats.Stats)
    profile_path = os.environ.get("TM_TPU_PROFILE")
    prof = None
    if profile_path:
        import cProfile

        prof = cProfile.Profile()
        prof.enable()

    async def run():
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop_ev.set)
        await node.start()
        logger.info("node started", node_id=node.node_key.node_id,
                    chain=node.genesis.chain_id)
        await stop_ev.wait()
        logger.info("shutting down")
        await node.stop()

    try:
        asyncio.run(run())
    finally:
        if prof is not None:
            prof.disable()
            prof.dump_stats(profile_path)
    return 0


def cmd_gen_validator(args) -> int:
    """reference gen_validator.go: print a fresh priv validator key."""
    from tendermint_tpu.crypto.keys import gen_priv_key

    from tendermint_tpu.utils import tmjson

    if getattr(args, "key_type", "ed25519") == "secp256k1":
        from tendermint_tpu.crypto import secp256k1

        key = secp256k1.gen_priv_key()
    else:
        key = gen_priv_key()
    print(json.dumps({
        "address": key.pub_key().address().hex().upper(),
        "pub_key": tmjson.encode(key.pub_key()),
        "priv_key": tmjson.encode(key),
    }, indent=2))
    return 0


def cmd_gen_node_key(args) -> int:
    from tendermint_tpu.node.node_key import load_or_gen_node_key

    home = _home(args)
    path = os.path.join(home, "config", "node_key.json")
    if os.path.exists(path):
        print(f"node key already exists at {path}", file=sys.stderr)
        return 1
    nk = load_or_gen_node_key(path)
    print(nk.node_id)
    return 0


def cmd_show_node_id(args) -> int:
    from tendermint_tpu.config import load_config
    from tendermint_tpu.node.node_key import NodeKey

    cfg = load_config(_home(args))
    nk = NodeKey.load(cfg.node_key_file)
    print(nk.node_id)
    return 0


def cmd_show_validator(args) -> int:
    from tendermint_tpu.config import load_config
    from tendermint_tpu.privval.file_pv import FilePV

    cfg = load_config(_home(args))
    from tendermint_tpu.utils import tmjson

    pv = FilePV.load(cfg.priv_validator_key_file, cfg.priv_validator_state_file)
    print(json.dumps(tmjson.encode(pv.get_pub_key())))
    return 0


def cmd_unsafe_reset_all(args) -> int:
    """reference reset_priv_validator.go ResetAll: wipe data, keep keys,
    reset the privval sign-state."""
    from tendermint_tpu.config import load_config

    cfg = load_config(_home(args))
    if os.path.isdir(cfg.db_dir):
        shutil.rmtree(cfg.db_dir)
        print(f"removed {cfg.db_dir}")
    os.makedirs(cfg.db_dir, exist_ok=True)
    if os.path.exists(cfg.priv_validator_key_file):
        # fresh zeroed sign-state (the old one went with the data dir)
        from tendermint_tpu.privval.file_pv import _LastSignState

        _LastSignState(cfg.priv_validator_state_file).save()
        print("reset priv validator state")
    return 0


def cmd_testnet(args) -> int:
    """reference testnet.go: generate N validator homes with a shared
    genesis and fully-wired persistent peers (localhost port layout)."""
    from tendermint_tpu.config import default_config, write_config
    from tendermint_tpu.node.node_key import load_or_gen_node_key
    from tendermint_tpu.privval.file_pv import load_or_gen_file_pv
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    n = args.v
    out = args.o
    chain_id = args.chain_id or f"chain-{os.urandom(3).hex()}"
    homes, pvs, nks = [], [], []
    for i in range(n):
        home = os.path.join(out, f"{args.node_dir_prefix}{i}")
        cfg = default_config(home)
        cfg.ensure_dirs()
        pvs.append(load_or_gen_file_pv(cfg.priv_validator_key_file,
                                       cfg.priv_validator_state_file,
                                       key_type=getattr(args, "key_type",
                                                        "ed25519")))
        nks.append(load_or_gen_node_key(cfg.node_key_file))
        homes.append(home)

    gen = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pub_key=pv.get_pub_key(), power=1)
                    for pv in pvs],
    )
    if getattr(args, "key_type", "ed25519") != "ed25519":
        gen.consensus_params.validator.pub_key_types = ["ed25519", args.key_type]
    if args.per_host:
        # one node per host (docker-compose / real deployments): every
        # node uses the standard ports, peers resolve by hostname
        # (reference testnet.go --hostname-prefix)
        peers = ",".join(
            f"{nks[i].node_id}@{args.node_dir_prefix}{i}:26656"
            for i in range(n)
        )
    else:
        peers = ",".join(
            f"{nks[i].node_id}@{args.hostname}:{args.starting_port + 2 * i}"
            for i in range(n)
        )
    for i, home in enumerate(homes):
        cfg = default_config(home)
        cfg.base.moniker = f"node{i}"
        if args.per_host:
            cfg.p2p.laddr = "tcp://0.0.0.0:26656"
            cfg.rpc.laddr = "tcp://0.0.0.0:26657"
        else:
            cfg.p2p.laddr = f"tcp://0.0.0.0:{args.starting_port + 2 * i}"
            cfg.rpc.laddr = f"tcp://127.0.0.1:{args.starting_port + 2 * i + 1}"
        cfg.p2p.persistent_peers = ",".join(
            p for j, p in enumerate(peers.split(",")) if j != i
        )
        write_config(cfg)
        with open(cfg.genesis_file, "w") as fh:
            fh.write(gen.to_json())
    print(f"wrote {n} node homes under {out} (chain {chain_id})")
    return 0


def cmd_signer_harness(args) -> int:
    """Conformance-test a remote signer (reference
    tools/tm-signer-harness/internal/test_harness.go): listen like a
    node, wait for the signer to dial in, then check (1) the public key
    matches this home's validator key, (2) proposal signing verifies,
    (3) prevote/precommit signing verifies, (4) the signer refuses a
    conflicting sign request at the same height/round/step."""
    from tendermint_tpu.config import load_config
    from tendermint_tpu.crypto import tmhash
    from tendermint_tpu.privval.file_pv import load_or_gen_file_pv
    from tendermint_tpu.privval.socket_pv import SignerClient
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.proposal import Proposal
    from tendermint_tpu.types.vote import Vote
    from tendermint_tpu.utils.log import new_logger

    logger = new_logger(level="info")
    cfg = load_config(_home(args))
    chain_id = args.chain_id
    host, port = args.addr.rsplit(":", 1)

    client = SignerClient(host=host.replace("tcp://", ""), port=int(port),
                          logger=logger)
    addr = client.start()
    logger.info("harness listening; start the signer now",
                addr=f"{addr[0]}:{addr[1]}")
    failures = 0
    try:
        client.wait_for_signer(timeout=args.accept_timeout)

        # 1. public key (test_harness.go TestPublicKey)
        remote = client.get_pub_key()
        local_pv = load_or_gen_file_pv(cfg.priv_validator_key_file,
                                       cfg.priv_validator_state_file)
        local = local_pv.get_pub_key()
        if remote.bytes_() == local.bytes_():
            logger.info("PASS public key matches", key=remote.bytes_().hex()[:16])
        else:
            logger.error("FAIL public key mismatch",
                         local=local.bytes_().hex()[:16],
                         remote=remote.bytes_().hex()[:16])
            failures += 1

        h = tmhash.sum_sha256(b"hash")
        bid = BlockID(hash=h, part_set_header=PartSetHeader(total=100, hash=h))

        # 2. proposal signing (TestSignProposal)
        prop = Proposal(height=100, round=0, pol_round=-1, block_id=bid,
                        timestamp_ns=1_700_000_000 * 10**9)
        try:
            client.sign_proposal(chain_id, prop)
            if remote.verify_signature(prop.sign_bytes(chain_id), prop.signature):
                logger.info("PASS proposal signature verifies")
            else:
                logger.error("FAIL proposal signature invalid")
                failures += 1
        except Exception as e:
            logger.error("FAIL proposal signing", err=str(e))
            failures += 1

        # 3. votes (TestSignVote: prevote + precommit)
        for vt in (SignedMsgType.PREVOTE, SignedMsgType.PRECOMMIT):
            v = Vote(type=vt, height=100, round=0, block_id=bid,
                     timestamp_ns=1_700_000_000 * 10**9,
                     validator_address=remote.address(), validator_index=0)
            try:
                client.sign_vote(chain_id, v)
                if remote.verify_signature(v.sign_bytes(chain_id), v.signature):
                    logger.info("PASS vote signature verifies", type=vt.name)
                else:
                    logger.error("FAIL vote signature invalid", type=vt.name)
                    failures += 1
            except Exception as e:
                logger.error("FAIL vote signing", err=str(e), type=vt.name)
                failures += 1

        # 4. double-sign refusal: same HRS, different block
        h2 = tmhash.sum_sha256(b"other")
        conflicting = Vote(
            type=SignedMsgType.PRECOMMIT, height=100, round=0,
            block_id=BlockID(hash=h2,
                             part_set_header=PartSetHeader(total=100, hash=h2)),
            timestamp_ns=1_700_000_001 * 10**9,
            validator_address=remote.address(), validator_index=0,
        )
        try:
            client.sign_vote(chain_id, conflicting)
            logger.error("FAIL signer double-signed a conflicting precommit")
            failures += 1
        except Exception:
            logger.info("PASS signer refused the conflicting precommit")
    except Exception as e:
        logger.error("harness aborted", err=str(e))
        failures += 1
    finally:
        client.close()
    print(f"signer-harness: {4 - min(failures, 4)}/4 checks passed"
          if failures <= 4 else f"signer-harness: failures={failures}")
    return 1 if failures else 0


def cmd_signer(args) -> int:
    """Run a remote signer for this home's priv validator key.

    socket transport (default): dial the node's priv_validator_laddr
    (reference privval/signer_server.go).  grpc transport: LISTEN on
    --addr and let the node dial us (reference privval/grpc/server.go)."""
    from tendermint_tpu.config import load_config
    from tendermint_tpu.privval.file_pv import load_or_gen_file_pv
    from tendermint_tpu.utils.log import new_logger

    cfg = load_config(_home(args))
    pv = load_or_gen_file_pv(cfg.priv_validator_key_file, cfg.priv_validator_state_file)
    logger = new_logger(level="info")

    async def run():
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop_ev.set)
        if args.transport == "grpc":
            from tendermint_tpu.privval.grpc_pv import GRPCSignerServer

            server = GRPCSignerServer(pv, logger=logger)
            await server.start(args.addr)
        else:
            from tendermint_tpu.privval.socket_pv import SignerServer

            host, _, port = args.addr.rpartition(":")
            server = SignerServer(pv, host or "127.0.0.1", int(port), logger=logger)
            await server.start()
        logger.info("signer serving", validator=pv.get_pub_key().address().hex())
        await stop_ev.wait()
        await server.stop()

    asyncio.run(run())
    return 0


def _debug_snapshot(out: str, base: str, pprof_base: str, home: str) -> list[str]:
    """One archive of a running node's observable state."""
    import urllib.request

    os.makedirs(out, exist_ok=True)
    collected = []
    for route in ("status", "consensus_state", "dump_consensus_state",
                  "net_info", "num_unconfirmed_txs", "genesis"):
        try:
            with urllib.request.urlopen(f"{base}/{route}", timeout=10) as r:
                doc = json.loads(r.read())
            with open(os.path.join(out, f"{route}.json"), "w") as fh:
                json.dump(doc.get("result", doc), fh, indent=2)
            collected.append(route)
        except Exception as e:
            print(f"skip {route}: {e}", file=sys.stderr)
    if pprof_base:
        # goroutine/heap analogs (reference dump.go profile collection)
        for ep in ("goroutine", "heap"):
            try:
                with urllib.request.urlopen(
                    f"{pprof_base}/debug/pprof/{ep}", timeout=10
                ) as r:
                    with open(os.path.join(out, f"pprof_{ep}.txt"), "wb") as fh:
                        fh.write(r.read())
                collected.append(f"pprof_{ep}")
            except Exception as e:
                print(f"skip pprof {ep}: {e}", file=sys.stderr)
    cfg_path = os.path.join(home, "config", "config.toml")
    if os.path.exists(cfg_path):
        import shutil as _sh

        _sh.copy(cfg_path, os.path.join(out, "config.toml"))
        collected.append("config.toml")
    return collected


def cmd_debug(args) -> int:
    """Snapshot a running node's observable state over RPC into a
    directory (reference cmd/tendermint/commands/debug: dump.go —
    one-shot, or periodic archives with --interval)."""
    import time as _time

    base = args.rpc_laddr or "http://127.0.0.1:26657"
    if base.startswith("tcp://"):
        base = "http://" + base[len("tcp://"):]
    pprof_base = args.pprof_laddr or ""
    if pprof_base.startswith("tcp://"):
        pprof_base = "http://" + pprof_base[len("tcp://"):]
    home = _home(args)

    if not args.interval:
        collected = _debug_snapshot(args.output_dir, base, pprof_base, home)
        print(f"wrote {len(collected)} artifacts to {args.output_dir}: "
              f"{', '.join(collected)}")
        return 0 if collected else 1

    # periodic mode (reference debug dump --frequency)
    n = 0
    try:
        while args.count == 0 or n < args.count:
            stamp = _time.strftime("%Y%m%d-%H%M%S")
            out = os.path.join(args.output_dir, stamp)
            collected = _debug_snapshot(out, base, pprof_base, home)
            n += 1
            print(f"[{stamp}] archive {n}: {len(collected)} artifacts")
            if args.count and n >= args.count:
                break
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    return 0


def cmd_replay(args) -> int:
    """Replay the consensus WAL through a fresh node (reference
    consensus/replay_file.go RunReplayFile): rebuilds consensus state by
    re-handshaking the app against the block store, then reports the WAL
    tail relative to the store."""
    from tendermint_tpu.config import load_config
    from tendermint_tpu.consensus.wal import WAL
    from tendermint_tpu.node import Node

    cfg = load_config(_home(args))
    cfg.rpc.laddr = ""  # no servers during replay
    cfg.instrumentation.prometheus = False
    node = Node(cfg)  # construction runs the handshake replay
    height = node.block_store.height()
    print(f"store height {height}; app replayed to height "
          f"{node.initial_state.last_block_height}")
    wal = WAL(cfg.wal_file)
    try:
        n_msgs = len(wal.all_messages())
        print(f"WAL holds {n_msgs} records")
    except Exception as e:
        print(f"WAL read ended: {e}")
    finally:
        wal.close()

    async def _close():
        # node never started; release resources
        node.event_bus.shutdown()
        node.wal.close()

    asyncio.run(_close())
    return 0


def cmd_wal2json(args) -> int:
    """Dump a consensus WAL file as JSON lines (reference
    scripts/wal2json): lossless — each record carries its raw payload
    base64 next to a human-readable summary, so json2wal can rebuild a
    byte-equivalent WAL."""
    import base64
    import json as _json
    import sys

    from tendermint_tpu.consensus.messages import encode_wal_message
    from tendermint_tpu.consensus.wal import DataCorruptionError, decode_records

    with open(args.wal_file, "rb") as fh:
        data = fh.read()
    try:
        for rec in decode_records(data):
            doc = {
                "time_ns": rec.time_ns,
                "type": type(rec.msg).__name__,
                "msg_b64": base64.b64encode(encode_wal_message(rec.msg)).decode(),
            }
            height = getattr(rec.msg, "height", None)
            if height is None:
                inner = getattr(rec.msg, "msg", None)
                height = getattr(inner, "height", None) or getattr(
                    getattr(inner, "vote", None), "height", None
                ) or getattr(getattr(inner, "proposal", None), "height", None)
            if height is not None:
                doc["height"] = height
            print(_json.dumps(doc))
    except DataCorruptionError as e:
        print(f"WAL corrupt: {e}", file=sys.stderr)
        return 1
    return 0


def cmd_json2wal(args) -> int:
    """Rebuild a framed WAL from wal2json output (reference
    scripts/json2wal)."""
    import base64
    import json as _json
    import sys

    from tendermint_tpu.consensus.messages import decode_wal_message
    from tendermint_tpu.consensus.wal import encode_record

    out = open(args.wal_file, "wb")
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            doc = _json.loads(line)
            msg = decode_wal_message(base64.b64decode(doc["msg_b64"]))
            out.write(encode_record(int(doc["time_ns"]), msg))
    finally:
        out.close()
    return 0


def cmd_abci_server(args) -> int:
    """Serve a builtin app over the ABCI socket or gRPC protocol
    (reference abci-cli kvstore/counter servers, abci/cmd/abci-cli)."""
    from tendermint_tpu.node.node import _builtin_app
    from tendermint_tpu.utils.log import new_logger

    logger = new_logger(level="info")
    app = _builtin_app(args.app, snapshot_interval=args.snapshot_interval)
    if args.transport == "grpc":
        from tendermint_tpu.abci.grpc_app import GRPCAppServer

        server = GRPCAppServer(app, logger=logger)
    else:
        from tendermint_tpu.abci.socket import SocketServer

        server = SocketServer(app, logger=logger)

    async def run():
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop_ev.set)
        await server.start(args.addr)
        await stop_ev.wait()
        await server.stop()

    asyncio.run(run())
    return 0


def cmd_abci_cli(args) -> int:
    """Console/batch/one-shot driver against an ABCI socket server
    (reference abci/cmd/abci-cli: the conformance-test harness behind
    abci/tests/test_cli/)."""
    import sys

    from tendermint_tpu.abci.cli import CommandError, execute_line, run_batch, run_console
    from tendermint_tpu.abci.socket import SocketClient

    client = SocketClient(args.address)
    try:
        client.connect()
    except (ConnectionError, OSError) as e:
        print(f"error connecting to {args.address}: {e}", file=sys.stderr)
        return 1
    try:
        if args.abci_command == "batch":
            return run_batch(client, sys.stdin, sys.stdout)
        if args.abci_command == "console":
            return run_console(client, sys.stdin, sys.stdout)
        line = args.abci_command + (
            " " + " ".join(args.abci_args) if args.abci_args else ""
        )
        try:
            for ln in execute_line(client, line):
                print(ln)
        except CommandError as e:
            for ln in e.lines:
                print(ln)
            return 1
        return 0
    except (ConnectionError, OSError, EOFError) as e:
        # server dropped mid-command: report, don't traceback
        print(f"error talking to {args.address}: {e}", file=sys.stderr)
        return 1
    finally:
        client.close()


def cmd_light(args) -> int:
    """Run a light-client verifying proxy against a primary node
    (reference cmd/tendermint/commands/light.go)."""
    from tendermint_tpu.light.client import Client, TrustOptions
    from tendermint_tpu.light.http_provider import HTTPProvider
    from tendermint_tpu.light.proxy import LightProxy
    from tendermint_tpu.light.store import LightBlockStore
    from tendermint_tpu.store.db import open_db
    from tendermint_tpu.utils.log import new_logger

    logger = new_logger(level=args.log_level or "info")
    home = _home(args)
    os.makedirs(os.path.join(home, "light"), exist_ok=True)
    db = open_db("sqlite", os.path.join(home, "light", f"{args.chain_id}.db"))

    primary = HTTPProvider(args.chain_id, args.primary)
    witnesses = [HTTPProvider(args.chain_id, w)
                 for w in (args.witnesses or "").split(",") if w]
    client = Client(
        chain_id=args.chain_id,
        trust_options=TrustOptions(
            period_ns=args.trust_period * 10**9,
            height=args.trusted_height,
            hash=bytes.fromhex(args.trusted_hash),
        ),
        primary=primary,
        witnesses=witnesses or [primary],
        trusted_store=LightBlockStore(db),
        logger=logger,
    )
    proxy = LightProxy(client, args.primary, logger=logger)
    host, _, port = args.laddr.split("://")[-1].rpartition(":")

    async def run():
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop_ev.set)
        addr = await proxy.start(host or "127.0.0.1", int(port or 8888))
        logger.info("light proxy serving", addr=f"{addr[0]}:{addr[1]}",
                    primary=args.primary)
        await stop_ev.wait()
        await proxy.stop()

    asyncio.run(run())
    return 0


def cmd_gateway(args) -> int:
    """Run a standalone light-client gateway front end against a
    primary node (docs/gateway.md): the read endpoints light clients
    hammer are forwarded with a height-keyed response cache (immutable
    below the tip, invalidated on height advance), so N clients cost
    the primary ~1 client.  Node-embedded mode is TM_TPU_GATEWAY=1 on
    `start` instead."""
    from tendermint_tpu.gateway.frontend import GatewayProxy
    from tendermint_tpu.utils.log import new_logger

    logger = new_logger(level=args.log_level or "info")
    proxy = GatewayProxy(args.primary, logger=logger, timeout=args.timeout)
    host, _, port = args.laddr.split("://")[-1].rpartition(":")

    async def run():
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop_ev.set)
        addr = await proxy.start(host or "127.0.0.1", int(port or 8889))
        logger.info("gateway serving", addr=f"{addr[0]}:{addr[1]}",
                    primary=args.primary)
        await stop_ev.wait()
        await proxy.stop()

    asyncio.run(run())
    return 0


def _load_journals(args, wal: bool = False) -> "dict | None":
    """Shared journal loading for the timeline/txtrace subcommands:
    name resolution (testnet node-home directories), journal or WAL
    decoding, per-file error reporting.  None means a usage/IO error
    was already printed."""
    from tendermint_tpu.consensus.eventlog import (
        events_from_wal_file,
        read_events,
    )

    names = [n.strip() for n in (args.names or "").split(",") if n.strip()]
    journals = {}
    for i, path in enumerate(args.journals):
        if i < len(names):
            name = names[i]
        else:
            # default node name: the file's directory (testnet layouts
            # put each journal under its node home) or the file stem
            d = os.path.basename(os.path.dirname(os.path.abspath(path)))
            stem = os.path.splitext(os.path.basename(path))[0]
            name = d if len(args.journals) > 1 and d else stem
            if name in journals:
                name = f"{name}#{i}"
        try:
            events = (events_from_wal_file(path, node=name) if wal
                      else read_events(path))
        except OSError as e:
            print(f"cannot read {path}: {e}", file=sys.stderr)
            return None
        except Exception as e:
            print(f"cannot decode {path}: {e}", file=sys.stderr)
            return None
        journals[name] = events
    if not any(journals.values()):
        print("no events found in any input", file=sys.stderr)
        return None
    return journals


def cmd_timeline(args) -> int:
    """Merge N nodes' consensus event journals (TM_TPU_JOURNAL output;
    consensus/eventlog.py) into one cross-node timeline: proposal
    propagation, per-node polka and commit times, timeout distribution,
    vote-arrival skew, anomaly flags.  Cross-node clock skew is
    estimated from matched journal event pairs and corrected before
    alignment (--no-skew restores raw wall clocks).  With --wal the
    inputs are raw consensus WAL files instead and the journal subset is
    reconstructed offline (post-mortems where the journal was off)."""
    import json as _json

    from tendermint_tpu.cli.timeline import (
        build_timeline,
        estimate_offsets,
        render_timeline,
        report_json,
    )

    journals = _load_journals(args, wal=args.wal)
    if journals is None:
        return 1
    offsets = None if args.no_skew else estimate_offsets(journals)
    report = build_timeline(journals, offsets=offsets)
    if args.json:
        print(_json.dumps(report_json(report, offsets=offsets), indent=2))
    else:
        print(render_timeline(report, height=args.height, offsets=offsets))
    return 0


def cmd_txtrace(args) -> int:
    """Merge N nodes' event journals into per-transaction cross-node
    waterfalls (cli/txtrace.py): submit → gossip → propose → quorum →
    commit → apply, with skew-corrected timestamps (the same estimator
    the timeline uses).  Exit 0 when at least one tx lifecycle was
    found, 1 otherwise."""
    import json as _json

    from tendermint_tpu.cli.timeline import estimate_offsets
    from tendermint_tpu.cli.txtrace import build_txtrace, render_txtrace

    journals = _load_journals(args)
    if journals is None:
        return 1
    offsets = None if args.no_skew else estimate_offsets(journals)
    doc = build_txtrace(journals, offsets=offsets)
    if args.tx:
        want = args.tx.lower()
        doc["txs"] = [t for t in doc["txs"] if t["tx"].startswith(want)]
    if args.json:
        print(_json.dumps(doc, indent=2))
    else:
        print(render_txtrace(doc, limit=args.limit))
    return 0 if doc["txs"] else 1


def cmd_simnet(args) -> int:
    """Fault-injecting in-process scenario run (tendermint_tpu/simnet):
    stand up the scenario's node count over the FaultyNetwork, apply the
    fault schedule (partitions, slow links, churn with WAL replay,
    mavericks), and emit the analyzer-computed verdict as JSON.  Exit 0
    when every invariant held, 1 with the violated invariant named in
    `violations` otherwise (docs/simnet.md)."""
    import tempfile

    from tendermint_tpu.simnet.harness import run_scenario
    from tendermint_tpu.simnet.scenario import (
        generate_scenario,
        load_scenario,
    )
    from tendermint_tpu.utils.log import new_logger, nop_logger

    if bool(args.scenario) == (args.gen_seed is not None):
        print("simnet: exactly one of --scenario or --gen-seed required",
              file=sys.stderr)
        return 2
    try:
        if args.scenario:
            scenario = load_scenario(args.scenario)
        else:
            scenario = generate_scenario(args.gen_seed, args.gen_index)
        if args.time:
            # operator override: rerun any scenario file on the other
            # clock (e.g. confirm a virtual verdict against wall time)
            scenario.time = args.time
            scenario.validate()
    except (OSError, ValueError, ImportError) as e:
        print(f"simnet: cannot load scenario: {e}", file=sys.stderr)
        return 2

    logger = new_logger("tendermint_tpu.simnet") if args.verbose else nop_logger()
    root = args.root or tempfile.mkdtemp(prefix=f"simnet-{scenario.name}-")
    report = run_scenario(scenario, root, logger=logger)
    if not args.full:
        # the full timeline is bulky; keep the default report focused on
        # the verdict (--full restores it, and the journals stay under
        # --root for `tendermint-tpu timeline` post-mortems)
        report.pop("timeline", None)
    text = json.dumps(report, indent=2, default=str)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    print(text)
    if not args.root and not args.keep:
        shutil.rmtree(root, ignore_errors=True)
    else:
        print(f"# node homes (journals, WALs): {root}", file=sys.stderr)
    return 0 if report["ok"] else 1


def cmd_top(args) -> int:
    """Live ANSI dashboard over a node's RPC status + /metrics: consensus
    progress, peers + send queues, verify queue/occupancy/cache, jit
    compile events, device memory (cli/top.py).  `--once --json` emits
    one machine-readable snapshot."""
    from tendermint_tpu.cli.top import run_top

    return run_top(args.rpc_laddr, args.metrics_laddr,
                   interval=args.interval, once=args.once,
                   as_json=args.json, timeout=args.timeout)


def cmd_fleet(args) -> int:
    """Cluster dashboard + SLO verdicts over N nodes (cli/fleet.py):
    concurrent status+/metrics scrapes with per-node degradation,
    fleet-merged histograms/occupancy/compile/gateway/health rollups,
    and slo.toml burn-rate evaluation.  Exit 0 ok / 1 warn / 2 burning
    / 3 usage error (docs/fleet.md)."""
    from tendermint_tpu.cli.fleet import run_fleet

    return run_fleet(args.nodes, slo_path=args.slo, watch=args.watch,
                     once=args.once, as_json=args.json,
                     interval=args.interval, timeout=args.timeout)


def cmd_health(args) -> int:
    """One node's health-watchdog verdict over RPC (cli/health.py):
    per-detector status table or JSON, `--watch` refresh loop.  Exit 0
    ok / 1 warn / 2 critical (the firing detector is named) / 3 when
    the node is unreachable or the monitor is disabled
    (docs/observability.md "Health & watchdog")."""
    from tendermint_tpu.cli.health import run_health

    return run_health(args.rpc_laddr, watch=args.watch, as_json=args.json,
                      interval=args.interval, timeout=args.timeout)


def cmd_prof(args) -> int:
    """One node's statistical CPU profile over /debug/pprof/profile
    (cli/prof.py): top functions by self/cumulative samples per
    subsystem, `--seconds N` for a fresh delta capture, `--flame OUT`
    for flamegraph-ready folded text, `--watch` refresh loop; `--diff
    A.folded B.folded` is the function-level regression gate.  Exit 0
    ok / 1 diff regression / 2 usage error / 3 when the node is
    unreachable or the profiler is disabled
    (docs/observability.md "Continuous profiling")."""
    from tendermint_tpu.cli.prof import run_diff, run_prof

    if args.diff:
        return run_diff(args.diff[0], args.diff[1], as_json=args.json,
                        abs_threshold=args.abs_threshold,
                        rel_threshold=args.rel_threshold)
    return run_prof(args.pprof_laddr, seconds=args.seconds,
                    watch=args.watch, as_json=args.json, flame=args.flame,
                    interval=args.interval, timeout=args.timeout,
                    top_n=args.top)


def cmd_history(args) -> int:
    """One node's recorded metric time-series (cli/history.py): per-
    metric terminal sparklines, counter rates, quantiles-over-time —
    from `<home>/history/` segments on disk or a live node's
    `/debug/pprof/history`.  Exit 0 data / 1 empty range / 2 usage /
    3 unreachable or recorder disabled (docs/observability.md
    "Metric history")."""
    from tendermint_tpu.cli.history import run_history

    return run_history(args.pprof_laddr, home=args.home_dir,
                       metric=args.metric, since=args.since,
                       rate=args.rate, quantiles=args.quantiles,
                       list_metrics=args.list, as_json=args.json,
                       width=args.width, timeout=args.timeout)


def cmd_lint(args) -> int:
    """Repo-aware static analysis (tendermint_tpu/lint): six rules, each
    grounded in a shipped bug or a hot-path invariant.  Exit 0 = clean,
    1 = findings, 2 = usage error; `--json` is the scripting entry point
    (docs/linting.md)."""
    from tendermint_tpu.lint import run_cli

    return run_cli(paths=args.paths or None, as_json=args.json,
                   rules=args.rules, list_rules=args.list_rules)


def cmd_warm(args) -> int:
    """Ahead-of-time shape-plan warming (docs/tpu-verifier.md "AOT and
    warming"): compile every (kind, rung, impl) in the plan with
    jit().lower().compile(), serialize the executables where this jax
    supports it, and save the plan next to the persistent compile cache
    — so a restarted node/bench reaches full verify throughput in
    seconds and records zero cold-compile events.  Exit 0 = every entry
    warmed, 1 = some entries errored, 2 = usage error."""
    import json as _json

    from tendermint_tpu.ops import shape_plan

    if args.plan and args.rungs:
        print("--plan and --rungs are mutually exclusive", file=sys.stderr)
        return 2
    try:
        if args.plan:
            plan = shape_plan.load_plan(args.plan)
        elif args.rungs:
            plan = shape_plan.ShapePlan(
                [int(x) for x in args.rungs.split(",") if x.strip()],
                name="cli-rungs")
        else:
            stats = None
            if args.stats:
                with open(args.stats) as fh:
                    stats = _json.load(fh)
            plan = shape_plan.plan_for_warm(stats)
    except (OSError, ValueError, KeyError) as e:
        print(f"could not resolve a shape plan: {e}", file=sys.stderr)
        return 2
    impls = tuple(x.strip() for x in args.impls.split(",") if x.strip()) or None
    kinds = tuple(x.strip() for x in args.kinds.split(",") if x.strip()) or None

    if args.dry_run:
        report = {
            "plan": plan.to_dict(),
            "max_padding": round(plan.max_padding(), 4),
            "dry_run": True,
            "entries": [{"kind": k, "rung": r, "impl": i, "source": "dry-run"}
                        for k, r, i in plan.entries(kinds=kinds, impls=impls)]
                       + [{"kind": "verify_sharded", "rung": r, "impl": "",
                           "mesh": m, "source": "dry-run"}
                          for r, m in plan.mesh_entries()],
            "plan_path": shape_plan.plan_path(),
            "aot_dir": shape_plan.aot_dir(),
        }
    else:
        import jax

        from tendermint_tpu.utils import jaxcache

        jaxcache.enable(jax)
        report = shape_plan.warm_plan(plan, kinds=kinds, impls=impls,
                                      serialize=not args.no_serialize,
                                      save=not args.no_save)

    if args.json:
        print(_json.dumps(report))
    else:
        p = report["plan"]
        print(f"shape plan {p['name']!r}: {len(p['rungs'])} rungs "
              f"({p['rungs'][0]}..{p['rungs'][-1]}), "
              f"impls={','.join(impls or p['impls'])} "
              f"kinds={','.join(kinds or p['kinds'])} "
              f"max_padding={report['max_padding']}x")
        for e in report["entries"]:
            extra = ""
            if e.get("serialized"):
                extra = f"  serialized {e.get('serialized_bytes', 0)}B"
            elif e.get("serialized") is False:
                extra = "  (persistent-cache only)"
            if e.get("error"):
                extra = f"  ERROR: {e['error']}"
            print(f"  {e['kind']:>6} r{e['rung']:<6} {e['impl']:<6} "
                  f"{e['source']:<12} {e.get('seconds', 0.0):7.2f}s{extra}")
        if report.get("dry_run"):
            print(f"dry run — nothing compiled; plan would save to "
                  f"{report['plan_path']}")
        else:
            srcs = " ".join(f"{k}={v}"
                            for k, v in sorted(report["sources"].items()))
            print(f"warmed {len(report['entries'])} programs in "
                  f"{report['seconds_total']}s: {srcs}"
                  + (f"; plan saved to {report['plan_path']}"
                     if "plan_path" in report else ""))
    return 1 if any(e.get("error") for e in report["entries"]) else 0


def cmd_profile(args) -> int:
    """Per-rung kernel performance profiling (cli/profile.py): HLO
    cost-analysis FLOPs/bytes for every program in the selected shape
    plan plus budgeted timed windows (wall p50, sigs/s, FLOPs-util %)
    and optional Perfetto capture (docs/performance.md "Roofline").
    Exit 0 = every entry reported, 1 = some entries errored, 2 = usage
    error."""
    from tendermint_tpu.cli.profile import run_profile

    return run_profile(rungs=args.rungs, impls=args.impls, kinds=args.kinds,
                       runs=args.runs, budget=args.budget,
                       cost_only=args.cost_only, as_json=args.json,
                       perfetto=args.perfetto)


def cmd_benchdiff(args) -> int:
    """Stage-by-stage BENCH artifact comparison (cli/benchdiff.py) with
    per-metric relative thresholds: exit 0 = no regressions, 1 =
    regressions (or, with --fail-on-missing, lost stages), 2 = usage
    error (docs/observability.md)."""
    from tendermint_tpu.cli.benchdiff import run_cli as benchdiff_cli

    return benchdiff_cli(args.a, args.b, thresholds_path=args.thresholds,
                         as_json=args.json,
                         fail_on_missing=args.fail_on_missing)


def cmd_version(args) -> int:
    print(VERSION)
    return 0


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tendermint-tpu",
        description="TPU-native BFT state-machine-replication node",
    )
    p.add_argument("--home", default=os.environ.get("TMHOME", "~/.tendermint_tpu"),
                   help="node home directory")
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("init", help="initialize home dir (config, genesis, keys)")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--key-type", dest="key_type", default="ed25519",
                    choices=["ed25519", "secp256k1"],
                    help="validator consensus key type")
    sp.set_defaults(fn=cmd_init)

    sp = sub.add_parser("start", help="run the node")
    _add_node_flags(sp)
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("testnet", help="generate a localhost testnet")
    sp.add_argument("--v", type=int, default=4, help="number of validators")
    sp.add_argument("--o", default="./mytestnet", help="output directory")
    sp.add_argument("--chain-id", default="")
    sp.add_argument("--key-type", dest="key_type", default="ed25519",
                    choices=["ed25519", "secp256k1"],
                    help="validator consensus key type")
    sp.add_argument("--node-dir-prefix", default="node")
    sp.add_argument("--hostname", default="127.0.0.1")
    sp.add_argument("--starting-port", type=int, default=26656)
    sp.add_argument("--per-host", dest="per_host", action="store_true",
                    help="one node per host: standard ports, hostname peers "
                         "(docker-compose layout)")
    sp.set_defaults(fn=cmd_testnet)

    sp = sub.add_parser("debug", help="snapshot a running node's state over RPC")
    sp.add_argument("--rpc-laddr", dest="rpc_laddr", default="http://127.0.0.1:26657")
    sp.add_argument("--pprof-laddr", dest="pprof_laddr", default="",
                    help="also scrape /debug/pprof from this address")
    sp.add_argument("--output-dir", dest="output_dir", default="./debug-dump")
    sp.add_argument("--interval", type=int, default=0,
                    help="seconds between periodic archives (0 = one-shot)")
    sp.add_argument("--count", type=int, default=0,
                    help="number of periodic archives (0 = until interrupted)")
    sp.set_defaults(fn=cmd_debug)

    sp = sub.add_parser("replay", help="replay block store + WAL through the app")
    sp.set_defaults(fn=cmd_replay)

    sp = sub.add_parser("abci-server", help="serve a builtin ABCI app over a socket")
    sp.add_argument("--app", default="kvstore",
                    help="kvstore | persistent_kvstore | counter")
    sp.add_argument("--addr", default="tcp://127.0.0.1:26658")
    sp.add_argument("--transport", default="socket", choices=["socket", "grpc"])
    sp.add_argument("--snapshot-interval", type=int, default=0,
                    help="app takes a state-sync snapshot every N heights "
                         "(0 = never; external apps own their snapshot "
                         "schedule, so the node's base.snapshot_interval "
                         "does not apply to them)")
    sp.set_defaults(fn=cmd_abci_server)

    sp = sub.add_parser(
        "timeline",
        help="merge N nodes' event journals into a cross-node timeline")
    sp.add_argument("journals", nargs="+",
                    help="journal.jsonl files (one per node); with --wal, "
                         "raw consensus WAL files")
    sp.add_argument("--names", default="",
                    help="comma-separated node names matching the inputs")
    sp.add_argument("--height", type=int, default=None,
                    help="render only this height")
    sp.add_argument("--wal", action="store_true",
                    help="inputs are consensus WALs; reconstruct the "
                         "journal subset offline")
    sp.add_argument("--no-skew", dest="no_skew", action="store_true",
                    help="skip the pairwise clock-offset estimation; "
                         "align on raw wall clocks")
    sp.add_argument("--json", action="store_true",
                    help="emit the merged report as JSON")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser(
        "txtrace",
        help="merge N nodes' event journals into per-tx cross-node "
             "waterfalls (submit → gossip → propose → quorum → commit)")
    sp.add_argument("journals", nargs="+",
                    help="journal.jsonl files (one per node), written "
                         "with TM_TPU_JOURNAL on")
    sp.add_argument("--names", default="",
                    help="comma-separated node names matching the inputs")
    sp.add_argument("--tx", default="",
                    help="render only txs whose hash prefix starts with "
                         "this hex string")
    sp.add_argument("--limit", type=int, default=10,
                    help="max txs rendered (0 = all; default 10)")
    sp.add_argument("--no-skew", dest="no_skew", action="store_true",
                    help="skip the pairwise clock-offset estimation; "
                         "align on raw wall clocks")
    sp.add_argument("--json", action="store_true",
                    help="emit the waterfalls as JSON")
    sp.set_defaults(fn=cmd_txtrace)

    sp = sub.add_parser(
        "simnet",
        help="run a fault-injection scenario on an in-process net and "
             "emit the analyzer verdict (exit 0 = all invariants held)")
    sp.add_argument("--scenario", default="",
                    help="scenario file (.toml or .json; docs/simnet.md)")
    sp.add_argument("--gen-seed", dest="gen_seed", type=int, default=None,
                    help="generator mode: derive the scenario from this "
                         "seed instead of a file")
    sp.add_argument("--gen-index", dest="gen_index", type=int, default=0,
                    help="generator mode: scenario index within the seed's "
                         "sweep (default 0)")
    sp.add_argument("--time", choices=("wall", "virtual"), default="",
                    help="override the scenario's time mode: 'virtual' runs "
                         "on the deterministic discrete-event scheduler "
                         "(zero wall time per simulated second, "
                         "byte-reproducible verdicts; docs/simnet.md)")
    sp.add_argument("--root", default="",
                    help="directory for node homes (default: a temp dir, "
                         "removed unless --keep)")
    sp.add_argument("--out", default="",
                    help="also write the JSON report to this file")
    sp.add_argument("--full", action="store_true",
                    help="include the merged timeline in the report")
    sp.add_argument("--keep", action="store_true",
                    help="keep the temp node homes for post-mortems")
    sp.add_argument("--verbose", action="store_true",
                    help="log node/harness events to stderr")
    sp.set_defaults(fn=cmd_simnet)

    sp = sub.add_parser("top", help="live dashboard for one node "
                                    "(RPC status + /metrics)")
    sp.add_argument("--rpc-laddr", dest="rpc_laddr",
                    default="http://127.0.0.1:26657")
    sp.add_argument("--metrics-laddr", dest="metrics_laddr",
                    default="http://127.0.0.1:26660",
                    help="Prometheus listener; '' disables the metrics view")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-request HTTP timeout")
    sp.add_argument("--once", action="store_true",
                    help="print one frame and exit")
    sp.add_argument("--json", action="store_true",
                    help="emit the snapshot as JSON (implies one frame)")
    sp.set_defaults(fn=cmd_top)

    sp = sub.add_parser(
        "fleet",
        help="cluster dashboard + SLO burn-rate verdicts over N nodes "
             "(exit 0 ok / 1 warn / 2 burning)")
    sp.add_argument("nodes", nargs="+",
                    help="one spec per node: [name=]rpc_addr[,metrics_addr] "
                         "(e.g. node0=127.0.0.1:26657,127.0.0.1:26660); "
                         "omitting the metrics addr scrapes RPC only")
    sp.add_argument("--slo", default="",
                    help="slo.toml/.json objectives file (default: a "
                         "minimal availability objective; docs/fleet.md)")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds for --watch")
    sp.add_argument("--timeout", type=float, default=2.0,
                    help="per-node per-request HTTP timeout")
    sp.add_argument("--once", action="store_true",
                    help="print one frame and exit (the default; kept "
                         "for scripting symmetry with top)")
    sp.add_argument("--watch", action="store_true",
                    help="refresh every --interval seconds until "
                         "interrupted (burn rates accumulate across "
                         "frames)")
    sp.add_argument("--json", action="store_true",
                    help="emit the fleet snapshot + SLO verdict as JSON")
    sp.set_defaults(fn=cmd_fleet)

    sp = sub.add_parser(
        "health",
        help="node health watchdog status over RPC "
             "(exit 0 ok / 1 warn / 2 critical / 3 unreachable)")
    sp.add_argument("--rpc-laddr", dest="rpc_laddr",
                    default="http://127.0.0.1:26657")
    sp.add_argument("--once", action="store_true",
                    help="print one report and exit (the default; kept "
                         "for scripting symmetry with top)")
    sp.add_argument("--watch", action="store_true",
                    help="refresh every --interval seconds until "
                         "interrupted")
    sp.add_argument("--json", action="store_true",
                    help="emit the raw health block as JSON")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds for --watch")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-request HTTP timeout")
    sp.set_defaults(fn=cmd_health)

    sp = sub.add_parser(
        "prof",
        help="continuous statistical CPU profile over /debug/pprof/"
             "profile, plus .folded regression diffing "
             "(exit 0 ok / 1 diff regression / 2 usage / 3 unreachable "
             "or disabled)")
    sp.add_argument("--pprof-laddr", dest="pprof_laddr",
                    default="http://127.0.0.1:6060",
                    help="the node's pprof listener "
                         "(config.rpc.pprof_laddr)")
    sp.add_argument("--once", action="store_true",
                    help="print one report and exit (the default; kept "
                         "for scripting symmetry with top)")
    sp.add_argument("--watch", action="store_true",
                    help="refresh every --interval seconds until "
                         "interrupted")
    sp.add_argument("--seconds", type=float, default=None,
                    help="run a fresh delta capture of this many seconds "
                         "on the node (default: read the continuous ring)")
    sp.add_argument("--flame", default="",
                    help="write the folded profile text to this path "
                         "(flamegraph.pl / speedscope / inferno input)")
    sp.add_argument("--json", action="store_true",
                    help="emit the parsed profile (or diff result) as "
                         "JSON")
    sp.add_argument("--top", type=int, default=10,
                    help="functions shown per subsystem (default 10)")
    sp.add_argument("--diff", nargs=2, metavar=("BASE.folded", "NEW.folded"),
                    default=None,
                    help="compare two saved .folded profiles at function "
                         "level; exit 1 when a function's self-time "
                         "share regressed past the thresholds")
    sp.add_argument("--abs-threshold", dest="abs_threshold", type=float,
                    default=0.05,
                    help="--diff: absolute share growth (fraction of "
                         "samples) to flag (default 0.05)")
    sp.add_argument("--rel-threshold", dest="rel_threshold", type=float,
                    default=0.25,
                    help="--diff: relative share growth to flag "
                         "(default 0.25)")
    sp.add_argument("--interval", type=float, default=2.0,
                    help="refresh seconds for --watch")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-request HTTP timeout (a --seconds capture "
                         "extends it)")
    sp.set_defaults(fn=cmd_prof)

    sp = sub.add_parser(
        "history",
        help="recorded metric time-series from the node's embedded "
             "flight-data recorder: sparklines, counter rates, "
             "quantiles-over-time (exit 0 data / 1 empty range / "
             "2 usage / 3 unreachable or disabled)")
    sp.add_argument("--pprof-laddr", dest="pprof_laddr",
                    default="http://127.0.0.1:6060",
                    help="the node's pprof listener serving "
                         "/debug/pprof/history")
    sp.add_argument("--home-dir", dest="home_dir", default="",
                    help="read <home>/history/ segments straight from "
                         "disk instead of over HTTP (works on a "
                         "stopped node)")
    sp.add_argument("--metric", default="",
                    help="base metric name to plot (default: list "
                         "recorded metrics)")
    sp.add_argument("--since", type=float, default=0.0,
                    help="restrict to the last N seconds (default: "
                         "the whole recorded range)")
    sp.add_argument("--rate", action="store_true",
                    help="plot the per-second rate of a counter "
                         "instead of its level")
    sp.add_argument("--quantiles", action="store_true",
                    help="plot p50/p95-over-time re-read from the "
                         "metric's recorded histogram buckets")
    sp.add_argument("--list", action="store_true",
                    help="print recorded metric names with point "
                         "counts and exit")
    sp.add_argument("--json", action="store_true",
                    help="emit the decoded range (and selected "
                         "series) as JSON")
    sp.add_argument("--width", type=int, default=60,
                    help="sparkline width in cells (default 60)")
    sp.add_argument("--timeout", type=float, default=5.0,
                    help="per-request HTTP timeout")
    sp.set_defaults(fn=cmd_history)

    sp = sub.add_parser(
        "warm",
        help="AOT-compile the verify shape plan so restarts skip the "
             "compile tax (serializes executables + plan next to the "
             "persistent cache)")
    sp.add_argument("--plan", default="",
                    help="shape-plan JSON file (default: TM_TPU_RUNGS / "
                         "TM_TPU_SHAPE_PLAN / the saved plan / the "
                         "consolidated ladder)")
    sp.add_argument("--rungs", default="",
                    help="comma-separated rung override, e.g. 8,64,1024")
    sp.add_argument("--impls", default="",
                    help="comma-separated field impls (default: the plan's)")
    sp.add_argument("--kinds", default="",
                    help="comma-separated program kinds: verify "
                         "(default: the plan's)")
    sp.add_argument("--stats", default="",
                    help="devmon device_stats() JSON to tune the "
                         "consolidated ladder (keeps hot exact-fit rungs)")
    sp.add_argument("--json", action="store_true",
                    help="emit the warm report as one JSON object")
    sp.add_argument("--dry-run", dest="dry_run", action="store_true",
                    help="resolve and print the plan without compiling")
    sp.add_argument("--no-serialize", dest="no_serialize",
                    action="store_true",
                    help="warm the persistent cache only; write no "
                         "serialized executables")
    sp.add_argument("--no-save", dest="no_save", action="store_true",
                    help="do not save the plan next to the cache")
    sp.set_defaults(fn=cmd_warm)

    sp = sub.add_parser(
        "profile",
        help="per-rung kernel cost/roofline profile (HLO FLOPs/bytes + "
             "budgeted timed windows; --perfetto captures a device trace)")
    sp.add_argument("--rungs", default="",
                    help="comma-separated rung override (default: the "
                         "ACTIVE shape plan's rungs)")
    sp.add_argument("--impls", default="",
                    help="comma-separated field impls (default: the plan's)")
    sp.add_argument("--kinds", default="",
                    help="comma-separated program kinds: verify "
                         "(default: the plan's)")
    sp.add_argument("--runs", type=int, default=3,
                    help="timed runs per rung (default 3)")
    sp.add_argument("--budget", type=float, default=120.0,
                    help="seconds of execution budget; rungs past it keep "
                         "their cost rows and skip the timed window "
                         "(default 120; 0 = cost-only)")
    sp.add_argument("--cost-only", dest="cost_only", action="store_true",
                    help="skip the timed windows entirely (no device "
                         "execution, no compiles)")
    sp.add_argument("--perfetto", default="",
                    help="write a Perfetto-loadable device trace of the "
                         "timed windows to this path")
    sp.add_argument("--json", action="store_true",
                    help="emit the full profile report as one JSON object")
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser(
        "benchdiff",
        help="diff two BENCH artifacts with per-metric regression "
             "thresholds (exit 1 on regression)")
    sp.add_argument("a", help="older BENCH json (wrapper or flat shape)")
    sp.add_argument("b", help="newer BENCH json")
    sp.add_argument("--thresholds", default="",
                    help="TOML/JSON file: [thresholds] metric = rel, "
                         "[defaults] class = rel")
    sp.add_argument("--fail-on-missing", dest="fail_on_missing",
                    action="store_true",
                    help="also exit 1 when tracked metrics present in A "
                         "are missing from B (lost tail stages)")
    sp.add_argument("--json", action="store_true",
                    help="emit the diff report as one JSON object")
    sp.set_defaults(fn=cmd_benchdiff)

    sp = sub.add_parser("lint", help="repo-aware static analysis (tmlint)")
    sp.add_argument("paths", nargs="*",
                    help="files/directories to analyze (default: the "
                         "installed tendermint_tpu package)")
    sp.add_argument("--json", action="store_true",
                    help="emit findings as one JSON object")
    sp.add_argument("--rules", default="",
                    help="comma-separated rule ids to run (default: all)")
    sp.add_argument("--list-rules", dest="list_rules", action="store_true",
                    help="print the rule catalogue and exit")
    sp.set_defaults(fn=cmd_lint)

    sp = sub.add_parser("wal2json", help="dump a consensus WAL as JSON lines")
    sp.add_argument("wal_file")
    sp.set_defaults(fn=cmd_wal2json)

    sp = sub.add_parser("json2wal", help="rebuild a WAL from wal2json output (stdin)")
    sp.add_argument("wal_file")
    sp.set_defaults(fn=cmd_json2wal)

    sp = sub.add_parser("abci-cli", help="console/batch driver for an ABCI server")
    sp.add_argument("abci_command",
                    help="batch | console | echo | info | check_tx | deliver_tx | query | commit")
    sp.add_argument("abci_args", nargs="*", help="command argument (quoted or 0x-hex)")
    sp.add_argument("--address", default="tcp://127.0.0.1:26658")
    sp.set_defaults(fn=cmd_abci_cli)

    sp = sub.add_parser("light", help="run a light-client verifying proxy")
    sp.add_argument("chain_id")
    sp.add_argument("--primary", required=True, help="primary node RPC URL")
    sp.add_argument("--witnesses", default="", help="comma-separated witness RPC URLs")
    sp.add_argument("--trusted-height", type=int, required=True)
    sp.add_argument("--trusted-hash", required=True, help="hex header hash")
    sp.add_argument("--trust-period", type=int, default=168 * 3600, help="seconds")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    sp.add_argument("--log-level", dest="log_level", default="info")
    sp.set_defaults(fn=cmd_light)

    sp = sub.add_parser(
        "gateway",
        help="run a caching/coalescing read-path gateway front end "
             "against a primary node (docs/gateway.md)")
    sp.add_argument("--primary", required=True, help="primary node RPC URL")
    sp.add_argument("--laddr", default="tcp://127.0.0.1:8889")
    sp.add_argument("--timeout", type=float, default=10.0,
                    help="per-request upstream HTTP timeout")
    sp.add_argument("--log-level", dest="log_level", default="info")
    sp.set_defaults(fn=cmd_gateway)

    sp = sub.add_parser("signer-harness",
                        help="conformance-test a remote signer")
    sp.add_argument("chain_id")
    sp.add_argument("--addr", default="127.0.0.1:0",
                    help="host:port to listen on for the signer")
    sp.add_argument("--accept-timeout", dest="accept_timeout", type=float,
                    default=60.0)
    sp.set_defaults(fn=cmd_signer_harness)

    sp = sub.add_parser("signer", help="run a remote signer")
    sp.add_argument("--addr", required=True,
                    help="socket: node's priv_validator_laddr to dial; "
                         "grpc: address to listen on")
    sp.add_argument("--transport", default="socket", choices=["socket", "grpc"])
    sp.set_defaults(fn=cmd_signer)

    for name, fn in (
        ("gen-validator", cmd_gen_validator),
        ("gen-node-key", cmd_gen_node_key),
        ("show-node-id", cmd_show_node_id),
        ("show-validator", cmd_show_validator),
        ("unsafe-reset-all", cmd_unsafe_reset_all),
        ("version", cmd_version),
    ):
        sp = sub.add_parser(name)
        if name == "gen-validator":
            sp.add_argument("--key-type", dest="key_type", default="ed25519",
                            choices=["ed25519", "secp256k1"])
        sp.set_defaults(fn=fn)
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)

"""Multi-process e2e runner.

Parity: reference test/e2e/runner/main.go:42-106 — Setup (testnet
generation) → Start (subprocess nodes) → Load (tx generator) → Perturb
(kill/pause/restart, perturb.go:29-66) → Test (RPC invariants: identical
headers across nodes, app state agreement) → Cleanup.

Manifest (reference test/e2e/pkg/manifest.go, reduced to what this
framework supports):
    {
      "chain_id": "e2e-0",
      "validators": 4,
      "target_height": 8,
      "load_rate": 5,            # txs/sec across the net
      "perturb": [{"node": 2, "op": "kill", "at_height": 3}],
      "misbehaviors": {"1": {"3": "double-prevote"}},  # node -> height -> name
      "abci": "socket",          # builtin | socket | unix | grpc (reference
                                 #   manifest ABCIProtocol): non-builtin run
                                 #   one external kvstore app process per node
      "config_overrides": {"base.db_backend": "memdb"},  # dotted config
                                 #   paths applied to every node (reference
                                 #   manifest db_backend etc.)
      "statesync_join": true     # last validator stays offline, then joins
                                 #   the live net via state sync (reference
                                 #   manifest state_sync node mode)
    }

Nodes run `python -m tendermint_tpu.cli start` with homes generated by
the `testnet` command; everything is driven over each node's RPC port.
"""

from __future__ import annotations

import asyncio
import base64
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

from tendermint_tpu.cli.main import main as cli_main
from tendermint_tpu.config import load_config, write_config
from tendermint_tpu.consensus.config import ConsensusConfig


class E2ENode:
    def __init__(self, index: int, home: str, rpc_port: int):
        self.index = index
        self.home = home
        self.rpc_port = rpc_port
        self.proc: subprocess.Popen | None = None

    # -- lifecycle -------------------------------------------------------
    def start(self, extra_env: dict | None = None) -> None:
        # a chip belongs to ONE process: several node processes on one
        # host cannot share it (the second to touch JAX fails or hangs),
        # so every child of the runner is pinned to the host verifier
        env = dict(os.environ, JAX_PLATFORMS="cpu", TM_TPU_CRYPTO_BACKEND="cpu")
        env.update(extra_env or {})
        self._log = open(os.path.join(self.home, "node.log"), "a")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu.cli", "--home", self.home,
             "start", "--log-level", "info"],
            env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def kill(self) -> None:
        if self.proc and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def terminate(self, timeout: float = 30.0) -> int | None:
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode if self.proc else None

    def pause(self) -> None:
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGSTOP)

    def resume(self) -> None:
        if self.proc and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGCONT)

    @property
    def running(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    # -- RPC -------------------------------------------------------------
    def rpc(self, path: str, timeout: float = 5.0):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{self.rpc_port}{path}", timeout=timeout
        ) as r:
            doc = json.loads(r.read())
        if "error" in doc:
            raise RuntimeError(doc["error"])
        return doc["result"]

    def height(self) -> int:
        return int(self.rpc("/status")["sync_info"]["latest_block_height"])

    def broadcast_tx(self, tx: bytes) -> dict:
        b64 = base64.b64encode(tx).decode()
        from urllib.parse import quote

        return self.rpc(f"/broadcast_tx_sync?tx={quote(b64)}")


class Testnet:
    __test__ = False  # not a pytest class despite the name

    def __init__(self, manifest: dict, root: str):
        self.manifest = manifest
        self.root = root
        self.nodes: list[E2ENode] = []

    # -- setup -----------------------------------------------------------
    def setup(self) -> None:
        n = self.manifest.get("validators", 4)
        chain_id = self.manifest.get("chain_id", "e2e-chain")
        base_port = self.manifest.get("base_port", 29000)
        cmd = ["testnet", "--v", str(n), "--o", self.root,
               "--chain-id", chain_id, "--starting-port", str(base_port)]
        if self.manifest.get("key_type"):
            # reference manifest KeyType: validator consensus key type
            cmd += ["--key-type", self.manifest["key_type"]]
        rc = cli_main(cmd)
        if rc != 0:
            raise RuntimeError("testnet generation failed")
        test_cc = ConsensusConfig.test_config()
        abci_mode = self.manifest.get("abci", "builtin")
        overrides = self.manifest.get("config_overrides", {})
        self._app_addrs: dict[int, str] = {}
        for i in range(n):
            home = os.path.join(self.root, f"node{i}")
            cfg = load_config(home)
            # fast in-proc-grade timeouts so e2e runs finish quickly
            for f in ("timeout_propose_ms", "timeout_propose_delta_ms",
                      "timeout_prevote_ms", "timeout_prevote_delta_ms",
                      "timeout_precommit_ms", "timeout_precommit_delta_ms",
                      "timeout_commit_ms"):
                setattr(cfg.consensus, f, getattr(test_cc, f))
            cfg.base.fast_sync = True
            if abci_mode in ("socket", "grpc", "unix"):
                # external app process per node (reference manifest
                # ABCIProtocol unix/tcp/grpc vs builtin).  App ports sit
                # just past the node p2p/rpc pairs (offsets 0..2n-1) so
                # they stay INSIDE this manifest's port slice — the
                # sweep generator strides base ports by 24 and an
                # out-of-slice port would collide with a later net.
                # "unix" is the TSP transport over an AF_UNIX socket in
                # the node home (reference ABCIProtocol "unix"); no port.
                if abci_mode == "unix":
                    addr = f"unix://{os.path.join(home, 'app.sock')}"
                    cfg.base.abci = "socket"
                else:
                    addr = f"tcp://127.0.0.1:{base_port + 2 * n + i}"
                    cfg.base.abci = abci_mode
                cfg.base.proxy_app = addr
                self._app_addrs[i] = addr
            for path, value in overrides.items():
                section, key = path.split(".", 1)
                obj = getattr(cfg, section)
                if not hasattr(obj, key):
                    raise ValueError(f"unknown config override {path!r}")
                setattr(obj, key, value)
            write_config(cfg)
            self.nodes.append(E2ENode(i, home, base_port + 2 * i + 1))

    def start_app_servers(self) -> None:
        """External ABCI app processes for socket/grpc manifests (one
        kvstore server per node, reference abci-cli kvstore server)."""
        self._app_procs: dict[int, subprocess.Popen] = {}
        transport = self.manifest.get("abci", "builtin")
        # external apps own the snapshot schedule (the node's
        # base.snapshot_interval only configures the BUILTIN app), so a
        # statesync manifest must thread the interval through to the
        # app server — without it a socket/grpc statesync-join net has
        # no snapshots to discover and the joiner dies (seed-42 gen-8)
        snap = int(self.manifest.get("config_overrides", {})
                   .get("base.snapshot_interval", 0))
        if transport == "unix":
            transport = "socket"  # TSP over AF_UNIX — same server protocol
        for i, addr in getattr(self, "_app_addrs", {}).items():
            log = open(os.path.join(self.nodes[i].home, "app.log"), "a")
            cmd = [sys.executable, "-m", "tendermint_tpu.cli", "abci-server",
                   "--app", "kvstore", "--transport", transport, "--addr", addr]
            if snap:
                cmd += ["--snapshot-interval", str(snap)]
            self._app_procs[i] = subprocess.Popen(
                cmd,
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                stdout=log, stderr=subprocess.STDOUT,
            )

    def start(self) -> None:
        self.start_app_servers()
        self._node_envs: dict[int, dict] = {}
        skip = {len(self.nodes) - 1} if self.manifest.get("statesync_join") else set()
        for i, node in enumerate(self.nodes):
            # manifest "env" applies to every node (e.g. crypto backend
            # selection — the jax-backend e2e net), per-node misbehaviors
            # on top
            env = dict(self.manifest.get("env", {}))
            mis = self.manifest.get("misbehaviors", {}).get(str(i))
            if mis:
                env["TM_TPU_MISBEHAVIORS"] = json.dumps(mis)
            self._node_envs[i] = env
            if i in skip:
                continue  # statesync joiner starts later, via run_statesync_join
            node.start(extra_env=env)

    def stop(self) -> list[int | None]:
        started = [n for n in self.nodes if n.proc is not None]
        rcs = [n.terminate() for n in started]
        for p in getattr(self, "_app_procs", {}).values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in getattr(self, "_app_procs", {}).values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        return rcs

    async def run_statesync_join(self, timeout: float = 240.0) -> None:
        """The last validator (left offline by start()) joins the live
        net via state sync once the chain is past a snapshot height,
        then must catch up and agree (reference test/e2e state_sync node
        mode; same flow as tests/test_e2e.py
        test_statesync_join_live_net).  Requires
        config_overrides["base.snapshot_interval"] on the serving nodes
        (the generator sets it alongside statesync_join)."""
        from tendermint_tpu.config import load_config, write_config

        live = self.nodes[:-1]
        joiner = self.nodes[-1]
        snap_interval = int(
            self.manifest.get("config_overrides", {}).get(
                "base.snapshot_interval", 0
            )
        )
        assert snap_interval > 0, "statesync_join needs base.snapshot_interval"
        trust_height = snap_interval + 1
        await self.wait_for_height(
            2 * snap_interval + 1, nodes=live, timeout=timeout
        )
        c = await asyncio.to_thread(live[0].rpc, f"/commit?height={trust_height}")
        trust_hash = c["signed_header"]["commit"]["block_id"]["hash"]

        cfg = load_config(joiner.home)
        cfg.statesync.enable = True
        cfg.statesync.rpc_servers = [
            f"http://127.0.0.1:{n.rpc_port}" for n in live[:2]
        ]
        cfg.statesync.trust_height = trust_height
        cfg.statesync.trust_hash = trust_hash
        cfg.statesync.discovery_time_s = 5.0
        write_config(cfg)
        joiner.start(extra_env=self._node_envs.get(joiner.index, {}))
        target = max(n.height() for n in live) + 2
        await self.wait_for_height(target, timeout=timeout)
        # snapshot restore, not replay: the joiner's store has no
        # genesis-era blocks
        st = await asyncio.to_thread(joiner.rpc, "/status")
        assert int(st["sync_info"]["earliest_block_height"]) > 1, st["sync_info"]

    async def run_perturbations(self, timeout: float = 180.0) -> int:
        """Apply the manifest's perturbation schedule (reference
        test/e2e/runner/perturb.go:29-66): each entry fires when the live
        nodes reach its height — kill bounces the process hard, restart
        stops it cleanly and relaunches, pause suspends it briefly.
        Returns the number of perturbations applied.  `timeout` bounds
        each height wait (callers pass their manifest budget so a slow
        but successful run is not misreported)."""
        sched = sorted(self.manifest.get("perturb", []),
                       key=lambda p: p["at_height"])
        applied = 0
        for p in sched:
            live = [n for n in self.nodes if n.running]
            await self.wait_for_height(p["at_height"], nodes=live,
                                       timeout=timeout)
            node = self.nodes[p["node"]]
            env = self._node_envs.get(p["node"], {})
            op = p["op"]
            if op == "kill":
                node.kill()
                await asyncio.sleep(1.0)
                node.start(extra_env=env)
            elif op == "restart":
                node.terminate()
                node.start(extra_env=env)
            elif op == "pause":
                node.pause()
                await asyncio.sleep(2.0)
                node.resume()
            else:
                raise ValueError(f"unknown perturbation op {op!r}")
            applied += 1
        return applied

    # -- wait/load -------------------------------------------------------
    async def wait_for_height(self, h: int, nodes=None, timeout: float = 180.0) -> None:
        nodes = nodes if nodes is not None else self.nodes
        deadline = time.time() + timeout

        def heights():
            out = []
            for n in nodes:
                try:
                    out.append(n.height())
                except Exception:
                    out.append(-1)
            return out

        while time.time() < deadline:
            hs = await asyncio.to_thread(heights)
            if all(x >= h for x in hs):
                return
            await asyncio.sleep(0.5)
        raise TimeoutError(f"heights {heights()} never all reached {h}")

    async def load(self, total_txs: int, rate: float = 10.0,
                   concurrency: int = 16) -> int:
        """Submit txs round-robin; returns accepted count
        (reference test/e2e/runner/load.go).

        Submission is pipelined (bounded concurrency) so the OFFERED rate
        is `rate` regardless of per-RPC latency — the old sequential
        await meant a loaded box self-throttled to 1/(rpc_latency+1/rate)
        and the measured "throughput" was mostly the loader's own
        round-trips (r2 config 5: 14 tx/s against a 40 tx/s target)."""
        ok = 0
        sem = asyncio.Semaphore(concurrency)

        async def submit(i: int) -> None:
            nonlocal ok
            node = self.nodes[i % len(self.nodes)]
            if not node.running:
                return
            tx = f"load-{i}={os.urandom(4).hex()}".encode()
            async with sem:
                try:
                    res = await asyncio.to_thread(node.broadcast_tx, tx)
                    if int(res.get("code", 1)) == 0:
                        ok += 1
                except Exception:
                    pass

        tasks = []
        for i in range(total_txs):
            tasks.append(asyncio.create_task(submit(i)))
            await asyncio.sleep(1.0 / rate)
        await asyncio.gather(*tasks)
        return ok

    # -- invariants (reference test/e2e/tests/) --------------------------
    @staticmethod
    def _rpc_retry(node, path: str, deadline_s: float = 25.0):
        """RPC with connection-refused retries: a node restarted by a
        late perturbation can be alive with its RPC listener not yet
        bound when the post-run invariant checks fire (observed in the
        sweep: kill at target-1 → check_blocks_identical → ECONNREFUSED).
        Protocol/application errors still raise immediately, and so does
        a DEAD node process (VERDICT r3 weak #7 / item 9): the retry
        window exists for a live node whose RPC listener is still
        binding — a crashed node must fail the invariant in seconds, not
        soak the full deadline against a port nobody will ever open."""
        t0 = time.time()
        while True:
            if not node.running:
                raise RuntimeError(
                    f"node{node.index} process is not running "
                    f"(rc={node.proc.returncode if node.proc else None}); "
                    f"refusing to retry {path}"
                )
            try:
                return node.rpc(path)
            except urllib.error.HTTPError:
                # application-level RPC error (the server maps JSON-RPC
                # errors to HTTP 500) — not a connectivity problem
                raise
            except OSError:  # covers ConnectionError and URLError
                if time.time() - t0 > deadline_s:
                    raise
                time.sleep(1.0)

    def check_blocks_identical(self, upto: int) -> None:
        """block_test.go: headers identical across all live nodes.  A
        node whose store starts past genesis (statesync restore, pruning)
        is only compared from its earliest stored height."""
        earliest = {
            n.index: int(
                self._rpc_retry(n, "/status")["sync_info"]["earliest_block_height"]
            )
            for n in self.nodes
            if n.running
        }
        for h in range(1, upto + 1):
            hashes = []
            for n in self.nodes:
                if not n.running or earliest[n.index] > h:
                    continue
                meta = self._rpc_retry(n, f"/block?height={h}")
                hashes.append(meta["block_id"]["hash"])
            assert len(set(hashes)) == 1, f"divergent block at height {h}: {hashes}"

    def check_app_hashes_agree(self) -> None:
        """All live nodes report the same app hash at the min common height."""
        hs = [int(self._rpc_retry(n, "/status")["sync_info"]["latest_block_height"])
              for n in self.nodes if n.running]
        h = min(hs)
        apps = {self._rpc_retry(n, f"/block?height={h}")["block"]["header"]["app_hash"]
                for n in self.nodes if n.running}
        assert len(apps) == 1, f"app hash divergence at {h}: {apps}"

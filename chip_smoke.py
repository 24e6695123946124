#!/usr/bin/env python3
"""chip_smoke.py — the served verify path, and a node that uses it, once,
on the attached TPU.  The quickest proof that the system still starts on
the chip; it measures nothing (its wall times are diagnostics of a smoke
run and belong in no document as a metric).

One process, one command, data from --seed.  It goes through the entry
points a node uses — `ValidatorSet.verify_commit*`,
`crypto.async_verify.verify_many`, `node.Node` — never `_compiled`,
`verify_batch` or a private copy of the routing:

  0. device     platform must be "tpu" (no chip -> exit 3, no result
                line); compile cache, native libraries, shape plan.
  1. readiness  the service as Node.start() builds it, one 64-signature
                flush, then a bounded wait for crypto.batch.device_ready()
                — failing WITH the exception that prevented it.
  2. full width two 10,000-signature commits through verify_commit and
                a 10,000-row mixed batch (seeded corrupt rows, ZIP-215
                edge encodings) through verify_many, checked per row
                against the plain reference crypto.ed25519.verify.
  3. node       a node.Node with fast_sync catches up a 200-validator
                kvstore chain from an in-process peer over MemoryNetwork
                and answers status / block / abci_query over its RPC.
  then, as the wall budget allows: verify_commit at 128 and
  verify_commit_light at 1,000 validators (route and reason reported
  only; a set whose rung would cold-compile past the budget is skipped
  and says so).

Exit code 0 and `"ok": true` only if every check of every stage passed.
The last line of stdout is the result, one JSON object with exactly
these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The line before it (`summary: {...}`, also written to
chiprun_out/chip_smoke.json) is the full summary: stages, routes,
counters, programs compiled, what was cut.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --dry-run-cpu     # tiny sizes on XLA-CPU, for
                                           # debugging before chip time
    python chip_smoke.py --through 2       # stop after a stage (a partial
                                           # run never reports ok)
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import faulthandler
import hashlib
import json
import logging
import os
import random
import sys
import tempfile
import time

# The driver allows 1200 s, compilation included.  Past this the run is
# lost anyway: dump every thread's stack (which compile, which wait) and
# exit non-zero rather than be killed silently.
WALL_LIMIT_S = 1180.0
READY_WAIT_S = 600.0
# what one more cold compile may cost before it endangers the wall limit:
# the slowest program measured on "TPU v5 lite" took 408 s (rung 8), and
# the same program's compile time varied 1.45x between machines
COMPILE_RESERVE_S = 520.0

# BASELINE.json widths: config 2 (128-validator commit), config 3
# (1000-validator light verify), config 4 (200 validators x 10k blocks,
# kvstore) and the north star's 10k-validator commit.  Widths are never
# cut; the chain's block count is (see "reduced" in the summary): 20
# blocks are ONE request pipeline of the pool (MAX_PENDING_PER_PEER), so
# the catch-up is one or two windows — one or two cold compiles.  Every
# further distinct window size is one more program at 190-340 s each
# (measured on a v5e).  Since the reactor cuts a step to one flush
# (blocksync/reactor.py verify_window), a longer catch-up runs the top
# rung, 16,384, step after step — one program more, however long the
# chain — but its last, short windows near the tip still meet a rung
# each, which a smoke that must end inside the wall limit cannot pay.
FULL = {"commit_validators": 10_000, "small_validators": 128,
        "light_validators": 1_000, "chain_validators": 200,
        "chain_blocks": 20, "valid_sample": 512, "corrupt_rows": 64}
# CPU dry run: control flow only.  64 rows is the one flush that takes
# the XLA-CPU "device" route (static threshold 64); the 3-validator
# chain keeps every blocksync window under it, so the dry run compiles
# exactly one program.
DRY = {"commit_validators": 64, "small_validators": 8,
       "light_validators": 16, "chain_validators": 3,
       "chain_blocks": 20, "valid_sample": 16, "corrupt_rows": 4}

CHAIN_ID = "chip-smoke"
SUMMARY_PREFIX = "summary: "
T0_NS = 1_700_000_000 * 10**9


class Smoke:
    def __init__(self, seed: int, dry_run: bool):
        self.seed = seed
        self.dry = dry_run
        self.sz = DRY if dry_run else FULL
        self.rng = random.Random(seed)
        self.t_start = time.monotonic()
        self.failed: list[str] = []
        self.stages: dict = {}
        self.summary: dict = {"ok": False, "device": None,
                              "dry_run": dry_run, "seed": seed}

    # -- reporting ------------------------------------------------------
    def say(self, msg: str) -> None:
        print(f"[smoke +{time.monotonic() - self.t_start:7.1f}s] {msg}",
              flush=True)

    def check(self, name: str, ok: bool, detail="") -> bool:
        self.say(f"{'PASS' if ok else 'FAIL'} {name}"
                 + (f": {detail}" if detail and not ok else ""))
        if not ok:
            self.failed.append(name if not detail else f"{name}: {detail}")
        return ok

    def key(self, label: str, i: int):
        from tendermint_tpu.crypto.keys import PrivKey

        return PrivKey(hashlib.sha256(
            b"%d|%s|%d" % (self.seed, label.encode(), i)).digest())

    def counters(self) -> dict:
        """The counters a device claim rests on, read together:
        `device_batches` counts enqueues, so alone it proves nothing."""
        from tendermint_tpu.crypto import async_verify as av
        from tendermint_tpu.utils import devmon

        st = av.service_stats()
        on_device = av.VERIFY_E2E_SECONDS.label_stats().get(("device",), (0, 0))
        return {
            "submitted": st["submitted"], "flushes": st["flushes"],
            "host_flushes": st["host_flushes"],
            "device_batches": st["device_batches"],
            "mesh_sharded_batches": st["mesh_sharded_batches"],
            "device_errors": st["device_errors"],
            "resolved_on_device": on_device[0],
            "programs": devmon.TRACKER.snapshot()["total"],
        }

    # -- stage 0 ---------------------------------------------------------
    def stage_device(self) -> None:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        want = "cpu" if self.dry else "tpu"
        if device["platform"] != want:
            # no result line: a run that found no chip has no result
            print(f"chip_smoke: need platform {want!r}, JAX found {device}"
                  + ("" if self.dry else
                     " (no accelerator; --dry-run-cpu is the only CPU mode)"),
                  file=sys.stderr)
            sys.exit(3)
        self.summary["device"] = device
        self.summary["jax"] = jax.__version__

        from tendermint_tpu.utils import jaxcache

        cache = jaxcache.enable(jax)  # the call tendermint_tpu.ops makes
        # the smoke runs on the DEFAULT plan: a plan or AOT artifacts a
        # CPU session left in a copied cache directory must not change
        # what it compiles (both knobs exist; see ops/shape_plan.py)
        saved = {"shape_plan.json": os.path.exists(jaxcache.plan_path()),
                 "aot/": os.path.isdir(jaxcache.aot_dir())}
        os.environ["TM_TPU_SHAPE_PLAN"] = "legacy"
        os.environ["TM_TPU_AOT"] = "0"
        os.environ.pop("TM_TPU_RUNGS", None)

        from tendermint_tpu.ops import shape_plan
        from tendermint_tpu.utils import host_prep, native_loader, trace

        host_prep.load_lib()
        # every verify.flush of the run is read back from the span ring
        trace.set_ring_size(1 << 16)
        trace.set_enabled(True)
        self.stages["0_device"] = {
            "device": device, "jax": jax.__version__, "compile_cache": cache,
            "native": native_loader.build_report(),
            "shape_plan": shape_plan.active_plan().name,
            "saved_plan_found": saved,
            "aot_save_load": "not exercised (TM_TPU_AOT=0: the smoke "
                             "compiles lazily, as a node without a saved "
                             "plan does)",
            "env_overrides": {k: v for k, v in sorted(os.environ.items())
                              if k.startswith("TM_TPU_")},
        }
        self.say(f"stage 0: {json.dumps(self.stages['0_device'])}")
        status = native_loader.build_report().get("libedhost.so")
        self.check("native host-prep library fresh and loaded",
                   status in ("loaded", "built", "rebuilt"), status)
        self.check("default shape plan active",
                   shape_plan.active_plan().name == "legacy")

    # -- stage 1 ---------------------------------------------------------
    def stage_readiness(self) -> None:
        from tendermint_tpu.crypto import async_verify as av
        from tendermint_tpu.crypto import batch as cbatch
        from tendermint_tpu.ops import ed25519_jax as dev

        t0 = time.monotonic()
        # as Node.start() does: prime the verifier, build the service
        bv = cbatch.new_batch_verifier()
        self.check("batch verifier is the jax backend",
                   isinstance(bv, cbatch.JAXBatchVerifier), type(bv).__name__)
        svc = av.get_service()
        keys = [self.key("ready", i) for i in range(64)]
        items = [(k.pub_key().bytes_(), b"ready-%d" % i, k.sign(b"ready-%d" % i))
                 for i, k in enumerate(keys)]
        before = self.counters()
        oks = av.verify_many(items)
        first_route = svc.last_route
        self.check("first 64-signature flush verdicts", all(oks))
        self.say(f"first flush routed {first_route}; waiting for the device")
        while (not cbatch.device_ready()
               and time.monotonic() - t0 < READY_WAIT_S):
            diag = cbatch.threshold_diagnostics()
            if "error" in diag or "warmup_error" in diag:
                break  # it raised: readiness will not come by waiting
            time.sleep(0.25)
        diag = cbatch.threshold_diagnostics()
        err = diag.get("error") or diag.get("warmup_error")
        self.check("device ready", cbatch.device_ready(),
                   (f"{err['type']}: {err['message']}\n{err['traceback']}"
                    if err else f"not ready after {READY_WAIT_S:.0f}s: {diag}"))
        impl = dev.default_impl() if cbatch.device_ready() else None
        self.summary["impl"] = impl
        self.summary["threshold"] = {
            k: diag.get(k) for k in ("measured", "device_rtt_ms",
                                     "host_us_per_sig", "threshold", "reason")
            if k in diag}
        self.stages["1_readiness"] = {
            "first_flush": {"route": first_route, "n": len(items)},
            "threshold_diagnostics": diag, "impl": impl,
            "impl_env": os.environ.get("TM_TPU_FIELD_IMPL", "auto"),
            "impl_goldens": dev.optin_report(),
            "counters_before": before, "counters_after": self.counters(),
            "wall_s_diagnostic": round(time.monotonic() - t0, 2),
        }
        self.say(f"stage 1: {json.dumps(self.stages['1_readiness'])}")
        self.check("readiness names the platform",
                   diag.get("platform") == self.summary["device"]["platform"],
                   diag)

    # -- stage 2 ---------------------------------------------------------
    def _validator_set(self, label: str, n: int):
        from tendermint_tpu.types.validator import Validator, ValidatorSet

        keys = [self.key(label, i) for i in range(n)]
        vset = ValidatorSet([Validator(pub_key=k.pub_key(), voting_power=10)
                             for k in keys])
        return vset, {k.pub_key().address(): k for k in keys}

    @staticmethod
    def _full_commit(vset, key_by_addr, height: int, block_id, ts: int):
        """Every validator of `vset` precommits `block_id`."""
        from tendermint_tpu.types.commit import BlockIDFlag, Commit, CommitSig
        from tendermint_tpu.types.vote import SignedMsgType, vote_sign_bytes_raw

        sb = vote_sign_bytes_raw(CHAIN_ID, SignedMsgType.PRECOMMIT, height, 0,
                                 block_id, ts)
        return Commit(height=height, round=0, block_id=block_id, signatures=[
            CommitSig(block_id_flag=BlockIDFlag.COMMIT,
                      validator_address=v.address, timestamp_ns=ts,
                      signature=key_by_addr[v.address].sign(sb))
            for v in vset.validators])

    def _commit(self, vset, key_by_addr, height: int):
        from tendermint_tpu.types.basic import BlockID, PartSetHeader

        tag = b"%d|block|%d" % (self.seed, height)
        block_id = BlockID(
            hash=hashlib.sha256(tag).digest(),
            part_set_header=PartSetHeader(
                total=1, hash=hashlib.sha256(tag + b"|parts").digest()))
        return block_id, self._full_commit(vset, key_by_addr, height, block_id,
                                           T0_NS + height * 10**9)

    def _expected_route(self, n: int) -> tuple[str, str]:
        """The DEFAULT routing for an n-row flush at/over the threshold:
        one chip pipelines; several shard >= 64 rows per device over all
        of them and pin the rest to device 0 (crypto/mesh_dispatch)."""
        from tendermint_tpu.crypto.mesh_dispatch import (
            DEFAULT_MIN_SHARD_PER_DEVICE)

        ndev = self.summary["device"]["count"]
        if ndev == 1:
            return "device", "pipelined"
        if n >= DEFAULT_MIN_SHARD_PER_DEVICE * ndev:
            return "device", "mesh_sharded"
        return "device", "mesh_pinned"

    def _threshold(self) -> int:
        """The dispatch threshold in force: the smoke's pin, an operator's
        TM_TPU_CPU_THRESHOLD, else what the node measured."""
        from tendermint_tpu.crypto import batch as cbatch

        pinned = self.summary["threshold_pinned"]
        env = os.environ.get("TM_TPU_CPU_THRESHOLD", "auto")
        return (pinned["pinned"] if pinned else int(env) if env != "auto"
                else cbatch.measured_cpu_threshold_ready())

    def _device_flush(self, name: str, n: int, fn) -> dict:
        """Run fn() — one n-signature flush — and require that it
        resolved ON THE DEVICE: default route, the path="device" count
        grew by n, no device error, and on a mesh the verdict array's
        own shards spanned every chip."""
        from tendermint_tpu.crypto import async_verify as av

        svc = av.get_service()
        before = self.counters()
        t0 = time.monotonic()
        fn()
        wall = time.monotonic() - t0
        after = self.counters()
        route = svc.last_route
        rec = {"n": n, "route": route, "counters_before": before,
               "counters_after": after, "wall_s_diagnostic": round(wall, 3)}
        want = self._expected_route(n)
        self.check(f"{name}: route {want}", route == want, route)
        self.check(f"{name}: {n} signatures resolved on the device",
                   after["resolved_on_device"] - before["resolved_on_device"] == n
                   and after["device_batches"] - before["device_batches"] == 1
                   and after["host_flushes"] == before["host_flushes"],
                   rec)
        self.check(f"{name}: no device errors", after["device_errors"] == 0, after)
        if want[1] == "mesh_sharded":
            from tendermint_tpu.ops.ed25519_jax import _bucket

            ndev = self.summary["device"]["count"]
            layout = svc.last_shard_layout or ()
            rec["shard_layout"] = layout
            rows = -(-_bucket(n) // ndev)
            self.check(
                f"{name}: verdict array sharded over {ndev} distinct devices, "
                f"{rows} rows each",
                len({d for d, _ in layout}) == ndev
                and all(r == rows for _, r in layout), layout)
        return rec

    def _edge_cases(self, pub: bytes, msg: bytes, sig: bytes) -> list[tuple]:
        """ZIP-215 edge encodings (the set tests/test_ed25519_jax.py
        differentially tests), all well-formed lengths so the flush
        stays on the pipelined route: s >= L, non-canonical and
        off-curve points, small-order A and R."""
        from tendermint_tpu.crypto import ed25519 as ref

        s_plus_l = int.from_bytes(sig[32:], "little") + ref.L
        cases = [
            (pub, msg, sig[:32] + s_plus_l.to_bytes(32, "little")),
            (pub, msg, sig[:32] + (ref.L + 12345).to_bytes(32, "little")),
            (pub, msg, sig[:32] + ref.L.to_bytes(32, "little")),
            ((2).to_bytes(32, "little"), msg, sig),           # off-curve A
            (pub, msg, (2).to_bytes(32, "little") + sig[32:]),  # off-curve R
            (ref.encode_point(ref.IDENTITY), msg, sig),
        ]
        for pt in ref.eight_torsion_points():
            for enc in ref.noncanonical_encodings(pt):
                cases.append((enc, b"any", enc + bytes(32)))
        for _ in range(4):
            cases.append((self.rng.randbytes(32), self.rng.randbytes(8),
                          self.rng.randbytes(64)))
        return cases

    def _parity_batch(self, key_by_addr) -> tuple[list, dict]:
        """commit_validators rows: fresh valid signatures, a seeded
        corrupt subset and the edge encodings mixed in at seeded rows.
        Returns (items, {row: "corrupt"|"edge"})."""
        n = self.sz["commit_validators"]
        keys = list(key_by_addr.values())[:n]
        items = []
        for i, k in enumerate(keys):
            m = b"%d|parity|%d" % (self.seed, i)
            items.append((k.pub_key().bytes_(), m, k.sign(m)))
        edges = self._edge_cases(*items[0])[: n // 4]  # (all, at full width)
        special = self.rng.sample(range(1, n),
                                  min(n - 1, self.sz["corrupt_rows"] + len(edges)))
        marks = {}
        for row, case in zip(special, edges):
            items[row] = case
            marks[row] = "edge"
        for j, row in enumerate(special[len(edges):]):
            pub, m, sig = items[row]
            items[row] = ((pub, m + b"!", sig) if j % 2 else
                          (pub, m, sig[:-1] + bytes([sig[-1] ^ 1])))
            marks[row] = "corrupt"
        return items, marks

    def stage_full_width(self) -> None:
        from tendermint_tpu.crypto import async_verify as av
        from tendermint_tpu.crypto import batch as cbatch
        from tendermint_tpu.crypto import ed25519 as ref

        t_stage = time.monotonic()
        n = self.sz["commit_validators"]
        out: dict = {}
        measured = cbatch.measured_cpu_threshold_ready()
        self.summary["threshold_pinned"] = None
        if measured is not None and measured > n:
            # a finding for the dispatch-floor question, not a pass: the
            # threshold measured on this host keeps full-width flushes
            # off the chip, so pin the static default and still show the
            # device path correct at full width
            av.reset_service(cpu_threshold=64)
            self.summary["threshold_pinned"] = {"pinned": 64,
                                                "measured": measured}
            self.say(f"threshold pinned: {self.summary['threshold_pinned']}")

        t0 = time.monotonic()
        vset, key_by_addr = self._validator_set("val", n)
        commits = [self._commit(vset, key_by_addr, h) for h in (7, 8)]
        self.say(f"{n}-validator set and two commits built in "
                 f"{time.monotonic() - t0:.1f}s")
        for i, (block_id, commit) in enumerate(commits):
            # a second height: the verified-signature cache cannot answer
            out[f"verify_commit_{n}_{i + 1}"] = self._device_flush(
                f"verify_commit {n} #{i + 1}", n,
                lambda: vset.verify_commit(CHAIN_ID, block_id, commit.height,
                                           commit))

        items, marks = self._parity_batch(key_by_addr)
        got: list = []
        out["verify_many_mixed"] = self._device_flush(
            f"verify_many {n} mixed", n,
            lambda: got.extend(av.verify_many(items)))
        by_construction = [i for i, ok in enumerate(got)
                           if ok != (i not in marks) and marks.get(i) != "edge"]
        self.check("mixed batch: valid rows accepted, corrupt rows rejected",
                   not by_construction, by_construction[:20])
        valid_rows = [i for i in range(n) if i not in marks]
        sample = self.rng.sample(valid_rows,
                                 min(len(valid_rows), self.sz["valid_sample"]))
        t0 = time.monotonic()
        rows = sorted(marks) + sample
        mismatch = [(i, marks.get(i, "valid"), got[i])
                    for i in rows if got[i] != ref.verify(*items[i])]
        out["verify_many_mixed"]["reference_rows"] = {
            "edge": sum(1 for v in marks.values() if v == "edge"),
            "corrupt": sum(1 for v in marks.values() if v == "corrupt"),
            "valid_sample": len(sample),
            "accepted": sum(1 for i in rows if got[i]),
            "reference_wall_s_diagnostic": round(time.monotonic() - t0, 2)}
        self.check(f"mixed batch: {len(rows)} rows equal the plain reference "
                   "crypto.ed25519.verify (every corrupt/edge row, "
                   f"{len(sample)} sampled valid rows)", not mismatch,
                   mismatch[:20])
        self.check("mixed batch exercises both verdicts on edge rows",
                   any(got[i] for i, v in marks.items() if v == "edge")
                   and not all(got[i] for i, v in marks.items() if v == "edge"))

        self.check("stage 2: no device errors",
                   self.counters()["device_errors"] == 0)
        out["wall_s_diagnostic"] = round(time.monotonic() - t_stage, 2)
        self.stages["2_full_width"] = out

    def small_sets(self) -> None:
        """BASELINE configs 2 and 3 — verify_commit at 128 validators,
        verify_commit_light at 1,000 (667 signatures reach +2/3): route
        and reason only.  Whether small sets belong on the chip is the
        dispatch-floor question, and this run gives it its first real
        threshold.  They run LAST and only as the wall budget allows: a
        set the measured threshold sends to the device cold-compiles its
        own rung (190-340 s each on a v5e, measured), and the checks
        that decide `ok` must not lose the run to a report-only datum.
        A skipped set says so, with the route the threshold implies."""
        from tendermint_tpu.crypto import async_verify as av
        from tendermint_tpu.ops.ed25519_jax import _bucket
        from tendermint_tpu.utils import devmon

        out = self.stages["2_full_width"]["small_sets"] = {}
        thr = self._threshold()
        for label, nv, light in (("verify_commit", self.sz["small_validators"], False),
                                 ("verify_commit_light", self.sz["light_validators"], True)):
            n = nv * 2 // 3 + 1 if light else nv
            compiled = {e["rung"] for e in devmon.TRACKER.snapshot()["events"]}
            left = WALL_LIMIT_S - (time.monotonic() - self.t_start)
            if n >= thr and _bucket(n) not in compiled and left < COMPILE_RESERVE_S:
                out[f"{label}_{nv}"] = {
                    "signatures": n, "skipped": True,
                    "route_implied": self._expected_route(n),
                    "why": f"{n} signatures >= threshold {thr} would "
                           f"cold-compile rung {_bucket(n)}; {left:.0f}s of "
                           f"the {WALL_LIMIT_S:.0f}s wall budget left, "
                           f"{COMPILE_RESERVE_S:.0f}s reserved per compile"}
                self.say(f"{label} {nv}: skipped — {out[f'{label}_{nv}']['why']}")
                continue
            vs, kba = self._validator_set(f"{label}{nv}", nv)
            block_id, commit = self._commit(vs, kba, 9)
            before = self.counters()
            t0 = time.monotonic()
            (vs.verify_commit_light if light else vs.verify_commit)(
                CHAIN_ID, block_id, 9, commit)
            after = self.counters()
            out[f"{label}_{nv}"] = {
                "signatures": after["submitted"] - before["submitted"],
                "route": av.get_service().last_route,
                "counters_before": before, "counters_after": after,
                "wall_s_diagnostic": round(time.monotonic() - t0, 3)}
            self.say(f"{label} {nv}: {out[f'{label}_{nv}']['signatures']} "
                     f"signatures routed {out[f'{label}_{nv}']['route']}")
        self.check("small sets: no device errors",
                   self.counters()["device_errors"] == 0)

    # -- stage 3 ---------------------------------------------------------
    def _build_chain(self):
        """What consensus would have committed: chain_blocks blocks of a
        chain_validators-validator kvstore chain, every validator
        precommitting every block, keys from the seed.  Set-up: the
        executor is told the commits are verified (they were signed two
        lines up) so that building the chain touches neither the device
        nor the counters the catch-up is judged by."""
        from tendermint_tpu.abci import AppConns
        from tendermint_tpu.abci.kvstore import KVStoreApplication
        from tendermint_tpu.consensus.replay import Handshaker
        from tendermint_tpu.state import (BlockExecutor, StateStore,
                                          make_genesis_state)
        from tendermint_tpu.store import BlockStore, MemDB
        from tendermint_tpu.types import GenesisDoc, GenesisValidator
        from tendermint_tpu.types.basic import BlockID
        from tendermint_tpu.types.commit import Commit

        keys = [self.key("chain", i) for i in range(self.sz["chain_validators"])]
        genesis = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time_ns=T0_NS,
            validators=[GenesisValidator(pub_key=k.pub_key(), power=10)
                        for k in keys])
        key_by_addr = {k.pub_key().address(): k for k in keys}
        state = make_genesis_state(genesis)
        state_store, block_store = StateStore(MemDB()), BlockStore(MemDB())
        state_store.save(state)
        conns = AppConns(KVStoreApplication())
        # InitChain, as every node of the chain ran it before block 1
        state = Handshaker(state_store, state, block_store,
                           genesis).handshake(conns)
        executor = BlockExecutor(state_store, conns.consensus())
        last_commit = Commit(height=0, round=0, block_id=BlockID(), signatures=[])
        for h in range(1, self.sz["chain_blocks"] + 1):
            block = executor.create_proposal_block(
                h, state, last_commit, state.validators.get_proposer().address)
            block.data.txs = [b"k%d=v%d" % (h, h)]
            block.header.data_hash = block.data.hash()
            parts = block.make_part_set()
            block_id = BlockID(hash=block.hash(), part_set_header=parts.header())
            validators = state.validators
            state, _ = executor.apply_block(state, block_id, block,
                                            commit_sigs_verified=True)
            last_commit = self._full_commit(validators, key_by_addr, h, block_id,
                                            block.header.time_ns + 10**9)
            block_store.save_block(block, parts, last_commit)
        return genesis, state, state_store, executor, block_store

    async def _catch_up(self, home: str, out: dict) -> None:
        from tendermint_tpu.blocksync import BlocksyncReactor
        from tendermint_tpu.config import test_config
        from tendermint_tpu.node import Node, load_or_gen_node_key
        from tendermint_tpu.p2p import MemoryNetwork, Router
        from tendermint_tpu.rpc.client import HTTPClient
        from tendermint_tpu.utils import native_loader
        from tendermint_tpu.utils.log import new_logger

        t0 = time.monotonic()
        genesis, tip_state, _, executor, served = self._build_chain()
        out["chain_build_wall_s_diagnostic"] = round(time.monotonic() - t0, 2)
        tip = served.height()
        target = tip - 1  # the tip needs a successor's commit to be applied
        self.say(f"chain built: {tip} blocks x {self.sz['chain_validators']} "
                 f"validators in {out['chain_build_wall_s_diagnostic']}s")

        network = MemoryNetwork()
        # serving end: a BlocksyncReactor over the seeded store (Node
        # opens its own databases and cannot be seeded through its
        # constructor); the follower is a real node.Node
        server_id = "aa" * 20
        server_router = Router(server_id, network.create_transport(server_id))
        server = BlocksyncReactor(tip_state, executor, served, server_router)
        cfg = test_config(home)
        cfg.base.fast_sync = True
        cfg.base.db_backend = "native"
        node_key = load_or_gen_node_key(cfg.node_key_file)
        follower = Node(cfg, genesis=genesis,
                        transport=network.create_transport(node_key.node_id),
                        logger=new_logger("tendermint_tpu.follower"))
        out["assembly"] = {"server": "BlocksyncReactor over a seeded store",
                           "follower": "node.Node fast_sync=True db=native",
                           "native": native_loader.build_report()}
        left = WALL_LIMIT_S - (time.monotonic() - self.t_start) - 30.0
        await server_router.start()
        await server.start(sync=False)
        await follower.start()
        try:
            await follower.router.dial(server_id)
            t0 = time.monotonic()
            pool = follower.blocksync_reactor.pool
            while (follower.block_store.height() < target
                   and not pool.banned and time.monotonic() - t0 < left):
                await asyncio.sleep(0.1)  # a banned server never comes back
            try:
                await asyncio.wait_for(follower._caught_up.wait(), timeout=60)
            except asyncio.TimeoutError:
                pass
            out["catch_up_wall_s_diagnostic"] = round(time.monotonic() - t0, 2)
            height = follower.block_store.height()
            out["follower_height"] = height
            self.check(f"follower reached the tip ({target})", height >= target,
                       f"height {height}; blocksync peers "
                       f"{list(follower.blocksync_reactor.pool.peers)}, banned "
                       f"{sorted(follower.blocksync_reactor.pool.banned)}")
            self.check("follower caught up (_caught_up)",
                       follower._caught_up.is_set())
            heights = sorted({1, height, *self.rng.sample(
                range(1, height + 1), min(height, 16))}) if height else []
            bad = [h for h in heights
                   if follower.block_store.load_block_meta(h).header.hash()
                   != served.load_block_meta(h).header.hash()]
            self.check(f"header hashes equal the served chain's at "
                       f"{len(heights)} sampled heights", heights and not bad, bad)

            client = HTTPClient(*follower.rpc_addr)
            try:
                status = await client.status()
                vs = status["verify_service"]
                out["rpc_status_verify_service"] = vs
                self.check("rpc status: device ready on this platform",
                           vs["device_ready"] is True
                           and vs["platform"] == self.summary["device"]["platform"]
                           and int(vs["device_batches"]) > 0
                           and int(vs["device_errors"]) == 0, vs)
                h = heights[len(heights) // 2] if heights else 1
                blk = await client.block(h)
                self.check(f"rpc block?height={h}",
                           blk["block_id"]["hash"].lower()
                           == served.load_block_meta(h).block_id.hash.hex(),
                           blk["block_id"])
                q = await client.abci_query("/key", b"k%d" % h)
                val = base64.b64decode(q["response"]["value"])
                self.check(f"rpc abci_query k{h}", val == b"v%d" % h, val)
            finally:
                await client.close()
        finally:
            await follower.stop()
            await server.stop()
            await server_router.stop()

    def stage_node(self) -> None:
        from tendermint_tpu.utils import trace

        t_stage = time.monotonic()
        out: dict = {}
        before = self.counters()
        t0_ns = time.perf_counter_ns()
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as home:
            asyncio.run(self._catch_up(home, out))
        after = self.counters()
        spans = [s for s in trace.spans() if s["t0_ns"] >= t0_ns]
        flushes = [s["attrs"] for s in spans if s["name"] == "verify.flush"]
        thr = self._threshold()
        eligible = [f for f in flushes if f["n"] >= thr]
        off_device = [f for f in eligible if f["path"] != "device"]
        out["threshold"] = thr
        out["flushes"] = len(flushes)
        out["flushes_at_or_above_threshold"] = len(eligible)
        out["flush_sizes"] = [f["n"] for f in flushes]
        self.check("every window flush at/above the threshold resolved on "
                   "the device", not off_device, off_device[:10])
        rows = sum(f["n"] for f in eligible)
        self.check(f"{rows} catch-up signatures resolved on the device",
                   after["resolved_on_device"] - before["resolved_on_device"] == rows
                   and (rows > 0 or self.dry), {"before": before, "after": after})
        self.check("stage 3: no device errors", after["device_errors"] == 0, after)
        if self.summary["device"]["count"] > 1:
            wrong = [f for f in eligible
                     if (f["path"], f["reason"]) != self._expected_route(f["n"])]
            self.check("every eligible window took the default mesh route",
                       not wrong, wrong[:10])
        rungs: list = []
        for s in spans:
            if (s["name"] == "verify.host_prep"
                    and s["attrs"]["rung"] not in [r["rung"] for r in rungs]):
                rungs.append({"rung": s["attrs"]["rung"],
                              "first_flush": s["attrs"]["n"]})
        out["rungs_touched_in_order"] = rungs
        out["counters_before"], out["counters_after"] = before, after
        out["wall_s_diagnostic"] = round(time.monotonic() - t_stage, 2)
        self.stages["3_node"] = out
        self.summary["reduced"] = (
            {"everything": "CPU dry run: control flow only, at toy sizes"}
            if self.dry else {"chain_blocks": {
                "from": 10_000, "to": self.sz["chain_blocks"],
                "why": "BASELINE config 4 is 10k blocks x 200 validators; "
                       "one chip call allows 1200 s, compilation included, "
                       "and every distinct window size of a catch-up "
                       "cold-compiles its own rung (190-340 s per program "
                       "measured on a v5e) on the service's only worker.  "
                       "20 blocks are one request pipeline of the pool: "
                       "one or two windows.  Widths (200 validators, full "
                       "commits, kvstore txs) are not cut."}})

    # -- stage 4 ---------------------------------------------------------
    def finish(self, stages_run: list[int]) -> int:
        from tendermint_tpu.utils import devmon

        events = devmon.TRACKER.snapshot()["events"]
        programs = [{"kind": e["kind"], "impl": e["impl"], "rung": e["rung"],
                     "mesh": e["flags"].get("devices", 1),
                     "first_call_s_diagnostic": e["seconds"],
                     "source": e["source"]} for e in events]
        complete = stages_run == [0, 1, 2, 3]
        self.summary.update(
            ok=complete and not self.failed,
            stages_run=stages_run,
            checks_failed=self.failed,
            programs=programs,
            programs_total=len(programs),
            programs_compiled_cold=sum(p["source"] == "cold" for p in programs),
            programs_from_persistent_cache=sum(
                p["source"] == "persistent-cache" for p in programs),
            stages=self.stages,
            note="wall times are diagnostics of a smoke run, not metrics",
            wall_s_diagnostic=round(time.monotonic() - self.t_start, 1),
            claim=None,
        )
        line = json.dumps(self.summary, default=str)
        try:
            os.makedirs("chiprun_out", exist_ok=True)
            with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as fh:
                fh.write(line + "\n")
        except OSError as e:
            self.say(f"could not write chiprun_out/chip_smoke.json: {e}")
        print(f"{SUMMARY_PREFIX}{line}", flush=True)
        if self.summary["device"] is not None:
            # the result line: these keys and no others, last on stdout
            print(json.dumps({"ok": self.summary["ok"],
                              "device": self.summary["device"]}), flush=True)
        return 0 if self.summary["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="tiny sizes on XLA-CPU; never reached by fallback")
    ap.add_argument("--through", type=int, default=3, choices=(0, 1, 2, 3),
                    help="stop after this stage (a partial run is not ok)")
    args = ap.parse_args()

    faulthandler.dump_traceback_later(WALL_LIMIT_S, exit=True)
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="%(asctime)s %(levelname).1s %(name)s | %(message)s")
    if args.dry_run_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    # outside a checkout this fails here: non-zero, and no result line
    import tendermint_tpu  # noqa: F401

    smoke = Smoke(args.seed, args.dry_run_cpu)
    stages = [smoke.stage_device, smoke.stage_readiness,
              smoke.stage_full_width, smoke.stage_node]
    ran: list[int] = []
    for i, stage in enumerate(stages[:args.through + 1]):
        if smoke.failed and i > 1:
            smoke.say(f"skipping stage {i}: earlier checks failed")
            break
        try:
            stage()
        except Exception as e:  # noqa: BLE001 — a stage that raises failed
            logging.exception("stage %d raised", i)
            smoke.check(f"stage {i} ran to its end", False,
                        f"{type(e).__name__}: {e}")
        ran.append(i)
    if ran == [0, 1, 2, 3] and "2_full_width" in smoke.stages:
        try:
            smoke.small_sets()
        except Exception as e:  # noqa: BLE001
            logging.exception("small sets raised")
            smoke.check("small sets ran to their end", False,
                        f"{type(e).__name__}: {e}")
    return smoke.finish(ran)


if __name__ == "__main__":
    sys.exit(main())

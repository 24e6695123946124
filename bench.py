#!/usr/bin/env python
"""Headline benchmark: batched Ed25519 signature verification throughput.

Metric (BASELINE.json): Ed25519 sig-verifies/sec + p50 commit-verify
latency.  The reference verifies sequentially on CPU
(crypto/ed25519/ed25519.go:149-156, no BatchVerifier); this framework
verifies the whole batch as one XLA device program.

vs_baseline: ratio against a sequential single-core libcrypto (OpenSSL)
verify loop measured in the same process — a *harder* baseline than the
reference's Go ed25519consensus path (OpenSSL's cofactorless verify is
roughly 2-3x faster per signature than Go's ZIP-215 batch-equation code),
so the ratio understates the advantage over the actual reference.

Hardened (round-2): the round-1 run produced no number because the first
device contact was a 16,384-row warmup against a backend that failed to
initialize.  Now the bench (a) smoke-tests the backend with a trivial jit
and an n=8 bucket first, (b) retries backend init with backoff, (c) runs
every stage under a watchdog deadline, and (d) on ANY failure prints a
single diagnostic JSON line (machine-parseable) instead of a traceback.

Prints exactly ONE JSON line on stdout, always.

Env knobs:
  TM_BENCH_N          batch size (default 16384; power-of-two bucket)
  TM_BENCH_RUNS       timed runs (default 5)
  TM_BENCH_DEADLINE   global watchdog seconds (default 480)
  TM_BENCH_BACKENDS   comma list of platforms tried in order (default
                      "<auto>,cpu": the JAX default platform first, then
                      CPU devices so an environment hiccup still yields
                      a number, flagged by the "backend" output key)
  TM_BENCH_SHOOTOUT_N      impl-shootout batch size (default 1024 cpu /
                           4096 device; bucketed to the active plan)
  TM_BENCH_SHOOTOUT_IMPLS  comma list for the impl-shootout stage
                           (default "int64,packed")
"""

import json
import os
import secrets
import statistics
import sys
import threading
import time
import traceback

N = int(os.environ.get("TM_BENCH_N", "16384"))
TIMED_RUNS = int(os.environ.get("TM_BENCH_RUNS", "5"))
DEADLINE = float(os.environ.get("TM_BENCH_DEADLINE", "480"))
BASELINE_SAMPLE = 2048
COMMIT_N = 10_000  # BASELINE.md north star: 10k-validator commit batch
# headline metric name; main() prefixes "host_" on the explicit CPU run
METRIC = "ed25519_sig_verifies_per_sec"

_t_start = time.monotonic()
_stage = "init"
_emit_lock = threading.Lock()
_result_printed = False
_partial: dict = {}  # filled as stages complete; emitted if the watchdog fires


def _emit(obj) -> None:
    # atomic test-and-set: the watchdog thread and the main thread can
    # race at the deadline; exactly one JSON line may reach stdout
    global _result_printed
    with _emit_lock:
        if _result_printed:
            return
        _result_printed = True
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _fail(err: str) -> None:
    out = {
        "metric": METRIC,
        "value": 0,
        "unit": "sigs/s",
        "vs_baseline": 0,
        "error": err[-2000:],
        "stage": _stage,
        "elapsed_s": round(time.monotonic() - _t_start, 1),
    }
    out.update(_partial)  # keep any stage results measured before the failure
    _flush_partial()
    _emit(out)


def _watchdog() -> None:
    # A hard exit path: if the deadline passes, print the diagnostic
    # line and kill the process, non-zero (os._exit — an XLA client
    # stuck in a C extension call never returns to Python to see
    # SystemExit).
    remaining = DEADLINE - (time.monotonic() - _t_start)
    if remaining > 0:
        time.sleep(remaining)
    _fail(f"watchdog: deadline {DEADLINE}s exceeded")  # no-op if already emitted
    os._exit(1)


def _flush_partial() -> None:
    """Write the stages measured SO FAR to disk (atomic replace).  The
    in-memory `_partial` only reaches stdout via the failure handler or
    the final emit — a watchdog KILL mid-stage (the round-5 driver run failure
    mode: the driver's timeout fired and every tail stage vanished)
    loses everything after the last flush, so flush after every stage.
    TM_BENCH_PARTIAL overrides the path; "0" disables."""
    path = os.environ.get("TM_BENCH_PARTIAL", "bench_partial.json")
    if not path or path == "0":
        return
    try:
        doc = {"stage": _stage,
               "elapsed_s": round(time.monotonic() - _t_start, 1)}
        doc.update(_partial)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            fh.write(json.dumps(doc, default=str) + "\n")
        os.replace(tmp, path)
    except Exception:  # noqa: BLE001 — the flush is advisory; a read-only
        pass           # cwd or odd value must not cost the bench


def _stage_set(name: str) -> None:
    global _stage
    _flush_partial()  # everything measured before this stage is on disk
    _stage = name
    print(f"[bench] stage={name} t={time.monotonic() - _t_start:.1f}s", file=sys.stderr)


def _deadline_left() -> float:
    """Seconds of watchdog budget remaining.  Optional stages budget
    themselves against this (the round-5 driver run overran the 480 s deadline inside
    an optional stage and the artifact line reported the watchdog
    error instead of the already-measured headline): a stage that cannot
    afford its runs skips or shrinks, so the final JSON reports clean."""
    return DEADLINE - (time.monotonic() - _t_start)


def _init_backend():
    """Initialize JAX on the platform this run is FOR and smoke it with
    a trivial jit.  Unset, TM_BENCH_BACKENDS means the TPU, and a run
    that finds none fails: a device benchmark has no CPU fallback.  CPU
    is what you get only by asking for it (TM_BENCH_BACKENDS=cpu, as the
    contract tests do), and its numbers never carry a device metric's
    name.  Backend init runs in this process: one process holds the
    chip, and nothing this bench starts needs it."""
    import jax

    want = os.environ.get("TM_BENCH_BACKENDS", "tpu").strip()
    if want not in ("tpu", "cpu"):
        raise RuntimeError(
            f"TM_BENCH_BACKENDS={want!r}: want 'tpu' (default) or 'cpu'")
    if want == "cpu":
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    plat = devs[0].platform
    if plat != want:
        raise RuntimeError(
            f"no usable {want} backend: JAX found {plat} "
            f"({devs[0].device_kind} x{len(devs)}); set "
            "TM_BENCH_BACKENDS=cpu for the host-only run")
    x = jax.jit(lambda v: v * 2 + 1)(jax.numpy.arange(8, dtype=jax.numpy.int32))
    assert int(x.sum()) == 64
    print(f"[bench] backend={plat} kind={devs[0].device_kind} "
          f"devices={len(devs)}", file=sys.stderr)
    return plat, devs


def main() -> int:
    """Exit code 0 only for a run that measured its headline; any
    failure still prints the one diagnostic JSON line, then exits 1."""
    threading.Thread(target=_watchdog, daemon=True).start()

    try:
        _stage_set("backend-init")
        # persistent XLA compile cache (utils/jaxcache:
        # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache): reruns
        # skip the compiles
        import jax

        from tendermint_tpu.utils import jaxcache

        jaxcache.enable(jax)
        platform, devs = _init_backend()
        _partial["backend"] = platform
        _partial["device_kind"] = devs[0].device_kind
        _partial["n_devices"] = len(devs)
        global METRIC
        if platform == "cpu":
            # a CPU number is never written under a device metric's name
            METRIC = "host_" + METRIC

        global N, TIMED_RUNS
        device_n = N
        if platform == "cpu" and "TM_BENCH_N" not in os.environ:
            # CPU fallback (round-3, VERDICT r2 item 3): the HEADLINE
            # number is now the PRODUCTION cpu verifier — the libcrypto
            # batch path every CPU deployment actually runs
            # (crypto/batch.py CPUBatchVerifier) — not the XLA-CPU device
            # program, which no deployment would choose and which made
            # BENCH_r02 read "37x slower than Go" when the true CPU story
            # is ~1x.  The XLA-CPU device path is still measured below,
            # at a reduced batch, under diagnostic keys for trend
            # tracking.
            device_n = 1024
            TIMED_RUNS = min(TIMED_RUNS, 2)

        _stage_set("keygen")
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PrivateKey,
                Ed25519PublicKey,
            )

            have_libcrypto = True
        except ImportError:
            # minimal-container fallback (the PR 1 gated-dep class): the
            # builder image ships no `cryptography`, so keygen/signing
            # run the in-repo pure-Python path (~7 ms/sig) at a reduced
            # N and the sequential baseline below samples
            # ed25519.verify_fast instead of raw libcrypto objects.
            # Flagged in the artifact (keygen_path/baseline_path) so
            # benchdiff readers know the vs_baseline denominator moved.
            have_libcrypto = False

        global BASELINE_SAMPLE
        if not have_libcrypto:
            if "TM_BENCH_N" not in os.environ:
                N = min(N, 2048)
            BASELINE_SAMPLE = min(BASELINE_SAMPLE, 256)
            _partial["keygen_path"] = "pure-python-fallback"
            from tendermint_tpu.crypto.keys import priv_key_from_seed

            signers = [priv_key_from_seed(secrets.token_bytes(32))
                       for _ in range(N)]
            pubs = [s.pub_key().bytes_() for s in signers]
            msgs = [b"block-commit-sig-%d" % i for i in range(N)]
            sigs = [s.sign(m) for s, m in zip(signers, msgs)]
        else:
            signers = [
                Ed25519PrivateKey.from_private_bytes(secrets.token_bytes(32))
                for _ in range(N)
            ]
            pubs = [s.public_key().public_bytes_raw() for s in signers]
            msgs = [b"block-commit-sig-%d" % i for i in range(N)]
            sigs = [s.sign(m) for s, m in zip(signers, msgs)]

        # Same-moment baseline sampler (VERDICT r3 weak #1 / item 2): the
        # r3 driver artifact read 0.798x because the sequential baseline
        # was sampled ONCE, AFTER the timed runs, on a 1-core box whose
        # cpu-steal drifts >2x between moments.  Baseline and production
        # runs are now interleaved A/B/A/B and the ratio is the median of
        # per-pair ratios — the fix already proven in
        # benchmarks/baseline_suite.py and tests/test_replay_ratio.py.
        if have_libcrypto:
            baseline_pub_objs = [
                Ed25519PublicKey.from_public_bytes(p)
                for p in pubs[:BASELINE_SAMPLE]
            ]

            def run_baseline() -> float:
                """One sequential-verify pass; returns sigs/s at this moment."""
                t0 = time.perf_counter()
                for po, m, s in zip(baseline_pub_objs, msgs, sigs):
                    po.verify(s, m)
                return len(baseline_pub_objs) / (time.perf_counter() - t0)
        else:
            from tendermint_tpu.crypto import ed25519 as _ref_ed

            _partial["baseline_path"] = "verify_fast-fallback"
            baseline_pub_objs = pubs[:BASELINE_SAMPLE]

            def run_baseline() -> float:
                """Sequential in-repo host verify (the fastest
                single-item path this container has)."""
                t0 = time.perf_counter()
                for p, m, s in zip(baseline_pub_objs, msgs, sigs):
                    assert _ref_ed.verify_fast(p, m, s)
                return len(baseline_pub_objs) / (time.perf_counter() - t0)

        def run_baseline_for(duration_s: float) -> float:
            """Sequential passes until ~duration_s elapsed: a baseline
            window the SAME length as a production window, so cpu-steal
            drift cancels in the pair ratio even when the production
            batch is much larger than BASELINE_SAMPLE."""
            done = 0
            t0 = time.perf_counter()
            while True:
                for po, m, s in zip(baseline_pub_objs, msgs, sigs):
                    po.verify(s, m)
                done += len(baseline_pub_objs)
                if time.perf_counter() - t0 >= duration_s:
                    return done / (time.perf_counter() - t0)

        run_baseline()  # warm

        # (production sigs/s, same-moment baseline sigs/s) pairs for the
        # path that carries the headline
        headline_pairs: list = []

        # -- simnet under adversity (round 6, ISSUE 6): a fixed-seed
        # 20-node in-process net with a partition+heal, a slow-link
        # phase, a fail-point crash-restart (WAL replay) and one
        # equivocating maverick — the "bounded degradation" BENCH
        # metrics: accepted-tx/s under faults, heights/min, the longest
        # consecutive rounds>0 streak, and recovery time after heal.
        # Runs BEFORE the device stages: in the round-5 driver run the watchdog fired
        # in a device stage and every later stage never landed, so a tail position
        # would silently drop these keys.  Budgeted: the scenario's own
        # max_runtime is capped so the device stages keep >=300s, and
        # the stage skips outright when too little is left.
        _stage_set("simnet")
        try:
            # measured 80s on one CPU core; 150s cap absorbs noise while
            # the device stages keep >=280s of the watchdog budget
            budget = min(150.0, _deadline_left() - 280.0)
            if budget < 90:
                raise RuntimeError("skipped: %.0fs left" % _deadline_left())
            import tempfile

            from tendermint_tpu.simnet.harness import run_scenario
            from tendermint_tpu.simnet.scenario import FaultOp, Scenario

            sim_sc = Scenario(
                name="bench-20", seed=601, validators=20,
                validator_slots=200, target_height=4,
                max_runtime_s=budget,
                load_rate=20, gossip_sleep_ms=50, timeout_scale=6.0,
                mesh_degree=6, max_rounds=12, stall_factor=0.0,
                mavericks={"9": {"3": "double-prevote"}},
                faults=[
                    FaultOp(op="partition", at_height=1, nodes=[17, 18, 19]),
                    FaultOp(op="heal", at_height=2),
                    FaultOp(op="slow", at_height=2, nodes=[2, 3],
                            latency_ms=40, jitter_ms=20),
                    FaultOp(op="clear", at_height=3),
                    FaultOp(op="crash", at_height=2, nodes=[5],
                            restart_after_s=1.0,
                            fail_label="commit-after-save"),
                ],
            )
            with tempfile.TemporaryDirectory() as td:
                rep = run_scenario(sim_sc, td)
            _partial.update({
                "simnet_ok": rep["ok"],
                "simnet_violations": [v["invariant"]
                                      for v in rep["violations"]],
                "simnet_nodes": sim_sc.validators,
                "simnet_validator_slots": sim_sc.total_slots(),
                "simnet_duration_s": rep["duration_s"],
                "simnet_min_honest_height": rep["heights"]["min_honest"],
                "simnet_heights_per_min": rep["heights"]["per_min"],
                "simnet_accepted_tx_per_s": rep["load"]["accepted_tx_per_s"],
                "simnet_offered_tx": rep["load"]["offered_tx"],
                "simnet_accepted_tx": rep["load"]["accepted_tx"],
                "simnet_max_round": rep["rounds"]["max_round"],
                "simnet_max_consecutive_rounds_gt0":
                    rep["rounds"]["max_consecutive_gt0"],
                "simnet_max_recovery_s": rep["recovery"]["max_recovery_s"],
                "simnet_restarts": rep["restarts"],
                "simnet_wal_replays": rep["wal_replays"],
                "simnet_frames_dropped": rep["network"]["frames_dropped"],
                "simnet_evidence_committed": rep["evidence"]["committed"],
            })
        except Exception as e:  # noqa: BLE001
            _partial["simnet_error"] = str(e)[-300:]

        # -- virtual-time simnet (round 15, ISSUE 15): the same harness
        # on the deterministic discrete-event scheduler.  A fixed-seed
        # 50-node / 1000-slot scenario runs TWICE; the stage reports the
        # wall cost of simulating it (slots/s, virtual-seconds per wall
        # second) and whether the two verdicts are byte-identical — the
        # determinism contract as a tracked boolean.  Before the device
        # stages (the r05 tail-loss lesson) and budgeted like its wall
        # twin above.
        _stage_set("simnet-virtual")
        try:
            budget = min(140.0, _deadline_left() - 240.0)
            if budget < 80:
                raise RuntimeError("skipped: %.0fs left" % _deadline_left())
            import hashlib
            import json as _json
            import tempfile

            from tendermint_tpu.simnet.harness import run_scenario
            from tendermint_tpu.simnet.scenario import FaultOp, Scenario

            def _vsc():
                return Scenario(
                    name="bench-virtual-50", seed=701, validators=50,
                    validator_slots=1000, slot_power=1, target_height=4,
                    max_runtime_s=60.0, load_rate=15, time="virtual",
                    mesh_degree=5, max_rounds=10,
                    faults=[
                        FaultOp(op="slow", at_height=2, nodes=[2, 3],
                                latency_ms=40, jitter_ms=10),
                        FaultOp(op="clear", at_height=3),
                    ],
                )

            walls, hashes, reps = [], [], []
            for _run in range(2):
                t0 = time.monotonic()
                with tempfile.TemporaryDirectory() as td:
                    rep = run_scenario(_vsc(), td)
                walls.append(time.monotonic() - t0)
                hashes.append(hashlib.sha256(
                    _json.dumps(rep, sort_keys=True,
                                default=str).encode()).hexdigest())
                reps.append(rep)
            rep = reps[0]
            sc0 = _vsc()
            heights = rep["heights"]["min_honest"]
            wall = walls[0]
            _partial.update({
                "simnet_virtual_ok": rep["ok"],
                "simnet_virtual_nodes": sc0.validators,
                "simnet_virtual_slots": sc0.total_slots(),
                "simnet_virtual_heights": heights,
                # validator-slot-heights simulated per wall second: the
                # scale x progress the scheduler buys per core-second
                "simnet_virtual_slots_per_s": round(
                    sc0.total_slots() * heights / wall, 2),
                # virtual seconds simulated per wall second
                "simnet_time_compression": round(
                    rep["duration_s"] / wall, 4) if wall else 0.0,
                "simnet_virtual_wall_s": round(wall, 2),
                "simnet_virtual_duration_s": rep["duration_s"],
                "simnet_virtual_deterministic": hashes[0] == hashes[1],
            })
        except Exception as e:  # noqa: BLE001
            _partial["simnet_virtual_error"] = str(e)[-300:]

        # -- tx latency (round 9, ISSUE 9): finality percentiles on a
        # clean 4-node localnet — the latency twin of the simnet stage's
        # accepted-tx/s.  The metric keys end in _ms so benchdiff tracks
        # them in the latency class (10% rel threshold).  Placed BEFORE
        # the device stages with the simnet stage (the round-5 driver run lesson:
        # tail stages silently vanish when the watchdog fires),
        # and budgeted so the device pipeline keeps its reserve.
        _stage_set("tx-latency")
        try:
            budget = min(70.0, _deadline_left() - 240.0)
            if budget < 35:
                raise RuntimeError("skipped: %.0fs left" % _deadline_left())
            import tempfile

            from tendermint_tpu.simnet.harness import run_scenario
            from tendermint_tpu.simnet.scenario import Scenario

            lat_sc = Scenario(
                name="txlat-4", seed=901, validators=4, target_height=6,
                max_runtime_s=budget, load_rate=30, timeout_scale=2.0,
                max_rounds=10,
            )
            with tempfile.TemporaryDirectory() as td:
                rep = run_scenario(lat_sc, td)
            fin = rep.get("finality", {})

            def _ms(key):
                v = fin.get(key)
                return round(v * 1e3, 2) if v is not None else None

            _partial.update({
                "tx_latency_ok": rep["ok"],
                "tx_latency_count": fin.get("count", 0),
                "tx_finality_p50_ms": _ms("p50_s"),
                "tx_finality_p95_ms": _ms("p95_s"),
                "tx_finality_p99_ms": _ms("p99_s"),
                "tx_finality_max_ms": _ms("max_s"),
                "tx_latency_accepted_tx_per_s":
                    rep["load"]["accepted_tx_per_s"],
            })
        except Exception as e:  # noqa: BLE001
            _partial["tx_latency_error"] = str(e)[-300:]

        # -- gateway fan-out (round 13, ISSUE 13): the read-path serving
        # surface — N concurrent in-process light clients syncing one
        # synthetic chain through the gateway's cross-client verify
        # coalescer + height-keyed response cache, vs the sequential
        # one-client-at-a-time baseline on the same host.  Headline at
        # the larger N; the dedup ratio is ALSO reported at N=8 (the
        # acceptance bar reads that point).  Pinned to the host verify
        # path inside the harness (a window-sized flush crossing the
        # device threshold on a cold cache would pay a compile per
        # rung — this stage measures serving architecture, not the
        # kernel).  Placed before the device stages (the r05
        # tail-loss lesson) and budgeted: chain signing is the dominant
        # term (~3-5s per fresh chain, 3 chains + a probe).
        _stage_set("gateway-fanout")
        try:
            budget = min(60.0, _deadline_left() - 220.0)
            if budget < 25:
                raise RuntimeError("skipped: %.0fs left" % _deadline_left())
            from tendermint_tpu.gateway.testkit import run_fanout_bench

            gw_rep = run_fanout_bench()
            _partial.update({
                "gateway_clients": gw_rep["clients"],
                "gateway_fanout_ok": gw_rep["all_ok"],
                "gateway_clients_synced_per_s":
                    gw_rep["clients_synced_per_s"],
                "gateway_fanout_speedup": gw_rep["speedup"],
                "gateway_seq_client_s": gw_rep["sequential_client_s"],
                "gateway_fanout_wall_s": gw_rep["fanout_wall_s"],
                "gateway_verify_dedup_ratio": gw_rep["dedup_ratio"],
                "gateway_n8_dedup_ratio": gw_rep.get("n8_dedup_ratio"),
                "gateway_cache_hit_ratio": gw_rep["cache_hit_ratio"],
                "gateway_verify_flushes": gw_rep["verify_flushes"],
                "gateway_backpressure_ok": gw_rep["backpressure_ok"],
            })
        except Exception as e:  # noqa: BLE001
            _partial["gateway_fanout_error"] = str(e)[-300:]

        # -- fleet scrape (round 14, ISSUE 14): cluster-scope
        # observability overhead — scrape+aggregate+SLO wall time over a
        # LIVE 4-node localnet (real Node objects, RPC + metrics
        # listeners) via the shared fleet/testkit.py harness, the same
        # one behind the tests/test_fleet.py acceptance.  The scraper
        # fans out over a thread pool, so the budget tracks the slowest
        # NODE, not the node count — p50 of 5 scrape+aggregate+evaluate
        # cycles vs a 2s budget.  Placed before the device stages (the
        # r05 tail-loss lesson) and budgeted so the device pipeline
        # keeps its reserve.
        _stage_set("fleet-scrape")
        try:
            budget = min(60.0, _deadline_left() - 200.0)
            if budget < 30:
                raise RuntimeError("skipped: %.0fs left" % _deadline_left())
            from tendermint_tpu.fleet.testkit import run_fleet_bench

            fl_rep = run_fleet_bench()
            _partial.update({
                "fleet_nodes": fl_rep["nodes"],
                "fleet_scrape_ms": fl_rep["scrape_ms_p50"],
                "fleet_scrape_max_ms": fl_rep["scrape_ms_max"],
                "fleet_scrape_within_budget": fl_rep["within_budget"],
                "fleet_availability": fl_rep["availability"],
                "fleet_slo_ok": fl_rep["slo_ok"],
                "fleet_rows_scraped": fl_rep["rows_ok"],
                "fleet_finality_observations": fl_rep["finality_count"],
            })
        except Exception as e:  # noqa: BLE001
            _partial["fleet_scrape_error"] = str(e)[-300:]

        # -- impl shootout (round 9, ISSUE 12): the field-representation
        # comparison int64 vs packed on ONE rung, timed side by side, with each
        # impl's HLO bytes/row and FLOPs/row from the cost harvest — the
        # steering metrics of the representation attack, landing in
        # benchdiff's tracked set (_sigs_per_sec / _bytes_per_row rules)
        # so a regression in EITHER the winner or a non-default impl is
        # flagged next round.  Placed BEFORE the device stages (the r05
        # tail-loss lesson) and budgeted per impl: a fresh compile
        # shrinks or skips, never threatens the headline stages.
        _stage_set("impl-shootout")
        try:
            from tendermint_tpu.ops import ed25519_jax as _dev9

            sn = int(os.environ.get(
                "TM_BENCH_SHOOTOUT_N",
                "1024" if platform == "cpu" else "4096"))
            sn = max(8, min(sn, N))
            shoot_rung = _dev9._bucket(sn)
            impls_s = [i.strip() for i in os.environ.get(
                "TM_BENCH_SHOOTOUT_IMPLS", "int64,packed").split(",")
                if i.strip()]
            shoot_runs = max(2, min(TIMED_RUNS, 3))
            # the reserve keeps the production headline + device stages
            # affordable even if one impl pays a cold compile
            reserve9 = 180.0
            for impl in impls_s:
                key = f"shootout_{impl}"
                try:
                    # cost rows first (a TRACE, never a compile): the
                    # bytes/row number is the representation win itself
                    try:
                        from tendermint_tpu.cli.profile import harvest_entry

                        rec = harvest_entry("verify", shoot_rung, impl)
                        if rec.get("bytes_accessed"):
                            _partial[f"{key}_hlo_bytes_per_row"] = round(
                                rec["bytes_accessed"] / shoot_rung, 1)
                        if rec.get("flops"):
                            _partial[f"{key}_flops_per_row"] = round(
                                rec["flops"] / shoot_rung, 1)
                    except Exception as e:  # noqa: BLE001
                        _partial[f"{key}_cost_error"] = str(e)[-200:]
                    if _deadline_left() < reserve9:
                        raise RuntimeError(
                            "skipped: %.0fs left" % _deadline_left())
                    t_w = time.perf_counter()
                    ok = _dev9.verify_batch(
                        pubs[:sn], msgs[:sn], sigs[:sn], impl=impl)
                    assert ok.all(), f"shootout warmup failed ({impl})"
                    _partial[f"{key}_warm_s"] = round(
                        time.perf_counter() - t_w, 3)
                    times9 = []
                    for _ in range(shoot_runs):
                        t0 = time.perf_counter()
                        ok = _dev9.verify_batch(
                            pubs[:sn], msgs[:sn], sigs[:sn], impl=impl)
                        times9.append(time.perf_counter() - t0)
                        assert ok.all()
                    p50_9 = statistics.median(times9)
                    _partial[f"{key}_sigs_per_sec"] = round(sn / p50_9, 1)
                    _partial[f"{key}_wall_p50_ms"] = round(p50_9 * 1e3, 3)
                except Exception as e:  # noqa: BLE001 — one impl failing
                    # (compile OOM, budget) must not cost the others
                    _partial[f"{key}_error"] = str(e)[-300:]
            _partial["shootout_rung"] = shoot_rung
            _partial["shootout_n"] = sn
            _partial["shootout_runs"] = shoot_runs
        except Exception as e:  # noqa: BLE001
            _partial["impl_shootout_error"] = str(e)[-300:]

        if platform == "cpu":
            _stage_set("timed-production-cpu")
            from tendermint_tpu.crypto.batch import new_batch_verifier

            def run_production(count: int) -> float:
                bv = new_batch_verifier("cpu")
                for p, m, s in zip(pubs[:count], msgs[:count], sigs[:count]):
                    bv.add(p, m, s)
                t0 = time.perf_counter()
                all_ok, _oks = bv.verify()
                dt = time.perf_counter() - t0
                assert all_ok, "production cpu verification failed"
                return dt

            run_production(64)  # warm the libcrypto binding
            # headline throughput: full-N timed runs
            times = [run_production(N) for _ in range(3)]
            ours = N / statistics.median(times)
            # vs_baseline: EQUAL-SIZE same-moment pairs — both sides
            # verify BASELINE_SAMPLE sigs back to back, so each pair's
            # two timed windows are the same length and cpu-steal drift
            # cancels in the ratio (16384-vs-2048 windows left a
            # residual bias that read as 0.92-0.97 on a loaded box)
            for _ in range(5):
                base_rate = run_baseline()
                dt = run_production(BASELINE_SAMPLE)
                headline_pairs.append((BASELINE_SAMPLE / dt, base_rate))
            # stash now: a watchdog firing in a later (diagnostic) stage
            # must not cost the already-measured ratio
            _partial["vs_baseline"] = round(
                statistics.median(p / b for p, b in headline_pairs), 3
            )
            _partial["baseline_sampling"] = "interleaved-pair-median"
            _partial.update({"value": round(ours, 1), "n": N,
                             "production_path": "libcrypto-batch"})
            cn = min(COMMIT_N, N)
            lat = [run_production(cn) for _ in range(3)]
            p50_ms = statistics.median(lat) * 1e3
            # label honestly: only a full 10k batch earns the north-star
            # key, and a host number never wears the device key's name
            lat_key = ("host_commit10k_p50_ms" if cn == COMMIT_N
                       else f"host_commit{cn}_p50_ms")
            _partial[lat_key] = round(p50_ms, 3)

        # Continuous-profiler overhead (ISSUE 18): the sampler's cost
        # contract, measured BEFORE the device stages so it always runs
        # within budget — the DISABLED path is one attribute-load +
        # branch against the NOP singleton per call site, one ENABLED
        # sweep (all-thread frame walk + fold) stays under a stated
        # budget, and a verify workload sampled at the default ~19 Hz
        # keeps >=97% of its unsampled throughput (the always-on
        # claim: profiling may never cost the thing it measures).
        _stage_set("prof-overhead")
        try:
            from tendermint_tpu.crypto.batch import new_batch_verifier \
                as _nbv
            from tendermint_tpu.utils import profiler as _pf

            N_EV = 20_000
            nop = _pf.NOP
            t0 = time.perf_counter()
            for _ in range(N_EV):
                # measured exactly as call sites write it
                if nop.enabled:
                    nop.sample()
            disabled_ns = (time.perf_counter() - t0) / N_EV * 1e9

            state_p = {"t": 0.0}
            prof = _pf.Profiler(node="bench", hz=_pf.DEFAULT_HZ,
                                clock=lambda: state_p["t"])
            N_S = 2_000
            t0 = time.perf_counter()
            for _ in range(N_S):
                state_p["t"] += 1.0 / prof.hz
                if prof.enabled:
                    prof.sample()
            enabled_us = (time.perf_counter() - t0) / N_S * 1e6
            budget_us = 50.0  # per sweep; default cadence is ~19 Hz

            # sampled-vs-unsampled verify throughput: interleaved
            # same-size pairs on the production CPU path so cpu-steal
            # drift cancels in the ratio (the vs_baseline idiom)
            pn = max(8, min(2048, N))

            def _run_verify() -> float:
                bv = _nbv("cpu")
                for p, m, s in zip(pubs[:pn], msgs[:pn], sigs[:pn]):
                    bv.add(p, m, s)
                t0 = time.perf_counter()
                all_ok, _oks = bv.verify()
                dt = time.perf_counter() - t0
                assert all_ok, "prof-overhead verification failed"
                return pn / dt

            _run_verify()  # warm the libcrypto binding
            live = _pf.Profiler(node="bench", hz=_pf.DEFAULT_HZ)
            ratios = []
            for _ in range(3):
                off = _run_verify()
                live.start()
                try:
                    on = _run_verify()
                finally:
                    live.stop()
                ratios.append(on / off)
            verify_ratio = statistics.median(ratios)
            _partial.update({
                "prof_disabled_ns_per_sample": round(disabled_ns, 1),
                "prof_enabled_us_per_sample": round(enabled_us, 2),
                "prof_budget_us_per_sample": budget_us,
                "prof_within_budget": bool(enabled_us <= budget_us),
                "prof_verify_ratio": round(verify_ratio, 4),
                "prof_hz": _pf.DEFAULT_HZ,
                "prof_sweep_samples": live.samples + prof.samples,
            })
            assert enabled_us <= budget_us, (
                f"prof {enabled_us:.1f}us/sweep exceeds {budget_us}us")
            assert verify_ratio >= 0.97, (
                f"sampled verify throughput {verify_ratio:.3f}x of "
                "unsampled (>=0.97 required)")
        except Exception as e:  # noqa: BLE001
            _partial["prof_overhead_error"] = str(e)[-300:]

        # Flight-data history overhead (ISSUE 19): the recorder's cost
        # contract, measured BEFORE the device stages like the other
        # observability gates — the DISABLED path is one attribute-load
        # + branch against the NOP singleton, one ENABLED sample
        # (source render + parse + delta-encode + disk append) stays
        # under a stated budget, and the segment growth at the default
        # cadence is reported as bytes/hour so retention math stays an
        # artifact fact, not a doc promise.
        _stage_set("history-overhead")
        try:
            import shutil as _sh
            import tempfile as _tf

            from tendermint_tpu.utils import history as _hist

            N_EV = 20_000
            nop = _hist.NOP
            t0 = time.perf_counter()
            for _ in range(N_EV):
                # measured exactly as call sites write it
                if nop.enabled:
                    nop.sample()
            disabled_ns = (time.perf_counter() - t0) / N_EV * 1e9

            # ~30-series synthetic exposition (a small node's /metrics),
            # two of them moving per sample so deltas stay non-trivial;
            # the static block is pre-rendered so the measurement
            # charges the RECORDER (parse + delta + append), not
            # synthetic string construction
            static_h = "\n".join(f"tendermint_bench_gauge_{i} {i * 1.5}"
                                 for i in range(28))
            state_h = {"n": 0}

            def _src() -> str:
                state_h["n"] += 1
                n = state_h["n"]
                return (f"{static_h}\n"
                        f"tendermint_bench_commits_total {n}\n"
                        f"tendermint_bench_height {n // 2}\n")

            hist_dir = _tf.mkdtemp(prefix="bench-history-")
            rec = _hist.HistoryRecorder(node="bench", root=hist_dir,
                                        source=_src)
            N_S = 2_000
            t0 = time.perf_counter()
            for _ in range(N_S):
                if rec.enabled:
                    rec.sample()
            enabled_us = (time.perf_counter() - t0) / N_S * 1e6
            budget_us = 50.0  # per sample; default cadence is 0.1 Hz
            bytes_per_hour = (rec.bytes_written / N_S
                              * 3600.0 / _hist.DEFAULT_INTERVAL_S)
            rec.stop()
            _sh.rmtree(hist_dir, ignore_errors=True)
            _partial.update({
                "history_disabled_ns_per_sample": round(disabled_ns, 1),
                "history_enabled_us_per_sample": round(enabled_us, 2),
                "history_budget_us_per_sample": budget_us,
                "history_within_budget": bool(enabled_us <= budget_us),
                "history_bytes_per_hour": round(bytes_per_hour, 1),
                "history_interval_s": _hist.DEFAULT_INTERVAL_S,
            })
            assert enabled_us <= budget_us, (
                f"history {enabled_us:.1f}us/sample exceeds {budget_us}us")
        except Exception as e:  # noqa: BLE001
            _partial["history_overhead_error"] = str(e)[-300:]

        # Race-sanitizer overhead (ISSUE 20): the tmsan cost contract,
        # measured BEFORE the device stages like the other
        # observability gates — an instrumented class left behind with
        # the checker OFF costs one predictable branch per attribute
        # access (the promise that lets instrument() stay wired into
        # long-lived classes), and one ENABLED access (ident + held-set
        # + lockset fold under the checker mutex) stays under a stated
        # budget so sanitized test suites remain usable.
        _stage_set("racecheck-overhead")
        try:
            from tendermint_tpu.utils import racecheck as _rc

            class _Probe:
                def __init__(self):
                    self.x = 0

            assert not _rc.CHECKER._active, (
                "race sanitizer left active before the bench stage")
            _rc.instrument(_Probe)
            N_EV = 20_000

            def _spin(n: int) -> float:
                obj = _Probe()
                t0 = time.perf_counter()
                for _ in range(n):
                    obj.x = obj.x + 1  # one tracked read + one write
                return (time.perf_counter() - t0) / (2 * n)

            _spin(1_000)  # warm the wrapper path
            disabled_ns = min(_spin(N_EV) for _ in range(3)) * 1e9

            _rc.install()
            try:
                _spin(1_000)
                enabled_us = min(_spin(5_000) for _ in range(3)) * 1e6
                races = len(_rc.violations())
            finally:
                _rc.reset()
                _rc.uninstall()
            _rc.uninstrument(_Probe)
            budget_us = 25.0  # per tracked access, single-thread
            _partial.update({
                "racecheck_disabled_ns_per_attr": round(disabled_ns, 1),
                "racecheck_enabled_us_per_attr": round(enabled_us, 3),
                "racecheck_budget_us_per_attr": budget_us,
                "racecheck_within_budget": bool(enabled_us <= budget_us),
            })
            assert races == 0, "single-thread probe raced?"
            assert enabled_us <= budget_us, (
                f"racecheck {enabled_us:.2f}us/access exceeds {budget_us}us")
            assert disabled_ns <= 5_000, (
                f"disabled racecheck branch costs {disabled_ns:.0f}ns "
                "per access — the NOP contract regressed")
        except Exception as e:  # noqa: BLE001
            _partial["racecheck_overhead_error"] = str(e)[-300:]

        if platform == "cpu":
            # XLA-CPU device path: diagnostic only (trend tracking), at a
            # reduced batch; NOTHING here — including the import and the
            # smoke batch — may cost the already-measured production
            # headline
            _stage_set(f"diag-device-n{device_n}")
            try:
                from tendermint_tpu.ops import ed25519_jax as dev

                ok = dev.verify_batch(pubs[:8], msgs[:8], sigs[:8])
                assert ok.all(), "n=8 smoke verification failed"
                dev.verify_batch(pubs[:device_n], msgs[:device_n], sigs[:device_n])
                dt = []
                for _ in range(TIMED_RUNS):
                    t0 = time.perf_counter()
                    ok = dev.verify_batch(
                        pubs[:device_n], msgs[:device_n], sigs[:device_n]
                    )
                    dt.append(time.perf_counter() - t0)
                    assert ok.all()
                _partial["xla_cpu_device_sigs_per_sec"] = round(
                    device_n / statistics.median(dt), 1
                )
                _partial["xla_cpu_device_n"] = device_n
            except Exception as e:  # noqa: BLE001
                _partial["xla_cpu_device_error"] = str(e)[-300:]
        else:
            # Device headline path: every impl in TM_BENCH_FIELD_IMPLS is
            # measured and the faster one carries the headline.
            from tendermint_tpu.ops import ed25519_jax as dev

            _stage_set("smoke-n8")
            ok = dev.verify_batch(pubs[:8], msgs[:8], sigs[:8])
            assert ok.all(), "n=8 smoke verification failed"

            # int64 only by default: a second impl's rungs cost a cold
            # compile of the 480 s watchdog budget.
            # TM_BENCH_FIELD_IMPLS=int64,packed measures both.
            impls = os.environ.get("TM_BENCH_FIELD_IMPLS", "int64").split(",")
            ours = 0.0
            p50_ms = None
            for impl in [i.strip() for i in impls if i.strip()]:
                _stage_set(f"warmup-{impl}-n{N}")
                try:
                    ok = dev.verify_batch(pubs, msgs, sigs, impl=impl)
                    assert ok.all(), f"warmup verification failed ({impl})"

                    _stage_set(f"timed-throughput-{impl}")
                    times = []
                    impl_pairs = []
                    for _ in range(TIMED_RUNS):
                        t0 = time.perf_counter()
                        ok = dev.verify_batch(pubs, msgs, sigs, impl=impl)
                        dt = time.perf_counter() - t0
                        times.append(dt)
                        # matched-duration baseline window right after:
                        # same-length A/B windows, same as the CPU branch
                        base_rate = run_baseline_for(dt)
                        impl_pairs.append((N / dt, base_rate))
                        assert ok.all()
                    rate = N / statistics.median(times)
                    _partial[f"field_impl_{impl}_sigs_per_sec"] = round(rate, 1)

                    _stage_set(f"timed-commit-latency-{impl}")
                    cn = min(COMMIT_N, N)
                    lat = []
                    for _ in range(max(TIMED_RUNS, 5)):
                        t0 = time.perf_counter()
                        ok = dev.verify_batch(
                            pubs[:cn], msgs[:cn], sigs[:cn], impl=impl
                        )
                        lat.append(time.perf_counter() - t0)
                        assert ok.all()
                    impl_p50 = statistics.median(lat) * 1e3
                    _partial[f"field_impl_{impl}_commit_p50_ms"] = round(impl_p50, 3)
                    if rate > ours:
                        ours = rate
                        p50_ms = impl_p50
                        headline_pairs = impl_pairs
                        _partial.update(
                            {"value": round(ours, 1), "n": N, "field_impl": impl}
                        )
                except Exception as e:  # noqa: BLE001
                    # one impl failing (e.g. compile OOM) must not cost
                    # the other's headline
                    _partial[f"field_impl_{impl}_error"] = str(e)[-300:]
            if headline_pairs:
                # stash now: a watchdog firing in any later (optional)
                # stage must not cost the already-measured ratio — same
                # hardening the CPU branch has had since r3
                _partial["vs_baseline"] = round(
                    statistics.median(p / b for p, b in headline_pairs), 3
                )
                _partial["baseline_sampling"] = "interleaved-pair-median"
            # Device-only 10k-commit latency (VERDICT r4 item 2): rows
            # prepared ONCE and placed on device, then only the compiled
            # program + the verdict-bit readback are timed — the
            # device's share of the end-to-end p50 reported beside it.
            _stage_set("timed-commit-device-only")
            try:
                if _deadline_left() < 60:
                    raise RuntimeError(
                        "skipped: %.0fs left" % _deadline_left())
                import numpy as _np

                import jax as _jax

                impl0 = _partial.get("field_impl", "int64")
                cn = min(COMMIT_N, N)
                b = dev._bucket(cn)
                padded_np = dev._pad_rows(
                    cn, b, *dev.prepare_batch(pubs[:cn], msgs[:cn], sigs[:cn]))

                # donated row buffers (ISSUE 7) mean a device array
                # is DELETED by the call that consumes it, so the
                # pre-placed inputs are re-placed per run — the
                # device_put stays OUTSIDE the timed window, which
                # is exactly the device-only semantics this stage
                # has always measured
                def _place():
                    return [_jax.device_put(x) for x in padded_np]

                _np.asarray(dev._compiled(b, impl0)(*_place()))  # warm
                lat = []
                for _ in range(5):
                    placed = _place()
                    t0 = time.perf_counter()
                    okd = _np.asarray(dev._compiled(b, impl0)(*placed))[:cn]
                    lat.append(time.perf_counter() - t0)
                    assert okd.all()
                _partial["commit10k_device_only_p50_ms"] = round(
                    statistics.median(lat) * 1e3, 3)
            except Exception as e:  # noqa: BLE001
                _partial["commit10k_device_only_error"] = str(e)[-300:]

            if ours == 0.0:
                raise RuntimeError("no field impl produced a device number")
            cn = min(COMMIT_N, N)
            lat_key = "commit10k_p50_ms" if cn == COMMIT_N else f"commit{cn}_p50_ms"
            _partial[lat_key] = round(p50_ms, 3)

        # Concurrent-submitter coalescing (round 6): N parallel streams,
        # each repeatedly verifying its own 64-sig slice — the gossip /
        # blocksync / commit-verify shape, where every individual batch
        # sits below the dispatch threshold and a per-caller verifier
        # can never amortize anything.  Arm A: one verifier per stream
        # (the pre-r6 production shape).  Arm B: every stream submits to
        # the async verification service (crypto.async_verify), which
        # coalesces the streams into single flushes.  Same backend, same
        # threshold policy; only the batching point differs — this is
        # the win the single-caller throughput stages above cannot see.
        _stage_set("async-coalesce")
        try:
            if _deadline_left() < 60:
                raise RuntimeError("skipped: %.0fs left" % _deadline_left())
            from tendermint_tpu.crypto import async_verify as _av
            from tendermint_tpu.crypto import batch as _cbatch
            from tendermint_tpu.crypto import ed25519 as _ced

            streams = int(os.environ.get("TM_BENCH_STREAMS", "16"))
            rounds = int(os.environ.get("TM_BENCH_STREAM_ROUNDS", "4"))
            per = min(64, N)
            streams = max(1, min(streams, N // per))
            rounds = max(1, min(rounds, N // (streams * per)))
            # every (stream, round) slice is a distinct set of triples so
            # the service's verified-signature cache cannot shortcut the
            # timed arm (dedup is measured separately below)
            data = []
            base = 0
            for _s in range(streams):
                rows = []
                for _r in range(rounds):
                    sl = slice(base, base + per)
                    rows.append(list(zip(pubs[sl], msgs[sl], sigs[sl])))
                    base += per
                data.append(rows)
            # XLA-CPU's device program is a diagnostic path (and a fresh
            # bucket compile costs minutes): pin both arms to the host
            # route there; real accelerators keep the production policy
            thr_pin = (1 << 30) if platform == "cpu" else None
            _ced.verify_batch_fast(pubs[:per], msgs[:per], sigs[:per])  # warm

            def _run_arm(worker) -> float:
                errs: list = []
                ths = [
                    threading.Thread(target=worker, args=(s, errs))
                    for s in range(streams)
                ]
                t0 = time.perf_counter()
                for t in ths:
                    t.start()
                for t in ths:
                    t.join()
                dt = time.perf_counter() - t0
                assert not errs, errs[0]
                return streams * rounds * per / dt

            def indep_worker(s: int, errs: list) -> None:
                try:
                    bv = (_cbatch.JAXBatchVerifier(cpu_threshold=thr_pin)
                          if thr_pin is not None
                          else _cbatch.new_batch_verifier())
                    for tri in data[s]:
                        for p, m, g in tri:
                            bv.add(p, m, g)
                        ok, _oks = bv.verify()
                        assert ok, "independent arm verification failed"
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

            indep_rate = _run_arm(indep_worker)

            svc = _av.reset_service(cpu_threshold=thr_pin)

            def svc_worker(s: int, errs: list) -> None:
                try:
                    for tri in data[s]:
                        oks = svc.verify_many(tri)
                        assert all(oks), "service arm verification failed"
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

            svc_rate = _run_arm(svc_worker)
            st = _av.service_stats()
            # dedup demonstration: resubmitting an already-verified slice
            # must resolve from the cache without any host/device work
            hits0, host0, dev0 = (st["cache_hits"], st["host_flushes"],
                                  st["device_batches"])
            assert all(svc.verify_many(data[0][0]))
            st2 = _av.service_stats()
            _partial.update({
                "async_svc_sigs_per_sec": round(svc_rate, 1),
                "independent_sigs_per_sec": round(indep_rate, 1),
                "async_coalesce_speedup": round(svc_rate / indep_rate, 3),
                "async_streams": streams,
                "async_stream_rounds": rounds,
                "async_flushes": st["flushes"],
                "async_coalesced_max": st["coalesced_max"],
                "async_device_batches": st["device_batches"],
                "async_cache_hits_on_resubmit": st2["cache_hits"] - hits0,
                "async_work_on_resubmit": (st2["host_flushes"] - host0
                                           + st2["device_batches"] - dev0),
            })
        except Exception as e:  # noqa: BLE001
            _partial["async_coalesce_error"] = str(e)[-300:]

        # Per-stage trace summary (round 7): with TM_TPU_TRACE=1 the
        # async-coalesce stage above ran with span tracing live, so the
        # verify pipeline's submit/coalesce/flush/host/device spans are
        # in the utils.trace ring.  Fold p50/p95/p99 per span name into
        # the BENCH json and dump the Perfetto-loadable Chrome trace next
        # to it — per-stage timing now ships in the artifact instead of
        # living in ad-hoc bench code.
        _stage_set("trace-export")
        try:
            from tendermint_tpu.utils import trace as _tr

            if _tr.enabled():
                summ = _tr.summary()
                _partial["trace_summary"] = summ
                _partial["trace_spans"] = sum(
                    v["count"] for v in summ.values())
                out_path = os.environ.get("TM_TPU_TRACE_OUT",
                                          "bench_trace.json")
                with open(out_path, "w") as f:
                    f.write(_tr.export_chrome())
                _partial["trace_out"] = out_path
        except Exception as e:  # noqa: BLE001
            _partial["trace_error"] = str(e)[-300:]

        # Journal overhead (round 8, ISSUE 3): prove the cost contract of
        # the consensus event journal — the DISABLED path is one
        # attribute-load + branch per event site (nanoseconds), and the
        # ENABLED path (json dump + buffered write + flush) stays under a
        # stated per-event budget, so journaling a live net is safe.
        _stage_set("journal-overhead")
        try:
            import tempfile

            from tendermint_tpu.consensus import eventlog as _el

            N_EV = 20_000
            nop = _el.NOP
            # measure the guard as event sites actually write it:
            # `if journal.enabled: journal.log(...)`
            t0 = time.perf_counter()
            for _ in range(N_EV):
                if nop.enabled:
                    nop.log("vote", h=1, r=0)
            disabled_ns = (time.perf_counter() - t0) / N_EV * 1e9

            with tempfile.TemporaryDirectory() as td:
                jr = _el.EventJournal(os.path.join(td, "bench.jsonl"),
                                      node="bench")
                t0 = time.perf_counter()
                for i in range(N_EV):
                    if jr.enabled:
                        jr.log("vote", h=i, r=0, type="prevote", val=i % 4,
                               block="ab" * 8, at_r=0, **{"from": "peer"})
                enabled_us = (time.perf_counter() - t0) / N_EV * 1e6
                jr.close()
            budget_us = 150.0  # per-event ceiling; ~40 events/block today
            _partial.update({
                "journal_disabled_ns_per_event": round(disabled_ns, 1),
                "journal_enabled_us_per_event": round(enabled_us, 2),
                "journal_budget_us_per_event": budget_us,
                "journal_within_budget": bool(enabled_us <= budget_us),
            })
            assert enabled_us <= budget_us, (
                f"journal {enabled_us:.1f}us/event exceeds {budget_us}us")
        except Exception as e:  # noqa: BLE001
            _partial["journal_overhead_error"] = str(e)[-300:]

        # Tx lifecycle overhead (round 9, ISSUE 9): the cost contract of
        # EVERY lifecycle hook site (rpc ingress, mempool admit/recv,
        # gossip send, proposal inclusion, commit/apply) is the journal's
        # — the DISABLED path is one attribute-load + branch against the
        # NOP singleton, and the ENABLED path (dict ops, no journal, no
        # hashing: sites reuse the mempool's sha256 keys) stays under a
        # stated per-stamp budget.
        _stage_set("txlife-overhead")
        try:
            from tendermint_tpu.utils import txlife as _tl

            N_EV = 20_000
            nop = _tl.NOP
            t0 = time.perf_counter()
            for _ in range(N_EV):
                # measured exactly as hook sites write it
                if nop.enabled:
                    nop.stamp(b"k" * 32, "admit")
            disabled_ns = (time.perf_counter() - t0) / N_EV * 1e9

            life = _tl.TxLifecycle(node="bench")  # journal off: store cost
            keys = [i.to_bytes(32, "big") for i in range(N_EV)]
            t0 = time.perf_counter()
            for k in keys:  # distinct keys: insert + eviction-bound path
                if life.enabled:
                    life.stamp(k, "admit")
            enabled_us = (time.perf_counter() - t0) / N_EV * 1e6
            budget_us = 25.0  # per stamp; a tx makes ~6 stamps per node
            _partial.update({
                "txlife_disabled_ns_per_stamp": round(disabled_ns, 1),
                "txlife_enabled_us_per_stamp": round(enabled_us, 2),
                "txlife_budget_us_per_stamp": budget_us,
                "txlife_within_budget": bool(enabled_us <= budget_us),
                "txlife_evicted": life.evicted,
            })
            assert enabled_us <= budget_us, (
                f"txlife {enabled_us:.1f}us/stamp exceeds {budget_us}us")
        except Exception as e:  # noqa: BLE001
            _partial["txlife_overhead_error"] = str(e)[-300:]

        # Health watchdog overhead (round 10, ISSUE 10): the monitor's
        # cost contract — the DISABLED path is one attribute-load +
        # branch against the NOP singleton per call site, and one
        # ENABLED sample (probe merge + six detector updates) stays
        # under a stated budget.  Plus a short soak: a monitor fed a
        # healthy synthetic node (height advancing, round 0, flat RSS,
        # empty queue, quiet peers) must record ZERO critical
        # transitions — the spurious-alarm guard for real soak runs.
        _stage_set("health-overhead")
        try:
            from tendermint_tpu.utils import health as _hl

            N_EV = 20_000
            nop = _hl.NOP
            t0 = time.perf_counter()
            for _ in range(N_EV):
                # measured exactly as call sites write it
                if nop.enabled:
                    nop.sample()
            disabled_ns = (time.perf_counter() - t0) / N_EV * 1e9

            state = {"h": 0, "t": 0.0}

            def _healthy_probe():
                state["h"] += 1
                return {"height": state["h"], "round": 0,
                        "rss_bytes": 100 << 20, "verify_queue_depth": 0,
                        "peer_disconnects": 0, "cold_compiles": 0}

            mon = _hl.HealthMonitor(
                node="bench", probes={"bench": _healthy_probe},
                detectors=_hl.default_detectors(expected_block_s=0.5),
                clock=lambda: state["t"])
            N_S = 5_000
            t0 = time.perf_counter()
            for _ in range(N_S):
                state["t"] += 0.5   # healthy cadence: one commit/sample
                if mon.enabled:
                    mon.sample()
            enabled_us = (time.perf_counter() - t0) / N_S * 1e6
            budget_us = 50.0  # per sample; default cadence is 1/2s
            criticals = sum(1 for tr in mon.report()["transitions"]
                            if tr["to"] == _hl.CRITICAL)
            _partial.update({
                "health_disabled_ns_per_sample": round(disabled_ns, 1),
                "health_enabled_us_per_sample": round(enabled_us, 2),
                "health_budget_us_per_sample": budget_us,
                "health_within_budget": bool(enabled_us <= budget_us),
                "health_soak_samples": N_S,
                "health_soak_criticals": criticals,
            })
            assert enabled_us <= budget_us, (
                f"health {enabled_us:.1f}us/sample exceeds {budget_us}us")
            assert criticals == 0, (
                f"{criticals} spurious critical transition(s) on a "
                "healthy synthetic node")
        except Exception as e:  # noqa: BLE001
            _partial["health_overhead_error"] = str(e)[-300:]

        # Remediation controller overhead (round 11, ISSUE 11): the
        # detector->action loop's cost contract — the DISABLED path is
        # one attribute-load + branch against the NOP singleton per
        # transition dispatch, and one ENABLED shed transition (mempool
        # set_shed + bookkeeping + journal branch) stays under a stated
        # budget.  Transitions are rare by construction (hysteresis), so
        # the budget is per TRANSITION, never per tx or per sample.
        _stage_set("remediation-overhead")
        try:
            from tendermint_tpu.mempool.mempool import (
                Mempool as _Mp,
                MempoolConfig as _MpCfg,
            )
            from tendermint_tpu.utils import remediate as _rm

            N_EV = 20_000
            nop = _rm.NOP
            tr_warn = {"detector": "verify_queue_saturation",
                       "from": 0, "to": 1, "detail": "", "excused": False}
            t0 = time.perf_counter()
            for _ in range(N_EV):
                # measured exactly as the monitor's dispatch writes it
                if nop.enabled:
                    nop.act(tr_warn)
            disabled_ns = (time.perf_counter() - t0) / N_EV * 1e9

            class _ShedOnly:
                """set_shed/shed_state surface only — no ABCI app."""

                def set_shed(self, level, rpc_max_bytes=0,
                             retry_after_ms=0):
                    self.level = level

                def shed_state(self):
                    return {"level": getattr(self, "level", 0)}

            ctl = _rm.RemediationController(
                node="bench", mempool=_ShedOnly(),
                rewarm=lambda reason: False)
            N_TR = 5_000
            t0 = time.perf_counter()
            for k in range(N_TR):
                # alternate warn/clear so every act() is a level CHANGE
                # (the expensive arm: set_shed + note + history)
                if ctl.enabled:
                    ctl.act({"detector": "verify_queue_saturation",
                             "from": k % 2, "to": (k + 1) % 2,
                             "detail": "", "excused": False})
            enabled_us = (time.perf_counter() - t0) / N_TR * 1e6
            budget_us = 200.0  # per transition; transitions are rare
            _partial.update({
                "remediation_disabled_ns_per_event": round(disabled_ns, 1),
                "remediation_enabled_us_per_transition": round(enabled_us, 2),
                "remediation_budget_us_per_transition": budget_us,
                "remediation_within_budget": bool(enabled_us <= budget_us),
                "remediation_actions_total": sum(
                    v for _l, v in ctl.action_samples()),
            })
            assert enabled_us <= budget_us, (
                f"remediation {enabled_us:.1f}us/transition exceeds "
                f"{budget_us}us")
            # shed-path contract: a shedding mempool rejects a gossip tx
            # in O(1) with the typed error (no app round-trip)
            mp = _Mp(_MpCfg(), app_conn=None)
            mp.set_shed(1, rpc_max_bytes=4096, retry_after_ms=500)
            from tendermint_tpu.mempool.mempool import (
                MempoolBackpressureError as _Bp,
            )

            try:
                mp.check_tx(b"bench-tx", sender="peer1")
                raise AssertionError("shedding mempool admitted gossip tx")
            except _Bp as e:
                assert e.retry_after_ms == 500
            _partial["remediation_shed_path_ok"] = True
        except Exception as e:  # noqa: BLE001
            _partial["remediation_overhead_error"] = str(e)[-300:]

        # Device observability (round 9, ISSUE 4): the occupancy/padding
        # accounting rides EVERY device flush site, so its cost contract
        # mirrors the journal's — the DISABLED path is one branch per
        # flush, and the ENABLED path (lock + dict bumps + one histogram
        # observe, per batch, never per signature) stays under a stated
        # budget.  The stages above ran with the accounting live, so the
        # real occupancy/compile picture folds into the artifact too.
        _stage_set("device-observability")
        try:
            from tendermint_tpu.utils import devmon as _dm
            from tendermint_tpu.utils.metrics import Histogram as _Hist

            N_FLUSH = 20_000
            hist = _Hist("bench_occupancy_ratio", "", label_names=("rung",),
                         buckets=_dm.OCCUPANCY_BUCKETS)
            st_off = _dm.DeviceStats(enabled=False, hist=hist)
            t0 = time.perf_counter()
            for _ in range(N_FLUSH):
                if st_off.enabled:
                    st_off.record_flush("verify", 129, 192, nbytes=24768)
            disabled_ns = (time.perf_counter() - t0) / N_FLUSH * 1e9

            st_on = _dm.DeviceStats(enabled=True, hist=hist)
            t0 = time.perf_counter()
            for _ in range(N_FLUSH):
                if st_on.enabled:
                    st_on.record_flush("verify", 129, 192, nbytes=24768)
            enabled_us = (time.perf_counter() - t0) / N_FLUSH * 1e6
            budget_us = 25.0  # per device flush (one flush per batch)

            snap = _dm.device_stats()  # the run's REAL accounting
            _partial.update({
                "devstats_disabled_ns_per_flush": round(disabled_ns, 1),
                "devstats_enabled_us_per_flush": round(enabled_us, 2),
                "devstats_budget_us_per_flush": budget_us,
                "devstats_within_budget": bool(enabled_us <= budget_us),
                "device_flushes": snap["flushes_total"],
                "device_padding_rows_total": snap["padding_rows_total"],
                "device_transfer_bytes_total": snap["transfer_bytes_total"],
                "device_occupancy": [
                    {"kind": r["kind"], "rung": r["rung"],
                     "flushes": r["flushes"],
                     "mean_occupancy": r["mean_occupancy"]}
                    for r in snap["rungs"]],
                "jit_compiles": snap["compile"]["total"],
                "jit_compile_seconds_total": snap["compile"]["seconds_total"],
                "jit_compile_by_rung": snap["compile"]["by_rung"],
                "jit_recompiles": snap["compile"]["recompiles"],
            })
            assert enabled_us <= budget_us, (
                f"device accounting {enabled_us:.1f}us/flush exceeds "
                f"{budget_us}us")
        except Exception as e:  # noqa: BLE001
            _partial["device_observability_error"] = str(e)[-300:]

        # -- tmlint over the full tree: analyzer wall time (budget: the
        # tier-1 gate runs it on every suite, so it must stay trivially
        # cheap — <5 s for the whole package) + finding count.  A
        # non-zero count here is a regression the tier-1 test will also
        # catch; surfacing it in the BENCH artifact makes the drift
        # visible even when only the bench runs.
        _stage_set("lint")
        try:
            from tendermint_tpu.lint import lint_package

            t0 = time.perf_counter()
            lint_findings = lint_package()
            lint_s = time.perf_counter() - t0
            lint_budget_s = 5.0
            _partial.update({
                "lint_seconds": round(lint_s, 3),
                "lint_budget_s": lint_budget_s,
                "lint_within_budget": bool(lint_s <= lint_budget_s),
                "lint_findings": len(lint_findings),
            })
            if lint_findings:
                _partial["lint_first_finding"] = lint_findings[0].format()
        except Exception as e:  # noqa: BLE001
            _partial["lint_error"] = str(e)[-300:]

        _stage_set("pair-median")
        assert headline_pairs, "headline path recorded no (prod, baseline) pairs"
        base = statistics.median(b for _p, b in headline_pairs)
        vs_baseline = statistics.median(p / b for p, b in headline_pairs)

        out = {
            "metric": METRIC,
            "value": round(ours, 1),
            "unit": "sigs/s",
            "vs_baseline": round(vs_baseline, 3),
            lat_key: _partial[lat_key],
            "backend": platform,
            "n": N,
            "baseline_sigs_per_sec": round(base, 1),
            "baseline_sampling": "interleaved-pair-median",
        }
        for k, v in _partial.items():
            out.setdefault(k, v)

        # -- benchdiff (round 8, ISSUE 8): compare THIS run against the
        # newest checked-in BENCH_r*.json and embed the verdict, so a
        # throughput regression like r04→r05 (-4.7% sigs/s, which
        # shipped unflagged) is named in the artifact itself instead of
        # waiting for a human to eyeball two JSON files.  Never fails
        # the bench — the verdict keys are the signal.
        _stage_set("benchdiff")
        try:
            from tendermint_tpu.cli import benchdiff as _bd

            base_path = os.environ.get("TM_BENCH_DIFF_BASE") or \
                _bd.latest_artifact(os.path.dirname(os.path.abspath(__file__)))
            if base_path:
                base_metrics, _meta = _bd.normalize(
                    _bd.load_artifact(base_path))
                rep = _bd.diff(base_metrics, out)
                out["benchdiff_base"] = os.path.basename(base_path)
                out["benchdiff_regressions"] = rep["regressions"]
                out["benchdiff_missing"] = rep["missing_in_b"]
                out["benchdiff_ok"] = rep["ok"]
        except Exception as e:  # noqa: BLE001 — diffing must not cost the run
            out["benchdiff_error"] = str(e)[-300:]

        _partial.update(out)
        _flush_partial()
        _emit(out)
        return 0
    except BaseException:  # noqa: BLE001
        _fail(traceback.format_exc())
        return 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Many seeds in one process — the readings the limits of `correct` are
set from, and the sets of windows a four-chip cell's spreads are read from
(PERF.md section 2).  Set-up is minutes, so the system is started once and
each seed gets its own data, its own warm-up and a window that walks the
whole pool at least once:

    python3 chipbench/prove.py --workload W --seeds 11,12,13@40,14@40t
                               [--seconds 3] [--out DIR] [--control] [--rehearse]

A seed may carry its own window length (`@40`) and a trailing `t` for a
traced window (per-layer metrics instead of the end-to-end ones, as
`run.py --trace 1`).  --out writes each window's result line, as run.py
prints it, to DIR/<workload>.<k>.seed<seed>.json (for spread.py); its
`setup_s` is the first seed's alone (process start to first window) and
for later seeds only that seed's data and warm-up, so it is no reading of
the metric.

--control puts the strict verifier (chipbench/control.py) in the program's
place: every seed must then come out NOT correct.  It touches no device
(but runs at the cell's own size, wherever it is started).

One line per seed on stdout, then a summary line; exit 0 if every seed
read as it must (correct without --control, not correct with it).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import control, correct, generator  # noqa: E402
from chipbench import run as runner  # noqa: E402

SEED = re.compile(r"^(\d+)(?:@([0-9.]+))?(t?)$")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated: SEED[@SECONDS][t]")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", help="directory for each window's result line")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    plan = []
    for word in args.seeds.split(","):
        m = SEED.match(word.strip())
        if not m:
            ap.error(f"not SEED[@SECONDS][t]: {word!r}")
        plan.append((int(m[1]), float(m[2] or args.seconds), bool(m[3])))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        bench = runner.Bench(args.workload, args.rehearse)
        if args.control:
            # the control needs the data builders (the program's types)
            # and the cache's capacity, but no device and no service
            import tendermint_tpu  # noqa: F401
            from tendermint_tpu.crypto.async_verify import DEFAULT_CACHE_SIZE

            bench.system = {"cache_capacity": DEFAULT_CACHE_SIZE}
        else:
            from chipbench import system

            bench.find_device()
            bench.start(False)
        rows = []
        t_seed = runner.T_START
        for k, (seed, seconds, traced) in enumerate(plan):
            d = bench.build(seed)
            if args.control:
                calls, _, _ = generator.run_window(
                    bench.traffic, d.pool, control.bound(bench.entry, d), seconds,
                    min_calls=len(d.pool))
                numbers = correct.check_calls(bench.entry, d, calls, seed)
                ok, compared = correct.compared(numbers)
                row = {"seed": seed, "control": True, "correct": ok,
                       "calls": len(calls), "compared": compared}
            else:
                if k == 0:
                    bench.ready(seed)
                bench.warm(d)
                gc.collect()
                gc.freeze()      # as run.py: the pool must not weigh on the window's collections
                system.tracing(traced)
                setup_s = time.monotonic() - t_seed
                w = bench.window(d, seed, seconds, traced, min_calls=len(d.pool))
                res = bench.result(w, setup_s, traced)
                lat = sorted(c.seconds for c in w["obs"].calls)
                row = {"seed": seed, "control": False, "correct": w["ok"],
                       "calls": len(lat), "median_call_ms": lat[len(lat) // 2] * 1e3,
                       "traced": traced, "metrics": res["metrics"],
                       "device": res["device"], "last_route": w["route"],
                       "last_shard_layout": w["shards"], "gc_in_window": w["gc"],
                       "compared": w["compared"], "detail": w["detail"]}
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    with open(os.path.join(
                            args.out, f"{args.workload}.{k:02d}.seed{seed}.json"), "w") as fh:
                        fh.write(json.dumps(res) + "\n")
                del w, res
                gc.unfreeze()
            del d
            t_seed = time.monotonic()
            rows.append(row)
            print(json.dumps(row, default=str), flush=True)
    except runner.StageFailed as e:
        print(f"chipbench.prove: stage {e.stage!r} failed (exit {e.code}): {e.why}",
              file=sys.stderr, flush=True)
        return e.code
    as_must = all(r["correct"] != args.control for r in rows)
    print(json.dumps({"workload": args.workload, "control": args.control,
                      "seeds": len(rows), "all_as_they_must": as_must,
                      "calls_wrong": [r["compared"]["calls_wrong"]["value"] for r in rows],
                      "wall_s": time.monotonic() - runner.T_START}), flush=True)
    return 0 if as_must else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)

#!/usr/bin/env python3
"""Many seeds in one process — the readings the limits of `correct` are
set from (PERF.md section 2).  Set-up is minutes, so the system is started
once and each seed gets its own data and a short window that walks the
whole pool at least once:

    python3 chipbench/prove.py --workload W --seeds 11,12,13 [--seconds 3]
                               [--control] [--rehearse]

--control puts the strict verifier (chipbench/control.py) in the program's
place: every seed must then come out NOT correct.  It touches no device
(but runs at the cell's own size, wherever it is started).

One line per seed on stdout, then a summary line; exit 0 if every seed
read as it must (correct without --control, not correct with it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import control, correct, generator  # noqa: E402
from chipbench import run as runner  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
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
            bench.find_device()
            bench.start(False)
        rows = []
        for k, seed in enumerate(seeds):
            d = bench.build(seed)
            if args.control:
                call = control.entry(d)
                calls, _, _ = generator.run_window(
                    bench.traffic, d.pool, call, args.seconds,
                    min_calls=len(d.pool))
                numbers = correct.check_calls(d, calls, seed)
                ok, compared = correct.compared(numbers)
                row = {"seed": seed, "control": True, "correct": ok,
                       "calls": len(calls), "compared": compared}
            else:
                if k == 0:
                    bench.ready(seed)
                bench.warm(d)
                w = bench.window(d, seed, args.seconds, False,
                                 min_calls=len(d.pool))
                lat = sorted(c.seconds for c in w["obs"].calls)
                row = {"seed": seed, "control": False, "correct": w["ok"],
                       "calls": len(lat), "median_call_ms": lat[len(lat) // 2] * 1e3,
                       "compared": w["compared"], "detail": w["detail"]}
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

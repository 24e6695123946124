"""Device benchmark for the RLC batch-verification path (round 4).

Times the cofactored random-linear-combination program
(ops/ed25519_jax.verify_core_rlc — shared-doubling Straus accumulator)
against the per-row program on the same batch, same backend, same field
impl.  The RLC equation is what the reference's batch verifier computes
(ed25519consensus); the per-row program is the exact fallback.

Usage:
    python benchmarks/rlc_bench.py [--impl int64|f32] [--batch 16384]
        [--reps 5] [--platform cpu|tpu]

Prints ONE JSON line:
  {"impl":..., "batch":N, "platform":..., "rlc_device_ms":p50,
   "row_device_ms":p50, "speedup":..., "us_per_sig_rlc":...,
   "host_scalars_ms":..., "rlc_ok":true, "mixed_verdicts_exact":true}
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kernel_bench import _force_platform, _gen_batch  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="int64", choices=["int64", "f32"])
    ap.add_argument("--batch", type=int, default=16384)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    args = ap.parse_args()

    _force_platform(args.platform)
    import numpy as np

    import jax

    from tendermint_tpu.ops import ed25519_jax as dev

    # all-valid batch: the honest consensus path the RLC equation serves
    pubs, msgs, sigs, _want = _gen_batch(args.batch, bad_every=0)

    inputs = dev.prepare_batch(pubs, msgs, sigs)
    pub_rows, r_rows, s_rows, k_rows, valid = inputs
    t0 = time.perf_counter()
    z_rows, zk_rows, c_row = dev.prepare_rlc_scalars(s_rows, k_rows, valid)
    host_scalars_ms = (time.perf_counter() - t0) * 1000.0

    # shared jit cache; TM_TPU_RLC_LANES resolved per call since r5
    core_rlc = dev._compiled_rlc(args.batch, args.impl,
                                 dev.rlc_reduce_lanes())
    core_row = jax.jit(dev._core(args.impl).verify_core)

    dp = jax.device_put
    rlc_in = [dp(np.asarray(x)) for x in (pub_rows, r_rows, zk_rows, z_rows, valid)]
    row_in = [dp(np.asarray(x)) for x in inputs]

    def _materialize(out):
        # force a host copy of every leaf of the tuple output inside
        # the timed region, so no deferred work surfaces later
        return jax.tree.map(np.asarray, out)

    t0 = time.perf_counter()
    acc, prevalid = _materialize(core_rlc(*rlc_in))
    compile_rlc_s = time.perf_counter() - t0
    all_prevalid = bool(np.asarray(prevalid).all())
    # end-to-end verdict (device program + host big-int finalization)
    e2e = dev.verify_batch_rlc(pubs, msgs, sigs, impl=args.impl)
    rlc_ok = bool(np.asarray(e2e).all()) and dev.RLC_STATS["fallback"] == 0

    t0 = time.perf_counter()
    _materialize(core_row(*row_in))
    compile_row_s = time.perf_counter() - t0

    def timed(fn):
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            _materialize(fn())
            ts.append((time.perf_counter() - t0) * 1000.0)
        return ts

    rlc_ts = timed(lambda: core_rlc(*rlc_in))
    row_ts = timed(lambda: core_row(*row_in))

    # exactness: a mixed-validity batch must match the per-row verdicts
    # through the public entrypoint (fallback path) — small batch, its
    # compile is cheap relative to the main ones above
    mpubs, mmsgs, msigs, mwant = _gen_batch(64, bad_every=13)
    got = [bool(v) for v in dev.verify_batch_rlc(mpubs, mmsgs, msigs, impl=args.impl)]
    mixed_exact = got == mwant

    rlc_ms = statistics.median(rlc_ts)
    row_ms = statistics.median(row_ts)
    print(json.dumps({
        "impl": args.impl,
        "batch": args.batch,
        "platform": jax.devices()[0].platform,
        "rlc_device_ms": round(rlc_ms, 3),
        "rlc_device_ms_min": round(min(rlc_ts), 3),
        "row_device_ms": round(row_ms, 3),
        "speedup": round(row_ms / rlc_ms, 3) if rlc_ms else None,
        "us_per_sig_rlc": round(rlc_ms * 1000.0 / args.batch, 3),
        "host_scalars_ms": round(host_scalars_ms, 3),
        "compile_rlc_s": round(compile_rlc_s, 2),
        "compile_row_s": round(compile_row_s, 2),
        "rlc_ok": rlc_ok and all_prevalid,
        "mixed_verdicts_exact": mixed_exact,
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

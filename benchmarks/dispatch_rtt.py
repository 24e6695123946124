"""Dispatch round-trip measurement → data-derived cpu_threshold.

VERDICT r2 weak #5: `JAXBatchVerifier.cpu_threshold = 64` was an
unvalidated guess.  This tool measures, on whatever JAX backend is
reachable:

  * host per-sig cost: the production libcrypto path (`verify_fast`),
  * device end-to-end latency per bucket n (host prep + transfer +
    kernel + readback) via the production `verify_batch`,

fits `latency(n) = dispatch + n * device_per_sig` by least squares over
the measured buckets, and derives the breakeven batch size

  n* = smallest n with  dispatch/n + device_per_sig < host_per_sig

(below n* the host loop wins; above it the device does).  If the device
never wins (device_per_sig >= host_per_sig — true on XLA-CPU, where the
"device" is the same core running a worse program), it reports
breakeven = null and the operator guidance is to keep the CPU path.

Usage:  python benchmarks/dispatch_rtt.py [--buckets 8,16,...,1024]
        [--reps 3] [--platform cpu|tpu] [--impl int64|f32]
Prints one JSON document; paste the table into docs/performance.md.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fit_dispatch_model(ns: list[int], lat_s: list[float]) -> tuple[float, float]:
    """Least-squares fit latency = dispatch + n * per_sig.  Returns
    (dispatch_s, per_sig_s), clamped non-negative."""
    k = len(ns)
    sx = sum(ns)
    sy = sum(lat_s)
    sxx = sum(n * n for n in ns)
    sxy = sum(n * t for n, t in zip(ns, lat_s))
    denom = k * sxx - sx * sx
    if denom == 0:
        return max(lat_s[0], 0.0), 0.0
    per_sig = (k * sxy - sx * sy) / denom
    dispatch = (sy - per_sig * sx) / k
    return max(dispatch, 0.0), max(per_sig, 0.0)


def breakeven(dispatch_s: float, dev_per_sig_s: float,
              host_per_sig_s: float, max_n: int = 1 << 20) -> int | None:
    """Smallest n where the device call beats n host verifies."""
    if dev_per_sig_s >= host_per_sig_s:
        return None
    n = 1
    while n <= max_n:
        if dispatch_s + n * dev_per_sig_s < n * host_per_sig_s:
            return n
        n += 1 if n < 128 else n // 64
    return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default="8,16,32,64,128,256")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--impl", default=None, choices=[None, "int64", "f32"])
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", args.platform)
    # importing tendermint_tpu.ops (below) turns the persistent compile
    # cache on where utils/jaxcache resolves it: JAX_COMPILATION_CACHE_DIR
    # if set, else <checkout>/.jax_cache

    from tendermint_tpu.crypto import ed25519 as ed
    from tendermint_tpu.crypto.keys import gen_priv_key
    from tendermint_tpu.ops import ed25519_jax as dev

    buckets = [int(b) for b in args.buckets.split(",")]
    nmax = max(buckets)
    keys = [gen_priv_key() for _ in range(min(64, nmax))]
    pubs, msgs, sigs = [], [], []
    for i in range(nmax):
        k = keys[i % len(keys)]
        m = b"rtt-%d" % i
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(k.sign(m))

    # host per-sig cost (production libcrypto path), warm
    ed.verify_batch_fast(pubs[:64], msgs[:64], sigs[:64])
    host_n = min(512, nmax)
    t0 = time.perf_counter()
    ed.verify_batch_fast(pubs[:host_n], msgs[:host_n], sigs[:host_n])
    host_per_sig = (time.perf_counter() - t0) / host_n

    rows = []
    for n in buckets:
        # warm (compile) then measure end-to-end
        dev.verify_batch(pubs[:n], msgs[:n], sigs[:n], impl=args.impl)
        lat = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            ok = dev.verify_batch(pubs[:n], msgs[:n], sigs[:n], impl=args.impl)
            lat.append(time.perf_counter() - t0)
            assert all(ok)
        rows.append({"n": n, "p50_ms": round(statistics.median(lat) * 1e3, 3)})

    ns = [r["n"] for r in rows]
    lats = [r["p50_ms"] / 1e3 for r in rows]
    dispatch_s, dev_per_sig = fit_dispatch_model(ns, lats)
    be = breakeven(dispatch_s, dev_per_sig, host_per_sig)
    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "impl": args.impl or dev.default_impl(),
        "host_per_sig_us": round(host_per_sig * 1e6, 2),
        "device_dispatch_ms": round(dispatch_s * 1e3, 3),
        "device_per_sig_us": round(dev_per_sig * 1e6, 2),
        "breakeven_n": be,
        "recommended_cpu_threshold": be if be is not None else "keep CPU path",
        "rows": rows,
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

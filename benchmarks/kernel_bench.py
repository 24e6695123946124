"""Device-kernel microbenchmark: time the batched ZIP-215 verify core per
field backend (int64 radix-17 vs f32 radix-5, optionally the MXU
incidence-matmul fe_mul) on whatever JAX backend is reachable.

This is the round-3 measurement tool for VERDICT item 1: the round-1 TPU
run spent ~340 ms device math per 16k batch (~21 us/sig) with every limb op
riding XLA's int64 emulation on the float-centric VPU; the f32 backend is
the same mathematics on the native f32 datapath.

Usage:
    python benchmarks/kernel_bench.py [--impl int64|f32] [--mxu] \
        [--batch 16384] [--reps 5] [--platform cpu|tpu]

Prints ONE JSON line per run:
  {"impl": ..., "batch": N, "platform": ..., "device_ms": p50,
   "device_ms_min": ..., "us_per_sig": ..., "host_prep_ms": ...,
   "compile_s": ..., "verify_ok": true}

`verify_ok` asserts the measured program still returns the right verdicts
(mixed-validity batch) — a benchmark of a wrong kernel is worthless.

Run every impl (subprocesses, so the MXU env flag and platform forcing are
clean per child):
    python benchmarks/kernel_bench.py --all [--batch N]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _force_platform(platform: str) -> None:
    """Pin the JAX platform before first backend contact.  The compile
    cache is placed by utils/jaxcache when tendermint_tpu.ops is
    imported (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)."""
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def _gen_batch(n: int, bad_every: int = 97):
    """n signatures, ~1/bad_every invalid, deterministic.  bad_every=0
    disables corruption entirely (note: any value >= 8 corrupts at
    least row 7 — i % bad_every == 7 first fires at i = 7 — and values
    1..7 corrupt nothing, so pass 0 or >= 8)."""
    import hashlib

    from tendermint_tpu.crypto.keys import gen_priv_key

    keys = [gen_priv_key() for _ in range(min(64, n))]
    pubs, msgs, sigs, want = [], [], [], []
    for i in range(n):
        k = keys[i % len(keys)]
        m = hashlib.sha256(i.to_bytes(4, "little")).digest()
        s = k.sign(m)
        ok = True
        if bad_every and i % bad_every == 7:
            s = s[:-1] + bytes([s[-1] ^ 1])
            ok = False
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(s)
        want.append(ok)
    return pubs, msgs, sigs, want


def run_bench(impl: str, batch: int, reps: int, platform: str) -> dict:
    _force_platform(platform)
    import numpy as np

    import jax

    from tendermint_tpu.ops import ed25519_jax as dev

    pubs, msgs, sigs, want = _gen_batch(batch)

    t0 = time.perf_counter()
    inputs = dev.prepare_batch(pubs, msgs, sigs)
    host_prep_ms = (time.perf_counter() - t0) * 1000.0

    # benches measure the RAW requested path on purpose — no golden gate
    # (verify_ok below reports wrongness instead of hiding it behind the
    # production fallback).  Named wrapper keeps the HLO module name (and
    # so the persistent-compile-cache key) identical to production.
    base_mxu = os.environ.get("TM_TPU_BASE_MXU", "0") == "1"
    _raw = dev._core(impl)

    def verify_core(pub_rows, r_rows, s_rows, k_rows, valid):
        return _raw.verify_core(pub_rows, r_rows, s_rows, k_rows, valid,
                                base_mxu=base_mxu)

    core = jax.jit(verify_core)
    # move inputs to device once — we're timing the kernel, not transfers
    dev_inputs = [jax.device_put(np.asarray(x)) for x in inputs]

    t0 = time.perf_counter()
    # a host copy of the verdicts ends the timed region: compile and
    # the first execution are both inside compile_s
    out = np.asarray(core(*dev_inputs))
    compile_s = time.perf_counter() - t0

    got = [bool(v) for v in out]
    verify_ok = got == want

    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        # a host copy of the [N] verdict row (16 KB) ends the timed
        # region: unambiguous, and it costs nothing at this scale
        np.asarray(core(*dev_inputs))
        times.append((time.perf_counter() - t0) * 1000.0)

    device_ms = statistics.median(times)
    return {
        "impl": impl
        + ("+fe_mxu" if os.environ.get("TM_TPU_FE_MXU") == "1" else "")
        + ("+base_mxu" if base_mxu else ""),
        "batch": batch,
        "platform": jax.devices()[0].platform,
        "device_ms": round(device_ms, 3),
        "device_ms_min": round(min(times), 3),
        "us_per_sig": round(device_ms * 1000.0 / batch, 3),
        "host_prep_ms": round(host_prep_ms, 3),
        "compile_s": round(compile_s, 2),
        "verify_ok": verify_ok,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="int64", choices=["int64", "f32"])
    ap.add_argument("--mxu", action="store_true")
    ap.add_argument("--batch", type=int, default=16384)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--all", action="store_true",
                    help="run int64, f32, f32+mxu as subprocesses")
    args = ap.parse_args()

    if args.all:
        rc = 0
        for impl, mxu in (("int64", False), ("f32", False), ("f32", True)):
            env = dict(os.environ)
            env["TM_TPU_FE_MXU"] = "1" if mxu else "0"
            cmd = [sys.executable, __file__, "--impl", impl,
                   "--batch", str(args.batch), "--reps", str(args.reps),
                   "--platform", args.platform]
            r = subprocess.run(cmd, env=env)
            rc = rc or r.returncode
        return rc

    if args.mxu:
        os.environ["TM_TPU_FE_MXU"] = "1"
    print(json.dumps(run_bench(args.impl, args.batch, args.reps,
                               args.platform)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Round-5 roofline + kernel probes (VERDICT r4 item 1).

Four rounds of kernel work sit at ~21 us/sig with every limb op riding
XLA's int64 emulation, and the one question that decides the north-star
trajectory — is that the VPU floor, or is XLA leaving 10x on the table? —
has only ever been answered by argument.  This tool answers it by
measurement, in three parts:

1. `--census`: an EXACT elementwise-op census of the production per-row
   program (ops/ed25519_jax.verify_core, int64 backend).  Runs the real
   code on XLA-CPU with `lax.fori_loop` shimmed to a Python loop and
   every field/point op wrapped with a lane-op meter, so loop bodies are
   counted per-iteration.  Output: int64 lane-multiplies and total
   elementwise lane-ops per signature.

2. `--chain KIND`: device throughput probes — saturating elementwise
   chains (jit-fused into one kernel) that measure what the hardware
   actually sustains for each op class:
     i64mul / i32mul / f32mul / i64add   raw multiply/add+mask chains
     femul17      the production radix-17 int64 fe_mul
     femul8       an int32 radix-8 (32x8-bit) fe_mul — the "int32
                  redesign" dismissed by radix arithmetic in
                  docs/tpu-verifier.md, now measured
   Each runs at several (rows, lanes) shapes so the [N,15]-layout lane-
   utilization question gets measured too.

3. `--pallas`: the same probes as hand-written Pallas kernels (int32
   mul chain; radix-8 fe_mul), so "a manual kernel could not beat XLA's
   fusion here" (docs/tpu-verifier.md:176-182) is measured, not argued.

The roofline: achieved int64-op rate inside the verifier
(census / measured us-per-sig) vs the sustained rate of the probe
chains.  If the probe rate is ~the achieved rate, the kernel is at the
hardware's elementwise-int floor and the <2 ms north star needs chips
or a different equation; if the probe rate is several x higher, XLA is
leaving it on the table and the avenue it names stays open.

Usage:
    python benchmarks/roofline_probe.py --census
    python benchmarks/roofline_probe.py --chain i64mul --platform tpu
    python benchmarks/roofline_probe.py --pallas --platform tpu
    python benchmarks/roofline_probe.py --all --platform tpu \
        [--out benchmarks/tpu_kernel_r05.jsonl]

Every invocation prints one JSON line per probe (and appends to --out).
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

from kernel_bench import _force_platform  # noqa: E402

OUT_DEFAULT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tpu_kernel_r05.jsonl")


def _emit(obj: dict, out_path: str | None) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    if out_path:
        with open(out_path, "a") as f:
            f.write(line + "\n")


# ---------------------------------------------------------------------------
# 1. Census — exact per-signature elementwise lane-op counts
# ---------------------------------------------------------------------------

def run_census() -> dict:
    """Count lane-ops per signature by executing the REAL per-row program
    eagerly (XLA-CPU) with fori_loop unrolled in Python and the field/
    point layer metered.  Exact for the int64 backend at any batch size
    (the program is elementwise over the batch)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax import lax as real_lax

    from tendermint_tpu.ops import ed25519_jax as dev
    from tendermint_tpu.ops import fe25519 as fe

    NL = fe.NLIMBS  # 15

    # lane-op meter: category -> lane-ops per batch element
    ops = {"mul": 0, "add": 0, "shift": 0, "and": 0, "cmp": 0, "sel": 0}
    calls: dict[str, int] = {}

    def meter(name, **contrib):
        calls[name] = calls.get(name, 0) + 1
        for k, v in contrib.items():
            ops[k] += v

    class _LaxShim:
        """lax with fori_loop run as a Python loop (bodies metered per
        iteration); everything else passes through."""

        def __getattr__(self, n):
            return getattr(real_lax, n)

        @staticmethod
        def fori_loop(lo, hi, body, init):
            v = init
            for i in range(lo, hi):
                v = body(i, v)
            return v

    shim = _LaxShim()

    orig = {}

    def wrap(mod, name, contrib_fn):
        f = getattr(mod, name)
        orig[(mod, name)] = f

        def g(*a, **k):
            meter(name, **contrib_fn(*a, **k))
            return f(*a, **k)

        setattr(mod, name, g)

    try:
        fe.lax, dev.lax = shim, shim
        # Leaf-level lane-op weights (per batch element), derived from
        # the op bodies in ops/fe25519.py; compound fns (fe_mul calls
        # _fold_cols calls fe_carry) are split so nothing double-counts.
        wrap(fe, "fe_mul", lambda a, b: {"mul": NL * NL, "add": NL * NL})
        wrap(fe, "fe_sq", lambda a: {"mul": NL * (NL + 1) // 2,
                                     "add": NL * (NL + 1) // 2 + NL})
        wrap(fe, "_fold_cols", lambda c: {"mul": NL - 1, "add": NL - 1})
        wrap(fe, "fe_carry", lambda c, rounds=4: {
            "shift": NL * rounds, "and": NL * rounds,
            "add": NL * rounds, "mul": rounds})
        wrap(fe, "_fe_carry_exact", lambda c: {
            "add": NL + 2, "shift": NL + 1, "and": NL + 1, "mul": 1})
        wrap(fe, "fe_canonical", lambda a: {
            "add": 2 * NL, "cmp": NL, "shift": NL, "sel": NL})
        wrap(fe, "fe_add", lambda a, b: {"add": NL})
        wrap(fe, "fe_sub", lambda a, b: {"add": 2 * NL})
        wrap(fe, "fe_neg", lambda a: {"add": NL})
        wrap(fe, "pt_select", lambda bit, p1, p0: {"sel": 4 * NL})
        wrap(fe, "fe_eq", lambda a, b: {"cmp": NL})
        wrap(fe, "fe_is_zero", lambda a: {"cmp": NL})

        # one real signature through the real program
        from tendermint_tpu.crypto.keys import priv_key_from_seed

        k = priv_key_from_seed(b"\x07" * 32)
        pub = k.pub_key().bytes_()
        msg = b"roofline-census"
        sig = k.sign(msg)
        inputs = dev.prepare_batch([pub], [msg], [sig])
        core = dev._Core(fe)
        out = core.verify_core(*[jax.numpy.asarray(x) for x in inputs])
        assert bool(out[0]), "census run must verify its signature"
    finally:
        fe.lax, dev.lax = real_lax, real_lax
        for (mod, name), f in orig.items():
            setattr(mod, name, f)

    total = sum(ops.values())
    return {
        "probe": "census",
        "impl": "int64",
        "lane_ops_per_sig": {k: int(v) for k, v in ops.items()},
        "lane_mul_per_sig": int(ops["mul"]),
        "lane_ops_total_per_sig": int(total),
        "calls": {k: int(v) for k, v in sorted(calls.items())},
        "note": ("unpack (_bits_of/_limbs_of/_nibbles_of) and scattered "
                 "jnp.where in decompress are excluded: one-time per "
                 "batch, <2% of volume"),
    }


# ---------------------------------------------------------------------------
# 2. Device chain probes
# ---------------------------------------------------------------------------

NL8, BITS8, MASK8 = 32, 8, 255


def _fe_mul8(a, b):
    """int32 radix-8 fe_mul: 32 limbs x 8 bits.  2^256 = 38 (mod p) so the
    fold multiplies by 38; carries are the same relaxation as radix-17
    but converge slower (factor ~38/256 per round), hence 6 rounds.
    Bound: inputs < 2^10 (the relaxed fixed point ~300 plus headroom),
    columns <= 32*2^20 < 2^25, fold < 39*2^25 < 2^30.3 — fits int32."""
    import jax.numpy as jnp

    nd = a.ndim - 1
    cols = jnp.zeros(a.shape[:-1] + (2 * NL8 - 1,), dtype=jnp.int32)
    for i in range(NL8):
        term = a[..., i: i + 1] * b
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(i, NL8 - 1 - i)])
    lo = cols[..., :NL8]
    hi = cols[..., NL8:]
    lo = lo.at[..., : NL8 - 1].add(38 * hi)
    c = lo
    for _ in range(6):
        h = c >> BITS8
        c = (c & MASK8) + jnp.concatenate(
            [38 * h[..., -1:], h[..., :-1]], axis=-1)
    return c


def _int8_from_int(v: int):
    import numpy as np

    return np.array([(v >> (BITS8 * i)) & MASK8 for i in range(NL8)],
                    dtype=np.int32)


def _int_from_8(a) -> int:
    import numpy as np

    a = np.asarray(a, dtype=object)
    return sum(int(a[..., i]) << (BITS8 * i) for i in range(NL8))


def run_chain(kind: str, rows: int, lanes: int, chain: int, reps: int,
              platform: str) -> dict:
    _force_platform(platform)
    import numpy as np

    import jax

    jax.config.update("jax_enable_x64", True)  # int64 lanes stay int64
    import jax.numpy as jnp

    rng = np.random.default_rng(11)

    if kind == "floor":
        # dispatch-floor probe: negligible compute, device-resident
        # inputs, scalar output — everything else is dispatch+runtime
        x = rng.integers(1, 256, (rows, lanes)).astype(np.int32)
        y = rng.integers(1, 256, (rows, lanes)).astype(np.int32)

        def f(x, y):
            for _ in range(chain):
                x = (x * y) & np.int32(255)
            return jnp.sum(x)

        ops_per_iter = 2
        elems = rows * lanes
    elif kind in ("i64mul", "i64add", "i32mul", "f32mul"):
        if kind.startswith("i64"):
            dt, hi = np.int64, 1 << 17
        elif kind == "i32mul":
            dt, hi = np.int32, 1 << 8
        else:
            dt, hi = np.float32, None
        if hi:
            x = rng.integers(1, hi, (rows, lanes)).astype(dt)
            y = rng.integers(1, hi, (rows, lanes)).astype(dt)
        else:
            x = rng.uniform(0.5, 2.0, (rows, lanes)).astype(dt)
            y = rng.uniform(0.99999, 1.00001, (rows, lanes)).astype(dt)
        mask = dt(hi - 1) if hi else None

        def f(x, y):
            for _ in range(chain):
                if kind == "i64add":
                    x = (x + y) & mask
                elif kind == "f32mul":
                    x = x * y
                else:
                    x = (x * y) & mask
            # host copy must be O(1): returning the full tensor would
            # measure the device-to-host transfer, not the VPU.
            # The sum depends on every element — nothing DCEs.
            return jnp.sum(x)

        ops_per_iter = 2 if mask is not None else 1
        elems = rows * lanes
    elif kind == "femul17":
        from tendermint_tpu.ops import fe25519 as fe

        assert lanes == fe.NLIMBS
        x = rng.integers(0, 1 << 17, (rows, lanes), dtype=np.int64)
        y = rng.integers(0, 1 << 17, (rows, lanes), dtype=np.int64)

        def f(x, y):
            for _ in range(chain):
                x = fe.fe_mul(x, y)
            # O(1)-sized host copy (see raw-chain comment): row 0 for the
            # correctness check + a sum that keeps every row live
            return x[0], jnp.sum(x)

        # per fe_mul per element: census weights (mul 225+14+3, add ...)
        ops_per_iter = None
        elems = rows
    elif kind == "femul8":
        assert lanes == NL8
        x = rng.integers(0, 256, (rows, lanes)).astype(np.int32)
        y = rng.integers(0, 256, (rows, lanes)).astype(np.int32)

        def f(x, y):
            for _ in range(chain):
                x = _fe_mul8(x, y)
            return x[0], jnp.sum(x)

        ops_per_iter = None
        elems = rows
    else:
        raise ValueError(kind)

    jf = jax.jit(f)
    dx, dy = jax.device_put(x), jax.device_put(y)

    def run():
        return jax.tree_util.tree_map(np.asarray, jf(dx, dy))

    t0 = time.perf_counter()
    out = run()
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run()
        ts.append(time.perf_counter() - t0)
    ms = statistics.median(ts) * 1000.0

    res = {
        "probe": "chain",
        "kind": kind,
        "platform": jax.devices()[0].platform,
        "rows": rows,
        "lanes": lanes,
        "chain": chain,
        "ms": round(ms, 3),
        "ms_min": round(min(ts) * 1000.0, 3),
        "compile_s": round(compile_s, 2),
    }
    if kind == "femul8":
        # correctness: limb vectors are a radix-2^8 representation; the
        # chained product must agree with big-int arithmetic mod p
        from tendermint_tpu.crypto.ed25519 import P

        xi = _int_from_8(x[0]) % P
        yi = _int_from_8(y[0]) % P
        want = xi
        for _ in range(chain):
            want = want * yi % P
        res["agree"] = bool(_int_from_8(out[0].astype(object)) % P == want)
        res["ns_per_femul_elem"] = round(ms * 1e6 / (chain * elems), 3)
    elif kind == "femul17":
        from tendermint_tpu.crypto.ed25519 import P
        from tendermint_tpu.ops import fe25519 as fe

        xi = fe.int_from_limbs(x[0].astype(object)) % P
        yi = fe.int_from_limbs(y[0].astype(object)) % P
        want = xi
        for _ in range(chain):
            want = want * yi % P
        res["agree"] = bool(
            fe.int_from_limbs(out[0].astype(object)) % P == want)
        res["ns_per_femul_elem"] = round(ms * 1e6 / (chain * elems), 3)
    else:
        giga = elems * chain * (ops_per_iter or 1) / (ms * 1e-3) / 1e9
        res["g_lane_iters_per_s"] = round(elems * chain / (ms * 1e-3) / 1e9, 3)
        res["g_ops_per_s"] = round(giga, 3)
    return res


# ---------------------------------------------------------------------------
# 3. Pallas probes
# ---------------------------------------------------------------------------

def run_pallas(kind: str, rows: int, chain: int, reps: int,
               platform: str) -> dict:
    """Hand-written Mosaic kernels for the same op mixes, so the 'XLA
    already fuses this optimally' claim is measured.  Layout inside the
    kernel is limb-major [NLIMBS, 128-lane block] — full lane packing,
    the thing the XLA [N, 15] layout may be wasting."""
    _force_platform(platform)
    import numpy as np

    import jax

    # x64 OFF here: these kernels are pure int32, and with x64 on the
    # BlockSpec index-map functions return i64 — Mosaic fails to
    # legalize the mixed (i32, i64) func.return (measured: both pallas
    # probes died on exactly that in the first r5 sweep)
    jax.config.update("jax_enable_x64", False)
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    BLK = 2048  # lanes per grid step (512 in the first sweep: grid-bound)

    if kind == "pl_i32mul":
        def kernel(x_ref, y_ref, o_ref):
            x = x_ref[...]
            y = y_ref[...]
            for _ in range(chain):
                x = (x * y) & 255
            o_ref[...] = x

        shape = (rows, 128)
        rng = np.random.default_rng(3)
        x = rng.integers(1, 256, shape).astype(np.int32)
        y = rng.integers(1, 256, shape).astype(np.int32)

        BLKR = 1024  # rows per grid step: the first r5 sweep's 8-row
        # blocks measured grid overhead, not the VPU (2048-step grid)

        @jax.jit
        def f(x, y):
            out = pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
                grid=(rows // BLKR,),
                in_specs=[pl.BlockSpec((BLKR, 128), lambda i: (i, 0)),
                          pl.BlockSpec((BLKR, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((BLKR, 128), lambda i: (i, 0)),
            )(x, y)
            return jnp.sum(out)  # O(1) host copy, not a transfer test

        elems = rows * 128
        ops_per_iter = 2
    elif kind == "pl_femul8":
        # limb-major [32, N]: limbs on sublanes, batch on lanes; the
        # schoolbook uses per-limb [1, BLK] rows (full 128-lane tiles)
        def mul8_lm(a, b):
            # a, b: [32, BLK] int32
            cols = [jnp.zeros((1, BLK), jnp.int32) for _ in range(2 * NL8 - 1)]
            for i in range(NL8):
                ai = a[i: i + 1]  # [1, BLK]
                for j in range(NL8):
                    cols[i + j] = cols[i + j] + ai * b[j: j + 1]
            lo = cols[:NL8]
            for i in range(NL8 - 1):
                lo[i] = lo[i] + 38 * cols[NL8 + i]
            c = jnp.concatenate(lo, axis=0)  # [32, BLK]
            for _ in range(6):
                h = c >> BITS8
                c = (c & MASK8) + jnp.concatenate(
                    [38 * h[-1:], h[:-1]], axis=0)
            return c

        def kernel(x_ref, y_ref, o_ref):
            x = x_ref[...]
            y = y_ref[...]
            for _ in range(chain):
                x = mul8_lm(x, y)
            o_ref[...] = x

        shape = (NL8, rows)
        rng = np.random.default_rng(3)
        x = rng.integers(0, 256, shape).astype(np.int32)
        y = rng.integers(0, 256, shape).astype(np.int32)

        @jax.jit
        def f(x, y):
            out = pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
                grid=(rows // BLK,),
                in_specs=[pl.BlockSpec((NL8, BLK), lambda i: (0, i)),
                          pl.BlockSpec((NL8, BLK), lambda i: (0, i))],
                out_specs=pl.BlockSpec((NL8, BLK), lambda i: (0, i)),
            )(x, y)
            return out[:, 0], jnp.sum(out)  # O(1) host copy

        elems = rows
        ops_per_iter = None
    else:
        raise ValueError(kind)

    dx, dy = jax.device_put(x), jax.device_put(y)

    def run():
        return jax.tree_util.tree_map(np.asarray, f(dx, dy))

    t0 = time.perf_counter()
    out = run()
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = run()
        ts.append(time.perf_counter() - t0)
    ms = statistics.median(ts) * 1000.0

    res = {
        "probe": "pallas",
        "kind": kind,
        "platform": jax.devices()[0].platform,
        "rows": rows,
        "chain": chain,
        "ms": round(ms, 3),
        "ms_min": round(min(ts) * 1000.0, 3),
        "compile_s": round(compile_s, 2),
    }
    if kind == "pl_i32mul":
        res["g_ops_per_s"] = round(
            elems * chain * ops_per_iter / (ms * 1e-3) / 1e9, 3)
    else:
        from tendermint_tpu.crypto.ed25519 import P

        xi = _int_from_8(x[:, 0].astype(object)) % P
        yi = _int_from_8(y[:, 0].astype(object)) % P
        want = xi
        for _ in range(chain):
            want = want * yi % P
        res["agree"] = bool(_int_from_8(out[0].astype(object)) % P == want)
        res["ns_per_femul_elem"] = round(ms * 1e6 / (chain * elems), 3)
    return res


# ---------------------------------------------------------------------------
# Orchestration
# ---------------------------------------------------------------------------

def _sub(args: list[str], out_path: str | None) -> int:
    cmd = [sys.executable, os.path.abspath(__file__)] + args
    if out_path:
        cmd += ["--out", out_path]
    r = subprocess.run(cmd)
    return r.returncode


# Shapes sized so the on-device work dwarfs the per-call dispatch floor
# (~60-100 ms on the round-5 machine — the first r5 sweep's 64-chain
# probes all measured the same ~1.3-2 G ops/s regardless of dtype, i.e.
# they measured the floor, not the VPU).  At these sizes a probe that
# still lands near the floor would imply a sustained rate far above any
# plausible VPU peak and flag itself as invalid.
ALL_CHAINS = [
    ("floor", 8, 128, 2),
    # raw-rate probes at two shapes: the production-like minor-dim-15
    # layout and a full-lane 128 layout (equal element counts)
    ("i64mul", 65536, 128, 512),
    ("i64mul", 559240, 15, 512),
    ("i32mul", 65536, 128, 512),
    ("f32mul", 65536, 128, 512),
    ("i64add", 65536, 128, 512),
    # field-multiply chains: production radix-17/int64 vs radix-8/int32
    ("femul17", 65536, 15, 256),
    ("femul8", 32768, 32, 128),
]

ALL_PALLAS = [
    ("pl_i32mul", 16384, 64),
    ("pl_femul8", 16384, 8),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--census", action="store_true")
    ap.add_argument("--chain", default=None,
                    choices=["floor", "i64mul", "i64add", "i32mul",
                             "f32mul", "femul17", "femul8"])
    ap.add_argument("--pallas-kind", default=None,
                    choices=["pl_i32mul", "pl_femul8"])
    ap.add_argument("--pallas", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--lanes", type=int, default=128)
    ap.add_argument("--chain-len", type=int, default=64)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-census", action="store_true")
    args = ap.parse_args()

    if args.all:
        rc = 0 if args.skip_census else _sub(["--census"], args.out)
        for kind, rows, lanes, cl in ALL_CHAINS:
            rc = rc or _sub(["--chain", kind, "--rows", str(rows),
                             "--lanes", str(lanes), "--chain-len", str(cl),
                             "--platform", args.platform], args.out)
        for kind, rows, cl in ALL_PALLAS:
            # pallas probes may fail to compile (Mosaic int availability);
            # a failure is itself a recorded verdict, not an abort
            r = _sub(["--pallas-kind", kind, "--rows", str(rows),
                      "--chain-len", str(cl),
                      "--platform", args.platform], args.out)
            if r:
                _emit({"probe": "pallas", "kind": kind,
                       "error": f"subprocess exit {r} (see stderr)"},
                      args.out)
        return 0

    if args.census:
        _emit(run_census(), args.out)
        return 0
    if args.chain:
        _emit(run_chain(args.chain, args.rows, args.lanes, args.chain_len,
                        args.reps, args.platform), args.out)
        return 0
    if args.pallas_kind:
        _emit(run_pallas(args.pallas_kind, args.rows, args.chain_len,
                         args.reps, args.platform), args.out)
        return 0
    if args.pallas:
        for kind, rows, cl in ALL_PALLAS:
            _emit(run_pallas(kind, rows, cl, args.reps, args.platform),
                  args.out)
        return 0
    ap.error("pick a mode: --census / --chain / --pallas / --all")
    return 2


if __name__ == "__main__":
    raise SystemExit(main())

"""Round-5 chunked-dispatch probe + bucket pre-warm (VERDICT r4 item 2).

Measures the 10k-commit and 16k-throughput paths under three dispatch
policies on the real device:

  single   TM_TPU_CHUNK=0      one bucket (12,288 for 10k — the new
                               3*2^k ladder; 16,384 for 16k)
  chunk4k  TM_TPU_CHUNK=4096   pipelined sub-batches (4096+4096+2048)
  chunk2k  TM_TPU_CHUNK=2048   deeper pipeline (5x2048)

For each: end-to-end wall time (host prep + transfer + device + verdict
readback — what a submitter sees) and device-only time (rows
pre-placed, only compiled programs + verdict-bit readback).  Chunk programs are enqueued before
any verdict is read, so chunked device-only also measures whether the
runtime overlaps queued executions.

Side effect (deliberate): compiles the 2048/4096/12288/16384 per-row
buckets into the persistent XLA cache so the driver's bench.py never
pays a cold compile inside its watchdog.

Usage: python benchmarks/chunk_probe.py [--platform tpu] [--reps 5]
       [--out benchmarks/tpu_kernel_r05.jsonl]
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


def _emit(obj: dict, out_path: str | None) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    if out_path:
        with open(out_path, "a") as f:
            f.write(line + "\n")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--n", type=int, default=10000)
    ap.add_argument("--n-throughput", type=int, default=16384)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    _force_platform(args.platform)
    import numpy as np

    import jax

    from tendermint_tpu.ops import ed25519_jax as dev

    pubs, msgs, sigs, want = _gen_batch(max(args.n, args.n_throughput))

    def end_to_end(n: int, chunk: int) -> dict:
        os.environ["TM_TPU_CHUNK"] = str(chunk)
        t0 = time.perf_counter()
        ok = dev.verify_batch(pubs[:n], msgs[:n], sigs[:n])
        warm_s = time.perf_counter() - t0
        assert [bool(v) for v in ok] == want[:n], "verdict mismatch"
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            ok = dev.verify_batch(pubs[:n], msgs[:n], sigs[:n])
            ts.append(time.perf_counter() - t0)
        return {"e2e_p50_ms": round(statistics.median(ts) * 1e3, 3),
                "e2e_min_ms": round(min(ts) * 1e3, 3),
                "warm_s": round(warm_s, 2)}

    def device_only(n: int, chunk: int) -> dict:
        rows = dev.prepare_batch(pubs[:n], msgs[:n], sigs[:n])
        plan = (dev.chunks_of(n, chunk) if chunk and n > chunk
                else [(0, n, dev._bucket(n))])
        placed = []
        for start, end, b in plan:
            sub = tuple(r[start:end] for r in rows)
            padded = dev._pad_rows(end - start, b, *sub)
            placed.append(([jax.device_put(np.asarray(x)) for x in padded],
                           b, end - start))
        for inputs, b, _m in placed:  # warm every bucket
            np.asarray(dev._compiled(b, "int64")(*inputs))
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            enq = [(dev._compiled(b, "int64")(*inputs), m)
                   for inputs, b, m in placed]
            ok = np.concatenate([np.asarray(o)[:m] for o, m in enq])
            ts.append(time.perf_counter() - t0)
        assert [bool(v) for v in ok] == want[:n], "verdict mismatch"
        return {"device_p50_ms": round(statistics.median(ts) * 1e3, 3),
                "device_min_ms": round(min(ts) * 1e3, 3),
                "plan": [[b, m] for _inp, b, m in placed]}

    for label, n, chunk in (
        ("single", args.n, 0),
        ("chunk4k", args.n, 4096),
        ("chunk2k", args.n, 2048),
        ("single", args.n_throughput, 0),
        ("chunk4k", args.n_throughput, 4096),
    ):
        res = {"probe": "chunk", "policy": label, "n": n, "chunk": chunk,
               "platform": jax.devices()[0].platform}
        try:
            res.update(end_to_end(n, chunk))
            res.update(device_only(n, chunk))
        except Exception as e:  # noqa: BLE001
            res["error"] = str(e)[-300:]
        _emit(res, args.out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

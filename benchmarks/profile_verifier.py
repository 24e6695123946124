#!/usr/bin/env python
"""Split-timing profile of the batch verifier: host prep vs device math
vs host->device transfer.  Run from the repo root (on the attached TPU,
or with JAX_PLATFORMS=cpu)."""

import os
import secrets
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = int(os.environ.get("PROFILE_N", "16384"))


def main() -> None:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    ks = [Ed25519PrivateKey.from_private_bytes(secrets.token_bytes(32)) for _ in range(N)]
    pubs = [k.public_key().public_bytes_raw() for k in ks]
    msgs = [b"block-commit-sig-%d" % i for i in range(N)]
    sigs = [k.sign(m) for k, m in zip(ks, msgs)]

    import jax
    import numpy as np

    from tendermint_tpu.ops import ed25519_jax as dev

    t0 = time.perf_counter()
    rows = dev.prepare_batch(pubs, msgs, sigs)
    print("host prepare_batch: %.1f ms" % ((time.perf_counter() - t0) * 1e3))

    f = dev._compiled(N)
    args = [jax.device_put(a) for a in rows]
    r = f(*args)
    assert np.asarray(r).all()  # compile + correctness

    for label, call_args in (("device-only (args resident)", args),
                             ("device + H2D", rows)):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            f(*call_args).block_until_ready()
            ts.append(time.perf_counter() - t0)
        print("%s: %.1f ms" % (label, statistics.median(ts) * 1e3))


if __name__ == "__main__":
    main()

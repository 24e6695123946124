"""Device-mesh sharding for the crypto data plane.

The reference's scale dimension is validator-set size N: every commit
verification is O(N) sequential CPU there (SURVEY §5.7).  Here the batch
axis of the signature-verification tensors is sharded over a
`jax.sharding.Mesh` — data parallelism over ICI — so a 10k-validator commit
splits across chips with zero collectives (the program is elementwise over
the batch; only the final per-signature bits travel back).

This module is deliberately mesh-shape agnostic: a 1-D ("batch",) mesh is
the natural layout; multi-host DCN meshes work identically because no
cross-batch communication exists.
"""

from __future__ import annotations

import math

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tendermint_tpu.ops import ed25519_jax as _dev
from tendermint_tpu.utils import devmon as _devmon


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D mesh over the batch axis."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("batch",))


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def sharded_bucket(n: int, n_dev: int) -> int:
    """Padded batch size for an n-row flush sharded over n_dev devices:
    the single-chip bucket-ladder rung, rounded up to a device multiple
    so every shard is equal-sized — and to a multiple of 8 rows, which
    the packed program folds [N] -> [N/8, 8] (fe25519_packed.batch_in).
    Every ladder rung is one already on 1, 2, 4 or 8 devices; at the
    rungs a mesh shards by default a shard is whole groups of 8 too, so
    its rows stay on its chip."""
    b = max(_dev._bucket(n), pad_to_multiple(n, n_dev))
    return pad_to_multiple(b, math.lcm(8, n_dev))


def device_ids(mesh: Mesh) -> tuple:
    """Stable per-device attribution key for devmon's per-device series."""
    return tuple(int(d.id) for d in mesh.devices.flat)


def prepartition(mesh: Mesh, rows) -> list:
    """jax.device_put each packed row tensor against the mesh's
    NamedSharding BEFORE dispatch, so the arrays arrive already laid out
    exactly as the sharded jit's in_shardings declare and XLA never
    inserts a reshard (the pjit exemplar contract: producer layout ==
    consumer in_axis_resources)."""
    batch = NamedSharding(mesh, P("batch"))
    batch2 = NamedSharding(mesh, P("batch", None))
    return [jax.device_put(a, batch2 if getattr(a, "ndim", 1) == 2 else batch)
            for a in rows]


import functools


@functools.lru_cache(maxsize=8)
def sharded_verify_fn(mesh: Mesh):
    """jit of the batched ZIP-215 verify core with all inputs/outputs
    sharded along the batch axis of `mesh`.  Cached per mesh; XLA caches
    per input shape under it."""
    batch = NamedSharding(mesh, P("batch"))
    batch2 = NamedSharding(mesh, P("batch", None))
    # (pub_rows, r_rows, s_rows, k_rows, valid) — packed [N,32] u8 + bool[N].
    # The field impl inside _verify_core resolves per trace via
    # default_impl() — whatever TM_TPU_FIELD_IMPL=auto resolved to on
    # this backend lands here too, and the devmon label below records
    # which one this mesh program traced.
    in_sh = (batch2, batch2, batch2, batch2, batch)
    # donated row buffers, same policy as the single-chip entry points
    # (ops.ed25519_jax.donate_rows — off on XLA-CPU so cache keys and
    # tier-1 behavior are unchanged there)
    kw = {"donate_argnums": _dev._DONATE_ARGNUMS} if _dev.donate_rows() else {}
    # one jit compiles one program per input shape: rung=None tracks the
    # first call per leading-axis size (utils/devmon)
    return _devmon.track_jit(
        jax.jit(_dev._verify_core, in_shardings=in_sh, out_shardings=batch,
                **kw),
        kind="sharded_verify", impl=_dev.default_impl(),
        devices=int(mesh.devices.size))


def _verify_rows_sharded(inputs, n: int, mesh: Mesh) -> np.ndarray:
    """Sharded per-row program on already-prepared packed rows
    (pub_rows, r_rows, s_rows, k_rows, valid); pads to the bucket/mesh
    multiple here."""
    n_dev = mesh.devices.size
    b = sharded_bucket(n, n_dev)
    if b != n:
        pad = b - n
        inputs = tuple(
            np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) for x in inputs
        )
    if _devmon.STATS.enabled:
        _devmon.STATS.record_flush(
            "verify_sharded", n, b, nbytes=sum(a.nbytes for a in inputs),
            devices=device_ids(mesh))
    ok = sharded_verify_fn(mesh)(*prepartition(mesh, inputs))
    return np.asarray(ok)[:n]


def verify_batch_sharded(pubs, msgs, sigs, mesh: Mesh | None = None) -> np.ndarray:
    """Like ops.ed25519_jax.verify_batch but sharded across all devices."""
    n = len(pubs)
    if n == 0:
        return np.zeros(0, dtype=bool)
    if mesh is None:
        mesh = make_mesh()
    return _verify_rows_sharded(_dev.prepare_batch(pubs, msgs, sigs), n, mesh)

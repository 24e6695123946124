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
    so every shard is equal-sized."""
    b = max(_dev._bucket(n), pad_to_multiple(n, n_dev))
    return pad_to_multiple(b, n_dev)


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


@functools.lru_cache(maxsize=8)
def sharded_rlc_fn(mesh: Mesh, impl: str, reduce_lanes: int = 2048):
    """shard_map of the RLC core: each device runs the IDENTICAL
    single-chip program on its local batch shard (no cross-chip
    collectives — the only fan-in is each device's P-lane accumulator,
    ~61 KB, folded on host by ops.ed25519_jax.finalize_rlc).  out_specs
    concatenate the per-device accumulator lanes along axis 0.
    reduce_lanes is baked into the trace, hence part of the cache key."""
    from jax import shard_map

    _raw = _dev._core(impl)

    # named wrapper, not functools.partial: the HLO module name derives
    # from __name__ and the persistent compile cache keys on it
    def verify_core_rlc(pub_rows, r_rows, zk_rows, z_rows, valid):
        return _raw.verify_core_rlc(pub_rows, r_rows, zk_rows, z_rows,
                                    valid, shard_varying=True,
                                    reduce_lanes=reduce_lanes)

    core = verify_core_rlc
    b2 = P("batch", None)
    # donated row buffers (see sharded_verify_fn)
    kw = {"donate_argnums": _dev._DONATE_ARGNUMS} if _dev.donate_rows() else {}
    return _devmon.track_jit(
        jax.jit(
            shard_map(
                core,
                mesh=mesh,
                in_specs=(b2, b2, b2, b2, P("batch")),
                out_specs=((b2, b2, b2, b2), P("batch")),
            ),
            **kw,
        ),
        kind="sharded_rlc", impl=impl, devices=int(mesh.devices.size),
        reduce_lanes=reduce_lanes)


def verify_batch_rlc_sharded(pubs, msgs, sigs, mesh: Mesh | None = None,
                             impl: str | None = None) -> np.ndarray:
    """RLC batch verification sharded over the mesh's batch axis, exact
    per-row sharded fallback on combined-check failure (same contract
    as ops.ed25519_jax.verify_batch_rlc)."""
    n = len(pubs)
    if n == 0:
        return np.zeros(0, dtype=bool)
    if mesh is None:
        mesh = make_mesh()
    impl = impl or _dev.default_impl()
    # opt-in kernel gate (ADVICE r5): a direct sharded call must run the
    # same golden-batch self-check as the single-chip entry points — a
    # wrong-verdict TM_TPU_FE_MXU program is disabled (and the sharded
    # jit caches cleared) before any mesh trace is built
    _dev._resolve_optin(impl)
    n_dev = mesh.devices.size
    pub_rows, r_rows, s_rows, k_rows, valid = _dev.prepare_batch(pubs, msgs, sigs)
    z_rows, zk_rows, c_row = _dev.prepare_rlc_scalars(s_rows, k_rows, valid)
    b = sharded_bucket(n, n_dev)
    pub_p, r_p, zk_p, z_p, valid_p = _dev._pad_rows(
        n, b, pub_rows, r_rows, zk_rows, z_rows, valid
    )
    if _devmon.STATS.enabled:
        _devmon.STATS.record_flush(
            "rlc_sharded", n, b,
            nbytes=sum(a.nbytes for a in (pub_p, r_p, zk_p, z_p, valid_p)),
            devices=device_ids(mesh))
    acc, prevalid = sharded_rlc_fn(mesh, impl, _dev.rlc_reduce_lanes())(
        pub_p, r_p, zk_p, z_p, valid_p
    )
    if _dev.finalize_rlc(acc, c_row, impl):
        _dev.RLC_STATS["pass"] += 1
        return np.asarray(prevalid)[:n]
    _dev.RLC_STATS["fallback"] += 1
    # exact per-row sharded fallback on the ALREADY-prepared rows — no
    # second host prep (parsing + SHA-512) on the adversarial path,
    # matching single-chip verify_batch_rlc (ADVICE r4 #2)
    return _verify_rows_sharded(
        (pub_rows, r_rows, s_rows, k_rows, valid), n, mesh
    )


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
    # fe_mxu golden gate before any sharded trace (ADVICE r5): the
    # mismatch branch flips the field-module flag and clears this
    # module's jit caches, so the program built below is the safe one
    _dev._resolve_optin(_dev.default_impl())
    return _verify_rows_sharded(_dev.prepare_batch(pubs, msgs, sigs), n, mesh)

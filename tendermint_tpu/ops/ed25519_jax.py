"""Batched ZIP-215 Ed25519 verification as one XLA device program.

This is the framework's north star (SURVEY §2.9, BASELINE.md): the reference
verifies every consensus signature sequentially on CPU; here the entire batch
— all commit signatures for a height, a whole fast-sync window, a light-client
header range — becomes a single jitted program of elementwise limb arithmetic
over the batch axis, shaped for the TPU VPU and shardable over a device mesh
(tendermint_tpu.parallel).

Pipeline per batch:
  host:   parse sig/pubkey bytes, check s < L (ZIP-215 rule 1), hash
          k = SHA-512(R||A||M) mod L (variable-length messages stay on host);
          ship PACKED 32-byte rows (128 B/signature).
  device: unpack bytes → bits/nibbles → limbs (elementwise, free next to the
          curve math), then permissive point decompression for A and R
          (ZIP-215 rule 2 — y >= p accepted, x=0/sign=1 accepted, small order
          accepted), W = [s]B + [k](-A), Q = W - R, and the cofactored
          check [8]Q == identity (ZIP-215 rule 3).

Both scalar multiplications read their scalar as 64 SIGNED radix-16 digits
in -8..7 (_signed_digits: exact as an integer, a 6-step prefix scan over the
nibbles) and add, per window, one table entry PRECOMPUTED into the form its
addition wants, |digit| picked by a 3-level select tree and the sign applied
inside the addition for free (a negated entry swaps its first two
coordinates and the addition's F and G):
  * [s]B: fixed-base tables of j·16^i·B, j = 1..8, as affine Niels triples
    (y+x, y-x, 2d·x·y) — host constants; 64 mixed additions (pt_madd, 7
    multiplications), zero doublings.
  * [k](-A): a per-signature table [1..8](-A) in cached form (Y+X, Y-X, Z,
    2d·T) — a chain of 7 additions of the cached -A, 8 conversions — then
    63 windows of 4 doublings (the dedicated doubling formula) + 1 cached
    addition (pt_add_cached, 8 multiplications): 37 field operations a
    window.
3,433 field multiplications and squarings a signature in all
(tests/kernel_cases.py counts them: decompressions 2 x 275, base 448,
table 64, loop 2,331, finish 40); 3,677 by the same count with unsigned
digits and the general addition (before PR 29).  The unified a = -1
formulas are complete, so small-order keys, the identity and non-canonical
encodings take the same path as honest rows.

Note: -[k]A is computed as [k](-A), never as [L-k]A — the latter is wrong for
points with a torsion component (L·A ≠ O), exactly the inputs ZIP-215 admits.
The signed recoding keeps that: digits sum to k itself, nothing is reduced.

Field backends (TM_TPU_FIELD_IMPL, or the `impl=` argument):
  * "int64"  — 15 limbs × 17 bits in int64 lanes (fe25519.py).  The
    XLA-CPU default (every tier-1 program) and the fallback of the
    start-up golden check.
  * "packed" — 10 limbs at the mixed radix 25.5 stored as uint32 with
    the limb axis LEADING, uint64 only in the 19 product columns
    (fe25519_packed.py).  ~2.2x fewer limb products, and no step of a
    field operation crosses a tiled axis; what an accelerator runs.
TM_TPU_FIELD_IMPL also accepts "auto" (the default, and what any other
value reads as): XLA-CPU resolves to "int64" with no golden run (tier-1
warm cache keys stay bit-identical); accelerator backends run the golden
differential check once at startup and take packed where it validates,
else int64 (see _resolve_auto_impl).
The curve/scalar pipeline below is field-agnostic — it never touches a
limb axis: a backend brings fe_const, fe_select, fe_parity, limbs_of_bits
and batch_in / batch_out for its own layout; both backends share it and
both are differentially tested against the pure ZIP-215 reference.

Static batch sizes: inputs are padded to a bucket ladder — the ACTIVE
shape plan (ops/shape_plan.py; default: the formula ladder of powers of
two up to 64, then 3*2^(k-1) interleaved: 96, 128, 192, ...) so XLA
compiles one program per bucket.  Programs compile lazily on first call
OR ahead of time: `tendermint-tpu warm` / the shape plan's background
warm pre-builds (and serializes) every plan rung's executable, so a warm
node never pays a first-call compile (first call per bucket pays compile
otherwise; consensus reuses steady-state buckets) with measured
worst-case padding 1.49x (n=129→192; <=1.34x for n>=321).  A flush is
one program: a second program in a flush costs its fixed latency
(≈ 27.7 ms on a v5e, PERF.md section 5) to hide at most a few ms of host
prep.
"""

from __future__ import annotations

import functools
import hashlib
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ref
from tendermint_tpu.utils import devmon as _devmon

L = _ref.L
SCALAR_BITS = 253  # s, k < L < 2^253

NWINDOWS = 64  # 253-bit scalars as 64 little-endian radix-16 digits

IMPLS = ("int64", "packed")

# TM_TPU_FIELD_IMPL=auto resolution, memoized per process (the
# TM_TPU_DONATE=auto idiom): None = not yet resolved.  Resolved lazily at
# the first dispatch, never at import (tmlint import-time-env), and only
# on non-cpu backends does resolution run golden checks / compiles —
# XLA-CPU short-circuits to "int64" so tier-1 runs trace the exact same
# programs (bit-identical warm cache keys) as before the auto default.
_AUTO_IMPL: str | None = None


def default_impl() -> str:
    impl = os.environ.get("TM_TPU_FIELD_IMPL", "auto")
    if impl in IMPLS:
        return impl
    global _AUTO_IMPL
    if _AUTO_IMPL is None:
        _AUTO_IMPL = _resolve_auto_impl()
    return _AUTO_IMPL


def _resolve_auto_impl() -> str:
    """The "auto" field impl for this process's backend.  cpu: int64,
    immediately (no golden run, no new compiles — the tier-1 contract).
    An accelerator: the packed layout if it reproduces the golden
    verdicts on THIS device, else the historical int64 layout as the
    unconditional fallback.
    Which of packed/int64 SHOULD lead is a question of chip timings
    this function does not try to answer."""
    if jax.default_backend() == "cpu":
        return "int64"
    if _optin_safe("impl", "packed"):
        return "packed"
    return "int64"


def _field(impl: str):
    if impl == "packed":
        from . import fe25519_packed as m
    elif impl == "int64":
        from . import fe25519 as m
    else:
        # a saved plan or `warm --impls` can name anything: never run
        # one backend under another's label
        raise ValueError(f"unknown field impl {impl!r} (known: {IMPLS})")
    return m


@functools.cache
def _base_point_table() -> list[list[tuple[int, int, int]]]:
    """[j * 16^i]B for i in 0..63, j in 1..8 as big-int AFFINE Niels
    triples (y+x, y-x, 2d·x·y) mod p — host-side, shared by every field
    backend's constant encoding.  Eight entries a window: a signed digit
    in -8..7 picks |d| and negates for free (pt_madd)."""
    p = _ref.P
    rows = []
    g = _ref.BASE
    for _i in range(NWINDOWS):
        row, pt = [], g
        for _j in range(8):
            zi = pow(pt[2], p - 2, p)
            x, y = pt[0] * zi % p, pt[1] * zi % p
            row.append(((y + x) % p, (y - x) % p, 2 * _ref.D * x * y % p))
            pt = _ref.pt_add(pt, g)
        rows.append(row)
        g = _ref.scalar_mult(16, g)
    return rows


# ---------------------------------------------------------------------------
# Device program (field-agnostic; fe = the selected limb backend)
# ---------------------------------------------------------------------------

class _Core:
    """The verify pipeline specialized to one field backend."""

    def __init__(self, fe):
        self.fe = fe

    # -- unpacking -----------------------------------------------------------

    @staticmethod
    def _bits_of(rows: jnp.ndarray) -> jnp.ndarray:
        """[..., 32] uint8 → [..., 256] bits (LE bit order), on device."""
        b = (rows[..., :, None].astype(jnp.int32) >> jnp.arange(8, dtype=jnp.int32)) & 1
        return b.reshape(rows.shape[:-1] + (256,))

    @staticmethod
    def _nibbles_of(rows: jnp.ndarray) -> jnp.ndarray:
        """[..., B] uint8 → [..., 2B] little-endian radix-16 digits."""
        lo = (rows & 15).astype(jnp.int32)
        hi = (rows >> 4).astype(jnp.int32)
        return jnp.stack([lo, hi], axis=-1).reshape(rows.shape[:-1] + (2 * rows.shape[-1],))

    # -- curve pipeline ------------------------------------------------------

    def decompress(self, y: jnp.ndarray, sign: jnp.ndarray):
        """Permissive (ZIP-215/dalek) decompression.

        y: an element, the 255-bit y encoding (possibly >= p — arithmetic
        tolerates unreduced input); sign: [...] in {0,1}, the batch's
        shape.  Returns (point, on_curve).
        """
        fe = self.fe
        one = fe.fe_const(fe.ONE, sign.shape)
        yy = fe.fe_sq(y)
        u = fe.fe_sub(yy, one)
        v = fe.fe_carry(fe.fe_add(fe.fe_mul(yy, fe.fe_const(fe.D_CONST, sign.shape)), one))
        v2 = fe.fe_sq(v)
        v3 = fe.fe_mul(v2, v)
        v7 = fe.fe_mul(fe.fe_sq(v3), v)
        t = fe.fe_pow_p58(fe.fe_mul(u, v7))
        x = fe.fe_mul(fe.fe_mul(u, v3), t)  # candidate sqrt(u/v)
        vx2 = fe.fe_mul(v, fe.fe_sq(x))
        is_pos = fe.fe_eq(vx2, u)
        is_neg = fe.fe_eq(vx2, fe.fe_carry(fe.fe_neg(fe.fe_canonical(u))))
        ok = is_pos | is_neg
        x = fe.fe_select(is_neg, fe.fe_mul(x, fe.fe_const(fe.SQRT_M1_CONST, sign.shape)), x)
        # sign-bit adjustment on the canonical representative; x=0/sign=1 is
        # accepted and stays 0 mod p — dalek semantics.
        cx = fe.fe_canonical(x)
        flip = fe.fe_parity(cx) != sign
        x = fe.fe_select(flip, fe.fe_carry(fe.fe_neg(cx)), cx)
        yr = fe.fe_canonical(y)
        return fe.Pt(x, yr, one, fe.fe_mul(x, yr)), ok

    @staticmethod
    def _signed_digits(nibbles: jnp.ndarray) -> jnp.ndarray:
        """[..., 64] radix-16 digits in 0..15 → [..., 64] digits in -8..7
        with the same value, sum(d_i * 16^i), EXACTLY (as an integer —
        nothing is reduced, so [k](-A) stays [k](-A)): a digit >= 8
        becomes digit - 16 and carries one up.  The carry chain is a
        6-step prefix scan (generate: d >= 8, propagate: d == 7), not 64
        dependent steps.  Scalars below 2^253 (s and k are) leave a top
        digit of 0..2 and no carry out."""
        def up(x, k):  # x[..., i - k] at i, False below k
            return jnp.pad(x[..., :-k], [(0, 0)] * (x.ndim - 1) + [(k, 0)])

        gen, prop = nibbles >= 8, nibbles == 7
        k = 1
        while k < NWINDOWS:
            gen, prop = gen | (prop & up(gen, k)), prop & up(prop, k)
            k *= 2
        # gen[i] is now the carry OUT of digit i
        return (nibbles + up(gen, 1).astype(jnp.int32)
                - 16 * gen.astype(jnp.int32))

    def _select_signed(self, digit: jnp.ndarray, tbl: list, identity):
        """(|digit|·P, digit < 0) from tbl = [1P, ..., 8P], each entry a
        tuple of coordinates in a precomputed form whose negation is the
        addition's business (pt_madd / pt_add_cached take the sign): a
        3-level binary select tree over |digit| - 1 (7 selects a
        coordinate — elementwise, no gathers), then `identity` (limb
        vectors), the same form's neutral element, where the digit is 0."""
        fe = self.fe
        mag = jnp.abs(digit)
        idx = (mag - 1) & 7
        cur = list(tbl)
        for b in range(3):
            bit = ((idx >> b) & 1) == 1
            cur = [tuple(fe.fe_select(bit, hi, lo) for lo, hi in zip(*cur[i:i + 2]))
                   for i in range(0, len(cur), 2)]
        zero = mag == 0
        sel = tuple(fe.fe_select(zero, fe.fe_const(o, digit.shape), c)
                    for o, c in zip(identity, cur[0]))
        return sel, digit < 0

    def _scalarmul_var(self, digits: jnp.ndarray, neg_a):
        """[k](-A) by signed 4-bit windows (digits: _signed_digits of a
        scalar below 2^253): the table [1..8](-A) in cached form (a
        chain of 7 additions of the cached -A, 8 conversions: 64
        multiplications), then 63 iterations of 4 doublings + 1 cached
        addition (37).  The top digit is 0..2, so the loop starts from
        O, -A or 2(-A) as they stand.

        The table is a CHAIN on purpose.  Built as 4 doublings + 3
        additions (the same 64 multiplications, a shallower tree) the
        whole program came out of the TPU compiler's memory-space
        assignment with the product columns of EVERY field operation, in
        every loop, copied out to HBM and sliced back (`slice-start` in
        the compiled text: 448 of them, none with the chain), and a
        squaring took 55 us where it takes 39.5 at rung 10,240 (PERF.md
        section 6, PR 29).  tests/test_fe25519_packed.py compiles the
        program for a described v5e and counts them (slow)."""
        fe = self.fe
        c1 = fe.pt_to_cached(neg_a)
        ext = [neg_a]
        for _ in range(7):
            ext.append(fe.pt_add_cached(ext[-1], c1))
        tbl = [c1] + [fe.pt_to_cached(p) for p in ext[1:]]
        identity = (fe.ONE, fe.ONE, fe.ONE, fe.ZERO)

        def body(i, acc):
            d = jnp.take(digits, NWINDOWS - 1 - i, axis=-1)
            acc = fe.pt_dbl_n(acc, 4)
            return fe.pt_add_cached(acc, *self._select_signed(d, tbl, identity))

        top = jnp.take(digits, NWINDOWS - 1, axis=-1)
        acc0 = fe.pt_select(top == 2, ext[1], fe.pt_select(
            top == 1, neg_a, fe.pt_identity(digits.shape[:-1])))
        return lax.fori_loop(1, NWINDOWS, body, acc0)

    @functools.cached_property
    def _fixed_base_tables(self) -> tuple[np.ndarray, ...]:
        """The shared big-int table encoded as three [64, 8, NLIMBS]
        tensors of limb VECTORS (y+x, y-x, 2d·x·y — canonical limbs) in
        this backend's limb dtype; fe_const places an entry over the
        batch.  numpy, NOT jnp: device constants created inside one
        jit trace must not be cached across traces; callers convert
        per-trace (XLA folds them into program constants)."""
        fe = self.fe
        dtype = np.asarray(fe.ONE).dtype
        coords = [np.zeros((NWINDOWS, 8, fe.NLIMBS), dtype=dtype) for _ in range(3)]
        for i, row in enumerate(_base_point_table()):
            for j, niels in enumerate(row):
                for c in range(3):
                    coords[c][i, j] = fe.limbs_from_int(niels[c])
        return tuple(coords)

    def _scalarmul_base(self, digits: jnp.ndarray):
        """[s]B from the fixed-base Niels tables (digits: _signed_digits
        of s): 64 mixed additions, 7 multiplications each, no
        doublings."""
        fe = self.fe
        tables = [jnp.asarray(c) for c in self._fixed_base_tables]
        identity = (fe.ONE, fe.ONE, fe.ZERO)

        def body(i, acc):
            rows = [jnp.take(c, i, axis=0) for c in tables]
            d = jnp.take(digits, i, axis=-1)
            tbl = [tuple(fe.fe_const(r[j], d.shape) for r in rows)
                   for j in range(8)]
            return fe.pt_madd(acc, *self._select_signed(d, tbl, identity))

        return lax.fori_loop(0, NWINDOWS, body,
                             fe.pt_identity(digits.shape[:-1]))

    def verify_core(self, pub_rows, r_rows, s_rows, k_rows, valid):
        """Inputs are PACKED byte rows ([N,32] uint8 each) — unpacking to
        bits/limbs happens on device, so the host→device transfer is 128
        bytes/signature instead of ~2.3KB of pre-expanded tensors."""
        fe = self.fe
        # one named scope per phase: metadata only — the scope
        # lands in each HLO op's op_name, which a profiler trace keeps
        # per device op, and JAX's persistent-cache key strips it, so
        # the lowered computation and every cached program are unchanged
        with jax.named_scope("ed25519.unpack"):
            pub_rows, r_rows, s_rows, k_rows, valid = (
                fe.batch_in(x) for x in (pub_rows, r_rows, s_rows, k_rows, valid))
            pub_bits = self._bits_of(pub_rows)
            r_bits = self._bits_of(r_rows)
            y_a, sign_a = fe.limbs_of_bits(pub_bits[..., :255]), pub_bits[..., 255]
            y_r, sign_r = fe.limbs_of_bits(r_bits[..., :255]), r_bits[..., 255]
            s_digits = self._signed_digits(self._nibbles_of(s_rows))
            k_digits = self._signed_digits(self._nibbles_of(k_rows))
        with jax.named_scope("ed25519.decompress_a"):
            a_pt, ok_a = self.decompress(y_a, sign_a)
        with jax.named_scope("ed25519.decompress_r"):
            r_pt, ok_r = self.decompress(y_r, sign_r)
        with jax.named_scope("ed25519.scalarmul_base"):
            sb = self._scalarmul_base(s_digits)
        with jax.named_scope("ed25519.scalarmul_var"):
            ka = self._scalarmul_var(k_digits, fe.pt_neg(a_pt))
        with jax.named_scope("ed25519.finish"):
            w = fe.pt_add(sb, ka)
            q = fe.pt_add(w, fe.pt_neg(r_pt))
            q8 = fe.pt_dbl_n(q, 3)
            return fe.batch_out(valid & ok_a & ok_r & fe.pt_is_identity(q8))


@functools.cache
def _core(impl: str) -> _Core:
    return _Core(_field(impl))


def _verify_core(pub_rows, r_rows, s_rows, k_rows, valid):
    """Default-impl core — the traceable entrypoint parallel/sharding jits."""
    return _core(default_impl()).verify_core(pub_rows, r_rows, s_rows, k_rows, valid)


# Donated input buffers (ISSUE 7): donate_argnums on the row arrays lets
# XLA reuse the freshly-transferred input buffers as scratch/output
# instead of defensively copying them on device — dropping the
# steady-state 129 B/row on-device copy devmon measured.  CAVEAT (also
# docs/tpu-verifier.md): a DEVICE array passed to a donating program is
# deleted by the call — callers that re-dispatch pre-placed inputs must
# re-place them (bench's device-only stage does); the production paths
# all ship fresh numpy rows per flush, which donation cannot invalidate.
# Resolved lazily, never at import (tmlint import-time-env): "auto"
# donates only where the backend implements it (not XLA-CPU, which would
# warn per dispatch AND change the persistent-cache key of every tier-1
# program).
_DONATE: bool | None = None
_DONATE_ARGNUMS = (0, 1, 2, 3)  # the packed row arrays; `valid` stays


def donate_rows() -> bool:
    global _DONATE
    if _DONATE is None:
        mode = os.environ.get("TM_TPU_DONATE", "auto")
        if mode == "1":
            donate = True
        elif mode == "0":
            donate = False
        else:
            donate = jax.default_backend() != "cpu"
        if donate:
            import warnings

            # shapes here rarely alias (bool verdicts vs u8 rows), and
            # jax warns per compile when a donated buffer goes unused;
            # the donation is still worth it where XLA can take it
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        _DONATE = donate
    return _DONATE


def reload_env() -> None:
    """Drop lazily-resolved env state (TM_TPU_DONATE, the
    TM_TPU_FIELD_IMPL=auto resolution) so the next call re-reads the
    environment — same contract as crypto.batch.reload_env.  Does NOT
    clear _OPTIN_STATE: golden verdicts are per-process facts about the
    backend, not configuration (tests reset them via monkeypatch)."""
    global _DONATE, _AUTO_IMPL
    _DONATE = None
    _AUTO_IMPL = None


def _jit_for(kind: str, impl: str, *, donate: bool | None = None):
    """The raw jax.jit for one (kind, impl) — shared by the lazy
    _compiled cache below and the AOT shape-plan compiler
    (ops/shape_plan.py), so ahead-of-time executables and first-call
    jits have IDENTICAL call conventions, donation included.

    A named wrapper, NOT functools.partial: jit derives the HLO module
    name from __name__, and the persistent compile cache keys on it —
    a partial would rename every program and cold-recompile the world."""
    if kind != "verify":
        raise ValueError(f"unknown jit kind {kind!r}")
    core = _core(impl)
    if donate is None:
        donate = donate_rows()

    def verify_core(pub_rows, r_rows, s_rows, k_rows, valid):
        return core.verify_core(pub_rows, r_rows, s_rows, k_rows, valid)

    kw = {"donate_argnums": _DONATE_ARGNUMS} if donate else {}
    return jax.jit(verify_core, **kw)


def _compiled(n: int, impl: str | None = None):
    """The tracked program for one (rung, impl).  The impl is resolved
    HERE, before the cache: functools.cache keys on the call's form, so
    `_compiled(8)` and `_compiled(8, "packed")` would otherwise be two
    jits — one more trace, lower and compile (or ~1 min cache load) of
    the same program."""
    return _compiled_entry(n, impl or default_impl())


@functools.cache
def _compiled_entry(n: int, impl_r: str):
    donate = donate_rows()

    # AOT first (ops/shape_plan): an executable warmed ahead of time —
    # `tendermint-tpu warm`, service/node start, or the bench warm
    # stages — is handed out directly; its compile event (source aot/
    # deserialized) was recorded by the warm path, so the proxy is
    # prerecorded and the steady state records nothing.
    from . import shape_plan as _plan

    entry = _plan.aot_lookup("verify", n, impl_r, donate=donate)
    if entry is not None:
        return _devmon.track_jit(entry.executable, kind="verify",
                                 impl=impl_r, rung=n, prerecorded=True)

    # compile tracking (utils/devmon): the first call per cache entry is
    # the one that pays trace+compile; re-tracing the same key after a
    # cache_clear is the unexpected-recompile the tracker warns about
    jitted = _jit_for("verify", impl_r, donate=donate)
    # cost model (utils/costmodel): register the program for HLO-cost
    # harvest; the thunk only runs when `tendermint-tpu profile` (or a
    # costmodel.resolve_pending caller) asks — a trace, never a compile
    from tendermint_tpu.utils import costmodel as _cost

    if _cost.COSTS.enabled:
        _cost.COSTS.record_pending(
            "verify", n, impl_r, {"donate": donate},
            lambda: jitted.lower(*_plan.abstract_rows("verify", n)))
    return _devmon.track_jit(jitted, kind="verify", impl=impl_r, rung=n)


_compiled.cache_clear = _compiled_entry.cache_clear


# ---------------------------------------------------------------------------
# Host preprocessing
# ---------------------------------------------------------------------------

_L_WORDS = np.frombuffer(L.to_bytes(32, "little"), dtype="<u8").copy()


def prepare_batch(pubs, msgs, sigs):
    """Parse/validate on host; returns packed device inputs
    (pub_rows, r_rows, s_rows, k_rows, valid) — all [N,32] uint8 + bool[N].

    Host work is only what must stay on host: the variable-length
    SHA-512 (hashlib C) and the s < L canonicality test (ZIP-215 rule 1)
    — both vectorized/batched so host prep stays a small fraction of the
    device call."""
    n = len(pubs)
    valid = np.ones(n, dtype=bool)

    well_formed = all(len(p) == 32 for p in pubs) and all(len(s) == 64 for s in sigs)
    if well_formed:
        pub_rows = np.frombuffer(b"".join(pubs), dtype=np.uint8).reshape(n, 32).copy()
        sig_rows = np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(n, 64)
        r_rows = sig_rows[:, :32].copy()
        s_rows = sig_rows[:, 32:].copy()
    else:
        pub_rows = np.zeros((n, 32), dtype=np.uint8)
        r_rows = np.zeros((n, 32), dtype=np.uint8)
        s_rows = np.zeros((n, 32), dtype=np.uint8)
        for i, (pub, sig) in enumerate(zip(pubs, sigs)):
            if len(pub) != 32 or len(sig) != 64:
                valid[i] = False
                continue
            pub_rows[i] = np.frombuffer(pub, dtype=np.uint8)
            r_rows[i] = np.frombuffer(sig[:32], dtype=np.uint8)
            s_rows[i] = np.frombuffer(sig[32:], dtype=np.uint8)

    # ZIP-215 rule 1 (s < L), vectorized: lexicographic compare on the
    # four little-endian 64-bit words, most significant first
    sw = s_rows.view("<u8")  # [n, 4]
    lt = np.zeros(n, dtype=bool)
    gt = np.zeros(n, dtype=bool)
    for w in (3, 2, 1, 0):
        lt = lt | (~gt & (sw[:, w] < _L_WORDS[w]))
        gt = gt | (~lt & (sw[:, w] > _L_WORDS[w]))
    valid &= lt  # s == L is also non-canonical

    # k = SHA-512(R || A || M) mod L per row.  The native kernel
    # (src/native/edhost.cpp via ops.host_prep) does the whole batch in
    # one threaded C call (~1us/row); the hashlib+bigint loop below is
    # the fallback (~4.7us/row — 50ms for a 10k commit, which alone
    # would blow the 2ms BASELINE target).
    from . import host_prep

    k_rows = host_prep.batch_k_native(r_rows, pub_rows, msgs)
    if k_rows is None:
        sha512 = hashlib.sha512
        from_bytes = int.from_bytes
        ks = bytearray(32 * n)
        for i in range(n):
            if not valid[i]:
                continue
            sig, pub = sigs[i], pubs[i]
            k = from_bytes(sha512(sig[:32] + pub + msgs[i]).digest(), "little") % L
            ks[32 * i : 32 * (i + 1)] = k.to_bytes(32, "little")
        k_rows = np.frombuffer(bytes(ks), dtype=np.uint8).reshape(n, 32).copy()
    return pub_rows, r_rows, s_rows, k_rows, valid


def _ladder_bucket(n: int) -> int:
    """The built-in FORMULA ladder: powers of two up to 64, then
    3*2^(k-1) rungs interleaved (96, 128, 192, ...), then 5*2^(k-2)
    rungs too from 320 up (320, 384, 512, 640, 768, 1024, ...).
    Measured worst-case padding over the device-eligible range
    (exhaustive sweep, n in [65, 20000]): 1.49x at n=129→192, and
    <=1.34x once the 5*2^(k-2) rungs kick in (n>=321; the max there is
    12289→16384) — down from 2.0x on a pure power-of-two ladder.  The
    north-star 10,000-sig commit runs the 10,240 bucket (1.024x padded)
    instead of 16,384 (1.64x) — VERDICT r4 item 2.  Each bucket
    compiles once (persistent XLA cache); steady-state consensus reuses
    a handful.

    This is the DEFAULT shape plan ("legacy") and the above-the-plan
    fallback; production bucketing goes through _bucket below."""
    b = 8
    while b < n:
        if b >= 256 and 5 * (b // 4) >= n:
            return 5 * (b // 4)
        if b >= 64 and 3 * (b // 2) >= n:
            return 3 * (b // 2)
        b *= 2
    return b


def _bucket(n: int) -> int:
    """Smallest compiled bucket >= n under the ACTIVE shape plan
    (ops/shape_plan.py).  The default plan IS _ladder_bucket's formula
    ladder — bit-identical behavior until an operator installs a
    consolidated plan (`tendermint-tpu warm`, TM_TPU_SHAPE_PLAN,
    TM_TPU_RUNGS); resolved per call so plan/env changes are honored
    without re-imports."""
    from . import shape_plan as _plan

    return _plan.bucket(n)


def _pad_rows(n: int, b: int, *arrays):
    """Zero-pad leading axis from n to bucket b."""
    if b == n:
        return arrays
    pad = b - n
    return tuple(np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) for x in arrays)


# ---------------------------------------------------------------------------
# Golden-batch self-check of a field backend on the device it runs on
# ---------------------------------------------------------------------------
#
# A program that compiles is not a program that is right: the f32 backend
# this kernel once had computed WRONG verdicts on the TPU (CHANGES PR 21)
# where XLA-CPU ran it exactly.  So before "auto" takes a backend on an
# accelerator, the backend's own floor-rung program runs ONCE per process
# against a known mixed-validity batch, and is refused (loudly, with the
# int64 fallback) on any verdict mismatch or error.

_OPTIN_STATE: dict[tuple[str, str], bool] = {}
# (flag, impl) -> {"outcome": "pass" | "wrong_verdicts" | "error", ...}:
# a candidate that RAISES (a compile refusal) and one that computes
# WRONG VERDICTS are different findings, kept apart for optin_report()
_OPTIN_REPORT: dict[tuple[str, str], dict] = {}


def optin_report() -> dict:
    """Golden self-check outcomes so far, keyed "flag/impl": outcome
    "pass", "wrong_verdicts" (with the verdicts got/wanted) or "error"
    (with the exception's type and message — e.g. the compiler's words
    for a program it refused)."""
    return {f"{flag}/{impl}": dict(rec)
            for (flag, impl), rec in _OPTIN_REPORT.items()}


def _golden_batch():
    """8 deterministic signatures, rows 3 and 6 corrupted."""
    from tendermint_tpu.crypto.keys import priv_key_from_seed

    pubs, msgs, sigs, want = [], [], [], []
    for i in range(8):
        k = priv_key_from_seed(bytes([i + 41]) * 32)
        m = b"optin-golden-%d" % i
        s = k.sign(m)
        ok = True
        if i in (3, 6):
            s = s[:-1] + bytes([s[-1] ^ 1])
            ok = False
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(s)
        want.append(ok)
    return prepare_batch(pubs, msgs, sigs), want


def _optin_safe(flag: str, impl: str) -> bool:
    """True iff `impl`'s standard program reproduces the golden verdicts
    on the current backend — the very program (and cache entry)
    production dispatch runs.  Memoized per process; a mismatch or an
    error warns and pins False (the caller falls back to int64).
    `flag` is "impl": the name and the "impl/<impl>" key of
    optin_report() are what chipbench and chip_smoke.py read."""
    key = (flag, impl)
    if key in _OPTIN_STATE:
        return _OPTIN_STATE[key]
    import warnings

    try:
        inputs, want = _golden_batch()
        got = _compiled(8, impl)(*inputs)
        got = [bool(v) for v in np.asarray(got)]
    except Exception as e:  # noqa: BLE001 — a crash is also a refusal
        ok = False
        _OPTIN_REPORT[key] = {"outcome": "error", "type": type(e).__name__,
                              "message": str(e)[-1000:]}
        warnings.warn(f"field backend {impl!r} RAISED in its golden "
                      f"self-check (a compile or runtime refusal, not a "
                      f"verdict mismatch); disabled for this process: "
                      f"{type(e).__name__}: {e}")
    else:
        ok = got == want
        _OPTIN_REPORT[key] = ({"outcome": "pass"} if ok else
                              {"outcome": "wrong_verdicts", "got": got,
                               "want": want})
        if not ok:
            warnings.warn(
                f"field backend {impl!r} computed WRONG verdicts on this "
                "backend (golden-batch self-check); it is disabled for "
                "this process and the int64 program is used instead")
    _OPTIN_STATE[key] = ok
    return ok


def verify_batch(pubs, msgs, sigs, impl: str | None = None) -> np.ndarray:
    """ZIP-215 verification of the whole batch on device, as one
    program at the batch's rung.

    Returns bool[N].  Inputs are bytes-like sequences of equal length N.
    """
    n = len(pubs)
    if n == 0:
        return np.zeros(0, dtype=bool)
    # resolve the env default BEFORE the jit cache key so a later change
    # to TM_TPU_FIELD_IMPL is honored (and impl=None vs impl="int64"
    # share one compiled program per bucket)
    impl = impl or default_impl()
    rows = prepare_batch(pubs, msgs, sigs)
    b = _bucket(n)
    padded = _pad_rows(n, b, *rows)
    if _devmon.STATS.enabled:
        _devmon.STATS.record_flush(
            "verify", n, b, nbytes=sum(a.nbytes for a in padded))
    return np.asarray(_compiled(b, impl)(*padded))[:n]

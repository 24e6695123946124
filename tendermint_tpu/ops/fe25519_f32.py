"""GF(2^255-19) field and edwards25519 point arithmetic on f32 lanes.

Round-3 device-kernel redesign (VERDICT r2 item 1).  The radix-17 int64
layer (`fe25519.py`) is numerically ideal for a 64-bit integer machine, but
the TPU VPU is float-centric: XLA *emulates* int64 limb products from 32-bit
pieces, and the round-1 TPU measurement showed ~21 us/sig of device math —
all of it riding that emulation.  This module is the same mathematics
reshaped onto the datapath the hardware actually has: **every operation is a
native f32 multiply/add/floor**, with exactness guaranteed by keeping every
intermediate an integer of magnitude <= 2^24 (f32's exact-integer ceiling).

Representation: 51 limbs x 5 bits, signed, in f32 lanes, batch-shaped
`[..., 51]`.  255 = 51*5 exactly, so the 2^255 wrap folds with a bare x19
(same property as the 15x17 int64 layout).

Why radix 5 (and not more): for products a_i*b_j to accumulate exactly in
f32, the worst folded column must stay under 2^24.  A column takes <= 51
products plus the 19-fold, worst coefficient sum 951 (see fe_mul), so the
product magnitude budget is 2^24/951 = 17641.  With the lazy-operand bounds
below (|limbs| <= 153 at mul inputs after one partial carry) radix 5 fits
with ~11% margin; radix 6 (43 limbs, fold 152) and radix 7 (37 limbs, fold
304) are infeasible even with fully reduced operands.

Why SIGNED limbs: subtraction becomes a bare `a - b` — no 2p/4p padding
constants, no "subtrahend must be reduced" preconditions — and magnitudes
stay small through the add/sub chains of the point formulas.  floor()-based
carries keep low limbs in [0, 32) regardless of sign, so negative values
relax to the same reduced band.

Bound ledger (magnitudes; "reduced" = carry output):
  * reduced limbs: in [-20, 51] — lo in [0,32) plus at most one +-19*hi
    re-entry at limb 0 and +-hi at limbs 1..50 with |hi| <= 1.
  * fe_add/fe_sub of two reduced: <= 102.
  * fe_mul operand contract: |a|_inf * |b|_inf <= 17641; callers document
    their operand bounds at each site (worst in-tree: 153*102 = 15606).
  * fe_sq operand contract: |a|_inf <= 63 (doubled cross terms).
  * fe_carry(c, rounds=6) reduces any |c| <= 2^24; rounds=3 reduces
    |c| <= 204 (the point-op partial carries).  Verified at the bound in
    tests/test_ed25519_f32.py.

Parity target: identical to fe25519.py — the reference's ed25519consensus
verify semantics (reference: crypto/ed25519/ed25519.go:149-156), ZIP-215
rules, differentially tested against tendermint_tpu.crypto.ed25519.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ref

NLIMBS = 51
LIMB_BITS = 5
RADIX = float(1 << LIMB_BITS)  # 32.0
INV_RADIX = 1.0 / RADIX

P = _ref.P


def limbs_from_int(v: int) -> np.ndarray:
    return np.array(
        [(v >> (LIMB_BITS * i)) & (RADIX_INT - 1) for i in range(NLIMBS)],
        dtype=np.float32,
    )


RADIX_INT = 1 << LIMB_BITS


def int_from_limbs(a) -> int:
    a = np.asarray(a)
    return sum(int(a[..., i]) << (LIMB_BITS * i) for i in range(NLIMBS))


# ---------------------------------------------------------------------------
# Constants (limb form)
# ---------------------------------------------------------------------------

P_LIMBS = limbs_from_int(P)  # [13, 31, 31, ..., 31]
ONE = limbs_from_int(1)
ZERO = limbs_from_int(0)
D_CONST = limbs_from_int(_ref.D)
D2_CONST = limbs_from_int(2 * _ref.D % P)
SQRT_M1_CONST = limbs_from_int(_ref.SQRT_M1)

# 4p in non-canonical limb form with every limb >= 52: all limbs 124 except
# limb0 = 52.  sum(124 * 2^(5i), i=0..50) = 4*(2^255 - 1) = 2^257 - 4, and
# 2^257 - 4 - 72 = 2^257 - 76 = 4p.  Added before canonicalization so the
# exact ripple runs on non-negative limbs (inputs are |limbs| <= 52).
_V4P = np.full(NLIMBS, 124.0, dtype=np.float32)
_V4P[0] = 52.0
assert int_from_limbs(_V4P) == 4 * P


# ---------------------------------------------------------------------------
# Field ops  (all take/return [..., 51] f32)
# ---------------------------------------------------------------------------

def fe_carry(c: jnp.ndarray, rounds: int = 6) -> jnp.ndarray:
    """Carry-propagate columns to reduced form via floor-division relaxation.

    Each round moves every limb's overflow one limb up simultaneously; the
    2^255-weight top overflow re-enters limb 0 as x19.  floor() keeps the
    retained limb in [0, 32) for negative values too, so signed inputs relax
    to the same band.  Convergence: the excess mass travels one limb per
    round shrinking x1/32, and the x19 wrap re-entry only ever sees the
    already-shrunk top overflow, so |c| <= 2^24 settles to reduced in 6
    rounds (2^19 -> 2^14 -> 2^9 -> 2^4 -> ~42 -> <= 51) and |c| <= 204 in 3.
    Empirically verified at the bounds (tests/test_ed25519_f32.py)."""
    for _ in range(rounds):
        hi = jnp.floor(c * INV_RADIX)
        lo = c - hi * RADIX
        c = lo + jnp.concatenate([19.0 * hi[..., -1:], hi[..., :-1]], axis=-1)
    return c


def _fold_cols(cols: jnp.ndarray) -> jnp.ndarray:
    """Fold product columns [..., 101] at the 2^255 wrap (x19) and carry.

    Worst folded column: col_j + 19*col_{j+51} with (j+1) + 19*(50-j) <= 951
    products, so |fold_j| <= 951 * max|a_i*b_j| — exact in f32 as long as
    the fe_mul operand contract (product magnitude <= 17641) holds."""
    lo = cols[..., :NLIMBS]
    hi = cols[..., NLIMBS:]
    lo = lo.at[..., : NLIMBS - 1].add(19.0 * hi)
    return fe_carry(lo, rounds=6)


def _mul_cols(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    nd = a.ndim - 1
    cols = jnp.zeros(a.shape[:-1] + (2 * NLIMBS - 1,), dtype=jnp.float32)
    for i in range(NLIMBS):
        term = a[..., i : i + 1] * b  # [..., 51]
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(i, NLIMBS - 1 - i)])
    return cols


# None = not yet resolved: TM_TPU_FE_MXU is read lazily at the first
# fe_mul (not at import — tmlint import-time-env), so tests/operators
# can still flip it after this module loads.  Round 9 promoted the flag
# from opt-in to "auto" (the TM_TPU_DONATE=auto idiom): "1" forces on,
# "0" forces off, and the default "auto" turns the MXU formulation on
# wherever a real accelerator backend is driving — EXCEPT that
# production dispatches still run ed25519_jax's golden self-check once
# per process and pin the flag False on any backend whose
# Precision.HIGHEST matmul is not exact (measured wrong on the r04
# TPU), so auto-on is always auto-validated before a verdict ships.
# XLA-CPU resolves auto to False: tier-1 traces (and their persistent
# compile-cache keys) are bit-identical to the pre-auto default.
_USE_MXU: bool | None = None


def _use_mxu() -> bool:
    global _USE_MXU
    if _USE_MXU is None:
        mode = os.environ.get("TM_TPU_FE_MXU", "auto")
        if mode == "1":
            _USE_MXU = True
        elif mode == "0":
            _USE_MXU = False
        else:
            try:
                _USE_MXU = jax.default_backend() != "cpu"
            except Exception:  # noqa: BLE001 — no backend: nothing to gain
                _USE_MXU = False
    return _USE_MXU


def reload_env() -> None:
    """Drop the cached flag so the next fe_mul re-reads TM_TPU_FE_MXU.
    Compiled programs bake the flag in: callers that flip it must also
    clear the jit caches (see ed25519_jax._optin_safe)."""
    global _USE_MXU
    _USE_MXU = None


def _inc_matrix() -> np.ndarray:
    """[51*51, 51] incidence map: product (i,j) lands in column i+j, with
    the 2^255 wrap folded in as x19.  Used by the (measurable, optional)
    MXU formulation of fe_mul — the product tensor contracts against this
    constant on the matrix unit instead of the pad/add tree on the VPU."""
    m = np.zeros((NLIMBS * NLIMBS, NLIMBS), dtype=np.float32)
    for i in range(NLIMBS):
        for j in range(NLIMBS):
            k = i + j
            if k < NLIMBS:
                m[i * NLIMBS + j, k] = 1.0
            else:
                m[i * NLIMBS + j, k - NLIMBS] = 19.0
    return m


_INC = _inc_matrix()


def _fe_mul_mxu(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    p = (a[..., :, None] * b[..., None, :]).reshape(a.shape[:-1] + (NLIMBS * NLIMBS,))
    cols = lax.dot_general(
        p,
        jnp.asarray(_INC),
        (((p.ndim - 1,), (0,)), ((), ())),
        # HIGHEST = XLA's 6-pass f32 emulation on TPU (bf16_3x would be
        # Precision.HIGH).  The 6-pass algorithm represents each f32
        # operand exactly as bf16 triples, so products of our <=2^24
        # integers accumulate exactly — but TPU-mode exactness is
        # asserted here by argument, not yet by test: the differential
        # test (test_fe_mul_mxu_variant_matches) has only ever run on
        # XLA-CPU, where dot is natively f32.  Unverified on device
        # until the TPU-side differential run lands (ADVICE r3).
        preferred_element_type=jnp.float32,
    )
    return fe_carry(cols, rounds=6)


def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product + 19-fold + carry.  Contract: |a|inf*|b|inf <= 17641."""
    shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, shape + (NLIMBS,))
    b = jnp.broadcast_to(b, shape + (NLIMBS,))
    if _use_mxu():
        return _fe_mul_mxu(a, b)
    return _fold_cols(_mul_cols(a, b))


def fe_sq(a: jnp.ndarray) -> jnp.ndarray:
    """Specialized squaring: ~half the products (diagonal once, cross terms
    doubled).  Contract: |a|inf <= 63 (doubled terms else overflow the
    column budget); use fe_mul(a, a) for larger operands."""
    shape = a.shape[:-1]
    nd = len(shape)
    a2 = a + a
    cols = jnp.zeros(shape + (2 * NLIMBS - 1,), dtype=jnp.float32)
    for i in range(NLIMBS):
        row = jnp.concatenate([a[..., i : i + 1], a2[..., i + 1 :]], axis=-1)
        term = a[..., i : i + 1] * row  # [..., NLIMBS - i]
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(2 * i, NLIMBS - 1 - i)])
    return _fold_cols(cols)


def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a + b


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b directly — signed limbs need no 2p padding or reduced-b rule."""
    return a - b


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    return -a


def fe_pow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    return lax.fori_loop(0, k, lambda _i, v: fe_sq(v), a)


def fe_pow_p58(a: jnp.ndarray) -> jnp.ndarray:
    """a^((p-5)/8) = a^(2^252 - 3) — same addition chain as fe25519.py."""
    z2 = fe_sq(a)
    z8 = fe_pow2k(z2, 2)
    z9 = fe_mul(z8, a)
    z11 = fe_mul(z9, z2)
    z22 = fe_sq(z11)
    z_5_0 = fe_mul(z22, z9)
    z_10_0 = fe_mul(fe_pow2k(z_5_0, 5), z_5_0)
    z_20_0 = fe_mul(fe_pow2k(z_10_0, 10), z_10_0)
    z_40_0 = fe_mul(fe_pow2k(z_20_0, 20), z_20_0)
    z_50_0 = fe_mul(fe_pow2k(z_40_0, 10), z_10_0)
    z_100_0 = fe_mul(fe_pow2k(z_50_0, 50), z_50_0)
    z_200_0 = fe_mul(fe_pow2k(z_100_0, 100), z_100_0)
    z_250_0 = fe_mul(fe_pow2k(z_200_0, 50), z_50_0)
    return fe_mul(fe_pow2k(z_250_0, 2), a)


def _fe_carry_exact(c: jnp.ndarray) -> jnp.ndarray:
    """Sequential full ripple (non-negative inputs): limbs < 32 afterwards
    except a bounded residue in limbs 0/1 from the x19 top-carry re-entry.
    Only used by fe_canonical."""
    outs = []
    carry = jnp.zeros(c.shape[:-1], dtype=jnp.float32)
    for i in range(NLIMBS):
        v = c[..., i] + carry
        carry = jnp.floor(v * INV_RADIX)
        outs.append(v - carry * RADIX)
    c0 = outs[0] + 19.0 * carry
    k0 = jnp.floor(c0 * INV_RADIX)
    outs[0] = c0 - k0 * RADIX
    outs[1] = outs[1] + k0
    return jnp.stack(outs, axis=-1)


def fe_canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Freeze to the canonical representative in [0, p).

    Contract: |limbs| <= 52 (every call site is a carry/mul output or a raw
    <32 unpack).  Adds the all-positive 4p vector so the exact ripple runs
    non-negative, then 3 ripple passes converge to proper limbs (< 32) and
    value < 2^255 + eps, and one conditional subtract lands in [0, p).
    Fuzz-tested against the big-int reference at the bound."""
    a = a + jnp.asarray(_V4P)
    a = _fe_carry_exact(_fe_carry_exact(_fe_carry_exact(a)))
    borrow = jnp.zeros(a.shape[:-1], dtype=jnp.float32)
    outs = []
    for i in range(NLIMBS):
        v = a[..., i] - float(P_LIMBS[i]) - borrow
        borrow = (v < 0).astype(jnp.float32)
        outs.append(v + borrow * RADIX)
    sub = jnp.stack(outs, axis=-1)
    keep = (borrow == 1.0)[..., None]  # underflow => a < p => keep a
    return jnp.where(keep, a, sub)


def fe_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_canonical(a) == fe_canonical(b), axis=-1)


def fe_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_canonical(a) == 0, axis=-1)


# ---------------------------------------------------------------------------
# Point ops — extended coordinates (X, Y, Z, T), T = XY/Z
# ---------------------------------------------------------------------------

class Pt:
    """Plain struct of four [..., 51] limb arrays (pytree-registered)."""

    __slots__ = ("x", "y", "z", "t")

    def __init__(self, x, y, z, t):
        self.x, self.y, self.z, self.t = x, y, z, t

    def astuple(self):
        return (self.x, self.y, self.z, self.t)


def pt_identity(shape=()) -> Pt:
    def c(v):
        return jnp.broadcast_to(jnp.asarray(v), shape + (NLIMBS,))

    return Pt(c(ZERO), c(ONE), c(ONE), c(ZERO))


def pt_add(p: Pt, q: Pt) -> Pt:
    """Unified, complete a=-1 extended addition (add-2008-hwcd-3 shape).

    Bounds with reduced inputs (|coords| <= 51): the operands of a and b
    are <= 102 each (10404), c's are reduced; a, b, c, d come out
    reduced and _add_tail's ledger takes over."""
    a = fe_mul(fe_sub(p.y, p.x), fe_sub(q.y, q.x))
    b = fe_mul(fe_add(p.y, p.x), fe_add(q.y, q.x))
    c = fe_mul(fe_mul(p.t, q.t), jnp.asarray(D2_CONST))
    d = fe_mul(p.z, q.z)
    return _add_tail(a, b, c, fe_add(d, d), None)


def _add_tail(a, b, c, d2, neg) -> Pt:
    """The second half of every addition: E, F, G, H and the four
    products, from reduced a, b, c and |d2| <= 102.  `neg` (bool [...],
    or None) adds the NEGATED entry: its C term changes sign, which is
    F and G exchanged.

    Ledger: |e|, |h| <= 102; f = d2 - c <= |153| always takes the
    3-round partial carry (back to reduced); g = d2 + c <= |153|.
      * neg None (pt_add, the table build): e*f 102*51, g*h 153*102 =
        15606 (the worst, 11% margin), f*g 51*153, e*h 102*102.
      * neg given: f*g must not meet two uncarried operands (153*153),
        so g takes the same carry BEFORE the exchange and both orders
        read e*f 102*51, g*h 51*102, f*g 51*51, e*h 102*102."""
    e = fe_sub(b, a)
    f = fe_carry(fe_sub(d2, c), rounds=3)
    g = fe_add(d2, c)
    h = fe_add(b, a)
    if neg is not None:
        g = fe_carry(g, rounds=3)
        m = neg[..., None]
        f, g = jnp.where(m, g, f), jnp.where(m, f, g)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def _add_entry(p: Pt, ypx, ymx, tc, d2, neg) -> Pt:
    """p ± a table entry whose addition-side terms are precomputed:
    (y+x, y-x), the coefficient `tc` of T1 and D2 = 2·Z1·Z2.  Negating
    the entry is free: its first two coordinates swap (and F, G in
    _add_tail).  Entry coordinates are reduced (<= 51), the p side is
    <= 102: a, b 102*51, c 51*51, swapped or not."""
    if neg is not None:
        m = neg[..., None]
        ypx, ymx = jnp.where(m, ymx, ypx), jnp.where(m, ypx, ymx)
    a = fe_mul(fe_sub(p.y, p.x), ymx)
    b = fe_mul(fe_add(p.y, p.x), ypx)
    c = fe_mul(p.t, tc)
    return _add_tail(a, b, c, d2, neg)


def pt_madd(p: Pt, niels, neg=None) -> Pt:
    """p ± an AFFINE point precomputed as a Niels triple (y+x, y-x,
    2d·x·y) of canonical limbs, Z = 1: the unified addition less Z1·Z2
    and the ·2d — 7 multiplications.  neg: bool [...] or None (= add)."""
    ypx, ymx, xy2d = niels
    return _add_entry(p, ypx, ymx, xy2d, fe_add(p.z, p.z), neg)


def pt_to_cached(p: Pt):
    """(Y+X, Y-X, Z, 2d·T), every coordinate reduced (the sum and the
    difference, <= 102, each take a 3-round carry here, once per table
    entry)."""
    return (fe_carry(fe_add(p.y, p.x), rounds=3),
            fe_carry(fe_sub(p.y, p.x), rounds=3),
            p.z, fe_mul(p.t, jnp.asarray(D2_CONST)))


def pt_add_cached(p: Pt, cached, neg=None) -> Pt:
    """p ± a point in cached form (pt_to_cached): 8 multiplications."""
    ypx, ymx, z, t2d = cached
    d = fe_mul(p.z, z)
    return _add_entry(p, ypx, ymx, t2d, fe_add(d, d), neg)


def pt_dbl(p: Pt) -> Pt:
    """Dedicated doubling (dbl-2008-hwcd for a=-1), complete for every
    curve point.  sq(x+y) goes through fe_mul (operand 102 > fe_sq's 63
    ceiling); f = c2 + g <= |204| gets the 3-round partial carry.  Worst
    product: e*h = 153*102 = 15606."""
    a = fe_sq(p.x)
    b = fe_sq(p.y)
    c = fe_sq(p.z)
    c = fe_add(c, c)
    h = fe_add(a, b)
    xy = fe_add(p.x, p.y)
    e = fe_sub(h, fe_mul(xy, xy))
    g = fe_sub(a, b)
    f = fe_carry(fe_add(c, g), rounds=3)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_double(p: Pt) -> Pt:
    return pt_dbl(p)


def pt_dbl_n(p: Pt, k: int) -> Pt:
    """k chained doublings with the T coordinate computed only on the
    last (see fe25519.pt_dbl_n — trace-size/doc win; XLA DCEs the dead
    muls either way).  Same bound ledger as pt_dbl: every intermediate
    re-enters the loop reduced (the outputs of e*f, g*h, f*g are
    fe_mul-reduced), so the chain is bound-safe for any k."""
    assert k >= 1
    x, y, z = p.x, p.y, p.z
    for i in range(k):
        a = fe_sq(x)
        b = fe_sq(y)
        c = fe_sq(z)
        c = fe_add(c, c)
        h = fe_add(a, b)
        xy = fe_add(x, y)
        e = fe_sub(h, fe_mul(xy, xy))
        g = fe_sub(a, b)
        f = fe_carry(fe_add(c, g), rounds=3)
        if i == k - 1:
            return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))
        x, y, z = fe_mul(e, f), fe_mul(g, h), fe_mul(f, g)


def pt_neg(p: Pt) -> Pt:
    # signed limbs: negation is free, magnitudes unchanged
    return Pt(-p.x, p.y, p.z, -p.t)


def pt_select(bit: jnp.ndarray, p1: Pt, p0: Pt) -> Pt:
    m = bit.astype(bool)[..., None]
    return Pt(
        jnp.where(m, p1.x, p0.x),
        jnp.where(m, p1.y, p0.y),
        jnp.where(m, p1.z, p0.z),
        jnp.where(m, p1.t, p0.t),
    )


def pt_is_identity(p: Pt) -> jnp.ndarray:
    return fe_is_zero(p.x) & fe_eq(p.y, p.z)


jax.tree_util.register_pytree_node(
    Pt, lambda p: (p.astuple(), None), lambda _aux, ch: Pt(*ch)
)


_BX, _BY, _BZ, _BT = _ref.BASE
BASE_X = limbs_from_int(_BX)
BASE_Y = limbs_from_int(_BY)
BASE_Z = limbs_from_int(_BZ)
BASE_T = limbs_from_int(_BT)


def pt_base(shape=()) -> Pt:
    def c(v):
        return jnp.broadcast_to(jnp.asarray(v), shape + (NLIMBS,))

    return Pt(c(BASE_X), c(BASE_Y), c(BASE_Z), c(BASE_T))

"""GF(2^255-19) field and edwards25519 point arithmetic on integer lanes.

Design (TPU-first, not a port): field elements are vectors of 15 limbs x 17
bits held in int64 lanes, batch-shaped `[..., 15]` so every operation is a
fused elementwise XLA program over the whole signature batch — no per-element
control flow anywhere.  255 = 15*17 exactly, so the wrap at 2^255 folds with
a bare multiply-by-19 (no shift residue).

Bound analysis (why int64 never overflows):
  * "reduced" limbs are < 2^17.2 (post-carry invariant).
  * adds/subs produce limbs < 2^20 (see fe_sub/fe_neg, which add 2p/4p in
    limb form to stay non-negative).
  * schoolbook product column: <= 15 terms of a_i*b_j plus <= 14 folded
    terms * 19, inputs < 2^20  =>  column < 281 * 2^40 < 2^49  << 2^63.
  * carry chain brings columns back to reduced form; the 2^255 wrap carry
    (< 2^32) re-enters limb 0 via *19 and one extra carry step.

The addition law is the unified a=-1 extended-coordinates formula, complete
for ALL curve points (ed25519's -d is a nonsquare, so the isomorphic a=1
curve satisfies the Bernstein–Lange completeness theorem) — small-order and
doubling inputs included, which ZIP-215 verification requires.

Parity target: semantics of the reference's ed25519consensus verify path
(reference: crypto/ed25519/ed25519.go:149-156); numerics differentially
tested against tendermint_tpu.crypto.ed25519.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ref

NLIMBS = 15
LIMB_AXIS = -1  # an element is int64[*batch, NLIMBS]
LIMB_BITS = 17
MASK = (1 << LIMB_BITS) - 1

P = _ref.P


def limbs_from_int(v: int) -> np.ndarray:
    return np.array([(v >> (LIMB_BITS * i)) & MASK for i in range(NLIMBS)], dtype=np.int64)


def int_from_limbs(a) -> int:
    a = np.asarray(a)
    return sum(int(a[..., i]) << (LIMB_BITS * i) for i in range(NLIMBS))


# What the verify pipeline (ed25519_jax._Core) needs so as never to touch
# a limb axis itself; fe25519_packed.py has the same six for its layout.

def fe_const(limbs, batch_shape=()) -> jnp.ndarray:
    """A limb vector [NLIMBS] (host constant or traced) as an element
    over `batch_shape`."""
    return jnp.broadcast_to(jnp.asarray(limbs, dtype=jnp.int64),
                            tuple(batch_shape) + (NLIMBS,))


def fe_select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """mask ? a : b, mask a bool of the batch's shape."""
    return jnp.where(mask[..., None], a, b)


def fe_parity(a: jnp.ndarray) -> jnp.ndarray:
    """The low bit of a CANONICAL element, int32 of the batch's shape."""
    return a[..., 0].astype(jnp.int32) & 1


def batch_in(rows: jnp.ndarray) -> jnp.ndarray:
    """verify_core's input rows [N, ...] in this module's batch shape:
    as they are."""
    return rows


def batch_out(verdicts: jnp.ndarray) -> jnp.ndarray:
    return verdicts


def limbs_of_bits(bits255: jnp.ndarray) -> jnp.ndarray:
    """[..., 255] LE bits -> [..., NLIMBS] limbs, on device: uniform
    widths, so one reshape and one weighted sum."""
    shaped = bits255.reshape(bits255.shape[:-1] + (NLIMBS, LIMB_BITS))
    w = jnp.asarray(1 << np.arange(LIMB_BITS, dtype=np.int64))
    return (shaped.astype(jnp.int64) * w).sum(-1)


# ---------------------------------------------------------------------------
# Constants (limb form)
# ---------------------------------------------------------------------------

P_LIMBS = limbs_from_int(P)
_2P = 2 * P_LIMBS  # limb-wise: borrow headroom for one reduced subtrahend
_4P = 4 * P_LIMBS
ONE = limbs_from_int(1)
ZERO = limbs_from_int(0)
D_CONST = limbs_from_int(_ref.D)
D2_CONST = limbs_from_int(2 * _ref.D % P)
SQRT_M1_CONST = limbs_from_int(_ref.SQRT_M1)


# ---------------------------------------------------------------------------
# Field ops  (all take/return [..., 15] int64)
# ---------------------------------------------------------------------------

def fe_carry(c: jnp.ndarray, rounds: int = 4) -> jnp.ndarray:
    """Carry-propagate columns (each < 2^57) to reduced form (< 2^17.3).

    Vectorized relaxation instead of a sequential 15-step ripple: each
    round moves every limb's overflow one limb up simultaneously (the
    2^255-weight top overflow re-enters limb 0 as ×19).  Bound: limbs
    shrink to ≤ 2^17 + 19·C/2^17 per round, so 4 rounds take 2^57 →
    2^44.4 → 2^31.7 → 2^19.2 → < 2^17.3.  ~4 fused elementwise steps
    with a 4-deep dependency chain, vs 15 sequential carry steps.

    rounds=3 is sound for C ≤ 2^52.6, which is exactly _fold_cols'
    output bound: each round maps max limb C → 2^17 + 19·(C/2^17), so
    2^52.6 → ≤ 2^40.0 → ≤ 2^27.2 → ≤ 2^17 + 19·2^10.2 ≈ 153k, under
    the 2^17.3 (≈161k) reduced-form invariant.  Verified empirically at
    the worst-case input bound (tests/test_ed25519_jax.py carry stress)."""
    for _ in range(rounds):
        hi = c >> LIMB_BITS
        lo = c & MASK
        c = lo + jnp.concatenate(
            [19 * hi[..., -1:], hi[..., :-1]], axis=-1
        )
    return c


def _fold_cols(cols: jnp.ndarray) -> jnp.ndarray:
    """Fold product columns [..., 29] at the 2^255 wrap (x19) and carry.

    Post-fold limb bound: schoolbook columns ≤ 281·2^40 < 2^48.2 (inputs
    < 2^20 incl. the 19-fold inside fe_mul's analysis), so lo + 19·hi
    < 2^48.2·20 < 2^52.6 — the rounds=3 carry regime."""
    lo = cols[..., :NLIMBS]
    hi = cols[..., NLIMBS:]
    lo = lo.at[..., : NLIMBS - 1].add(19 * hi)
    return fe_carry(lo, rounds=3)


def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product with inline 19-fold, then carry.  Inputs < 2^20."""
    shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, shape + (NLIMBS,))
    b = jnp.broadcast_to(b, shape + (NLIMBS,))
    nd = len(shape)
    cols = jnp.zeros(shape + (2 * NLIMBS - 1,), dtype=jnp.int64)
    for i in range(NLIMBS):
        term = a[..., i : i + 1] * b  # [..., 15]
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(i, NLIMBS - 1 - i)])
    return _fold_cols(cols)


def fe_sq(a: jnp.ndarray) -> jnp.ndarray:
    """Specialized squaring: 120 limb products instead of 225 (diagonal
    once, cross terms doubled).  Inputs < 2^20; doubled terms < 2^41 and
    columns < 2^45, well under the int64 fold headroom."""
    shape = a.shape[:-1]
    nd = len(shape)
    a2 = a + a
    cols = jnp.zeros(shape + (2 * NLIMBS - 1,), dtype=jnp.int64)
    for i in range(NLIMBS):
        # row i: a_i^2 at column 2i, then 2*a_i*a_j (j > i) at i+j
        row = jnp.concatenate([a[..., i : i + 1], a2[..., i + 1 :]], axis=-1)
        term = a[..., i : i + 1] * row  # [..., NLIMBS - i]
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(2 * i, NLIMBS - 1 - i)])
    return _fold_cols(cols)


def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a + b


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (mod p), non-negative limbs; b must be reduced (< 2^17.2)."""
    return a + _2P - b


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    """-a (mod p); valid for limbs < 2^19 (4p headroom)."""
    return _4P - a


def fe_pow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a^(2^k) by repeated squaring (sequential; k is static)."""
    return lax.fori_loop(0, k, lambda _i, v: fe_sq(v), a)


def fe_pow_p58(a: jnp.ndarray) -> jnp.ndarray:
    """a^((p-5)/8) = a^(2^252 - 3) — the sqrt-ratio exponent.

    Standard 2/9/11/31-… addition chain (publicly known; ~254 squarings,
    11 multiplies)."""
    z2 = fe_sq(a)
    z8 = fe_pow2k(z2, 2)
    z9 = fe_mul(z8, a)
    z11 = fe_mul(z9, z2)
    z22 = fe_sq(z11)
    z_5_0 = fe_mul(z22, z9)  # a^(2^5-1)
    z_10_0 = fe_mul(fe_pow2k(z_5_0, 5), z_5_0)  # a^(2^10-1)
    z_20_0 = fe_mul(fe_pow2k(z_10_0, 10), z_10_0)
    z_40_0 = fe_mul(fe_pow2k(z_20_0, 20), z_20_0)
    z_50_0 = fe_mul(fe_pow2k(z_40_0, 10), z_10_0)
    z_100_0 = fe_mul(fe_pow2k(z_50_0, 50), z_50_0)
    z_200_0 = fe_mul(fe_pow2k(z_100_0, 100), z_100_0)
    z_250_0 = fe_mul(fe_pow2k(z_200_0, 50), z_50_0)
    return fe_mul(fe_pow2k(z_250_0, 2), a)  # a^(2^252-3)


def _fe_carry_exact(c: jnp.ndarray) -> jnp.ndarray:
    """Sequential full ripple: limbs strictly < 2^17 afterwards (plus one
    19-fold re-entry).  Used only by fe_canonical, where REPRESENTATION
    uniqueness matters (fe_eq compares limb vectors)."""
    outs = []
    carry = jnp.zeros(c.shape[:-1], dtype=jnp.int64)
    for i in range(NLIMBS):
        v = c[..., i] + carry
        carry = v >> LIMB_BITS
        outs.append(v & MASK)
    c0 = outs[0] + 19 * carry
    c1 = outs[1] + (c0 >> LIMB_BITS)
    outs[0] = c0 & MASK
    outs[1] = c1
    return jnp.stack(outs, axis=-1)


def fe_canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Freeze to the canonical representative in [0, p)."""
    # exact carry passes: converge to proper limbs (< 2^17) and value
    # < 2^255 for any column input < 2^57 (fuzz-tested against big-int ref)
    a = _fe_carry_exact(_fe_carry_exact(_fe_carry_exact(a)))
    # conditional subtract p (branchless, borrow chain)
    borrow = jnp.zeros(a.shape[:-1], dtype=jnp.int64)
    outs = []
    for i in range(NLIMBS):
        v = a[..., i] - int(P_LIMBS[i]) - borrow
        borrow = (v < 0).astype(jnp.int64)
        outs.append(v + (borrow << LIMB_BITS))
    sub = jnp.stack(outs, axis=-1)
    keep = (borrow == 1)[..., None]  # underflow => a < p => keep a
    return jnp.where(keep, a, sub)


def fe_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Canonical equality; returns bool [...]. Inputs any valid limb form."""
    return jnp.all(fe_canonical(a) == fe_canonical(b), axis=-1)


def fe_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_canonical(a) == 0, axis=-1)


# ---------------------------------------------------------------------------
# Point ops — extended coordinates (X, Y, Z, T), T = XY/Z
# ---------------------------------------------------------------------------

class Pt:
    """Plain struct of four [..., 15] limb arrays (pytree via tuple use)."""

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
    """Unified, complete a=-1 extended addition (add-2008-hwcd-3 shape)."""
    a = fe_mul(fe_sub(p.y, p.x), fe_sub(q.y, q.x))
    b = fe_mul(fe_add(p.y, p.x), fe_add(q.y, q.x))
    c = fe_mul(fe_mul(p.t, q.t), D2_CONST)
    d = fe_mul(p.z, q.z)
    return _add_tail(a, b, c, fe_add(d, d), None)


def _add_tail(a, b, c, d2, neg) -> Pt:
    """The second half of every addition: E, F, G, H and the four
    products.  `neg` (bool [...], or None) adds the NEGATED entry: its
    C term changes sign, which is F and G exchanged.  Bounds: E < 2^18.7,
    H < 2^18.3, F < 2^19.2, G < 2^18.9 — under fe_mul's 2^20 ceiling in
    either order."""
    e = fe_sub(b, a)
    f = fe_sub(d2, c)
    g = fe_add(d2, c)
    h = fe_add(b, a)
    if neg is not None:
        m = neg[..., None]
        f, g = jnp.where(m, g, f), jnp.where(m, f, g)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def _add_entry(p: Pt, ypx, ymx, tc, d2, neg) -> Pt:
    """p ± a table entry whose addition-side terms are precomputed:
    (y+x, y-x), the coefficient `tc` of T1 and D2 = 2·Z1·Z2.  Negating
    the entry is free: its first two coordinates swap (and F, G in
    _add_tail).  Entry coordinates are reduced; the p side is the same
    as pt_add's, so every product stays under 2^20 x 2^17.3."""
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
    """(Y+X, Y-X, Z, 2d·T), every coordinate reduced: what pt_add_cached
    wants of the point it adds, computed once per table entry."""
    return (fe_carry(fe_add(p.y, p.x), rounds=1),
            fe_carry(fe_sub(p.y, p.x), rounds=1),
            p.z, fe_mul(p.t, D2_CONST))


def pt_add_cached(p: Pt, cached, neg=None) -> Pt:
    """p ± a point in cached form (pt_to_cached): 8 multiplications."""
    ypx, ymx, z, t2d = cached
    d = fe_mul(p.z, z)
    return _add_entry(p, ypx, ymx, t2d, fe_add(d, d), neg)


def pt_dbl(p: Pt) -> Pt:
    """Dedicated doubling (dbl-2008-hwcd, the RFC 8032 point_double for
    a=-1): 4 squarings + 4 multiplies vs the unified add's 9 multiplies.
    Complete for every curve point, identity included (projective signs
    cancel).  Bounds: H,C < 2^18.4; E,G < 2^19.2; F < 2^19.7 — all under
    fe_mul's 2^20 input ceiling."""
    a = fe_sq(p.x)
    b = fe_sq(p.y)
    c = fe_sq(p.z)
    c = fe_add(c, c)
    h = fe_add(a, b)
    e = fe_sub(h, fe_sq(fe_add(p.x, p.y)))  # -2XY
    g = fe_sub(a, b)
    f = fe_add(c, g)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_double(p: Pt) -> Pt:
    return pt_dbl(p)


def pt_dbl_n(p: Pt, k: int) -> Pt:
    """k chained doublings, computing the extended T coordinate ONLY on
    the last: dbl-2008-hwcd reads just (X, Y, Z), so each intermediate
    T = E*H is a dead fe_mul.  XLA's DCE already eliminates those dead
    muls from the compiled program — this primitive makes the ladder's
    true op count explicit in the trace instead of relying on the
    compiler, and shrinks the traced graph (255 fewer fe_mul subgraphs
    per scalar ladder → faster tracing/compiles)."""
    assert k >= 1
    x, y, z = p.x, p.y, p.z
    for i in range(k):
        a = fe_sq(x)
        b = fe_sq(y)
        c = fe_sq(z)
        c = fe_add(c, c)
        h = fe_add(a, b)
        e = fe_sub(h, fe_sq(fe_add(x, y)))
        g = fe_sub(a, b)
        f = fe_add(c, g)
        if i == k - 1:
            return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))
        x, y, z = fe_mul(e, f), fe_mul(g, h), fe_mul(f, g)


def pt_neg(p: Pt) -> Pt:
    # re-carry: negated coordinates feed fe_sub, which needs reduced inputs
    return Pt(fe_carry(fe_neg(p.x)), p.y, p.z, fe_carry(fe_neg(p.t)))


def pt_select(bit: jnp.ndarray, p1: Pt, p0: Pt) -> Pt:
    """bit ? p1 : p0, elementwise over the batch; bit shape [...]."""
    m = bit.astype(bool)[..., None]
    return Pt(
        jnp.where(m, p1.x, p0.x),
        jnp.where(m, p1.y, p0.y),
        jnp.where(m, p1.z, p0.z),
        jnp.where(m, p1.t, p0.t),
    )


def pt_is_identity(p: Pt) -> jnp.ndarray:
    """X == 0 and Y == Z (projective identity test)."""
    return fe_is_zero(p.x) & fe_eq(p.y, p.z)


jax.tree_util.register_pytree_node(
    Pt, lambda p: (p.astuple(), None), lambda _aux, ch: Pt(*ch)
)


# Base point in limb form (host constants)
_BX, _BY, _BZ, _BT = _ref.BASE
BASE_X = limbs_from_int(_BX)
BASE_Y = limbs_from_int(_BY)
BASE_Z = limbs_from_int(_BZ)
BASE_T = limbs_from_int(_BT)


def pt_base(shape=()) -> Pt:
    def c(v):
        return jnp.broadcast_to(jnp.asarray(v), shape + (NLIMBS,))

    return Pt(c(BASE_X), c(BASE_Y), c(BASE_Z), c(BASE_T))

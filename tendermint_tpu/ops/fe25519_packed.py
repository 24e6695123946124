"""GF(2^255-19) field and edwards25519 point arithmetic, PACKED int64 lanes.

Round-9 representation attack (ROADMAP item 1, ISSUE 12).  The original
int64 backend (`fe25519.py`) spends 15 limbs x 17 bits per field element —
every int64 lane carries 17 payload bits and ~47 dead ones, and PR 8's
roofline harvest showed the verify program is memory-bound at AI ~ 0.03
FLOP/B: the limb encoding IS the HLO traffic.  This module is the same
mathematics repacked into the densest int64 layout the schoolbook product
admits: **10 limbs at the mixed radix 25.5** (alternating 26/25-bit widths
— the ref10/curve25519-donna-32 layout, vectorized over the batch axis).

What the repack buys, per field element:
  * 80 bytes/lane-vector instead of 120 (-33% on every limb tensor the
    program materializes — the dominant term in bytes-accessed/row);
  * 100 limb products per fe_mul instead of 225, 19 product columns
    instead of 29, and a 10-wide carry chain instead of 15-wide
    (~2.2x fewer multiply-adds per field op).

Mixed radix 25.5: limb i has weight 2^ceil(25.5 i) —
weights (0, 26, 51, 77, 102, 128, 153, 179, 204, 230) and widths
(26, 25, 26, 25, ...).  10 * 25.5 = 255 exactly, so the wrap at 2^255
folds with a bare multiply-by-19, like both sibling layouts.  The one
wrinkle: a product a_i*b_j with i and j BOTH odd has weight
w_i + w_j = w_{i+j} + 1 and enters column i+j doubled (the classic ref10
"2*" coefficients); with that correction every contribution to column k
has uniform weight w_k and the 19-fold at column 10 is exact
(w_k - 255 = w_{k-10} for every k >= 10).

Bound analysis (why int64 never overflows; R = reduced bound):
  * "reduced" limbs (post-carry invariant): even limbs < 2^26 + 64,
    odd limbs < 2^25 + 64; call the worst R < 2^26.01.
  * fe_add of two reduced: < 2^27.01.  fe_sub adds 2p in limb form
    (even limbs ~2^27): output < R + 2^27 < 2^27.59.  fe_neg adds 4p:
    output < 2^28.01 (callers re-carry; see pt_neg).
  * fe_mul PAIRWISE operand contract (not a single input
    ceiling): max|a_i| * max|b_j| <= 2^54.9.  Column
    coefficient sums C_j = sum(pairs at j) + 19*sum(pairs at j+10) with
    the odd-odd doubling counted are maximal at j=0: C_0 = 1 + 19*14 =
    267 < 2^8.07, so the worst column is < 267 * 2^54.9 < 2^63.
    Worst in-tree product (pt_add/pt_dbl g*h): 2^27.59 * 2^27.01 =
    2^54.61 — 1.25x margin.  Enforced empirically at the bound by
    tests/test_fe25519_packed.py.
  * The precomputed-form additions (pt_madd, pt_add_cached; PR 29) add a
    table ENTRY whose coordinates are reduced — canonical host constants,
    or pt_to_cached outputs, which carry Y+X (A) and Y-X (S) once with
    rounds=2 — so the accumulator's side needs no carry before a and b:
    a = (Y1-X1)*ymx is S*R = 2^53.60, b = (Y1+X1)*ypx is A*R = 2^53.02,
    c = T1*tc is R*R, d = Z1*Z2 is R*R (d2 = A; pt_madd's d2 = 2*Z1 = A),
    and the swap of (ypx, ymx) by the sign exchanges two R's.  The sign
    also exchanges f and g: raw f = d2 + 2p - c < 2^28.01 against h = A
    would be 2^55.02, PAST the contract, so with a sign both f and g take
    the rounds=2 carry before the exchange (then e*f = S*R, g*h = R*A,
    f*g = R*R, e*h = S*A = 2^54.60 in either order); without one (pt_add,
    the table build) only f does, as before.
  * fe_sq operand contract: |a| <= 2^26.9 (cross terms doubled AGAIN on
    top of the odd-odd doubling: worst coefficient sum 534) — i.e.
    reduced inputs only; wider operands route through fe_mul(a, a)
    (pt_add/pt_dbl do, for the (x+y)^2 term).
  * fe_carry(c, rounds=3) (the default) reduces ANY non-negative int64
    column (each round maps max limb C -> 2^26 + 19*C/2^25, so 2^63 ->
    2^42.3 -> 2^26.07 -> reduced); rounds=2 is the cheap point-op
    partial carry, sound for C <= 2^44.

The point formulas are the unified a=-1 extended-coordinate set shared
with both siblings (complete for all curve points, ZIP-215 included);
the only deltas are rounds=2 partial carries where the tighter headroom
(25.5+1.5 bits vs 17+3) demands them — in pt_add the first subtrahend and
the f term, in pt_madd/pt_add_cached f and g (never the subtrahend), two
in pt_dbl (e and f), two in pt_to_cached.

Parity target: identical to fe25519.py — the reference's ed25519consensus
verify semantics (crypto/ed25519/ed25519.go:149-156), ZIP-215 rules,
differentially tested against tendermint_tpu.crypto.ed25519.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ref

NLIMBS = 10
# limb i holds bits [WEIGHTS[i], WEIGHTS[i] + WIDTHS[i]) of the 255-bit value
LIMB_WIDTHS = tuple(26 - (i % 2) for i in range(NLIMBS))
LIMB_WEIGHTS = tuple((51 * i + 1) // 2 for i in range(NLIMBS))  # ceil(25.5 i)
_MASKS = tuple((1 << w) - 1 for w in LIMB_WIDTHS)

_WIDTHS_NP = np.array(LIMB_WIDTHS, dtype=np.int64)
_MASKS_NP = np.array(_MASKS, dtype=np.int64)
# odd-limb doubling vector for the mixed-radix product correction
_DBL_ODD = np.array([1 + (i % 2) for i in range(NLIMBS)], dtype=np.int64)

P = _ref.P


def limbs_from_int(v: int) -> np.ndarray:
    return np.array(
        [(v >> LIMB_WEIGHTS[i]) & _MASKS[i] for i in range(NLIMBS)],
        dtype=np.int64,
    )


def int_from_limbs(a) -> int:
    a = np.asarray(a)
    return sum(int(a[..., i]) << LIMB_WEIGHTS[i] for i in range(NLIMBS))


def limbs_of_bits(bits255: jnp.ndarray) -> jnp.ndarray:
    """[..., 255] LE bits -> [..., 10] limbs, on device (the mixed-radix
    analog of _Core._limbs_of's uniform reshape — widths differ per limb,
    so each limb is its own slice-and-weigh)."""
    outs = []
    for i in range(NLIMBS):
        lo = LIMB_WEIGHTS[i]
        w = LIMB_WIDTHS[i]
        seg = bits255[..., lo : lo + w].astype(jnp.int64)
        weights = jnp.asarray(1 << np.arange(w, dtype=np.int64))
        outs.append((seg * weights).sum(-1))
    return jnp.stack(outs, axis=-1)


# ---------------------------------------------------------------------------
# Constants (limb form)
# ---------------------------------------------------------------------------

P_LIMBS = limbs_from_int(P)  # [2^26-19, 2^25-1, 2^26-1, ...]
_2P = 2 * P_LIMBS  # limb-wise: borrow headroom for one reduced subtrahend
_4P = 4 * P_LIMBS
ONE = limbs_from_int(1)
ZERO = limbs_from_int(0)
D_CONST = limbs_from_int(_ref.D)
D2_CONST = limbs_from_int(2 * _ref.D % P)
SQRT_M1_CONST = limbs_from_int(_ref.SQRT_M1)

assert int_from_limbs(_2P) == 2 * P and int_from_limbs(_4P) == 4 * P


# ---------------------------------------------------------------------------
# Field ops  (all take/return [..., 10] int64)
# ---------------------------------------------------------------------------

def fe_carry(c: jnp.ndarray, rounds: int = 3) -> jnp.ndarray:
    """Carry-propagate columns to reduced form (even < 2^26+64, odd <
    2^25+64) by vectorized relaxation with PER-LIMB widths: each round
    moves every limb's overflow one limb up simultaneously (the
    2^255-weight top overflow re-enters limb 0 as x19).  Each round maps
    max limb C -> 2^26 + 19*C/2^25, so rounds=3 reduces any non-negative
    int64 column (2^63 -> 2^42.3 -> 2^26.07 -> reduced) and rounds=2 —
    the point-op partial carry — is sound for C <= 2^44.  Verified at
    the bounds in tests/test_fe25519_packed.py."""
    shifts = jnp.asarray(_WIDTHS_NP)
    masks = jnp.asarray(_MASKS_NP)
    for _ in range(rounds):
        hi = c >> shifts
        lo = c & masks
        c = lo + jnp.concatenate(
            [19 * hi[..., -1:], hi[..., :-1]], axis=-1
        )
    return c


def _fold_cols(cols: jnp.ndarray) -> jnp.ndarray:
    """Fold product columns [..., 19] at the 2^255 wrap (x19) and carry.

    The fold is weight-exact in this radix: column k >= 10 has weight
    w_k = 255 + w_{k-10} (the odd-odd doubling already normalized every
    contribution to its column's weight), so hi folds into lo with a
    bare x19.  Post-fold column bound: C_0 = 267 coefficient units x the
    pairwise product contract 2^54.9 < 2^63."""
    lo = cols[..., :NLIMBS]
    hi = cols[..., NLIMBS:]
    lo = lo.at[..., : NLIMBS - 1].add(19 * hi)
    return fe_carry(lo, rounds=3)


def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product (100 limb products, mixed-radix doubling on
    odd-odd pairs) + 19-fold + carry.  Contract: max|a_i| * max|b_j|
    <= 2^54.9 (pairwise; see module header for every in-tree site)."""
    shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, shape + (NLIMBS,))
    b = jnp.broadcast_to(b, shape + (NLIMBS,))
    nd = len(shape)
    b_odd2 = b * jnp.asarray(_DBL_ODD)  # odd lanes doubled, for odd-i rows
    cols = jnp.zeros(shape + (2 * NLIMBS - 1,), dtype=jnp.int64)
    for i in range(NLIMBS):
        term = a[..., i : i + 1] * (b_odd2 if i % 2 else b)  # [..., 10]
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(i, NLIMBS - 1 - i)])
    return _fold_cols(cols)


def fe_sq(a: jnp.ndarray) -> jnp.ndarray:
    """Specialized squaring: 55 limb products instead of 100 (diagonal
    once, cross terms doubled) on top of the odd-odd radix doubling.
    Contract: |a| <= 2^26.9 (worst coefficient sum 534) — reduced inputs
    only; use fe_mul(a, a) for wider operands."""
    shape = a.shape[:-1]
    nd = len(shape)
    a2 = a + a
    a2_odd2 = a2 * jnp.asarray(_DBL_ODD)  # cross terms x2, odd lanes x2 again
    cols = jnp.zeros(shape + (2 * NLIMBS - 1,), dtype=jnp.int64)
    for i in range(NLIMBS):
        # row i: coeff(i,i) * a_i^2 at column 2i, then coeff 2*c(i,j) *
        # a_i*a_j (j > i) at i+j; c(i,j) = 2 iff i and j both odd
        if i % 2:
            row = jnp.concatenate(
                [a2[..., i : i + 1], a2_odd2[..., i + 1 :]], axis=-1
            )
        else:
            row = jnp.concatenate(
                [a[..., i : i + 1], a2[..., i + 1 :]], axis=-1
            )
        term = a[..., i : i + 1] * row  # [..., NLIMBS - i]
        cols = cols + jnp.pad(term, [(0, 0)] * nd + [(2 * i, NLIMBS - 1 - i)])
    return _fold_cols(cols)


def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a + b


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (mod p), non-negative limbs; b must be reduced."""
    return a + jnp.asarray(_2P) - b


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    """-a (mod p); valid for limbs <= 4p limb-wise (~2^28).  Output is
    ~2^28 — callers re-carry (pt_neg does)."""
    return jnp.asarray(_4P) - a


def fe_pow2k(a: jnp.ndarray, k: int) -> jnp.ndarray:
    """a^(2^k) by repeated squaring (sequential; k is static)."""
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
    """Sequential full ripple with per-limb widths: limbs strictly
    in-width afterwards (plus one 19-fold re-entry into limbs 0/1).
    Only used by fe_canonical."""
    outs = []
    carry = jnp.zeros(c.shape[:-1], dtype=jnp.int64)
    for i in range(NLIMBS):
        v = c[..., i] + carry
        carry = v >> LIMB_WIDTHS[i]
        outs.append(v & _MASKS[i])
    c0 = outs[0] + 19 * carry
    c1 = outs[1] + (c0 >> LIMB_WIDTHS[0])
    outs[0] = c0 & _MASKS[0]
    outs[1] = c1
    return jnp.stack(outs, axis=-1)


def fe_canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Freeze to the canonical representative in [0, p).  Contract:
    non-negative limbs < 2^57 (every call site is a carry/mul output or
    a raw unpack) — 3 exact ripple passes converge to proper limbs and
    value < 2^255 + eps, then one branchless conditional subtract."""
    a = _fe_carry_exact(_fe_carry_exact(_fe_carry_exact(a)))
    borrow = jnp.zeros(a.shape[:-1], dtype=jnp.int64)
    outs = []
    for i in range(NLIMBS):
        v = a[..., i] - int(P_LIMBS[i]) - borrow
        borrow = (v < 0).astype(jnp.int64)
        outs.append(v + (borrow << LIMB_WIDTHS[i]))
    sub = jnp.stack(outs, axis=-1)
    keep = (borrow == 1)[..., None]  # underflow => a < p => keep a
    return jnp.where(keep, a, sub)


def fe_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Canonical equality; returns bool [...]."""
    return jnp.all(fe_canonical(a) == fe_canonical(b), axis=-1)


def fe_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_canonical(a) == 0, axis=-1)


# ---------------------------------------------------------------------------
# Point ops — extended coordinates (X, Y, Z, T), T = XY/Z
# ---------------------------------------------------------------------------

class Pt:
    """Plain struct of four [..., 10] limb arrays (pytree-registered)."""

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

    Bound ledger (R < 2^26.01 reduced, S = R + 2p < 2^27.59 sub output,
    A = 2R < 2^27.01 add output): the first subtrahend and f each get a
    rounds=2 partial carry so every product meets the pairwise 2^54.9
    contract — a: R*S, b: A*A = 2^54.02, and _add_tail's four."""
    a = fe_mul(fe_carry(fe_sub(p.y, p.x), rounds=2), fe_sub(q.y, q.x))
    b = fe_mul(fe_add(p.y, p.x), fe_add(q.y, q.x))
    c = fe_mul(fe_mul(p.t, q.t), jnp.asarray(D2_CONST))
    d = fe_mul(p.z, q.z)
    return _add_tail(a, b, c, fe_add(d, d), None)


def _add_tail(a, b, c, d2, neg) -> Pt:
    """The second half of every addition: E, F, G, H and the four
    products, from reduced a, b, c and d2 <= A.  `neg` (bool [...], or
    None) adds the NEGATED entry: its C term changes sign, which is F
    and G exchanged.

    Ledger: e = S, h = A; raw f = d2 + 2p - c < 2^28.01 always takes a
    rounds=2 carry (-> R); g = d2 + c < A + R = 2^27.6.
      * neg None (pt_add, the table build): e*f S*R, g*h (A+R)*A =
        2^54.61 (the in-tree worst), f*g R*(A+R), e*h S*A = 2^54.60.
      * neg given: whichever of the two lands in g meets h = A, and an
        uncarried f there would be 2^28.01 * 2^27.01 = 2^55.02, past
        the contract — so g takes the same carry BEFORE the exchange
        and both orders read e*f S*R, g*h R*A, f*g R*R, e*h S*A."""
    e = fe_sub(b, a)
    f = fe_carry(fe_sub(d2, c), rounds=2)
    g = fe_add(d2, c)
    h = fe_add(b, a)
    if neg is not None:
        g = fe_carry(g, rounds=2)
        m = neg[..., None]
        f, g = jnp.where(m, g, f), jnp.where(m, f, g)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def _add_entry(p: Pt, ypx, ymx, tc, d2, neg) -> Pt:
    """p ± a table entry whose addition-side terms are precomputed:
    (y+x, y-x), the coefficient `tc` of T1 and D2 = 2·Z1·Z2.  Negating
    the entry is free: its first two coordinates swap (and F, G in
    _add_tail).  The entry's coordinates are REDUCED (canonical host
    constants, or pt_to_cached outputs), so the p side needs no carry,
    swapped or not: a = S*R = 2^53.60, b = A*R = 2^53.02, c = R*R."""
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
    and the ·2d — 7 multiplications.  neg: bool [...] or None (= add).
    d2 = 2·Z1 = A, as _add_tail wants."""
    ypx, ymx, xy2d = niels
    return _add_entry(p, ypx, ymx, xy2d, fe_add(p.z, p.z), neg)


def pt_to_cached(p: Pt):
    """(Y+X, Y-X, Z, 2d·T), every coordinate reduced (the sum A and the
    difference S each take a rounds=2 carry here, once per table entry,
    so that pt_add_cached needs none on the entry's side)."""
    return (fe_carry(fe_add(p.y, p.x), rounds=2),
            fe_carry(fe_sub(p.y, p.x), rounds=2),
            p.z, fe_mul(p.t, jnp.asarray(D2_CONST)))


def pt_add_cached(p: Pt, cached, neg=None) -> Pt:
    """p ± a point in cached form (pt_to_cached): 8 multiplications.
    d = Z1*Z2 is R*R, d2 = A."""
    ypx, ymx, z, t2d = cached
    d = fe_mul(p.z, z)
    return _add_entry(p, ypx, ymx, t2d, fe_add(d, d), neg)


def pt_dbl(p: Pt) -> Pt:
    """Dedicated doubling (dbl-2008-hwcd for a=-1), complete for every
    curve point.  (x+y)^2 routes through fe_mul (operand 2^27.01 >
    fe_sq's reduced-only ceiling); e and f get rounds=2 partial carries
    (raw e = h + 2p - (x+y)^2 < 2^28.01 would push e*h past the pairwise
    contract).  Worst product: g*h = 2^27.59 * 2^27.01 = 2^54.61."""
    a = fe_sq(p.x)
    b = fe_sq(p.y)
    c = fe_sq(p.z)
    c = fe_add(c, c)
    h = fe_add(a, b)
    xy = fe_add(p.x, p.y)
    e = fe_carry(fe_sub(h, fe_mul(xy, xy)), rounds=2)
    g = fe_sub(a, b)
    f = fe_carry(fe_add(c, g), rounds=2)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_double(p: Pt) -> Pt:
    return pt_dbl(p)


def pt_dbl_n(p: Pt, k: int) -> Pt:
    """k chained doublings with the T coordinate computed only on the
    last (see fe25519.pt_dbl_n — trace-size win; XLA DCEs the dead muls
    either way).  Every intermediate re-enters the loop reduced (fe_mul
    outputs), so the chain is bound-safe for any k."""
    assert k >= 1
    x, y, z = p.x, p.y, p.z
    for i in range(k):
        a = fe_sq(x)
        b = fe_sq(y)
        c = fe_sq(z)
        c = fe_add(c, c)
        h = fe_add(a, b)
        xy = fe_add(x, y)
        e = fe_carry(fe_sub(h, fe_mul(xy, xy)), rounds=2)
        g = fe_sub(a, b)
        f = fe_carry(fe_add(c, g), rounds=2)
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

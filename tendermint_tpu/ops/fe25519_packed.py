"""GF(2^255-19) field and edwards25519 point arithmetic: 10 limbs at the
mixed radix 25.5, stored as uint32 with the LIMB AXIS LEADING.

The same mathematics as the int64 backend (`fe25519.py`, 15 limbs x 17
bits) in the ref10 / curve25519-donna-32 layout, vectorized over the
batch: **10 limbs of alternating 26/25-bit widths** — 100 limb products
a multiplication instead of 225, 19 product columns instead of 29, a
10-wide carry instead of a 15-wide one.

Representation (PR 31; what an accelerator without 64-bit integers and
with two tiled minor axes can work on):
  * a field element is `uint32[NLIMBS, *batch]`: limb i of every row of
    the batch is one contiguous plane.  The verify program gives the
    batch two axes, `[NLIMBS, N/8, 8]` (batch_in / batch_out), so a slice,
    a pad or a shift along the limb axis — row i of the schoolbook
    product, the columns' offsets, the carry's move by one limb — moves
    whole tiles and never crosses a tiled (minor) axis.  With the limb
    axis minor, 10 limbs padded to 16 sublanes and every such step was a
    misaligned shuffle.
  * limbs are 26 bits wide, so they are STORED in 32: a 64-bit integer
    is two 32-bit ones on the chip, every add an add, a compare, a
    select and an add.  **uint64 exists only in the 19 product columns**
    of fe_mul / fe_sq: the operands are zero-extended there (the
    compiler sees high words of zero and drops the cross terms of the
    64 x 64 product), and a column returns to uint32 in the second carry
    round, as soon as its bound allows.
  * host constants (ONE, D_CONST, table entries, ...) are limb VECTORS
    `[NLIMBS]` (limbs_from_int); fe_const places one over a batch.

Mixed radix 25.5: limb i has weight 2^ceil(25.5 i) —
weights (0, 26, 51, 77, 102, 128, 153, 179, 204, 230) and widths
(26, 25, 26, 25, ...).  10 * 25.5 = 255 exactly, so the wrap at 2^255
folds with a bare multiply-by-19, like the sibling layout.  The one
wrinkle: a product a_i*b_j with i and j BOTH odd has weight
w_i + w_j = w_{i+j} + 1 and enters column i+j doubled (the classic ref10
"2*" coefficients); with that correction every contribution to column k
has uniform weight w_k and the 19-fold at column 10 is exact
(w_k - 255 = w_{k-10} for every k >= 10).

Bound ledger (every value is unsigned; R = reduced bound):
  * "reduced" limbs (post-carry invariant): even limbs < 2^26 + 64,
    odd limbs < 2^25 + 64; call the worst R < 2^26.01.
  * uint32 storage never wraps: fe_add of two reduced < 2^27.01 (A);
    fe_sub adds 2p in limb form (even limbs ~2^27) and needs a REDUCED
    subtrahend, so no limb goes below zero: output < R + 2^27 < 2^27.59
    (S) from a reduced minuend, < A + 2^27 = 2^28.01 from a sum; fe_neg
    is 4p - a, valid for a <= 4p limb-wise, output < 2^28.01 (callers
    re-carry; see pt_neg).  The widest value a function may RETURN is
    2^28.01; inside fe_mul the odd-doubled operand is < 2^29.01 and
    inside fe_sq the twice-doubled one < 2^28.9.  fe_carry of ANY uint32
    element is reduced after rounds=2 (2^32 -> 2^26 + 19*2^7 -> reduced).
  * fe_mul PAIRWISE operand contract (not a single input ceiling):
    max(a_i) * max(b_j) <= 2^54.9.  Column coefficient sums
    C_j = sum(pairs at j) + 19*sum(pairs at j+10) with the odd-odd
    doubling counted are maximal at j=0: C_0 = 1 + 19*14 = 267 < 2^8.07,
    so the worst column is < 267 * 2^54.9 < 2^63 — one bit under what
    uint64 holds.  Worst in-tree product (pt_add/pt_dbl g*h):
    2^27.59 * 2^27.01 = 2^54.61 — 1.25x margin.  Enforced empirically
    at the bound by tests/test_fe25519_packed.py.
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
  * fe_sq operand contract: a <= 2^26.9 (cross terms doubled AGAIN on
    top of the odd-odd doubling: worst coefficient sum 534, and
    534 * 2^53.8 < 2^63) — i.e. reduced inputs only; wider operands
    route through fe_mul(a, a) (pt_add/pt_dbl do, for the (x+y)^2 term).
  * the carry of the columns (fe_carry of a uint64 array, rounds=3):
    each round maps max limb C -> 2^26 + 19*C/2^25, so 2^63 -> 2^42.3 ->
    2^26.07 -> reduced.  Round 1 runs in uint64; in round 2 the overflow
    is < 2^17.3 and the remainder < 2^26, so both are narrowed BEFORE
    they are added; round 3 is uint32.  rounds=2 is sound for columns
    <= 2^44 and for any uint32 element: the cheap point-op partial carry.

The point formulas are the unified a=-1 extended-coordinate set shared
with the sibling (complete for all curve points, ZIP-215 included); the
only deltas are rounds=2 partial carries where the tighter headroom
(25.5+1.5 bits vs 17+3) demands them — in pt_add the first subtrahend and
the f term, in pt_madd/pt_add_cached f and g (never the subtrahend), two
in pt_dbl (e and f), two in pt_to_cached.

Parity target: identical to fe25519.py — the reference's ed25519consensus
verify semantics (crypto/ed25519/ed25519.go:149-156), ZIP-215 rules,
differentially tested against tendermint_tpu.crypto.ed25519 and, limb
for value, against fe25519.py.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as _ref

NLIMBS = 10
LIMB_AXIS = 0  # an element is uint32[NLIMBS, *batch]
# limb i holds bits [WEIGHTS[i], WEIGHTS[i] + WIDTHS[i]) of the 255-bit value
LIMB_WIDTHS = tuple(26 - (i % 2) for i in range(NLIMBS))
LIMB_WEIGHTS = tuple((51 * i + 1) // 2 for i in range(NLIMBS))  # ceil(25.5 i)
_MASKS = tuple((1 << w) - 1 for w in LIMB_WIDTHS)
# odd-limb doubling vector for the mixed-radix product correction
_DBL_ODD = tuple(1 + (i % 2) for i in range(NLIMBS))

_U32, _U64 = jnp.uint32, jnp.uint64

P = _ref.P


def limbs_from_int(v: int) -> np.ndarray:
    """The limb VECTOR [NLIMBS] of v < 2^255 (a host constant; fe_const
    places it over a batch)."""
    return np.array(
        [(v >> LIMB_WEIGHTS[i]) & _MASKS[i] for i in range(NLIMBS)],
        dtype=np.uint32,
    )


def int_from_limbs(a) -> int:
    a = np.asarray(a)
    return sum(int(a[i]) << LIMB_WEIGHTS[i] for i in range(NLIMBS))


def _vec(v, ndim: int, dtype=_U32) -> jnp.ndarray:
    """A per-limb constant shaped to broadcast against an element (or
    its columns) of `ndim` axes."""
    return jnp.asarray(np.asarray(v, dtype=np.uint64), dtype=dtype).reshape(
        (-1,) + (1,) * (ndim - 1))


def fe_const(limbs, batch_shape=()) -> jnp.ndarray:
    """A limb vector [NLIMBS] (host constant or traced) as an element
    over `batch_shape`."""
    v = jnp.asarray(limbs, dtype=_U32).reshape((NLIMBS,) + (1,) * len(batch_shape))
    return jnp.broadcast_to(v, (NLIMBS,) + tuple(batch_shape))


def fe_select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """mask ? a : b, mask a bool of the batch's shape."""
    return jnp.where(mask[None], a, b)


def fe_parity(a: jnp.ndarray) -> jnp.ndarray:
    """The low bit of a CANONICAL element, int32 of the batch's shape."""
    return (a[0] & 1).astype(jnp.int32)


def batch_in(rows: jnp.ndarray) -> jnp.ndarray:
    """verify_core's input rows [N, ...] in this module's batch shape
    [N/8, 8, ...] (every rung is a multiple of 8): consecutive rows stay
    together, so a shard of the rows is a shard of the leading axis."""
    return rows.reshape((rows.shape[0] // 8, 8) + rows.shape[1:])


def batch_out(verdicts: jnp.ndarray) -> jnp.ndarray:
    return verdicts.reshape(-1)


def limbs_of_bits(bits255: jnp.ndarray) -> jnp.ndarray:
    """[..., 255] LE bits -> element [NLIMBS, ...], on device (widths
    differ per limb, so each limb is its own slice-and-weigh)."""
    outs = []
    for i in range(NLIMBS):
        lo = LIMB_WEIGHTS[i]
        w = LIMB_WIDTHS[i]
        seg = bits255[..., lo : lo + w].astype(_U32)
        weights = jnp.asarray(1 << np.arange(w, dtype=np.uint32))
        outs.append((seg * weights).sum(-1, dtype=_U32))
    return jnp.stack(outs, axis=0)


# ---------------------------------------------------------------------------
# Constants (limb vectors)
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
# Field ops  (all take/return uint32[NLIMBS, *batch])
# ---------------------------------------------------------------------------

def fe_carry(c: jnp.ndarray, rounds: int = 3) -> jnp.ndarray:
    """Carry-propagate to reduced form (even < 2^26+64, odd < 2^25+64)
    by vectorized relaxation with PER-LIMB widths: each round moves
    every limb's overflow one limb up simultaneously (the 2^255-weight
    top overflow re-enters limb 0 as x19).  Each round maps max limb
    C -> 2^26 + 19*C/2^25.

    c is a uint32 element (rounds=2 reduces any) or uint64 product
    columns [NLIMBS, ...] (rounds=3 reduces any below 2^63: -> 2^42.3 ->
    2^26.07 -> reduced; rounds=2 is sound up to 2^44).  Columns come
    back as uint32: in their second round the overflow (< 2^17.3) and
    the remainder (< 2^26) are narrowed before they meet.  Verified at
    the bounds in tests/test_fe25519_packed.py."""
    wide = c.dtype == _U64
    assert rounds >= 2 or not wide
    for r in range(rounds):
        hi = c >> _vec(LIMB_WIDTHS, c.ndim, c.dtype)
        lo = c & _vec(_MASKS, c.ndim, c.dtype)
        if wide and r == 1:
            hi, lo = hi.astype(_U32), lo.astype(_U32)
        # one limb up: a pad that drops the top limb, which re-enters at
        # limb 0 as x19 (two pads, no slice of nine limbs to copy)
        rest = [(0, 0, 0)] * (c.ndim - 1)
        zero = jnp.zeros((), hi.dtype)
        c = (lo + lax.pad(hi, zero, [(1, -1, 0)] + rest)
             + lax.pad(19 * hi[-1:], zero, [(0, NLIMBS - 1, 0)] + rest))
    return c


def _fold_cols(cols: jnp.ndarray) -> jnp.ndarray:
    """Fold product columns uint64[19, ...] at the 2^255 wrap (x19) and
    carry back to a uint32 element.

    The fold is weight-exact in this radix: column k >= 10 has weight
    w_k = 255 + w_{k-10} (the odd-odd doubling already normalized every
    contribution to its column's weight), so hi folds into lo with a
    bare x19.  Post-fold column bound: C_0 = 267 coefficient units x the
    pairwise product contract 2^54.9 < 2^63."""
    lo = cols[:NLIMBS]
    hi = cols[NLIMBS:]
    pad = [(0, 1)] + [(0, 0)] * (cols.ndim - 1)
    return fe_carry(lo + jnp.pad(19 * hi, pad), rounds=3)


def _pad_cols(term: jnp.ndarray, lo: int) -> jnp.ndarray:
    """A row of products placed at columns lo.. of the 19."""
    hi = 2 * NLIMBS - 1 - lo - term.shape[0]
    return jnp.pad(term, [(lo, hi)] + [(0, 0)] * (term.ndim - 1))


def fe_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product (100 limb products, mixed-radix doubling on
    odd-odd pairs) + 19-fold + carry.  Contract: max(a_i) * max(b_j)
    <= 2^54.9 (pairwise; see module header for every in-tree site).
    The doubling is applied in uint32 (b < 2^28.01), the products are
    32 x 32 -> 64."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    b_odd2 = (b * _vec(_DBL_ODD, b.ndim)).astype(_U64)  # for odd-i rows
    a, b = a.astype(_U64), b.astype(_U64)
    cols = _pad_cols(a[0:1] * b, 0)
    for i in range(1, NLIMBS):
        cols = cols + _pad_cols(a[i : i + 1] * (b_odd2 if i % 2 else b), i)
    return _fold_cols(cols)


def fe_sq(a: jnp.ndarray) -> jnp.ndarray:
    """Specialized squaring: 55 limb products instead of 100 — the
    diagonal as ONE product of ten (a_i^2, doubled at odd i, interleaved
    into the even columns) and the 45 cross terms as nine rows, each a
    plain slice of the doubled operand (no per-row concatenation).
    Contract: a <= 2^26.9 (worst coefficient sum 534) — reduced inputs
    only; use fe_mul(a, a) for wider operands."""
    a2 = a + a
    a2_odd2 = (a2 * _vec(_DBL_ODD, a.ndim)).astype(_U64)  # cross x2, odd x2 again
    a_odd2 = (a * _vec(_DBL_ODD, a.ndim)).astype(_U64)
    a, a2 = a.astype(_U64), a2.astype(_U64)
    diag = a * a_odd2  # a_i^2 * c(i,i): belongs at column 2i
    cols = jnp.stack([diag, jnp.zeros_like(diag)], axis=1).reshape(
        (2 * NLIMBS,) + diag.shape[1:])[: 2 * NLIMBS - 1]
    for i in range(NLIMBS - 1):
        # 2*c(i,j) * a_i*a_j for j > i, at columns 2i+1 .. i+9;
        # c(i,j) = 2 iff i and j both odd
        row = (a2_odd2 if i % 2 else a2)[i + 1 :]
        cols = cols + _pad_cols(a[i : i + 1] * row, 2 * i + 1)
    return _fold_cols(cols)


def fe_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a + b


def fe_sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (mod p); b must be reduced (b <= 2p limb-wise keeps every
    limb of a + 2p - b non-negative)."""
    return a + _vec(_2P, a.ndim) - b


def fe_neg(a: jnp.ndarray) -> jnp.ndarray:
    """-a (mod p); valid for limbs <= 4p limb-wise (~2^28).  Output is
    ~2^28 — callers re-carry (pt_neg does)."""
    return _vec(_4P, a.ndim) - a


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
    carry = jnp.zeros(c.shape[1:], dtype=_U32)
    for i in range(NLIMBS):
        v = c[i] + carry
        carry = v >> LIMB_WIDTHS[i]
        outs.append(v & _MASKS[i])
    c0 = outs[0] + 19 * carry
    c1 = outs[1] + (c0 >> LIMB_WIDTHS[0])
    outs[0] = c0 & _MASKS[0]
    outs[1] = c1
    return jnp.stack(outs, axis=0)


def fe_canonical(a: jnp.ndarray) -> jnp.ndarray:
    """Freeze to the canonical representative in [0, p).  Contract:
    limbs < 2^31 (every call site is a carry/mul output or a raw unpack;
    a limb plus its neighbour's carry must not wrap) — 3 exact ripple
    passes converge to proper limbs and value < 2^255 + eps, then one
    branchless conditional subtract (in int32: proper limbs are < 2^26)."""
    a = _fe_carry_exact(_fe_carry_exact(_fe_carry_exact(a)))
    s = a.astype(jnp.int32)
    borrow = jnp.zeros(a.shape[1:], dtype=jnp.int32)
    outs = []
    for i in range(NLIMBS):
        v = s[i] - int(P_LIMBS[i]) - borrow
        borrow = (v < 0).astype(jnp.int32)
        outs.append(v + (borrow << LIMB_WIDTHS[i]))
    sub = jnp.stack(outs, axis=0).astype(_U32)
    return fe_select(borrow == 1, a, sub)  # underflow => a < p => keep a


def fe_eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Canonical equality; returns bool of the batch's shape."""
    return jnp.all(fe_canonical(a) == fe_canonical(b), axis=0)


def fe_is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(fe_canonical(a) == 0, axis=0)


# ---------------------------------------------------------------------------
# Point ops — extended coordinates (X, Y, Z, T), T = XY/Z
# ---------------------------------------------------------------------------

class Pt:
    """Plain struct of four elements (pytree-registered)."""

    __slots__ = ("x", "y", "z", "t")

    def __init__(self, x, y, z, t):
        self.x, self.y, self.z, self.t = x, y, z, t

    def astuple(self):
        return (self.x, self.y, self.z, self.t)


def pt_identity(shape=()) -> Pt:
    zero, one = fe_const(ZERO, shape), fe_const(ONE, shape)
    return Pt(zero, one, one, zero)


def pt_add(p: Pt, q: Pt) -> Pt:
    """Unified, complete a=-1 extended addition (add-2008-hwcd-3 shape).

    Bound ledger (R < 2^26.01 reduced, S = R + 2p < 2^27.59 sub output,
    A = 2R < 2^27.01 add output): the first subtrahend and f each get a
    rounds=2 partial carry so every product meets the pairwise 2^54.9
    contract — a: R*S, b: A*A = 2^54.02, and _add_tail's four."""
    a = fe_mul(fe_carry(fe_sub(p.y, p.x), rounds=2), fe_sub(q.y, q.x))
    b = fe_mul(fe_add(p.y, p.x), fe_add(q.y, q.x))
    c = fe_mul(fe_mul(p.t, q.t), _vec(D2_CONST, p.t.ndim))
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
        f, g = fe_select(neg, g, f), fe_select(neg, f, g)
    return Pt(fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def _add_entry(p: Pt, ypx, ymx, tc, d2, neg) -> Pt:
    """p ± a table entry whose addition-side terms are precomputed:
    (y+x, y-x), the coefficient `tc` of T1 and D2 = 2·Z1·Z2.  Negating
    the entry is free: its first two coordinates swap (and F, G in
    _add_tail).  The entry's coordinates are REDUCED (canonical host
    constants, or pt_to_cached outputs), so the p side needs no carry,
    swapped or not: a = S*R = 2^53.60, b = A*R = 2^53.02, c = R*R."""
    if neg is not None:
        ypx, ymx = fe_select(neg, ymx, ypx), fe_select(neg, ypx, ymx)
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
            p.z, fe_mul(p.t, _vec(D2_CONST, p.t.ndim)))


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
    m = bit.astype(bool)
    return Pt(*(fe_select(m, c1, c0)
                for c1, c0 in zip(p1.astuple(), p0.astuple())))


def pt_is_identity(p: Pt) -> jnp.ndarray:
    """X == 0 and Y == Z (projective identity test)."""
    return fe_is_zero(p.x) & fe_eq(p.y, p.z)


jax.tree_util.register_pytree_node(
    Pt, lambda p: (p.astuple(), None), lambda _aux, ch: Pt(*ch)
)

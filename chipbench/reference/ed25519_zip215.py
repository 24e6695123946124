"""Plain ZIP-215 Ed25519 verification on Python integers.

The semantics every row of every cell is held to (https://zips.z.cash/zip-0215):
  1. s is canonical, 0 <= s < L;
  2. A and R decode permissively (y taken mod p, small order accepted,
     x = 0 with the sign bit set accepted as 0);
  3. the cofactored equation [8][s]B == [8]R + [8][k]A,
     k = SHA-512(R || A || M) mod L.

Copied in substance from the program's own reference
(tendermint_tpu/crypto/ed25519.py) so that no later PR can move it; every
multiplication in GF(2^255 - 19) goes through `_mul` (and `_pow_count`
for the one exponentiation of a decompression), which is what
`count_field_muls` counts for the roofline's FIELD_MULS_PER_SIG.
"""

from __future__ import annotations

import hashlib

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493

# multiplications (squarings included) in GF(p) since the last reset; a
# plain module counter: the reference runs single-threaded after the window
_MULS = 0


def _mul(a: int, b: int) -> int:
    global _MULS
    _MULS += 1
    return a * b % P


def _pow(a: int, e: int) -> int:
    """a^e mod p, counted as plain square-and-multiply: one squaring per
    bit after the first, one multiplication per further set bit."""
    global _MULS
    _MULS += (e.bit_length() - 1) + (bin(e).count("1") - 1)
    return pow(a, e, P)


D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
_BY = (4 * pow(5, P - 2, P)) % P


def _recover_x(y: int) -> int | None:
    """x with x^2 = (y^2 - 1)/(d y^2 + 1): the principal root, or None."""
    yy = _mul(y, y)
    u = (yy - 1) % P
    v = (_mul(D, yy) + 1) % P
    v3 = _mul(_mul(v, v), v)
    v7 = _mul(_mul(v3, v3), v)
    x = _mul(_mul(u, v3), _pow(_mul(u, v7), (P - 5) // 8))
    vx2 = _mul(_mul(v, x), x)
    if vx2 == u:
        return x
    if vx2 == (-u) % P:
        return _mul(x, SQRT_M1)
    return None


_BX = _recover_x(_BY)
if _BX & 1:
    _BX = P - _BX

Point = tuple[int, int, int, int]  # extended coordinates (X, Y, Z, T)
IDENTITY: Point = (0, 1, 1, 0)
BASE: Point = (_BX, _BY, 1, _BX * _BY % P)


def pt_add(p: Point, q: Point) -> Point:
    """Unified addition on the a = -1 twisted Edwards curve (complete)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = _mul(y1 - x1, y2 - x2)
    b = _mul(y1 + x1, y2 + x2)
    c = _mul(_mul(2 * t1 % P, t2), D)
    dd = _mul(2 * z1 % P, z2)
    e, f, g, h = b - a, dd - c, dd + c, b + a
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def pt_neg(p: Point) -> Point:
    x, y, z, t = p
    return ((-x) % P, y, z, (-t) % P)


def pt_equal(p: Point, q: Point) -> bool:
    x1, y1, z1, _ = p
    x2, y2, z2, _ = q
    return ((_mul(x1, z2) - _mul(x2, z1)) % P == 0
            and (_mul(y1, z2) - _mul(y2, z1)) % P == 0)


def scalar_mult(k: int, p: Point) -> Point:
    """Double-and-add, most significant bit first."""
    acc = IDENTITY
    for i in reversed(range(k.bit_length())):
        acc = pt_add(acc, acc)
        if (k >> i) & 1:
            acc = pt_add(acc, p)
    return acc


def decode_point(b: bytes) -> Point | None:
    """Permissive ZIP-215 decompression; None if not on the curve."""
    if len(b) != 32:
        return None
    full = int.from_bytes(b, "little")
    sign = full >> 255
    y = (full & ((1 << 255) - 1)) % P
    x = _recover_x(y)
    if x is None:
        return None
    if (x & 1) != sign:
        x = P - x if x != 0 else 0
    return (x, y, 1, _mul(x, y))


def compute_k(r_bytes: bytes, pub: bytes, msg: bytes) -> int:
    return int.from_bytes(hashlib.sha512(r_bytes + pub + msg).digest(),
                          "little") % L


def verify(pub: bytes, msg: bytes, sig: bytes, *, cofactored: bool = True) -> bool:
    """ZIP-215 verdict for one signature.  `cofactored=False` drops the
    multiplication by 8 — rule 3 broken, kept only so that tests can show
    which rows tell the two apart (the control of a run uses OpenSSL's
    strict verifier, see chipbench/control.py)."""
    if len(sig) != 64 or len(pub) != 32:
        return False
    r_bytes, s = sig[:32], int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    a_pt, r_pt = decode_point(pub), decode_point(r_bytes)
    if a_pt is None or r_pt is None:
        return False
    k = compute_k(r_bytes, pub, msg)
    q = pt_add(scalar_mult(s, BASE),
               pt_add(pt_neg(scalar_mult(k, a_pt)), pt_neg(r_pt)))
    if cofactored:
        for _ in range(3):
            q = pt_add(q, q)
    return pt_equal(q, IDENTITY)


def count_field_muls(pub: bytes, msg: bytes, sig: bytes) -> int:
    """Field multiplications the reference spends on one verification."""
    global _MULS
    _MULS = 0
    verify(pub, msg, sig)
    return _MULS


# -- small-order / non-canonical encodings (the rows only ZIP-215 accepts) --

def eight_torsion_points() -> list[Point]:
    """The 8-torsion subgroup: [L] of a point outside the prime-order
    subgroup generates it."""
    y = 2
    while True:
        x = _recover_x(y)
        if x is not None:
            t = scalar_mult(L, (x, y, 1, x * y % P))
            pts, cur = [], t
            for _ in range(8):
                if not any(pt_equal(cur, q) for q in pts):
                    pts.append(cur)
                cur = pt_add(cur, t)
            if len(pts) == 8:
                return pts
        y += 1


def encodings(p: Point) -> list[bytes]:
    """Every 32-byte string ZIP-215 decodes to `p`: the canonical one, the
    flipped sign bit where x = 0, and y + p where that fits in 255 bits."""
    x, y, z, _ = p
    zi = pow(z, P - 2, P)
    x, y = x * zi % P, y * zi % P
    out = []
    for sign in (0, 1):
        if sign != (x & 1) and x != 0:
            continue
        for yy in ([y, y + P] if y + P < (1 << 255) else [y]):
            out.append((yy | (sign << 255)).to_bytes(32, "little"))
    return out


def small_order_encodings() -> list[bytes]:
    """All encodings of all eight torsion points, in a fixed order."""
    return sorted({e for pt in eight_torsion_points() for e in encodings(pt)})

"""The one home of kernel cases, for either field backend.  The ZIP-215
adversarial gauntlet (GAUNTLET: named, the same bytes on every run) with
its verdicts on the floor rung, and the mixed-validity batch of eight.
Checks of the scalar multiplications' building blocks: the signed
radix-16 recoding, the precomputed-form additions (pt_madd,
pt_add_cached, pt_to_cached), the two scalar multiplications, and the
static count of field operations — each takes the impl's name and
compares with tendermint_tpu.crypto.ed25519's big integers (run from
tests/test_ed25519_jax.py, parametrised over the impls) — and the
additions at the bounds of a backend's operand contract (run from each
backend's own file with its own patterns).  Not a test file."""

import functools
import hashlib
import random

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.ops import ed25519_jax as dev

P = ref.P


# ---------------------------------------------------------------------------
# End-to-end cases
# ---------------------------------------------------------------------------

def _gauntlet():
    """{name: (pub, msg, sig)} covering the honest, tampered and
    adversarial space ZIP-215 defines: keys from fixed seeds and garbage
    from SHA-256 of the case's name, so a failure replays."""
    cases = {}
    for i in range(6):
        k = priv_key_from_seed(bytes([i + 81]) * 32)
        msg = b"height=%d" % i
        cases[f"honest-{i}"] = (k.pub_key().bytes_(), msg, k.sign(msg))
    pub, msg, sig = cases["honest-0"]
    s_plus_l = int.from_bytes(sig[32:], "little") + ref.L
    cases.update({
        "tampered-sig": (pub, msg, sig[:-1] + bytes([sig[-1] ^ 1])),
        "wrong-msg": (pub, b"other", sig),
        "s-plus-L": (pub, msg, sig[:32] + s_plus_l.to_bytes(32, "little")),
        "s-above-L": (pub, msg,
                      sig[:32] + (ref.L + 12345).to_bytes(32, "little")),
        # y = 2 has no square root
        "off-curve-A": ((2).to_bytes(32, "little"), msg, sig),
        "off-curve-R": (pub, msg, (2).to_bytes(32, "little") + sig[32:]),
    })
    # small-order A and R with s = 0: valid under cofactored ZIP-215
    for t, pt in enumerate(ref.eight_torsion_points()[:4]):
        for e, enc in enumerate(ref.noncanonical_encodings(pt)):
            cases[f"small-order-A-R-noncanonical-{t}.{e}"] = (
                enc, b"any", enc + bytes(32))
    cases.update({
        "identity-A": (ref.encode_point(ref.IDENTITY), msg, sig),
        "short-pub": (pub[:31], msg, sig),
        "short-sig": (pub, msg, sig[:63]),
    })
    for i in range(4):
        name = f"garbage-{i}"
        h = [hashlib.sha256(b"%s/%d" % (name.encode(), j)).digest()
             for j in range(4)]
        cases[name] = (h[0], h[1][:8], h[2] + h[3])
    return cases


GAUNTLET = _gauntlet()


def reference_verdict(pub, msg, sig) -> bool:
    """crypto/ed25519.verify; a row of the wrong length is False."""
    return len(pub) == 32 and len(sig) == 64 and ref.verify(pub, msg, sig)


@functools.cache
def gauntlet_verdicts(impl) -> dict:
    """{name: the device verdict}, the cases verified in batches of
    eight: the floor rung, whose program the golden tests keep warm for
    both impls, so the gauntlet compiles nothing new.  Once a process
    and impl."""
    names, out = list(GAUNTLET), {}
    for i in range(0, len(names), 8):
        pubs, msgs, sigs = zip(*(GAUNTLET[n] for n in names[i:i + 8]))
        got = dev.verify_batch(list(pubs), list(msgs), list(sigs), impl=impl)
        out.update(zip(names[i:i + 8], (bool(v) for v in got)))
    return out


def batch8():
    """(pubs, msgs, sigs, want): 8 deterministic signatures, mixed
    validity (3 corruption modes)."""
    pubs, msgs, sigs, want = [], [], [], []
    for i in range(8):
        k = priv_key_from_seed(bytes([i + 61]) * 32)
        m = b"packed-e2e-%d" % i
        s = k.sign(m)
        ok = True
        if i == 2:  # corrupted signature byte
            s = s[:-1] + bytes([s[-1] ^ 1])
            ok = False
        elif i == 4:  # wrong message
            m = b"packed-e2e-other"
            ok = False
        elif i == 6:  # non-canonical s (>= L)
            s_int = int.from_bytes(s[32:], "little") + ref.L
            s = s[:32] + s_int.to_bytes(32, "little")
            ok = False
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(s)
        want.append(ok)
    return pubs, msgs, sigs, want


# ---------------------------------------------------------------------------
# Building blocks of the scalar multiplications
# ---------------------------------------------------------------------------

# scalars below 2^253 (what s and k are): the ends of the range, the
# longest propagate chain (all-7 nibbles), the longest generate chain
# (all-8), and one 8 at the bottom carried through 62 sevens to the top
SCALARS = [
    0, 1, 7, 8, 0x80, dev.L - 1, (1 << 253) - 1,
    int("0" + "7" * 63, 16), int("1" + "7" * 63, 16),
    int("0" + "8" * 63, 16), int("1" + "8" * 63, 16),
    int("1" + "7" * 62 + "8", 16),
] + [random.Random(29).getrandbits(256) % dev.L for _ in range(4)]


def scalar_rows():
    return jnp.asarray(np.stack([
        np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8)
        for v in SCALARS]))


def affine(q):
    zi = pow(q[2], P - 2, P)
    return q[0] * zi % P, q[1] * zi % P


def points():
    """Random points, the eight small-order points (the identity among
    them) and a point with a torsion component."""
    rng = random.Random(17)
    rand = [ref.scalar_mult(rng.getrandbits(252), ref.BASE) for _ in range(3)]
    tors = ref.eight_torsion_points()
    return rand + tors + [ref.pt_add(rand[0], tors[3])]


# The two helpers every case goes through, whatever a backend's layout
# (fe.LIMB_AXIS: -1 for int64's [*batch, NLIMBS], 0 for packed's
# [NLIMBS, *batch]): `elems`, ints → a batch of elements, and `ints_of`,
# elements → ints; `stack_limbs` / `raw_ints` are the same two for limb
# patterns that are not reduced (the at-the-bound cases).

def stack_limbs(fe, vectors):
    """Limb vectors [NLIMBS] → one batch of elements, as they are (not
    reduced: the at-the-bound cases put any pattern in a limb)."""
    return jnp.asarray(np.stack(vectors, axis=0 if fe.LIMB_AXIS == -1 else -1))


def elems(fe, vals):
    """Python ints below 2^255 → one batch of elements."""
    return stack_limbs(fe, [fe.limbs_from_int(v) for v in vals])


def limb_rows(fe, elem):
    """A batch of elements → numpy [n, NLIMBS], the limbs as stored."""
    a = np.moveaxis(np.asarray(elem), fe.LIMB_AXIS, -1)
    return a.reshape(-1, fe.NLIMBS)


def raw_ints(fe, elem):
    """The value each element's limbs stand for, unreduced."""
    return [fe.int_from_limbs(row) for row in limb_rows(fe, elem)]


def ints_of(fe, elem):
    """Elements → their canonical values mod p."""
    return [v % P for v in raw_ints(fe, fe.fe_canonical(jnp.asarray(elem)))]


def to_dev(fe, pts, z=1):
    """Big-int points → one batched fe.Pt: (x·z, y·z, z, x·y·z), the
    affine representative for z = 1."""
    cols = [[], [], [], []]
    for q in pts:
        x, y = affine(q)
        for col, v in zip(cols, (x * z, y * z, z, x * y * z)):
            col.append(v % P)
    return fe.Pt(*(elems(fe, c) for c in cols))


def assert_points_equal(fe, got, want, what):
    """A batched device point against big-int points, projectively, and
    the extended invariant T·Z = X·Y."""
    xs, ys, zs, ts = (ints_of(fe, c) for c in got.astuple())
    for i, w in enumerate(want):
        x, y, z, t = xs[i], ys[i], zs[i], ts[i]
        assert z != 0, (what, i)
        assert (x * w[2] - w[0] * z) % P == 0, (what, i, "x")
        assert (y * w[2] - w[1] * z) % P == 0, (what, i, "y")
        assert (t * z - x * y) % P == 0, (what, i, "t")


def niels_of(fe, pts):
    cols = [[], [], []]
    for q in pts:
        x, y = affine(q)
        for col, v in zip(cols, ((y + x) % P, (y - x) % P,
                                 2 * ref.D * x * y % P)):
            col.append(v)
    return tuple(elems(fe, c) for c in cols)


def _pairs():
    pts = points()
    return [p for p in pts for _ in pts], [q for _ in pts for q in pts]


def check_signed_digits(impl):
    core = dev._core(impl)
    d = np.asarray(core._signed_digits(core._nibbles_of(scalar_rows())))
    assert d.shape == (len(SCALARS), 64)
    assert d.min() >= -8 and d.max() <= 7
    for row, v in zip(d, SCALARS):
        assert sum(int(x) << (4 * i) for i, x in enumerate(row)) == v, hex(v)
        assert 0 <= row[-1] <= 2  # what _scalarmul_var's start relies on


def check_pt_madd(impl, sign):
    """p ± q for every pair of `points()`, q as a Niels triple: the
    doubling case p = q, p = -q and every small-order point included."""
    fe = dev._field(impl)
    ps, qs = _pairs()
    neg = None if sign is None else jnp.full((len(ps),), sign < 0)
    got = fe.pt_madd(to_dev(fe, ps), niels_of(fe, qs), neg)
    want = [ref.pt_add(p, ref.pt_neg(q) if sign == -1 else q)
            for p, q in zip(ps, qs)]
    assert_points_equal(fe, got, want, ("pt_madd", impl, sign))


def check_pt_add_cached(impl, sign):
    """The same pairs, q through pt_to_cached from a representative
    with Z != 1."""
    fe = dev._field(impl)
    ps, qs = _pairs()
    neg = None if sign is None else jnp.full((len(ps),), sign < 0)
    cached = fe.pt_to_cached(to_dev(fe, qs, z=0x1234567 << 200))
    got = fe.pt_add_cached(to_dev(fe, ps), cached, neg)
    want = [ref.pt_add(p, ref.pt_neg(q) if sign == -1 else q)
            for p, q in zip(ps, qs)]
    assert_points_equal(fe, got, want, ("pt_add_cached", impl, sign))


def check_mixed_signs(impl):
    """One batch, alternating signs: the sign is per row."""
    fe = dev._field(impl)
    ps, qs = _pairs()
    neg = jnp.asarray([i % 2 == 1 for i in range(len(ps))])
    want = [ref.pt_add(p, ref.pt_neg(q) if i % 2 else q)
            for i, (p, q) in enumerate(zip(ps, qs))]
    got = fe.pt_madd(to_dev(fe, ps), niels_of(fe, qs), neg)
    assert_points_equal(fe, got, want, ("pt_madd mixed", impl))
    got = fe.pt_add_cached(to_dev(fe, ps), fe.pt_to_cached(to_dev(fe, qs)), neg)
    assert_points_equal(fe, got, want, ("pt_add_cached mixed", impl))


def check_scalarmul_base(impl):
    core = dev._core(impl)
    digits = core._signed_digits(core._nibbles_of(scalar_rows()))
    got = jax.jit(core._scalarmul_base)(digits)
    want = [ref.scalar_mult(v, ref.BASE) for v in SCALARS]
    assert_points_equal(core.fe, got, want, ("scalarmul_base", impl))


def check_scalarmul_var(impl):
    """[k]P for the same scalars, P cycling through `points()`: small
    order, the identity and a torsion component get [k]P, not [k mod L]P."""
    core = dev._core(impl)
    pts = (points() * 2)[:len(SCALARS)]
    digits = core._signed_digits(core._nibbles_of(scalar_rows()))
    got = jax.jit(core._scalarmul_var)(digits, to_dev(core.fe, pts))
    want = [ref.scalar_mult(v, p) for v, p in zip(SCALARS, pts)]
    assert_points_equal(core.fe, got, want, ("scalarmul_var", impl))


# ---------------------------------------------------------------------------
# Static count of field operations
# ---------------------------------------------------------------------------

# field operations a signature in verify_core: 2 x 275 (decompressions),
# 64 x 7 (base), 64 + 63 x 37 (var: table, loop), 40 (finish); 3,677 by
# the same count with unsigned digits and general additions (before PR 29)
OPS_PER_SIGNATURE = 3433


class OpCounter:
    """Counting stand-ins for a field module's fe_mul and fe_sq (a
    multiplication and a squaring count alike; the stand-in returns the
    sum of its operands: the right shape and dtype, which is all a count
    needs), and a fori_loop that traces its body once and records (trip
    count, operations a trip)."""

    def __init__(self, monkeypatch, fe):
        self.n = 0
        self.loops = []
        monkeypatch.setattr(fe, "fe_mul", self._counted)
        monkeypatch.setattr(fe, "fe_sq", self._counted)
        monkeypatch.setattr(lax, "fori_loop", self._fori_loop)

    def _counted(self, a, b=0):
        self.n += 1
        return a + b

    def _fori_loop(self, lo, hi, body, init):
        before = self.n
        out = body(lo, init)
        self.loops.append((hi - lo, self.n - before))
        self.n = before  # a loop's operations are counted by its record
        return out

    def total(self):
        return self.n + sum(trips * ops for trips, ops in self.loops)


def check_op_counts(impl, monkeypatch):
    """7 a mixed addition, 8 a cached one, at most 64 for the table of
    -A, window bodies of 7 (base) and 37 (var) in the traced verify_core:
    a later change that puts operations back fails here, not on a chip."""
    core = dev._core(impl)
    fe = core.fe
    cnt = OpCounter(monkeypatch, fe)
    pts = to_dev(fe, points()[:4])
    neg = jnp.asarray([False, True, False, True])

    fe.pt_madd(pts, niels_of(fe, points()[:4]), neg)
    assert cnt.n == 7
    cached = fe.pt_to_cached(pts)
    assert cnt.n == 8
    fe.pt_add_cached(pts, cached, neg)
    assert cnt.n == 16

    cnt.n = 0
    digits = jnp.zeros((4, 64), dtype=jnp.int32)
    jax.eval_shape(core._scalarmul_var, digits, pts)
    assert cnt.loops == [(63, 37)]
    assert cnt.n <= 64  # the table of -A, built outside the loop

    cnt.n, cnt.loops = 0, []
    rows = jax.ShapeDtypeStruct((8, 32), jnp.uint8)
    jax.eval_shape(core.verify_core, rows, rows, rows, rows,
                   jax.ShapeDtypeStruct((8,), jnp.bool_))
    windows = [rec for rec in cnt.loops if rec[1] > 1]  # not fe_pow2k's
    assert windows == [(64, 7), (63, 37)]
    return cnt.total()


# ---------------------------------------------------------------------------
# The new operations at the bounds of a backend's operand contract
# ---------------------------------------------------------------------------

def check_products_at_bounds(fe, monkeypatch, patterns, contract_ok):
    """Run pt_madd, pt_to_cached and pt_add_cached eagerly, both signs in
    one batch, on coordinates at the extremes of the backend's reduced
    form (`patterns`: limb vectors), with every fe_mul operand pair held
    to the backend's contract by `contract_ok(a, b)` — and the results
    against the same formulas on Python integers (a wrapped product would
    mismatch).  The formulas are polynomial identities, so the inputs
    need not be curve points."""
    real_mul = fe.fe_mul
    seen = []

    def checked_mul(a, b):
        assert contract_ok(np.asarray(a), np.asarray(b)), (
            np.asarray(a).max(), np.asarray(b).max())
        seen.append(1)
        return real_mul(a, b)

    monkeypatch.setattr(fe, "fe_mul", checked_mul)
    d2 = 2 * ref.D % P
    n = len(patterns)
    # every pattern in every coordinate role, p against q, both signs
    idx = [(i, j, s) for i in range(n) for j in range(n) for s in (False, True)]

    def col(k):  # coordinate k of point `which` takes pattern (i + k) % n
        return lambda which: stack_limbs(
            fe, [patterns[(t[which] + k) % n] for t in idx])

    p = fe.Pt(*(col(k)(0) for k in range(4)))
    q = fe.Pt(*(col(k + 1)(1) for k in range(4)))
    neg = jnp.asarray([t[2] for t in idx])

    def val(elem):
        return [v % P for v in raw_ints(fe, elem)]

    px, py, pz, pt = (val(c) for c in p.astuple())
    qx, qy, qz, qt = (val(c) for c in q.astuple())

    def want_add(ypx, ymx, tc, dd, sign):
        out = []
        for r in range(len(idx)):
            u, v, c_ = ypx[r], ymx[r], pt[r] * tc[r]
            if sign[r]:
                u, v, c_ = v, u, -c_
            a = (py[r] - px[r]) * v
            b = (py[r] + px[r]) * u
            e, h = b - a, b + a
            f, g = dd[r] - c_, dd[r] + c_
            out.append(tuple(w % P for w in (e * f, g * h, f * g, e * h)))
        return out

    def assert_coords(got, want, what):
        cols = [ints_of(fe, c) for c in got.astuple()]
        for r, w in enumerate(want):
            assert tuple(c[r] for c in cols) == w, (what, idx[r])

    sign = [t[2] for t in idx]
    # pt_madd: the entry's three coordinates are q's first three
    niels = (q.x, q.y, q.z)
    got = fe.pt_madd(p, niels, neg)
    assert_coords(got, want_add(qx, qy, qz, [2 * z for z in pz], sign),
                  "pt_madd")
    # pt_to_cached then pt_add_cached
    cached = fe.pt_to_cached(q)
    want_c = [[(y + x) % P for x, y in zip(qx, qy)],
              [(y - x) % P for x, y in zip(qx, qy)],
              qz, [t * d2 % P for t in qt]]
    for c, w in zip(cached, want_c):
        assert ints_of(fe, c) == w
    got = fe.pt_add_cached(p, cached, neg)
    assert_coords(got, want_add(want_c[0], want_c[1], want_c[3],
                                [2 * a * b for a, b in zip(pz, qz)], sign),
                  "pt_add_cached")
    assert len(seen) == 7 + 1 + 8
    return cached

"""Differential tests for the packed (mixed radix 25.5) field backend —
uint32 limbs, the limb axis leading, uint64 only in the product columns:
field-level fuzz vs big-int arithmetic at the documented bound ledger,
point ops vs the pure reference and, value for value, vs the int64
backend, and end-to-end batch verification; the adversarial gauntlet runs
on both backends from tests/test_ed25519_jax.py (tests/kernel_cases.py
holds the cases and the two helpers int -> element, element -> int),
because every backend must be bit-identical to ZIP-215.

Tier-1 discipline: the end-to-end tests here stick to the warm n=8
floor rung (one program, already in the persistent compile cache — the
test_golden_standard_program_tier1 idiom).
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ref

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.ops import ed25519_jax as dev  # noqa: E402
from tendermint_tpu.ops import fe25519_packed as fe  # noqa: E402

import kernel_cases  # noqa: E402

slow = pytest.mark.slow


U32, U64 = np.uint32, np.uint64


def _elems(vals):
    """int → element, a batch of them (kernel_cases holds the helper for
    either backend's layout)."""
    return kernel_cases.elems(fe, vals)


def _pattern(*limb_values, n=1, dtype=U32):
    """n elements (or uint64 columns) whose every limb is the given value
    (one value), or the ten given values: as stored, not reduced."""
    v = (np.full(fe.NLIMBS, limb_values[0]) if len(limb_values) == 1
         else np.array(limb_values))
    return kernel_cases.stack_limbs(fe, [v.astype(dtype)] * n)


def _vals(elem):
    """element → int, the value its limbs stand for (unreduced)."""
    return kernel_cases.raw_ints(fe, elem)


def _canon_vals(elem):
    return kernel_cases.ints_of(fe, elem)


# ---------------------------------------------------------------------------
# Layout invariants (the test_exactness_margin idiom: guard the header's
# arithmetic so nobody widens a bound without re-deriving the budget)
# ---------------------------------------------------------------------------

def test_layout_invariants():
    assert fe.NLIMBS == 10
    assert sum(fe.LIMB_WIDTHS) == 255
    assert fe.LIMB_WEIGHTS == tuple(-(-51 * i // 2) for i in range(10))
    # the mixed-radix doubling rule: w_i + w_j == w_{i+j} + (i odd and j
    # odd), and the 19-fold is weight-exact at every folded column
    w = fe.LIMB_WEIGHTS + tuple(255 + x for x in fe.LIMB_WEIGHTS)
    for i in range(10):
        for j in range(10):
            assert w[i] + w[j] == w[i + j] + (i % 2 and j % 2), (i, j)
    # an element: ten uint32 planes, the limb axis leading — 40 bytes a
    # row against the 15x17 int64 layout's 120
    from tendermint_tpu.ops import fe25519 as fe_i64

    assert fe.LIMB_AXIS == 0 and fe_i64.LIMB_AXIS == -1
    e = _elems([1, 2, 3])
    assert e.shape == (10, 3) and e.dtype == U32
    assert fe.fe_mul(e, e).dtype == U32 and fe.fe_sq(e).dtype == U32
    assert fe.NLIMBS * 4 == 40 and fe_i64.NLIMBS * 8 == 120
    # the verify program's batch shape: [N] <-> [N/8, 8], rows in order
    rows = jnp.arange(48).reshape(16, 3)
    assert fe.batch_in(rows).shape == (2, 8, 3)
    assert np.array_equal(fe.batch_out(fe.batch_in(rows)[..., 0]), rows[:, 0])


def test_overflow_margin_documented():
    """Worst column coefficient sum (odd-odd doubling counted) is 267 at
    column 0; the pairwise product contract 2^54.9 keeps the worst
    uint64 column under 2^63 — one bit under what it holds."""
    def units(k):
        pairs = [(i, k - i) for i in range(10) if 0 <= k - i < 10]
        return sum(2 if (i % 2 and j % 2) else 1 for i, j in pairs)

    coeff = [units(j) + 19 * units(j + 10) for j in range(10)]
    assert max(coeff) == coeff[0] == 267
    assert 267 * 2 ** 54.9 < 2 ** 63
    # fe_sq doubles cross terms on top: worst 534, still under budget at
    # the reduced-only operand contract (2^26.9)
    assert 534 * (2 ** 26.9) ** 2 < 2 ** 63
    # what uint32 STORAGE must hold: the widest returned value (a sum or
    # a negation, 2^28.01) and the doubled operands inside the products
    assert 2 * 2 ** 28.01 < 2 ** 32 and 4 * 2 ** 26.9 < 2 ** 32


# ---------------------------------------------------------------------------
# Field-level fuzz vs big-int arithmetic
# ---------------------------------------------------------------------------

def _rand_fe_int(rng):
    choices = [
        rng.getrandbits(255),
        ref.P - 1 - rng.getrandbits(10),
        ref.P + rng.getrandbits(10),
        (1 << 255) - 1 - rng.getrandbits(5),
        rng.getrandbits(20),
        0,
        1,
        ref.P,
        ref.P - 1,
    ]
    return choices[rng.randrange(len(choices))] % (1 << 255)


def test_fe_mul_matches_bigint():
    import random

    rng = random.Random(2026)
    a_ints = [_rand_fe_int(rng) for _ in range(64)]
    b_ints = [_rand_fe_int(rng) for _ in range(64)]
    out = _canon_vals(fe.fe_mul(_elems(a_ints), _elems(b_ints)))
    for i in range(64):
        assert out[i] == (a_ints[i] * b_ints[i]) % ref.P, i


def test_fe_mul_at_pairwise_bound():
    """All-limbs-max operands at the documented contract (S x A: the
    pt_add/pt_dbl worst case g*h = 2^27.59 * 2^27.01), in either order: a
    uint64 column that wrapped, or a uint32 operand that wrapped when it
    was doubled, would mismatch big-int."""
    s = (1 << 27) + (1 << 26)   # 2^27.58
    a_mag = (1 << 27) + (1 << 25)  # 2^27.09
    assert s * a_mag <= 2 ** 63 / 267  # the pairwise budget itself
    x, y = _pattern(s, n=4), _pattern(a_mag, n=4)
    want = _vals(x)[0] * _vals(y)[0] % ref.P
    assert _canon_vals(fe.fe_mul(x, y)) == [want] * 4
    assert _canon_vals(fe.fe_mul(y, x)) == [want] * 4


def test_fe_sq_matches_and_respects_contract():
    import random

    rng = random.Random(9)
    a_ints = [_rand_fe_int(rng) for _ in range(32)]
    out = _canon_vals(fe.fe_sq(_elems(a_ints)))
    for i in range(32):
        assert out[i] == (a_ints[i] ** 2) % ref.P, i
    # at the reduced-only contract bound (2^26.9 > any reduced limb): the
    # twice-doubled operand (2^28.9) still fits its uint32
    m = int(2 ** 26.9)
    x = _pattern(m, n=2)
    assert _canon_vals(fe.fe_sq(x)) == [_vals(x)[0] ** 2 % ref.P] * 2


def _assert_reduced(elem):
    rows = kernel_cases.limb_rows(fe, elem)
    assert elem.dtype == U32
    assert rows.max() < (1 << 26) + 64, rows.max()
    # odd limbs obey the tighter width bound
    assert rows[:, 1::2].max() < (1 << 25) + 64


def test_fe_carry_full_default_reduces_any_column():
    """rounds=3 (the default) must reduce any uint64 column below 2^63
    (the _fold_cols output bound) to a uint32 element."""
    rng = np.random.default_rng(3)
    c = rng.integers(0, 1 << 63, size=(8, fe.NLIMBS), dtype=U64)
    c[0, :] = (1 << 63) - 1
    cols = kernel_cases.stack_limbs(fe, list(c))
    assert cols.dtype == U64
    out = fe.fe_carry(cols)
    _assert_reduced(out)
    assert _canon_vals(out) == [v % ref.P for v in _vals(cols)]


def test_fe_carry_partial_rounds2_at_2pow44():
    """rounds=2 (the point-op partial carry) is documented sound for
    uint64 columns <= 2^44 — and for ANY uint32 element, which is what
    the point operations hand it."""
    rng = np.random.default_rng(4)
    c = rng.integers(0, 1 << 44, size=(8, fe.NLIMBS), dtype=U64)
    c[0, :] = 1 << 44
    cols = kernel_cases.stack_limbs(fe, list(c))
    out = fe.fe_carry(cols, rounds=2)
    _assert_reduced(out)
    assert _canon_vals(out) == [v % ref.P for v in _vals(cols)]

    e = rng.integers(0, 1 << 32, size=(8, fe.NLIMBS), dtype=U64).astype(U32)
    e[0, :] = (1 << 32) - 1
    elem = kernel_cases.stack_limbs(fe, list(e))
    out = fe.fe_carry(elem, rounds=2)
    _assert_reduced(out)
    assert _canon_vals(out) == [v % ref.P for v in _vals(elem)]


def test_fe_sub_neg_roundtrip():
    import random

    rng = random.Random(5)
    a_ints = [_rand_fe_int(rng) for _ in range(16)]
    b_ints = [_rand_fe_int(rng) for _ in range(16)]
    a, b = _elems(a_ints), _elems(b_ints)
    d = _canon_vals(fe.fe_sub(a, b))
    n = _canon_vals(fe.fe_carry(fe.fe_neg(a)))
    for i in range(16):
        assert d[i] == (a_ints[i] - b_ints[i]) % ref.P, i
        assert n[i] == (-a_ints[i]) % ref.P, i


def test_a_uint32_limb_never_wraps():
    """The widest value each function may RETURN, limb by limb against
    Python integers (which do not wrap): sums of two reduced-ceiling
    elements (A), differences from a reduced and from a summed minuend
    (S, 2^28.01), a negation, and fe_mul with one operand at the widest
    returned value (its odd limbs are doubled in uint32 before they are
    widened)."""
    top = [(1 << w) + 63 for w in fe.LIMB_WIDTHS]
    r = _pattern(*top, n=2)
    rows = kernel_cases.limb_rows
    two_p, four_p = fe._2P.astype(object), fe._4P.astype(object)

    def exact(elem):
        return rows(fe, elem).astype(object)

    a = fe.fe_add(r, r)
    assert (exact(a) == 2 * exact(r)).all() and exact(a).max() < 2 ** 27.01
    zero = _elems([0, 0])
    s1 = fe.fe_sub(r, zero)                 # reduced minuend, widest
    assert (exact(s1) == exact(r) + two_p).all()
    assert exact(s1).max() < 2 ** 27.59
    s2 = fe.fe_sub(a, zero)                 # a sum as the minuend
    assert (exact(s2) == exact(a) + two_p).all()
    assert exact(s2).max() < 2 ** 28.01
    s3 = fe.fe_sub(zero, r)                 # the subtrahend at its ceiling
    assert (exact(s3) == two_p - exact(r)).all() and exact(s3).min() >= 0
    n = fe.fe_neg(zero)
    assert (exact(n) == four_p).all() and exact(n).max() < 2 ** 28.01
    assert exact(fe.fe_neg(fe.fe_const(fe._4P, (2,)))).max() == 0
    # the widest returned value as fe_mul's SECOND operand (the one whose
    # odd limbs are doubled), against the largest partner the pairwise
    # contract allows
    wide = int(2 ** 28.01)
    partner = int(2 ** 54.9 / wide)
    x, y = _pattern(partner, n=2), _pattern(wide, n=2)
    assert _canon_vals(fe.fe_mul(x, y)) == [
        _vals(x)[0] * _vals(y)[0] % ref.P] * 2


def test_fe_canonical_edge_patterns():
    rng = np.random.default_rng(99)
    pats = [rng.integers(0, 1 << 31, size=fe.NLIMBS, dtype=U64).astype(U32)
            for _ in range(64)]
    for v in [0, 1, ref.P - 1, ref.P, ref.P + 1, (1 << 255) - 1]:
        pats.append(fe.limbs_from_int(v))
    elem = kernel_cases.stack_limbs(fe, pats)
    out = fe.fe_canonical(elem)
    assert _vals(out) == [v % ref.P for v in _vals(elem)]
    got = kernel_cases.limb_rows(fe, out)
    for j in range(fe.NLIMBS):
        assert got[:, j].max() < (1 << fe.LIMB_WIDTHS[j])


def test_limbs_of_bits_matches_limbs_from_int():
    import random

    rng = random.Random(31)
    vals = [rng.getrandbits(255) for _ in range(8)]
    bits = np.zeros((8, 255), dtype=np.uint8)
    for i, v in enumerate(vals):
        for k in range(255):
            bits[i, k] = (v >> k) & 1
    got = fe.limbs_of_bits(jnp.asarray(bits))
    assert got.shape == (fe.NLIMBS, 8) and got.dtype == U32
    assert np.array_equal(np.asarray(got), np.asarray(_elems(vals)))
    # the program's batch shape
    got = fe.limbs_of_bits(fe.batch_in(jnp.asarray(bits)))
    assert got.shape == (fe.NLIMBS, 1, 8)
    assert _vals(got) == vals


# ---------------------------------------------------------------------------
# Point ops vs reference
# ---------------------------------------------------------------------------

def _to_dev(p):
    return kernel_cases.to_dev(fe, [p])


def _affine(pt: "fe.Pt"):
    x, y, z = (_canon_vals(c)[0] for c in (pt.x, pt.y, pt.z))
    zi = pow(z, ref.P - 2, ref.P)
    return x * zi % ref.P, y * zi % ref.P


def test_point_add_and_dbl_match_reference():
    import random

    rng = random.Random(7)
    pts = [ref.scalar_mult(rng.getrandbits(252), ref.BASE) for _ in range(8)]
    for i in range(0, 8, 2):
        p, q = pts[i], pts[i + 1]
        got = _affine(fe.pt_add(_to_dev(p), _to_dev(q)))
        want = ref.pt_add(p, q)
        wzi = pow(want[2], ref.P - 2, ref.P)
        assert got == (want[0] * wzi % ref.P, want[1] * wzi % ref.P)

        gd = _affine(fe.pt_dbl(_to_dev(p)))
        wd = ref.pt_add(p, p)
        wdzi = pow(wd[2], ref.P - 2, ref.P)
        assert gd == (wd[0] * wdzi % ref.P, wd[1] * wdzi % ref.P)


def test_point_ops_on_torsion():
    """The unified formulas must stay complete on small-order points —
    the inputs ZIP-215 admits."""
    for pt in ref.eight_torsion_points()[:4]:
        doubled = _affine(fe.pt_dbl(_to_dev(pt)))
        want = ref.pt_add(pt, pt)
        wzi = pow(want[2], ref.P - 2, ref.P)
        assert doubled == (want[0] * wzi % ref.P, want[1] * wzi % ref.P)
    ident = fe.pt_identity((1,))
    assert bool(np.asarray(fe.pt_is_identity(ident))[0])
    assert bool(np.asarray(fe.pt_is_identity(fe.pt_dbl(ident)))[0])


def test_pt_dbl_n_matches_chained():
    import random

    rng = random.Random(11)
    p = ref.scalar_mult(rng.getrandbits(252), ref.BASE)
    chained = _to_dev(p)
    for _ in range(4):
        chained = fe.pt_dbl(chained)
    assert _affine(fe.pt_dbl_n(_to_dev(p), 4)) == _affine(chained)


# ---------------------------------------------------------------------------
# The precomputed-form additions at the bounds of the operand contract (the
# checks shared by the backends run from tests/test_ed25519_jax.py)
# ---------------------------------------------------------------------------

def _reduced_ceiling():
    return np.array([(1 << w) + 63 for w in fe.LIMB_WIDTHS], dtype=U32)


def test_new_operations_at_pairwise_bound(monkeypatch):
    """pt_madd, pt_to_cached and pt_add_cached with every coordinate at
    the reduced ceiling (even limbs 2^26 + 63, odd 2^25 + 63): every
    product meets the pairwise 2^54.9 contract in BOTH orders of the
    sign (the ledger of _add_tail), and no column wraps."""
    patterns = [_reduced_ceiling(), fe.limbs_from_int(ref.P - 1),
                fe.ZERO, fe.ONE]
    cached = kernel_cases.check_products_at_bounds(
        fe, monkeypatch, patterns,
        lambda a, b: float(a.max()) * float(b.max()) <= 2 ** 54.9)
    # pt_to_cached's outputs are reduced
    ceiling = _reduced_ceiling()
    for c in cached:
        assert c.dtype == U32
        assert (kernel_cases.limb_rows(fe, c) <= ceiling).all()


def test_unswapped_g_would_break_the_contract():
    """Why _add_tail carries g when a sign is given: the raw difference
    d2 + 2p - c against h = 2R is past the pairwise contract."""
    r = float((1 << 26) + 63)
    raw_f = 2 * r + float(fe._2P.max())
    assert raw_f * (2 * r) > 2 ** 54.9
    assert (3 * r) * (2 * r) <= 2 ** 54.9  # g = d2 + c as pt_add leaves it


# ---------------------------------------------------------------------------
# Differential: packed (uint32, limb axis leading) == fe25519 (int64), value
# for value, eagerly on 8 rows
# ---------------------------------------------------------------------------

def _edge_values():
    """Eight field values: the edges, the value of the reduced-ceiling
    limb pattern (past 2^255 as limbs; the same element mod p), and two
    random ones."""
    import random

    rng = random.Random(3131)
    ceiling = fe.int_from_limbs(_reduced_ceiling()) % ref.P
    return [0, 1, ref.P - 1, ref.P, (1 << 255) - 1, ceiling,
            rng.getrandbits(255), rng.getrandbits(255)]


def _rotated(vals, k):
    return vals[k:] + vals[:k]


def _diff_fe_mul(f, v):
    return [f.fe_mul(kernel_cases.elems(f, v),
                     kernel_cases.elems(f, _rotated(v, 3)))]


def _diff_fe_sq(f, v):
    return [f.fe_sq(kernel_cases.elems(f, v))]


def _diff_point(f, v, k=0):
    return f.Pt(*(kernel_cases.elems(f, _rotated(v, k + j)) for j in range(4)))


def _diff_pt_dbl(f, v):
    return f.pt_dbl(_diff_point(f, v)).astuple()


def _diff_neg(n=8):
    return jnp.asarray([i % 2 == 1 for i in range(n)])


def _diff_pt_add_cached(f, v):
    cached = f.pt_to_cached(_diff_point(f, v, 2))
    return f.pt_add_cached(_diff_point(f, v), cached, _diff_neg()).astuple()


def _diff_pt_madd(f, v):
    niels = tuple(kernel_cases.elems(f, _rotated(v, 5 + j)) for j in range(3))
    return f.pt_madd(_diff_point(f, v), niels, _diff_neg()).astuple()


def _diff_decompress(f, v):
    # y encodings: on the curve (the base point's, a small-order one's),
    # off it (2), and whatever the edges are; the sign alternates
    ys = [ref.BASE[1], 2] + v[:6]
    pt, ok = dev._Core(f).decompress(kernel_cases.elems(f, ys), _diff_neg())
    return pt.astuple() + (ok,)


_DIFF_OPS = {"fe_mul": _diff_fe_mul, "fe_sq": _diff_fe_sq, "pt_dbl": _diff_pt_dbl,
             "pt_add_cached": _diff_pt_add_cached, "pt_madd": _diff_pt_madd,
             "decompress": _diff_decompress}


@pytest.mark.parametrize("op", list(_DIFF_OPS))
def test_packed_equals_int64(op):
    """The same operation through both field modules on the same eight
    values (0, 1, p - 1, p, 2^255 - 1, limbs at the reduced bound, two
    random): every output coordinate the same field element.  The
    formulas are polynomial identities, so the coordinates need not be
    curve points."""
    from tendermint_tpu.ops import fe25519 as fe_i64

    run = _DIFF_OPS[op]
    outs = []
    for f in (fe, fe_i64):
        outs.append([kernel_cases.ints_of(f, o) if o.dtype != bool
                     else [bool(b) for b in np.asarray(o)]
                     for o in run(f, _edge_values())])
    assert outs[0] == outs[1]
    assert all(len(o) == 8 for o in outs[0])


# ---------------------------------------------------------------------------
# The program as the TPU's compiler leaves it (no chip: a described v5e)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _while_body_fusions(text):
    """The count of fusions in the body of each `while` of a compiled
    module's text, largest first (the variable-base loop's is the
    largest: 37 field operations and a select a window)."""
    import re

    bodies, name = {}, None
    for line in text.split("\n"):
        m = re.match(r"^%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(1)
            bodies[name] = 0
        elif line.startswith("}"):
            name = None
        elif name and " fusion(" in line:
            bodies[name] += 1
    called = re.findall(r" while\(.*?body=%?([\w.\-]+)", text)
    return sorted((bodies[b] for b in called), reverse=True)


@slow
@pytest.mark.parametrize("rung", [768, 10240])
def test_no_field_operation_round_trips_through_hbm(one_v5e_chip, rung):
    """PR 29's finding (PERF.md section 6): the compiler's memory-space
    assignment has a second regime in which the product columns of EVERY
    field operation are copied out to HBM and sliced back — a
    `slice-start` per operation in the compiled text, 40 % on a squaring
    — and which of the two a program gets turned on how the 8-entry
    table of -A was built.  The program that is served has none.

    PR 31's: what a field operation costs is its SHUFFLES, not its
    products.  With the limb axis on a tiled axis one window of the
    variable-base loop was 1,001 fusions; with it leading, 547.  A
    change that brings the shuffles back (a slice, a concatenate or a
    pad that crosses a tiled axis) shows as a fusion count, here, without
    a chip.  ~1.5 min a rung (nothing is run), hence slow."""
    from jax.experimental.compilation_cache import compilation_cache

    rows = jax.ShapeDtypeStruct((rung, 32), jnp.uint8, sharding=one_v5e_chip)
    valid = jax.ShapeDtypeStruct((rung,), jnp.bool_, sharding=one_v5e_chip)
    # a compile for a described chip can be written to the persistent
    # cache but never read back: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = dev._jit_for("verify", "packed", donate=True).lower(
            rows, rows, rows, rows, valid).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert text.count(" while(") == 20  # 18 square chains, base, var
    assert text.count("slice-start(") == 0
    var_loop, base_loop = _while_body_fusions(text)[:2]
    assert var_loop <= 640 and base_loop <= 160, (var_loop, base_loop)


# ---------------------------------------------------------------------------
# End-to-end differential verification (warm n=8 rung: tier-1 eligible)
# ---------------------------------------------------------------------------

def test_differential_vs_reference_packed_tier1():
    """End-to-end packed verification on the warm n=8 floor rung agrees
    with the pure ZIP-215 reference on a mixed-validity batch."""
    pubs, msgs, sigs, want = kernel_cases.batch8()
    got = dev.verify_batch(pubs, msgs, sigs, impl="packed")
    assert [bool(v) for v in got] == want
    assert [ref.verify(p, m, s) for p, m, s in zip(pubs, msgs, sigs)] == want


def test_impls_agree_on_n8_batch():
    """int64 and packed return identical verdict vectors on the warm
    floor rung (both programs persistent-cached)."""
    pubs, msgs, sigs, want = kernel_cases.batch8()
    got_i64 = dev.verify_batch(pubs, msgs, sigs, impl="int64")
    got_pk = dev.verify_batch(pubs, msgs, sigs, impl="packed")
    assert list(got_i64) == list(got_pk) == want


def test_rfc8032_vector_on_packed():
    pub = bytes.fromhex(
        "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
    )
    # n=1 pads to the warm n=8 floor rung: no fresh program
    assert list(dev.verify_batch([pub], [b""], [sig], impl="packed")) == [True]

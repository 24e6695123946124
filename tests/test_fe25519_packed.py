"""Differential tests for the packed (mixed radix 25.5) int64 field
backend: field-level fuzz vs big-int arithmetic at the documented bound
ledger, point ops vs the pure reference, and end-to-end batch
verification; the adversarial gauntlet runs on both backends from
tests/test_ed25519_jax.py (tests/kernel_cases.py holds the cases),
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


def _val(limbs) -> int:
    return fe.int_from_limbs(np.asarray(limbs))


def _canon_val(limbs) -> int:
    return fe.int_from_limbs(np.asarray(fe.fe_canonical(jnp.asarray(limbs))))


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
    # packed element: 80 bytes of int64 lanes vs the 15x17 layout's 120
    from tendermint_tpu.ops import fe25519 as fe_i64

    assert fe.NLIMBS * 8 == 80 and fe_i64.NLIMBS * 8 == 120


def test_overflow_margin_documented():
    """Worst column coefficient sum (odd-odd doubling counted) is 267 at
    column 0; the pairwise product contract 2^54.9 keeps the worst
    column under 2^63."""
    def units(k):
        pairs = [(i, k - i) for i in range(10) if 0 <= k - i < 10]
        return sum(2 if (i % 2 and j % 2) else 1 for i, j in pairs)

    coeff = [units(j) + 19 * units(j + 10) for j in range(10)]
    assert max(coeff) == coeff[0] == 267
    assert 267 * 2 ** 54.9 < 2 ** 63
    # fe_sq doubles cross terms on top: worst 534, still under budget at
    # the reduced-only operand contract (2^26.9)
    assert 534 * (2 ** 26.9) ** 2 < 2 ** 63


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
    a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in a_ints]))
    b = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in b_ints]))
    out = np.asarray(fe.fe_canonical(fe.fe_mul(a, b)))
    for i in range(64):
        assert fe.int_from_limbs(out[i]) == (a_ints[i] * b_ints[i]) % ref.P, i


def test_fe_mul_at_pairwise_bound():
    """All-limbs-max operands at the documented contract (S x A: the
    pt_add/pt_dbl worst case g*h = 2^27.59 * 2^27.01): an int64 overflow
    anywhere in the column arithmetic would wrap and mismatch big-int."""
    s = (1 << 27) + (1 << 26)   # 2^27.58
    a_mag = (1 << 27) + (1 << 25)  # 2^27.09
    assert s * a_mag <= 2 ** 63 / 267  # the pairwise budget itself
    x = jnp.full((4, fe.NLIMBS), s, dtype=jnp.int64)
    y = jnp.full((4, fe.NLIMBS), a_mag, dtype=jnp.int64)
    got = np.asarray(fe.fe_canonical(fe.fe_mul(x, y)))
    want = (_val(np.full(fe.NLIMBS, s, dtype=np.int64))
            * _val(np.full(fe.NLIMBS, a_mag, dtype=np.int64))) % ref.P
    for i in range(4):
        assert fe.int_from_limbs(got[i]) == want, i


def test_fe_sq_matches_and_respects_contract():
    import random

    rng = random.Random(9)
    a_ints = [_rand_fe_int(rng) for _ in range(32)]
    a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in a_ints]))
    out = np.asarray(fe.fe_canonical(fe.fe_sq(a)))
    for i in range(32):
        assert fe.int_from_limbs(out[i]) == (a_ints[i] ** 2) % ref.P, i
    # at the reduced-only contract bound (2^26.9 > any reduced limb)
    m = (1 << 26) + (1 << 25)  # 2^26.58 < 2^26.9
    x = jnp.full((2, fe.NLIMBS), m, dtype=jnp.int64)
    got = np.asarray(fe.fe_canonical(fe.fe_sq(x)))
    want = (_val(np.full(fe.NLIMBS, m, dtype=np.int64)) ** 2) % ref.P
    assert fe.int_from_limbs(got[0]) == want


def test_fe_carry_full_default_reduces_any_column():
    """rounds=3 (the default) must reduce any non-negative int64 column
    (the _fold_cols output bound is < 2^63)."""
    rng = np.random.default_rng(3)
    c = rng.integers(0, 1 << 62, size=(8, fe.NLIMBS), dtype=np.int64)
    c[0, :] = (1 << 62) - 1
    out = np.asarray(fe.fe_carry(jnp.asarray(c)))
    assert out.min() >= 0 and out.max() < (1 << 26) + 64, (out.min(), out.max())
    for i in range(8):
        assert _canon_val(out[i]) == _val(c[i]) % ref.P, i
    # odd limbs obey the tighter width bound
    assert out[:, 1::2].max() < (1 << 25) + 64


def test_fe_carry_partial_rounds2_at_2pow44():
    """rounds=2 (the point-op partial carry) is documented sound for
    limbs <= 2^44."""
    rng = np.random.default_rng(4)
    c = rng.integers(0, 1 << 44, size=(8, fe.NLIMBS), dtype=np.int64)
    c[0, :] = 1 << 44
    out = np.asarray(fe.fe_carry(jnp.asarray(c), rounds=2))
    assert out.min() >= 0 and out.max() < (1 << 26) + 64
    for i in range(8):
        assert _canon_val(out[i]) == _val(c[i]) % ref.P, i


def test_fe_sub_neg_roundtrip():
    import random

    rng = random.Random(5)
    a_ints = [_rand_fe_int(rng) for _ in range(16)]
    b_ints = [_rand_fe_int(rng) for _ in range(16)]
    a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in a_ints]))
    b = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in b_ints]))
    d = np.asarray(fe.fe_canonical(fe.fe_sub(a, b)))
    n = np.asarray(fe.fe_canonical(fe.fe_carry(fe.fe_neg(a))))
    for i in range(16):
        assert fe.int_from_limbs(d[i]) == (a_ints[i] - b_ints[i]) % ref.P, i
        assert fe.int_from_limbs(n[i]) == (-a_ints[i]) % ref.P, i


def test_fe_canonical_edge_patterns():
    rng = np.random.default_rng(99)
    pats = [rng.integers(0, 1 << 57, size=fe.NLIMBS, dtype=np.int64)
            for _ in range(64)]
    for v in [0, 1, ref.P - 1, ref.P, ref.P + 1, (1 << 255) - 1]:
        pats.append(fe.limbs_from_int(v))
    arr = np.stack(pats)
    out = np.asarray(fe.fe_canonical(jnp.asarray(arr)))
    for i in range(len(pats)):
        got = fe.int_from_limbs(out[i])
        want = _val(arr[i]) % ref.P
        assert got == want, (i, got, want)
        assert out[i].min() >= 0
        for j in range(fe.NLIMBS):
            assert out[i][j] < (1 << fe.LIMB_WIDTHS[j])


def test_limbs_of_bits_matches_limbs_from_int():
    import random

    rng = random.Random(31)
    vals = [rng.getrandbits(255) for _ in range(8)]
    bits = np.zeros((8, 255), dtype=np.uint8)
    for i, v in enumerate(vals):
        for k in range(255):
            bits[i, k] = (v >> k) & 1
    got = np.asarray(fe.limbs_of_bits(jnp.asarray(bits)))
    for i, v in enumerate(vals):
        assert np.array_equal(got[i], fe.limbs_from_int(v)), i


# ---------------------------------------------------------------------------
# Point ops vs reference
# ---------------------------------------------------------------------------

def _to_dev(p):
    x, y, z, t = p
    zi = pow(z, ref.P - 2, ref.P)
    xa, ya = x * zi % ref.P, y * zi % ref.P
    return fe.Pt(
        jnp.asarray(fe.limbs_from_int(xa))[None, :],
        jnp.asarray(fe.limbs_from_int(ya))[None, :],
        jnp.asarray(fe.limbs_from_int(1))[None, :],
        jnp.asarray(fe.limbs_from_int(xa * ya % ref.P))[None, :],
    )


def _affine(pt: "fe.Pt"):
    zi = pow(_canon_val(pt.z[0]), ref.P - 2, ref.P)
    return (
        _canon_val(pt.x[0]) * zi % ref.P,
        _canon_val(pt.y[0]) * zi % ref.P,
    )


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
# checks shared by the three backends run from tests/test_ed25519_jax.py)
# ---------------------------------------------------------------------------

def _reduced_ceiling():
    return np.array([(1 << w) + 63 for w in fe.LIMB_WIDTHS], dtype=np.int64)


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
        c = np.asarray(c)
        assert c.min() >= 0 and (c <= ceiling).all()


def test_unswapped_g_would_break_the_contract():
    """Why _add_tail carries g when a sign is given: the raw difference
    d2 + 2p - c against h = 2R is past the pairwise contract."""
    r = float((1 << 26) + 63)
    raw_f = 2 * r + float(fe._2P.max())
    assert raw_f * (2 * r) > 2 ** 54.9
    assert (3 * r) * (2 * r) <= 2 ** 54.9  # g = d2 + c as pt_add leaves it


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


@slow
@pytest.mark.parametrize("rung", [768, 10240])
def test_no_field_operation_round_trips_through_hbm(one_v5e_chip, rung):
    """PR 29's finding (PERF.md section 6): the compiler's memory-space
    assignment has a second regime in which the product columns of EVERY
    field operation are copied out to HBM and sliced back — a
    `slice-start` per operation in the compiled text, 40 % on a squaring
    — and which of the two a program gets turned on how the 8-entry
    table of -A was built.  The program that is served has none.  ~2.5
    min a rung (no chip needed, nothing is run), hence slow."""
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

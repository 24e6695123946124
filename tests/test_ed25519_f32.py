"""Differential tests for the f32 (radix-5) field backend: field-level fuzz
vs big-int arithmetic at the documented bound ledger, point ops vs the pure
reference, and end-to-end batch verification over honest/tampered/adversarial
inputs — the same gauntlet as the int64 backend (tests/test_ed25519_jax.py),
because both must be bit-identical to ZIP-215."""

import secrets

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto.keys import gen_priv_key

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tendermint_tpu.ops import ed25519_jax as dev  # noqa: E402
from tendermint_tpu.ops import fe25519_f32 as fe  # noqa: E402

import kernel_cases  # noqa: E402


def _val(limbs) -> int:
    return fe.int_from_limbs(np.asarray(limbs))


def _canon_val(limbs) -> int:
    return fe.int_from_limbs(np.asarray(fe.fe_canonical(jnp.asarray(limbs))))


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

    rng = random.Random(1234)
    a_ints = [_rand_fe_int(rng) for _ in range(64)]
    b_ints = [_rand_fe_int(rng) for _ in range(64)]
    a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in a_ints]))
    b = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in b_ints]))
    out = np.asarray(fe.fe_canonical(fe.fe_mul(a, b)))
    for i in range(64):
        assert fe.int_from_limbs(out[i]) == (a_ints[i] * b_ints[i]) % ref.P, i


def test_fe_mul_signed_operands():
    """Signed limb vectors at the operand contract (|a|inf*|b|inf <= 17641):
    the pt_add worst case is 153*102."""
    rng = np.random.default_rng(42)
    a = rng.integers(-153, 154, size=(16, fe.NLIMBS)).astype(np.float32)
    b = rng.integers(-102, 103, size=(16, fe.NLIMBS)).astype(np.float32)
    # include the all-extremal rows
    a[0, :] = 153.0
    b[0, :] = 102.0
    a[1, :] = -153.0
    b[1, :] = 102.0
    got = np.asarray(fe.fe_mul(jnp.asarray(a), jnp.asarray(b)))
    assert np.abs(got).max() <= 51, f"limb not reduced: {np.abs(got).max()}"
    for i in range(16):
        assert _canon_val(got[i]) == (_val(a[i]) * _val(b[i])) % ref.P, i


def test_fe_sq_at_contract_bound():
    """fe_sq contract: |a|inf <= 63 (doubled cross terms)."""
    rng = np.random.default_rng(9)
    a = rng.integers(-63, 64, size=(8, fe.NLIMBS)).astype(np.float32)
    a[0, :] = 63.0
    a[1, :] = -63.0
    got = np.asarray(fe.fe_sq(jnp.asarray(a)))
    assert np.abs(got).max() <= 51
    for i in range(8):
        assert _canon_val(got[i]) == (_val(a[i]) ** 2) % ref.P, i


def test_fe_carry_full_rounds_at_2pow24():
    """rounds=6 must reduce any |column| <= 2^24 (the f32 exactness
    ceiling, which is also the worst folded-column bound)."""
    rng = np.random.default_rng(3)
    c = rng.integers(-(1 << 24), (1 << 24) + 1, size=(8, fe.NLIMBS)).astype(np.float32)
    c[0, :] = float(1 << 24)
    c[1, :] = -float(1 << 24)
    out = np.asarray(fe.fe_carry(jnp.asarray(c), rounds=6))
    assert out.min() >= -20 and out.max() <= 51, (out.min(), out.max())
    for i in range(8):
        assert _canon_val(out[i]) == _val(c[i]) % ref.P, i


def test_fe_carry_partial_rounds_at_204():
    """rounds=3 (the point-op partial carry) must reduce |limbs| <= 204."""
    rng = np.random.default_rng(4)
    c = rng.integers(-204, 205, size=(8, fe.NLIMBS)).astype(np.float32)
    c[0, :] = 204.0
    c[1, :] = -204.0
    out = np.asarray(fe.fe_carry(jnp.asarray(c), rounds=3))
    assert out.min() >= -20 and out.max() <= 51, (out.min(), out.max())
    for i in range(8):
        assert _canon_val(out[i]) == _val(c[i]) % ref.P, i


def test_fe_canonical_edge_patterns():
    """Freeze must canonicalize any limb pattern within the contract
    (|limbs| <= 52), including signed values and p-adjacent encodings."""
    rng = np.random.default_rng(99)
    pats = []
    for _ in range(64):
        pats.append(rng.integers(-52, 53, size=fe.NLIMBS).astype(np.float32))
    for v in [0, 1, ref.P - 1, ref.P, ref.P + 1, (1 << 255) - 1]:
        pats.append(fe.limbs_from_int(v))
    arr = np.stack(pats)
    out = np.asarray(fe.fe_canonical(jnp.asarray(arr)))
    for i in range(len(pats)):
        got = fe.int_from_limbs(out[i])
        want = _val(arr[i]) % ref.P
        assert got == want, (i, got, want)
        assert out[i].min() >= 0 and out[i].max() < 32


def test_exactness_margin_documented():
    """The bound ledger's safety argument: worst folded column must be
    under f32's exact-integer ceiling.  Guards against someone widening
    an operand bound without re-deriving the budget."""
    worst_product = 153 * 102
    worst_fold_coeff = max((j + 1) + 19 * (fe.NLIMBS - 1 - j) for j in range(fe.NLIMBS))
    assert worst_fold_coeff == 951
    assert worst_product * worst_fold_coeff < 2**24


def test_fe_mul_mxu_variant_matches():
    """The (optional) MXU incidence-matmul formulation must agree with the
    pad/add tree exactly."""
    rng = np.random.default_rng(11)
    a = rng.integers(-153, 154, size=(8, fe.NLIMBS)).astype(np.float32)
    b = rng.integers(-102, 103, size=(8, fe.NLIMBS)).astype(np.float32)
    tree = np.asarray(fe._fold_cols(fe._mul_cols(jnp.asarray(a), jnp.asarray(b))))
    mxu = np.asarray(fe._fe_mul_mxu(jnp.asarray(a), jnp.asarray(b)))
    for i in range(8):
        assert _canon_val(mxu[i]) == _canon_val(tree[i]), i


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


# ---------------------------------------------------------------------------
# The precomputed-form additions at the bounds of the operand contract (the
# checks shared by the three backends run from tests/test_ed25519_jax.py)
# ---------------------------------------------------------------------------

def test_new_operations_at_operand_contract(monkeypatch):
    """pt_madd, pt_to_cached and pt_add_cached with every coordinate at
    the ends of the reduced band [-20, 51], signs mixed so sums and
    differences both reach their extremes: every fe_mul operand pair
    stays within |a|inf * |b|inf <= 17641 in both orders of the sign."""
    alt = np.where(np.arange(fe.NLIMBS) % 2 == 0, 51.0, -20.0)
    patterns = [np.full(fe.NLIMBS, 51.0), np.full(fe.NLIMBS, -20.0),
                alt, alt[::-1].copy()]
    patterns = [p.astype(np.float32) for p in patterns]
    cached = kernel_cases.check_products_at_bounds(
        fe, monkeypatch, patterns,
        lambda a, b: np.abs(a).max() * np.abs(b).max() <= 17641)
    for c in cached:
        c = np.asarray(c)
        assert c.min() >= -20 and c.max() <= 51


# ---------------------------------------------------------------------------
# End-to-end differential verification
# ---------------------------------------------------------------------------

def _make_cases():
    cases = []
    keys = [gen_priv_key() for _ in range(6)]
    for i, k in enumerate(keys):
        msg = f"height={i}".encode()
        cases.append((k.pub_key().bytes_(), msg, k.sign(msg)))
    pub, msg, sig = cases[0]
    cases.append((pub, msg, sig[:-1] + bytes([sig[-1] ^ 1])))
    cases.append((pub, b"other", sig))
    s = int.from_bytes(sig[32:], "little") + ref.L
    cases.append((pub, msg, sig[:32] + s.to_bytes(32, "little")))
    cases.append((pub, msg, sig[:32] + (ref.L + 12345).to_bytes(32, "little")))
    cases.append(((2).to_bytes(32, "little"), msg, sig))
    cases.append((pub, msg, (2).to_bytes(32, "little") + sig[32:]))
    torsion = ref.eight_torsion_points()
    s0 = bytes(32)
    for pt in torsion[:4]:
        for enc in ref.noncanonical_encodings(pt):
            cases.append((enc, b"any", enc + s0))
    ident_enc = ref.encode_point(ref.IDENTITY)
    cases.append((ident_enc, msg, sig))
    cases.append((pub[:31], msg, sig))
    cases.append((pub, msg, sig[:63]))
    for _ in range(4):
        cases.append(
            (secrets.token_bytes(32), secrets.token_bytes(8), secrets.token_bytes(64))
        )
    return cases


def test_differential_vs_reference_f32():
    cases = _make_cases()
    pubs = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    got = dev.verify_batch(pubs, msgs, sigs, impl="f32")
    want = [
        ref.verify(p, m, s) if len(p) == 32 and len(s) == 64 else False
        for p, m, s in zip(pubs, msgs, sigs)
    ]
    assert list(got) == want, [
        (i, bool(g), w) for i, (g, w) in enumerate(zip(got, want)) if bool(g) != w
    ]
    assert any(want) and not all(want)


def test_rfc8032_vector_on_f32():
    pub = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
    )
    assert list(dev.verify_batch([pub], [b""], [sig], impl="f32")) == [True]


def test_impls_agree_on_random_batch():
    """int64 and f32 backends must return identical verdict vectors."""
    keys = [gen_priv_key() for _ in range(8)]
    pubs, msgs, sigs = [], [], []
    for i, k in enumerate(keys):
        m = f"msg-{i}".encode()
        s = k.sign(m)
        if i % 3 == 2:
            s = bytes(64)
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(s)
    got_i64 = dev.verify_batch(pubs, msgs, sigs, impl="int64")
    got_f32 = dev.verify_batch(pubs, msgs, sigs, impl="f32")
    assert list(got_i64) == list(got_f32)

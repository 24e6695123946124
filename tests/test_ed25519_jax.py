"""Differential tests: JAX/XLA batch verifier vs the pure-Python ZIP-215
reference, over honest, tampered, and adversarial (small-order,
non-canonical) inputs."""

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as ref
from tendermint_tpu.crypto.keys import gen_priv_key

jax = pytest.importorskip("jax")

from tendermint_tpu.ops import ed25519_jax as dev  # noqa: E402
from tendermint_tpu.ops import fe25519 as fe  # noqa: E402

import kernel_cases  # noqa: E402


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
    import jax.numpy as jnp

    a_ints = [_rand_fe_int(rng) for _ in range(64)]
    b_ints = [_rand_fe_int(rng) for _ in range(64)]
    a = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in a_ints]))
    b = jnp.asarray(np.stack([fe.limbs_from_int(v) for v in b_ints]))
    out = np.asarray(fe.fe_canonical(fe.fe_mul(a, b)))
    for i in range(64):
        assert fe.int_from_limbs(out[i]) == (a_ints[i] * b_ints[i]) % ref.P, i


def test_fe_canonical_edge_patterns():
    """Freeze must canonicalize any bounded limb pattern, incl. values just
    above/below p and wide (unreduced) limbs."""
    import random

    import jax.numpy as jnp

    rng = random.Random(99)
    pats = []
    vals = []
    for _ in range(128):
        limbs = np.array(
            [rng.getrandbits(rng.choice([5, 17, 30, 40])) for _ in range(fe.NLIMBS)],
            dtype=np.int64,
        )
        pats.append(limbs)
        vals.append(sum(int(limbs[i]) << (fe.LIMB_BITS * i) for i in range(fe.NLIMBS)))
    for v in [0, 1, ref.P - 1, ref.P, ref.P + 1, (1 << 255) - 1]:
        pats.append(fe.limbs_from_int(v))
        vals.append(v)
    out = np.asarray(fe.fe_canonical(jnp.asarray(np.stack(pats))))
    for i, v in enumerate(vals):
        got = fe.int_from_limbs(out[i])
        assert got == v % ref.P, (i, got, v % ref.P)
        assert all(0 <= int(x) < (1 << fe.LIMB_BITS) for x in out[i])


def test_point_add_matches_reference():
    import random

    import jax.numpy as jnp

    rng = random.Random(7)
    pts = []
    for _ in range(8):
        k = rng.getrandbits(252)
        pts.append(ref.scalar_mult(k, ref.BASE))

    def to_dev(p):
        x, y, z, t = p
        zi = pow(z, ref.P - 2, ref.P)
        xa, ya = x * zi % ref.P, y * zi % ref.P
        return fe.Pt(
            jnp.asarray(fe.limbs_from_int(xa))[None, :],
            jnp.asarray(fe.limbs_from_int(ya))[None, :],
            jnp.asarray(fe.limbs_from_int(1))[None, :],
            jnp.asarray(fe.limbs_from_int(xa * ya % ref.P))[None, :],
        )

    for i in range(0, 8, 2):
        p, q = pts[i], pts[i + 1]
        got = fe.pt_add(to_dev(p), to_dev(q))
        want = ref.pt_add(p, q)
        zi = pow(
            fe.int_from_limbs(np.asarray(fe.fe_canonical(got.z))[0]), ref.P - 2, ref.P
        )
        gx = fe.int_from_limbs(np.asarray(fe.fe_canonical(got.x))[0]) * zi % ref.P
        gy = fe.int_from_limbs(np.asarray(fe.fe_canonical(got.y))[0]) * zi % ref.P
        wzi = pow(want[2], ref.P - 2, ref.P)
        assert gx == want[0] * wzi % ref.P
        assert gy == want[1] * wzi % ref.P


# ---------------------------------------------------------------------------
# Signed digits and precomputed-form additions, on both field backends
# (tests/kernel_cases.py holds the checks; each backend's own file holds them
# to its operand contract at the bounds)
# ---------------------------------------------------------------------------

IMPLS = pytest.mark.parametrize("impl", dev.IMPLS)
SIGNS = pytest.mark.parametrize("sign", [None, 1, -1])


@IMPLS
def test_signed_digits_exact(impl):
    kernel_cases.check_signed_digits(impl)


@IMPLS
@SIGNS
def test_pt_madd_matches_reference(impl, sign):
    kernel_cases.check_pt_madd(impl, sign)


@IMPLS
@SIGNS
def test_pt_add_cached_matches_reference(impl, sign):
    kernel_cases.check_pt_add_cached(impl, sign)


@IMPLS
def test_precomputed_additions_sign_is_per_row(impl):
    kernel_cases.check_mixed_signs(impl)


@IMPLS
def test_scalarmul_base_matches_reference(impl):
    kernel_cases.check_scalarmul_base(impl)


@IMPLS
def test_scalarmul_var_matches_reference(impl):
    kernel_cases.check_scalarmul_var(impl)


@IMPLS
def test_field_operation_counts(impl, monkeypatch):
    assert kernel_cases.check_op_counts(impl, monkeypatch) == \
        kernel_cases.OPS_PER_SIGNATURE


def test_unknown_impl_is_refused():
    """A saved plan or `warm --impls` can name anything: an unknown name
    raises, it does not run int64 under that label."""
    with pytest.raises(ValueError, match="unknown field impl 'f32'"):
        dev._field("f32")


def test_new_operations_at_input_ceiling(monkeypatch):
    """Reduced limbs at their ceiling (< 2^17.3) in every coordinate:
    every fe_mul operand stays under the 2^20 input ceiling, in both
    orders of the sign."""
    top = np.full(fe.NLIMBS, 161_000, dtype=np.int64)
    patterns = [top, fe.limbs_from_int(ref.P - 1), fe.ZERO, fe.ONE]
    cached = kernel_cases.check_products_at_bounds(
        fe, monkeypatch, patterns,
        lambda a, b: a.max() < (1 << 20) and b.max() < (1 << 20))
    for c in cached:
        assert 0 <= np.asarray(c).min() and np.asarray(c).max() < 2 ** 17.3


# ---------------------------------------------------------------------------
# End-to-end differential verification
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", list(kernel_cases.GAUNTLET))
@IMPLS
def test_gauntlet_case_matches_reference(impl, case):
    """Each case of the ZIP-215 adversarial gauntlet, on each field
    backend, against crypto/ed25519.verify (a row of the wrong length is
    False)."""
    assert kernel_cases.gauntlet_verdicts(impl)[case] == \
        kernel_cases.reference_verdict(*kernel_cases.GAUNTLET[case])


def test_gauntlet_exercises_both_outcomes():
    want = [kernel_cases.reference_verdict(*c)
            for c in kernel_cases.GAUNTLET.values()]
    assert len(want) == 27 and any(want) and not all(want)


def test_rfc8032_vector_on_device():
    pub = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"
    )
    assert list(dev.verify_batch([pub], [b""], [sig])) == [True]


def test_jax_batch_verifier_interface():
    from tendermint_tpu.crypto.batch import new_batch_verifier

    bv = new_batch_verifier("jax")
    keys = [gen_priv_key() for _ in range(5)]
    for i, k in enumerate(keys):
        m = f"m{i}".encode()
        sig = k.sign(m)
        if i == 3:
            sig = bytes(64)
        bv.add(k.pub_key(), m, sig)
    ok, oks = bv.verify()
    assert not ok
    assert oks == [True, True, True, False, True]
    assert bv.count() == 0


def test_carry_stress_at_worst_case_bounds():
    """The rounds=3 carry regime for multiply outputs, exercised at the
    worst representable inputs: all limbs at the pt_add/pt_dbl headroom
    ceiling (fe_sub outputs ~2^19.5).  Any under-carry shows up as a
    non-reduced limb or a wrong canonical value vs big-int math."""
    import jax.numpy as jnp
    import numpy as np

    from tendermint_tpu.ops import fe25519 as fe

    rng = np.random.default_rng(7)
    # worst case: limbs near 722k (the F-bound in pt_dbl) and mixed
    # random values, squared and multiplied repeatedly
    worst = np.full((4, fe.NLIMBS), 722_000, dtype=np.int64)
    rand = rng.integers(0, 1 << 19, size=(4, fe.NLIMBS), dtype=np.int64)
    for a in (worst, rand):
        for b in (worst, rand):
            got = np.asarray(fe.fe_mul(jnp.asarray(a), jnp.asarray(b)))
            assert got.max() < (1 << 18), f"limb not reduced: {got.max()}"
            for row_a, row_b, row_g in zip(a, b, got):
                va = fe.int_from_limbs(row_a)
                vb = fe.int_from_limbs(row_b)
                vg = fe.int_from_limbs(
                    np.asarray(fe.fe_canonical(jnp.asarray(row_g))))
                assert vg == (va * vb) % fe.P
        got = np.asarray(fe.fe_sq(jnp.asarray(a)))
        assert got.max() < (1 << 18)
        for row_a, row_g in zip(a, got):
            va = fe.int_from_limbs(row_a)
            vg = fe.int_from_limbs(np.asarray(fe.fe_canonical(jnp.asarray(row_g))))
            assert vg == (va * va) % fe.P

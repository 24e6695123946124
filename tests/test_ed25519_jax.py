"""Differential tests: JAX/XLA batch verifier vs the pure-Python ZIP-215
reference, over honest, tampered, and adversarial (small-order,
non-canonical) inputs."""

import secrets

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
# Signed digits and precomputed-form additions, on the three field backends
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


@pytest.mark.parametrize("impl", [
    "int64", "packed",
    # a 75 s XLA-CPU compile of the 51-limb loop body; tier-1 covers the
    # f32 loop end to end (test_differential_vs_reference_f32)
    pytest.param("f32", marks=pytest.mark.slow)])
def test_scalarmul_var_matches_reference(impl):
    kernel_cases.check_scalarmul_var(impl)


@IMPLS
def test_field_operation_counts(impl, monkeypatch):
    assert kernel_cases.check_op_counts(impl, monkeypatch) == \
        kernel_cases.OPS_PER_SIGNATURE


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

def _make_cases():
    """(pub, msg, sig) triples covering honest/tampered/adversarial space."""
    cases = []
    keys = [gen_priv_key() for _ in range(6)]
    for i, k in enumerate(keys):
        msg = f"height={i}".encode()
        cases.append((k.pub_key().bytes_(), msg, k.sign(msg)))
    # tampered signature
    pub, msg, sig = cases[0]
    cases.append((pub, msg, sig[:-1] + bytes([sig[-1] ^ 1])))
    # wrong message
    cases.append((pub, b"other", sig))
    # non-canonical s (s + L)
    s = int.from_bytes(sig[32:], "little") + ref.L
    cases.append((pub, msg, sig[:32] + s.to_bytes(32, "little")))
    # s >= L random
    cases.append((pub, msg, sig[:32] + (ref.L + 12345).to_bytes(32, "little")))
    # off-curve A (y=2 has no sqrt)
    cases.append(((2).to_bytes(32, "little"), msg, sig))
    # off-curve R
    cases.append((pub, msg, (2).to_bytes(32, "little") + sig[32:]))
    # small-order A and R with s=0: valid under cofactored ZIP-215
    torsion = ref.eight_torsion_points()
    s0 = bytes(32)
    for pt in torsion[:4]:
        for enc in ref.noncanonical_encodings(pt):
            cases.append((enc, b"any", enc + s0))
    # identity pubkey with honest-format sig
    ident_enc = ref.encode_point(ref.IDENTITY)
    cases.append((ident_enc, msg, sig))
    # malformed lengths
    cases.append((pub[:31], msg, sig))
    cases.append((pub, msg, sig[:63]))
    # random garbage
    for _ in range(4):
        cases.append(
            (secrets.token_bytes(32), secrets.token_bytes(8), secrets.token_bytes(64))
        )
    return cases


def test_differential_vs_reference():
    cases = _make_cases()
    pubs = [c[0] for c in cases]
    msgs = [c[1] for c in cases]
    sigs = [c[2] for c in cases]
    got = dev.verify_batch(pubs, msgs, sigs)
    want = [
        ref.verify(p, m, s) if len(p) == 32 and len(s) == 64 else False
        for p, m, s in zip(pubs, msgs, sigs)
    ]
    assert list(got) == want, [
        (i, bool(g), w) for i, (g, w) in enumerate(zip(got, want)) if bool(g) != w
    ]
    # sanity: the case set actually exercises both outcomes
    assert any(want) and not all(want)


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


# ---------------------------------------------------------------------------
# MXU one-hot fixed-base path (TM_TPU_BASE_MXU)
# ---------------------------------------------------------------------------

def test_scalarmul_base_mxu_matches_tree_and_reference():
    """The w=8 one-hot/matmul comb must agree with the w=4 select-tree
    comb (projectively) and with the big-int reference (affinely) for
    random and edge scalars, on BOTH field backends."""
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    svals = [0, 1, dev.L - 1] + [
        int.from_bytes(rng.bytes(32), "little") % dev.L for _ in range(5)
    ]
    s_rows_np = np.stack([
        np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8) for v in svals
    ])
    for impl in dev.IMPLS:
        if impl == "packed":
            # the comb's f32 constant table cannot hold 26-bit packed
            # limbs exactly — structurally incompatible, and
            # _resolve_optin never routes base_mxu to it (pinned in
            # test_optin_golden.test_base_mxu_never_consulted_for_packed)
            continue
        core = dev._Core(dev._field(impl))
        f = core.fe
        s_rows = jnp.asarray(s_rows_np)
        p_tree = core._scalarmul_base(
            core._signed_digits(core._nibbles_of(s_rows)))
        p_mxu = core._scalarmul_base_mxu(s_rows)
        ex = np.asarray(f.fe_eq(f.fe_mul(p_tree.x, p_mxu.z),
                                f.fe_mul(p_mxu.x, p_tree.z)))
        ey = np.asarray(f.fe_eq(f.fe_mul(p_tree.y, p_mxu.z),
                                f.fe_mul(p_mxu.y, p_tree.z)))
        assert ex.all() and ey.all(), (impl, ex, ey)
        # affine check against the big-int reference
        for i, v in enumerate(svals):
            want = ref.encode_point(ref.scalar_mult(v, ref.BASE))
            zi = [int(c) for c in np.asarray(f.fe_canonical(p_mxu.z))[i]]
            # reconstruct ints from limbs via the backend's radix
            def limbs_to_int(row):
                return sum(int(c) << (f.LIMB_BITS * j)
                           for j, c in enumerate(row)) % ref.P
            x = limbs_to_int(np.asarray(f.fe_canonical(p_mxu.x))[i])
            y = limbs_to_int(np.asarray(f.fe_canonical(p_mxu.y))[i])
            z = limbs_to_int(np.asarray(f.fe_canonical(p_mxu.z))[i])
            zinv = pow(z, ref.P - 2, ref.P)
            got = ref.encode_point((x * zinv % ref.P, y * zinv % ref.P, 1,
                                    x * zinv * y * zinv % ref.P))
            assert got == want, (impl, i, v)


@pytest.mark.slow
def test_base_mxu_end_to_end_verdicts(monkeypatch):
    """verify_batch with TM_TPU_BASE_MXU flipped on must return the exact
    verdicts of the default path on a mixed-validity batch (r5: the flag
    is env-resolved per call and golden-gated — tests/test_optin_golden
    covers the gate; this covers verdict parity end to end)."""
    monkeypatch.setenv("TM_TPU_BASE_MXU", "1")
    monkeypatch.setattr(dev, "_OPTIN_STATE", {})
    dev._compiled.cache_clear()
    try:
        privs = [gen_priv_key() for _ in range(8)]
        pubs = [p.pub_key().bytes_() for p in privs]
        msgs = [b"mxu-%d" % i for i in range(8)]
        sigs = [p.sign(m) for p, m in zip(privs, msgs)]
        sigs[3] = bytes(64)
        sigs[6] = sigs[6][:-1] + bytes([sigs[6][-1] ^ 1])
        oks = dev.verify_batch(pubs, msgs, sigs)
        assert [bool(v) for v in oks] == [
            True, True, True, False, True, True, False, True
        ]
    finally:
        dev._compiled.cache_clear()

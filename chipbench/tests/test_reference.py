"""The plain reference against known answers, and against the program's
own reference where both exist (importing the program in a TEST is fine;
the reference itself imports nothing of it)."""

import hashlib

import pytest
from cryptography.hazmat.primitives import serialization as ser
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from chipbench import control
from chipbench.reference import ed25519_zip215 as ref
from chipbench.reference.commit_rules import consulted_rows, expected_outcome
from chipbench.reference.signbytes import precommit_sign_bytes


def _signed(i):
    key = Ed25519PrivateKey.from_private_bytes(hashlib.sha256(b"t%d" % i).digest())
    pub = key.public_key().public_bytes(ser.Encoding.Raw, ser.PublicFormat.Raw)
    msg = b"message %d" % i
    return pub, msg, key.sign(msg)


def test_rfc8032_vector_1():
    pub = bytes.fromhex("d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a")
    sig = bytes.fromhex(
        "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e06522490155"
        "5fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b")
    assert ref.verify(pub, b"", sig)
    assert not ref.verify(pub, b"x", sig)


@pytest.mark.parametrize("i", range(6))
def test_honest_and_corrupted(i):
    pub, msg, sig = _signed(i)
    assert ref.verify(pub, msg, sig) and control.strict_verify(pub, msg, sig)
    assert not ref.verify(pub, msg, sig[:-1] + bytes([sig[-1] ^ 1]))
    assert not ref.verify(pub, msg + b"!", sig)
    s_plus_l = int.from_bytes(sig[32:], "little") + ref.L
    assert not ref.verify(pub, msg, sig[:32] + s_plus_l.to_bytes(32, "little"))


def test_small_order_rows_tell_zip215_from_strict():
    encs = ref.small_order_encodings()
    assert len(encs) == 14
    zip215 = strict = cofactorless = 0
    for a in encs:
        for r in encs:
            sig = r + bytes(32)
            zip215 += ref.verify(a, b"any message", sig)
            cofactorless += ref.verify(a, b"any message", sig, cofactored=False)
            strict += control.strict_verify(a, b"any message", sig)
    assert zip215 == len(encs) ** 2
    assert cofactorless < zip215 // 4 and strict < zip215 // 4


def test_program_reference_agrees():
    from tendermint_tpu.crypto import ed25519 as prog

    for a in ref.small_order_encodings()[:6]:
        sig = a + bytes(32)
        assert ref.verify(a, b"m", sig) == prog.verify(a, b"m", sig)
    pub, msg, sig = _signed(99)
    assert prog.verify(pub, msg, sig) and ref.verify(pub, msg, sig)


def test_sign_bytes_equal_the_programs():
    from tendermint_tpu.types.basic import BlockID, PartSetHeader, SignedMsgType
    from tendermint_tpu.types.canonical import vote_sign_bytes_raw

    for h, ts in ((7, 1_700_000_007 * 10**9 + 5), (1, 1_700_000_000 * 10**9),
                  (300, 1_700_000_000 * 10**9 + 999_999_999)):
        bid = BlockID(hash=hashlib.sha256(b"a%d" % h).digest(),
                      part_set_header=PartSetHeader(total=1, hash=hashlib.sha256(b"b").digest()))
        assert (vote_sign_bytes_raw("chipbench", SignedMsgType.PRECOMMIT, h, 0, bid, ts)
                == precommit_sign_bytes("chipbench", h, 0, bid.hash, 1,
                                        bid.part_set_header.hash, ts))


def test_commit_rules():
    powers = [10] * 1000
    assert consulted_rows("full", powers) == 1000
    assert consulted_rows("light", powers) == 667
    bad = {5: "x", 700: "x"}
    assert expected_outcome("full", powers, bad, lambda i: False) == ("wrong_signature", 5)
    assert expected_outcome("light", powers, {700: "x"}, lambda i: False) == ("accept", None)
    assert expected_outcome("full", powers, {700: "x"}, lambda i: False) == ("wrong_signature", 700)
    assert expected_outcome("full", powers, bad, lambda i: True) == ("accept", None)

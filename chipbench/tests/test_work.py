"""The roofline's work count and the table of peaks."""

import hashlib

import pytest
from cryptography.hazmat.primitives import serialization as ser
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

from chipbench import peaks, work
from chipbench.reference import ed25519_zip215 as ref


def test_field_muls_per_sig_is_what_the_reference_spends():
    counts = []
    for i in range(48):
        key = Ed25519PrivateKey.from_private_bytes(hashlib.sha256(b"w%d" % i).digest())
        pub = key.public_key().public_bytes(ser.Encoding.Raw, ser.PublicFormat.Raw)
        msg = b"count %d" % i
        counts.append(ref.count_field_muls(pub, msg, key.sign(msg)))
    mean = sum(counts) / len(counts)
    assert abs(mean - work.FIELD_MULS_PER_SIG) / work.FIELD_MULS_PER_SIG < 0.01, mean


def test_floor_is_compute_bound_on_v5e():
    floor_s, roof = work.floor_seconds_per_sig("TPU v5 lite")
    assert roof == "int8 compute"
    assert floor_s == pytest.approx(7880 * 2048 / 393e12)
    assert work.BYTES_PER_SIG / 819e9 < floor_s


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")

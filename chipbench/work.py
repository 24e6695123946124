"""What one signature costs by the algorithm, whatever implements it: the
operations and bytes the roofline divides by the kernel's time.

FIELD_MULS_PER_SIG — multiplications (squarings included) in GF(2^255-19)
that the PLAIN REFERENCE (chipbench/reference/ed25519_zip215.py) spends on
one ZIP-215 verification of an honest signature, counted once:

  2 decompressions (A, R), each 12.5 multiplications around one
    exponentiation by (p-5)/8 = 2^252 - 3, as square-and-multiply
    251 squarings + 250 multiplications ................... 2 x 513.5 = 1027
  [s]B and [k]A by double-and-add over ~252-bit scalars with half their
    bits set: (252 doublings + 126 additions) x 9 multiplications each
    (the unified addition; the reference doubles with it too) 2 x 3402 = 6804
  [s]B - [k]A - R, three doublings for the cofactor, the comparison
    with the identity ....................... 2 x 9 + 3 x 9 + 4 =   49
                                                                  ------
                                                                   7880

chipbench/tests/test_work.py counts it on seeded signatures (mean within
1 %).  A production kernel needs fewer (windowed tables), so this count is
generous to the kernel; the share it yields is still far below 1 % because
the kernel multiplies on the VPU, not on the int8 matrix unit the peak is
quoted for.

One field multiplication is taken as a 32 x 32 byte schoolbook product:
32^2 multiply-accumulates = 2 x 32^2 int8 operations.

Bytes: the prepared row in (public key, R, s, k: 4 x 32 bytes, and the
`valid` flag) plus the verdict out.
"""

from __future__ import annotations

from chipbench.peaks import peaks_for

FIELD_MULS_PER_SIG = 7880
INT8_OPS_PER_FIELD_MUL = 2 * 32 * 32
BYTES_PER_SIG = 4 * 32 + 1 + 1


def ops_per_sig() -> int:
    return FIELD_MULS_PER_SIG * INT8_OPS_PER_FIELD_MUL


def floor_seconds_per_sig(device_kind: str) -> tuple[float, str]:
    """The least time the chip could take for one signature, and which of
    the two roofs sets it."""
    pk = peaks_for(device_kind)
    compute = ops_per_sig() / pk["int8_ops_per_s"]
    memory = BYTES_PER_SIG / pk["hbm_bytes_per_s"]
    return (compute, "int8 compute") if compute >= memory else (memory, "HBM")

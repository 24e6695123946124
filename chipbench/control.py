"""The control of `correct`: a verifier that breaks one guarantee the
configurations state, put in the program's place.

The guarantee broken is ZIP-215's cofactored, permissive verification: the
control is the STRICT RFC 8032 verifier of OpenSSL (through `cryptography`,
nothing of the program) — cofactorless equation, canonical encodings only.
It is the step that would tempt a later PR: the program's own fast host
path is this verifier plus a ZIP-215 re-check of what it refuses, and
dropping the re-check is faster.  On honest rows the two agree; on the
small-order rows every commit carries, the control refuses where ZIP-215
accepts, so a run with the control in place reads `calls_wrong` > 0.

`bound(entry, data)` returns a callable in the place of the entry's
`bind(data)`: it verifies every consulted row of the item with the strict
verifier (the cell's own size) and answers what the entry's rule gives
from those verdicts.
"""

from __future__ import annotations

import functools

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey


@functools.lru_cache(maxsize=None)
def _key(pub: bytes) -> Ed25519PublicKey | None:
    try:
        return Ed25519PublicKey.from_public_bytes(pub)
    except ValueError:
        return None


def strict_verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
    key = _key(pub)
    if key is None:
        return False
    try:
        key.verify(sig, msg)
        return True
    except InvalidSignature:
        return False


def bound(entry, d):
    def call(item) -> tuple:
        oks = [strict_verify(*item.row(i)) for i in range(item.n_rows)]
        return entry.expected(d, item, lambda i: oks[i])

    return call

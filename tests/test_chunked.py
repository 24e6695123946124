"""Finer buckets + pipelined chunked dispatch (VERDICT r4 item 2).

The bucket ladder gains 3*2^(k-1) intermediate shapes (96, 192, ...,
12288) so measured worst-case padding is 1.49x (n=129→192; <=1.34x from
the 320 rung up), and verify_batch splits large
batches into TM_TPU_CHUNK-sized sub-batches whose host prep overlaps
device execution.  Verdicts must be bit-identical to the unchunked
program for every split."""

import numpy as np

from tendermint_tpu.crypto.keys import priv_key_from_seed
from tendermint_tpu.ops import ed25519_jax as dev


def test_bucket_ladder():
    assert [dev._bucket(n) for n in (1, 8, 9, 16, 33, 64, 65, 96, 97,
                                     128, 129, 200)] == \
        [8, 8, 16, 16, 64, 64, 96, 96, 128, 128, 192, 256]
    # 5*2^(k-2) rungs from 320 up
    assert [dev._bucket(n) for n in (300, 321, 500, 600)] == \
        [320, 384, 512, 640]
    # the north-star shape: 10k pads 1.024x, not 1.64x
    assert dev._bucket(10_000) == 10_240
    assert dev._bucket(10_241) == 12_288
    assert dev._bucket(12_289) == 16_384
    assert dev._bucket(16_384) == 16_384


def test_chunks_of():
    assert dev.chunks_of(10_000, 4096) == [
        (0, 4096, 4096), (4096, 8192, 4096), (8192, 10_000, 2048)]
    assert dev.chunks_of(4096, 4096) == [(0, 4096, 4096)]
    assert dev.chunks_of(5, 4096) == [(0, 5, 8)]


def _batch(n, bad=()):
    pubs, msgs, sigs, want = [], [], [], []
    for i in range(n):
        k = priv_key_from_seed(bytes([(i % 250) + 1]) * 32)
        m = b"chunk-%d" % i
        s = k.sign(m)
        ok = True
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
            ok = False
        pubs.append(k.pub_key().bytes_())
        msgs.append(m)
        sigs.append(s)
        want.append(ok)
    return pubs, msgs, sigs, want


def test_chunked_verdicts_match_unchunked(monkeypatch):
    """n=20 with chunk=8 exercises the full pipeline (2 full chunks + a
    padded tail) on small, already-compiled buckets."""
    pubs, msgs, sigs, want = _batch(20, bad=(3, 11, 19))
    monkeypatch.setenv("TM_TPU_CHUNK", "8")
    got = [bool(v) for v in dev.verify_batch(pubs, msgs, sigs, impl="int64")]
    assert got == want
    monkeypatch.setenv("TM_TPU_CHUNK", "0")
    single = [bool(v) for v in dev.verify_batch(pubs, msgs, sigs, impl="int64")]
    assert single == got


def test_chunk_size_env_resolved_per_call(monkeypatch):
    monkeypatch.setenv("TM_TPU_CHUNK", "123")
    assert dev._chunk_size() == 123
    # default 0 = off (see the _chunk_size docstring)
    monkeypatch.setenv("TM_TPU_CHUNK", "garbage")
    assert dev._chunk_size() == 0
    monkeypatch.delenv("TM_TPU_CHUNK")
    assert dev._chunk_size() == 0


def test_negative_chunk_clamps_to_disabled(monkeypatch):
    """ADVICE r5: TM_TPU_CHUNK=-1 used to pass the `chunk and n > chunk`
    guard, build an empty chunk plan, and crash verify_batch inside
    np.concatenate([]).  A negative misconfig must clamp to 0 (chunking
    disabled) and verify identically to the unchunked program."""
    monkeypatch.setenv("TM_TPU_CHUNK", "-1")
    assert dev._chunk_size() == 0
    monkeypatch.setenv("TM_TPU_CHUNK", "-4096")
    assert dev._chunk_size() == 0
    pubs, msgs, sigs, want = _batch(12, bad=(7,))
    got = [bool(v) for v in dev.verify_batch(pubs, msgs, sigs, impl="int64")]
    assert got == want


def test_chunked_output_is_contiguous_bool_array(monkeypatch):
    pubs, msgs, sigs, want = _batch(17)
    monkeypatch.setenv("TM_TPU_CHUNK", "8")
    out = dev.verify_batch(pubs, msgs, sigs, impl="int64")
    assert isinstance(out, np.ndarray) and out.dtype == bool
    assert out.shape == (17,)
    assert out.all()

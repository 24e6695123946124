// Batched host-side Ed25519 verification preprocessing.
//
// The device program (tendermint_tpu/ops/ed25519_jax.py) needs
// k = SHA-512(R || A || M) mod L per signature.  Computing that in a
// Python loop costs ~4.7us/row (~50ms for a 10k-validator commit — 25x
// the BASELINE.md 2ms end-to-end target), so this kernel does the whole
// batch in one C call: a self-contained SHA-512 (FIPS 180-4; no OpenSSL
// headers in the image) and a Barrett reduction mod the Ed25519 group
// order, chunked across hardware threads.
//
// Plays the role the reference delegates to native deps (SURVEY §2.8);
// reference counterpart of the math: the scalar clamp/reduce inside
// ed25519consensus (crypto/ed25519/ed25519.go:149-156's verify path).
//
// Exposed C ABI (ctypes):
//   tmed_batch_k(n, r32cat, pub32cat, msgbuf, offsets, out32cat, nthreads)
//     r32cat/pub32cat: n*32 bytes each (R rows, A rows)
//     msgbuf + offsets: messages concatenated; offsets is uint64[n+1]
//     out32cat: n*32 bytes, little-endian k rows
//   tmed_sha512(data, len, out64): single hash (for tests)

#include <cstdint>
#include <cstring>
#include <dlfcn.h>
#include <thread>
#include <vector>

typedef unsigned __int128 u128;

// ---------------------------------------------------------------------------
// SHA-512 (FIPS 180-4)
// ---------------------------------------------------------------------------

static const uint64_t K[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

static inline uint64_t rotr(uint64_t x, int n) {
  return (x >> n) | (x << (64 - n));
}

struct Sha512 {
  uint64_t h[8];
  uint8_t buf[128];
  size_t buflen;
  uint64_t total;

  void init() {
    static const uint64_t iv[8] = {
        0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
        0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
        0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
    memcpy(h, iv, sizeof iv);
    buflen = 0;
    total = 0;
  }

  void block(const uint8_t* p) {
    uint64_t w[80];
    for (int i = 0; i < 16; i++) {
      w[i] = ((uint64_t)p[8 * i] << 56) | ((uint64_t)p[8 * i + 1] << 48) |
             ((uint64_t)p[8 * i + 2] << 40) | ((uint64_t)p[8 * i + 3] << 32) |
             ((uint64_t)p[8 * i + 4] << 24) | ((uint64_t)p[8 * i + 5] << 16) |
             ((uint64_t)p[8 * i + 6] << 8) | (uint64_t)p[8 * i + 7];
    }
    for (int i = 16; i < 80; i++) {
      uint64_t s0 = rotr(w[i - 15], 1) ^ rotr(w[i - 15], 8) ^ (w[i - 15] >> 7);
      uint64_t s1 = rotr(w[i - 2], 19) ^ rotr(w[i - 2], 61) ^ (w[i - 2] >> 6);
      w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    uint64_t a = h[0], b = h[1], c = h[2], d = h[3];
    uint64_t e = h[4], f = h[5], g = h[6], hh = h[7];
    for (int i = 0; i < 80; i++) {
      uint64_t S1 = rotr(e, 14) ^ rotr(e, 18) ^ rotr(e, 41);
      uint64_t ch = (e & f) ^ (~e & g);
      uint64_t t1 = hh + S1 + ch + K[i] + w[i];
      uint64_t S0 = rotr(a, 28) ^ rotr(a, 34) ^ rotr(a, 39);
      uint64_t maj = (a & b) ^ (a & c) ^ (b & c);
      uint64_t t2 = S0 + maj;
      hh = g; g = f; f = e; e = d + t1;
      d = c; c = b; b = a; a = t1 + t2;
    }
    h[0] += a; h[1] += b; h[2] += c; h[3] += d;
    h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
  }

  void update(const uint8_t* p, size_t n) {
    total += n;
    if (buflen) {
      size_t take = 128 - buflen;
      if (take > n) take = n;
      memcpy(buf + buflen, p, take);
      buflen += take;
      p += take;
      n -= take;
      if (buflen == 128) {
        block(buf);
        buflen = 0;
      }
    }
    while (n >= 128) {
      block(p);
      p += 128;
      n -= 128;
    }
    if (n) {
      memcpy(buf, p, n);
      buflen = n;
    }
  }

  void final(uint8_t out[64]) {
    uint64_t bits = total * 8;
    uint8_t pad = 0x80;
    update(&pad, 1);
    uint8_t z = 0;
    while (buflen != 112) update(&z, 1);
    uint8_t lenb[16] = {0};
    for (int i = 0; i < 8; i++) lenb[15 - i] = (uint8_t)(bits >> (8 * i));
    update(lenb, 16);
    for (int i = 0; i < 8; i++)
      for (int j = 0; j < 8; j++) out[8 * i + j] = (uint8_t)(h[i] >> (56 - 8 * j));
  }
};

// ---------------------------------------------------------------------------
// Barrett reduction mod L = 2^252 + 27742317777372353535851937790883648493
// ---------------------------------------------------------------------------

static const uint64_t L_LIMBS[4] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL,
                                    0x0ULL, 0x1000000000000000ULL};
// mu = floor(2^512 / L), 260 bits
static const uint64_t MU[5] = {0xed9ce5a30a2c131bULL, 0x2106215d086329a7ULL,
                               0xffffffffffffffebULL, 0xffffffffffffffffULL,
                               0xfULL};

// r = h mod L; h is 8 little-endian u64 limbs (the SHA-512 digest read
// little-endian, Ed25519 convention), out is 4 limbs (fits: L < 2^253).
static void mod_L(const uint64_t h8[8], uint64_t out[4]) {
  // q_hat = floor(h * mu / 2^512): full 8x5 product, keep limbs 8..12
  uint64_t prod[13] = {0};
  for (int i = 0; i < 8; i++) {
    u128 carry = 0;
    for (int j = 0; j < 5; j++) {
      u128 cur = (u128)h8[i] * MU[j] + prod[i + j] + carry;
      prod[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    prod[i + 5] += (uint64_t)carry;
  }
  uint64_t q[5];
  for (int i = 0; i < 5; i++) q[i] = prod[8 + i];

  // r = (h - q*L) mod 2^320 — fits in 5 limbs; true remainder < 3L
  uint64_t ql[5] = {0};
  for (int i = 0; i < 5; i++) {
    u128 carry = 0;
    for (int j = 0; j < 4 && i + j < 5; j++) {
      u128 cur = (u128)q[i] * L_LIMBS[j] + ql[i + j] + carry;
      ql[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    if (i + 4 < 5) ql[i + 4] += (uint64_t)carry;
  }
  uint64_t r[5];
  u128 borrow = 0;
  for (int i = 0; i < 5; i++) {
    u128 cur = (u128)(i < 8 ? h8[i] : 0) - ql[i] - borrow;
    r[i] = (uint64_t)cur;
    borrow = (cur >> 64) & 1;  // 1 when the subtraction wrapped
  }

  // at most a few conditional subtractions of L (Barrett bound)
  for (int iter = 0; iter < 4; iter++) {
    // compare r >= L (r has 5 limbs; L's limb 4 is 0)
    bool ge = r[4] != 0;
    if (!ge) {
      ge = true;
      for (int i = 3; i >= 0; i--) {
        if (r[i] != L_LIMBS[i]) {
          ge = r[i] > L_LIMBS[i];
          break;
        }
      }
    }
    if (!ge) break;
    u128 b2 = 0;
    for (int i = 0; i < 5; i++) {
      u128 cur = (u128)r[i] - (i < 4 ? L_LIMBS[i] : 0) - b2;
      r[i] = (uint64_t)cur;
      b2 = (cur >> 64) & 1;
    }
  }
  for (int i = 0; i < 4; i++) out[i] = r[i];
}

// ---------------------------------------------------------------------------
// batch driver
// ---------------------------------------------------------------------------

static void batch_range(size_t lo, size_t hi, const uint8_t* r32,
                        const uint8_t* pub32, const uint8_t* msgbuf,
                        const uint64_t* offsets, uint8_t* out32) {
  for (size_t i = lo; i < hi; i++) {
    Sha512 s;
    s.init();
    s.update(r32 + 32 * i, 32);
    s.update(pub32 + 32 * i, 32);
    s.update(msgbuf + offsets[i], offsets[i + 1] - offsets[i]);
    uint8_t digest[64];
    s.final(digest);
    uint64_t h8[8];
    for (int j = 0; j < 8; j++) {
      uint64_t v = 0;
      for (int b = 7; b >= 0; b--) v = (v << 8) | digest[8 * j + b];
      h8[j] = v;  // little-endian u64 limbs of the LE-interpreted digest
    }
    uint64_t k4[4];
    mod_L(h8, k4);
    for (int j = 0; j < 4; j++)
      for (int b = 0; b < 8; b++)
        out32[32 * i + 8 * j + b] = (uint8_t)(k4[j] >> (8 * b));
  }
}

// ---------------------------------------------------------------------------
// batched canonical sign-bytes assembly
//
// Within one commit the canonical precommit bytes differ per signature
// only by BlockID flavor (COMMIT vs NIL/ABSENT prefix) and timestamp,
// so the Python layer ships the two prefix templates + the chain-id
// suffix once and this kernel emits every delimited row.  The Python
// template fast path still costs ~4 us/row (40 ms for a 10k commit —
// 20x the BASELINE 2 ms target); this is ~40 ns/row.
// Byte-identity contract: google.protobuf.Timestamp{seconds=1,nanos=2}
// with omit-if-zero fields (types/basic.py encode_timestamp), field 5
// tag 0x2a, outer varint length delimiter (canonical.py
// vote_sign_bytes_raw) — differential-tested from Python.
// ---------------------------------------------------------------------------

static inline int put_uvarint(uint8_t* p, uint64_t v) {
  int i = 0;
  while (v >= 0x80) {
    p[i++] = (uint8_t)(v | 0x80);
    v >>= 7;
  }
  p[i++] = (uint8_t)v;
  return i;
}

extern "C" {

// Returns total bytes written, or 0 when `cap` is insufficient (callers
// size cap = n * (max_prefix + suffix + 30) which always suffices).
// flags[i] != 0 selects the block prefix, else the nil prefix.
uint64_t tmed_batch_sign_bytes(
    uint64_t n, const uint8_t* prefix_block, uint64_t pb_len,
    const uint8_t* prefix_nil, uint64_t pn_len, const uint8_t* suffix,
    uint64_t suf_len, const uint8_t* flags, const int64_t* ts_sec,
    const int32_t* ts_nanos, uint8_t* out, uint64_t cap,
    uint64_t* offsets) {
  // seconds/nanos are pre-split by the caller (Python divmod is exact
  // for timestamps beyond int64-nanosecond range, e.g. Go's zero time)
  uint64_t pos = 0;
  for (uint64_t i = 0; i < n; i++) {
    int64_t s = ts_sec[i];
    int64_t nan = ts_nanos[i];
    uint8_t ts[24];
    int tlen = 0;
    if (s != 0) {
      ts[tlen++] = 0x08;
      tlen += put_uvarint(ts + tlen, (uint64_t)s);  // two's-complement
    }
    if (nan != 0) {
      ts[tlen++] = 0x10;
      tlen += put_uvarint(ts + tlen, (uint64_t)nan);
    }
    const uint8_t* pre = flags[i] ? prefix_block : prefix_nil;
    uint64_t plen = flags[i] ? pb_len : pn_len;
    uint64_t body = plen + 1 + 1 + (uint64_t)tlen + suf_len;  // 0x2a len ts
    if (pos + body + 10 > cap) return 0;
    offsets[i] = pos;
    pos += (uint64_t)put_uvarint(out + pos, body);
    memcpy(out + pos, pre, plen);
    pos += plen;
    out[pos++] = 0x2a;
    out[pos++] = (uint8_t)tlen;  // tlen <= 23 < 0x80: single-byte varint
    memcpy(out + pos, ts, (size_t)tlen);
    pos += (uint64_t)tlen;
    memcpy(out + pos, suffix, suf_len);
    pos += suf_len;
  }
  offsets[n] = pos;
  return pos;
}

void tmed_sha512(const uint8_t* data, uint64_t len, uint8_t out[64]) {
  Sha512 s;
  s.init();
  s.update(data, (size_t)len);
  s.final(out);
}

// ---------------------------------------------------------------------------
// Batched libcrypto Ed25519 verification
//
// The CPU production path (crypto/batch.py CPUBatchVerifier →
// ed25519.verify_batch_fast) was a Python loop over libcrypto via the
// `cryptography` binding: ~45us/sig of which several us are Python
// dispatch, and the binding holds the GIL so threads give 0x.  This
// kernel verifies the WHOLE batch in one C call — no per-item FFI, GIL
// released for the duration, chunked across hardware threads (the
// multi-core CPU scaling the Python loop structurally cannot have).
//
// The image ships /usr/lib/x86_64-linux-gnu/libcrypto.so.3 but no
// OpenSSL headers, so the six EVP entry points are declared by hand and
// resolved with dlopen/dlsym at first use.  Semantics: OpenSSL verify
// is cofactorless RFC 8032 with canonical checks — acceptance implies
// ZIP-215 acceptance (see ed25519.verify_fast); every REJECTED row is
// re-checked by the caller against the pure ZIP-215 reference, so
// verdicts stay bit-identical to the consensus rules.
// ---------------------------------------------------------------------------

void tmed_batch_k(uint64_t n, const uint8_t* r32, const uint8_t* pub32,
                  const uint8_t* msgbuf, const uint64_t* offsets,
                  uint8_t* out32, int nthreads) {
  if (n == 0) return;
  unsigned hw = std::thread::hardware_concurrency();
  if (nthreads <= 0) nthreads = hw ? (int)hw : 1;
  size_t per = ((size_t)n + nthreads - 1) / nthreads;
  if (nthreads == 1 || n < 256) {
    batch_range(0, (size_t)n, r32, pub32, msgbuf, offsets, out32);
    return;
  }
  std::vector<std::thread> ts;
  for (int t = 0; t < nthreads; t++) {
    size_t lo = t * per, hi = lo + per;
    if (lo >= n) break;
    if (hi > n) hi = (size_t)n;
    ts.emplace_back(batch_range, lo, hi, r32, pub32, msgbuf, offsets, out32);
  }
  for (auto& t : ts) t.join();
}

// -- libcrypto EVP surface (hand-declared; see comment above tmed_batch_k) --

typedef struct evp_pkey_st EVP_PKEY;
typedef struct evp_md_ctx_st EVP_MD_CTX;

struct EvpApi {
  EVP_PKEY* (*new_raw_pub)(int, void*, const unsigned char*, size_t);
  void (*pkey_free)(EVP_PKEY*);
  EVP_MD_CTX* (*ctx_new)(void);
  void (*ctx_free)(EVP_MD_CTX*);
  int (*ctx_reset)(EVP_MD_CTX*);
  int (*dv_init)(EVP_MD_CTX*, void**, const void*, void*, EVP_PKEY*);
  int (*dv)(EVP_MD_CTX*, const unsigned char*, size_t, const unsigned char*,
            size_t);
  bool ok;
};

static EvpApi load_evp_api() {
  EvpApi a;
  memset(&a, 0, sizeof(a));
  void* h = dlopen("libcrypto.so.3", RTLD_NOW | RTLD_LOCAL);
  if (!h) h = dlopen("libcrypto.so.1.1", RTLD_NOW | RTLD_LOCAL);
  if (!h) return a;
  a.new_raw_pub = (EVP_PKEY * (*)(int, void*, const unsigned char*, size_t))
      dlsym(h, "EVP_PKEY_new_raw_public_key");
  a.pkey_free = (void (*)(EVP_PKEY*))dlsym(h, "EVP_PKEY_free");
  a.ctx_new = (EVP_MD_CTX * (*)(void)) dlsym(h, "EVP_MD_CTX_new");
  a.ctx_free = (void (*)(EVP_MD_CTX*))dlsym(h, "EVP_MD_CTX_free");
  a.ctx_reset = (int (*)(EVP_MD_CTX*))dlsym(h, "EVP_MD_CTX_reset");
  a.dv_init = (int (*)(EVP_MD_CTX*, void**, const void*, void*, EVP_PKEY*))
      dlsym(h, "EVP_DigestVerifyInit");
  a.dv = (int (*)(EVP_MD_CTX*, const unsigned char*, size_t,
                  const unsigned char*, size_t))dlsym(h, "EVP_DigestVerify");
  a.ok = a.new_raw_pub && a.pkey_free && a.ctx_new && a.ctx_free &&
         a.ctx_reset && a.dv_init && a.dv;
  return a;
}

static const EvpApi& evp_api() {
  static EvpApi a = load_evp_api();
  return a;
}

static const int kEvpPkeyEd25519 = 1087;  // NID_ED25519, stable ABI constant

static void verify_range(size_t lo, size_t hi, const uint8_t* pub32,
                         const uint8_t* sig64, const uint8_t* msgbuf,
                         const uint64_t* offsets, uint8_t* out) {
  const EvpApi& a = evp_api();
  // one ctx per range, EVP_MD_CTX_reset between signatures: a ctx that
  // has completed a one-shot EdDSA EVP_DigestVerify cannot be re-inited
  // without a reset (observed: every row after the first reported
  // failure), but reset+reinit is clean and saves an alloc/free pair
  // per signature
  EVP_MD_CTX* ctx = a.ctx_new();
  if (!ctx) {
    memset(out + lo, 0, hi - lo);
    return;
  }
  for (size_t i = lo; i < hi; i++) {
    out[i] = 0;
    EVP_PKEY* pk = a.new_raw_pub(kEvpPkeyEd25519, nullptr, pub32 + 32 * i, 32);
    if (!pk) continue;
    // md type is NULL for Ed25519 (pure EdDSA, one-shot)
    if (a.dv_init(ctx, nullptr, nullptr, nullptr, pk) == 1) {
      int rc = a.dv(ctx, sig64 + 64 * i, 64, msgbuf + offsets[i],
                    (size_t)(offsets[i + 1] - offsets[i]));
      out[i] = (rc == 1) ? 1 : 0;
    }
    a.pkey_free(pk);
    a.ctx_reset(ctx);
  }
  a.ctx_free(ctx);
}

int tmed_have_libcrypto(void) { return evp_api().ok ? 1 : 0; }

// Returns 0 on success (out[i] = 1 accept / 0 reject-or-recheck),
// -1 when libcrypto is unavailable (caller falls back to Python loop).
int tmed_batch_verify(uint64_t n, const uint8_t* pub32, const uint8_t* sig64,
                      const uint8_t* msgbuf, const uint64_t* offsets,
                      uint8_t* out, int nthreads) {
  if (!evp_api().ok) return -1;
  if (n == 0) return 0;
  unsigned hw = std::thread::hardware_concurrency();
  if (nthreads <= 0) nthreads = hw ? (int)hw : 1;
  size_t per = ((size_t)n + nthreads - 1) / nthreads;
  if (nthreads == 1 || n < 64) {
    verify_range(0, (size_t)n, pub32, sig64, msgbuf, offsets, out);
    return 0;
  }
  std::vector<std::thread> ts;
  for (int t = 0; t < nthreads; t++) {
    size_t lo = t * per, hi = lo + per;
    if (lo >= n) break;
    if (hi > n) hi = (size_t)n;
    ts.emplace_back(verify_range, lo, hi, pub32, sig64, msgbuf, offsets, out);
  }
  for (auto& t : ts) t.join();
  return 0;
}

}  // extern "C"

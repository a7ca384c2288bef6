// weedtpu native runtime library.
//
// C++ equivalents of the reference's native-performance dependencies:
//  - GF(2^8) Reed-Solomon coding kernels (reference: the AVX2 assembly inside
//    klauspost/reedsolomon v1.12.1, go.mod:61, driven by
//    weed/storage/erasure_coding/ec_encoder.go:120-196).  Same field
//    (poly 0x11D) and the same low/high-nibble split-table scheme the
//    assembly uses, expressed as AVX2 pshufb intrinsics with a scalar
//    fallback.  This is the CPU codec backend and the honest baseline the
//    TPU Pallas kernel is benchmarked against.
//  - CRC32C (Castagnoli) with SSE4.2 hardware instructions (reference:
//    needle checksums, weed/storage/needle/crc.go).
//  - AES-256-GCM and AES-256-CTR (reference: weed/util/cipher.go encrypts
//    chunks with AES-256-GCM).  4-wide AES-NI CTR with a portable fallback;
//    GHASH via Shoup-style 16x256 tables derived from the bit-level
//    reference multiply.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this image).

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <mutex>

#if defined(__x86_64__)
#include <immintrin.h>
#include <cpuid.h>
#endif

extern "C" {

// ---------------------------------------------------------------------------
// GF(2^8), poly 0x11D (matches ops/gf.py and Backblaze/klauspost tables)
// ---------------------------------------------------------------------------

static uint8_t GF_MUL[256][256];
// Split tables: for each coefficient c, MUL_LO[c][x] = c*(x) for x in 0..15
// (low nibble), MUL_HI[c][x] = c*(x<<4).  c*b = MUL_LO[c][b&15] ^ MUL_HI[c][b>>4].
static uint8_t MUL_LO[256][16];
static uint8_t MUL_HI[256][16];
// GFNI affine matrices: multiply-by-c over GF(2^8) is GF(2)-linear, so it is
// one 8x8 bit-matrix — GF2P8AFFINEQB applies it to 64 bytes per instruction.
// Layout per the ISA: result bit b of each byte = parity(A.byte[7-b] & x),
// so A.byte[7-b] bit t = bit b of (c * 2^t).
static uint64_t GF_AFFINE[256];
static int gf_initialized = 0;

static uint8_t gf_mul_slow(uint8_t a, uint8_t b) {
  uint16_t r = 0;
  uint16_t aa = a;
  while (b) {
    if (b & 1) r ^= aa;
    aa <<= 1;
    if (aa & 0x100) aa ^= 0x11D;
    b >>= 1;
  }
  return (uint8_t)r;
}

void wn_gf_init(void) {
  if (gf_initialized) return;
  for (int a = 0; a < 256; a++)
    for (int b = 0; b < 256; b++)
      GF_MUL[a][b] = gf_mul_slow((uint8_t)a, (uint8_t)b);
  for (int c = 0; c < 256; c++) {
    for (int x = 0; x < 16; x++) {
      MUL_LO[c][x] = GF_MUL[c][x];
      MUL_HI[c][x] = GF_MUL[c][x << 4];
    }
  }
  for (int c = 0; c < 256; c++) {
    uint64_t A = 0;
    for (int b = 0; b < 8; b++) {
      uint8_t row = 0;
      for (int t = 0; t < 8; t++)
        if ((GF_MUL[c][1 << t] >> b) & 1) row = (uint8_t)(row | (1u << t));
      A |= (uint64_t)row << (8 * (7 - b));
    }
    GF_AFFINE[c] = A;
  }
  gf_initialized = 1;
}

uint8_t wn_gf_mul(uint8_t a, uint8_t b) {
  wn_gf_init();
  return GF_MUL[a][b];
}

#if defined(__AVX2__)
// out[i] (^)= c * in[i] over n bytes, AVX2 pshufb split-table kernel —
// the same scheme as klauspost/reedsolomon's galMulAVX2 assembly.
static void gf_mul_slice_avx2(uint8_t c, const uint8_t* in, uint8_t* out,
                              size_t n, int accumulate) {
  const __m256i lo_tbl = _mm256_broadcastsi128_si256(
      _mm_loadu_si128((const __m128i*)MUL_LO[c]));
  const __m256i hi_tbl = _mm256_broadcastsi128_si256(
      _mm_loadu_si128((const __m128i*)MUL_HI[c]));
  const __m256i mask = _mm256_set1_epi8(0x0F);
  size_t i = 0;
  for (; i + 32 <= n; i += 32) {
    __m256i v = _mm256_loadu_si256((const __m256i*)(in + i));
    __m256i lo = _mm256_and_si256(v, mask);
    __m256i hi = _mm256_and_si256(_mm256_srli_epi64(v, 4), mask);
    __m256i r = _mm256_xor_si256(_mm256_shuffle_epi8(lo_tbl, lo),
                                 _mm256_shuffle_epi8(hi_tbl, hi));
    if (accumulate)
      r = _mm256_xor_si256(r, _mm256_loadu_si256((const __m256i*)(out + i)));
    _mm256_storeu_si256((__m256i*)(out + i), r);
  }
  for (; i < n; i++) {
    uint8_t r = (uint8_t)(MUL_LO[c][in[i] & 15] ^ MUL_HI[c][in[i] >> 4]);
    out[i] = accumulate ? (uint8_t)(out[i] ^ r) : r;
  }
}
#endif

__attribute__((unused)) static void gf_mul_slice_scalar(uint8_t c, const uint8_t* in, uint8_t* out,
                                size_t n, int accumulate) {
  const uint8_t* row = GF_MUL[c];
  if (accumulate) {
    for (size_t i = 0; i < n; i++) out[i] ^= row[in[i]];
  } else {
    for (size_t i = 0; i < n; i++) out[i] = row[in[i]];
  }
}

// out (^)= c * in over n bytes.
void wn_gf_mul_slice(uint8_t c, const uint8_t* in, uint8_t* out, size_t n,
                     int accumulate) {
  wn_gf_init();
  if (c == 0) {
    if (!accumulate) memset(out, 0, n);
    return;
  }
  if (c == 1) {
    if (accumulate) {
#if defined(__AVX2__)
      size_t i = 0;
      for (; i + 32 <= n; i += 32) {
        __m256i r = _mm256_xor_si256(
            _mm256_loadu_si256((const __m256i*)(in + i)),
            _mm256_loadu_si256((const __m256i*)(out + i)));
        _mm256_storeu_si256((__m256i*)(out + i), r);
      }
      for (; i < n; i++) out[i] ^= in[i];
#else
      for (size_t i = 0; i < n; i++) out[i] ^= in[i];
#endif
    } else {
      memmove(out, in, n);
    }
    return;
  }
#if defined(__AVX2__)
  gf_mul_slice_avx2(c, in, out, n, accumulate);
#else
  gf_mul_slice_scalar(c, in, out, n, accumulate);
#endif
}

// out[rows x n] = mat[rows x k] . in[k x n] over GF(2^8).
// Rows may live in scattered buffers (ptr-per-row), which lets the encode
// path feed the kernel straight from an mmap of the volume .dat with no
// staging copy.  This is the whole RS encode when `mat` is the parity
// sub-matrix, and the whole decode when `mat` is the inverted recovery
// matrix (reference hot loop: ec_encoder.go:120-196 enc.Encode).
#if defined(__AVX2__)
// Up to 4 output rows at once, accumulated in ymm registers across the k
// inputs: each input byte is read exactly once per row-group and each output
// byte written exactly once (the klauspost mulAvxTwo_NxM codegen scheme).
static void gf_matmul_avx2_group(const uint8_t* mat, int r0, int nrows, int k,
                                 const uint8_t* const* in_rows,
                                 uint8_t* const* out_rows, size_t n) {
  const __m256i mask = _mm256_set1_epi8(0x0F);
  size_t col = 0;
  for (; col + 64 <= n; col += 64) {
    __m256i acc[4][2];
    for (int r = 0; r < nrows; r++)
      acc[r][0] = acc[r][1] = _mm256_setzero_si256();
    for (int j = 0; j < k; j++) {
      const uint8_t* src = in_rows[j] + col;
      __m256i v0 = _mm256_loadu_si256((const __m256i*)src);
      __m256i v1 = _mm256_loadu_si256((const __m256i*)(src + 32));
      __m256i lo0 = _mm256_and_si256(v0, mask);
      __m256i hi0 = _mm256_and_si256(_mm256_srli_epi64(v0, 4), mask);
      __m256i lo1 = _mm256_and_si256(v1, mask);
      __m256i hi1 = _mm256_and_si256(_mm256_srli_epi64(v1, 4), mask);
      for (int r = 0; r < nrows; r++) {
        uint8_t c = mat[(size_t)(r0 + r) * k + j];
        if (c == 0) continue;
        const __m256i lo_tbl = _mm256_broadcastsi128_si256(
            _mm_loadu_si128((const __m128i*)MUL_LO[c]));
        const __m256i hi_tbl = _mm256_broadcastsi128_si256(
            _mm_loadu_si128((const __m128i*)MUL_HI[c]));
        acc[r][0] = _mm256_xor_si256(
            acc[r][0], _mm256_xor_si256(_mm256_shuffle_epi8(lo_tbl, lo0),
                                        _mm256_shuffle_epi8(hi_tbl, hi0)));
        acc[r][1] = _mm256_xor_si256(
            acc[r][1], _mm256_xor_si256(_mm256_shuffle_epi8(lo_tbl, lo1),
                                        _mm256_shuffle_epi8(hi_tbl, hi1)));
      }
    }
    for (int r = 0; r < nrows; r++) {
      uint8_t* dst = out_rows[r0 + r] + col;
      _mm256_storeu_si256((__m256i*)dst, acc[r][0]);
      _mm256_storeu_si256((__m256i*)(dst + 32), acc[r][1]);
    }
  }
  // scalar tail
  for (; col < n; col++) {
    for (int r = 0; r < nrows; r++) {
      uint8_t a = 0;
      for (int j = 0; j < k; j++) {
        uint8_t c = mat[(size_t)(r0 + r) * k + j];
        if (c) a ^= GF_MUL[c][in_rows[j][col]];
      }
      out_rows[r0 + r][col] = a;
    }
  }
}
#endif

#if defined(__x86_64__)
// GFNI + AVX512: one gf2p8affineqb per (coefficient, 64-byte lane) replaces
// the whole pshufb split-table dance — the encode becomes memory-bound on
// any GFNI host.  Guarded by runtime CPUID (compiled via target attribute,
// so the .so still loads and runs on plain-AVX2 machines).
//
// Access-pattern tuning: 256-byte column blocks give every output row four
// independent accumulator chains (gf2p8affineqb is a latency-3 op, so two
// chains leave the port idle between xors), and large aligned runs stream
// the parity out with non-temporal stores — parity is written once and
// read never, so letting it RFO through the cache would cost a read of
// every destination line and steal bandwidth from the source shards.
#define WN_GFNI_NT_MIN ((size_t)1 << 22)  // NT pays off only well past LLC

__attribute__((target("gfni,avx512f,avx512bw,avx512vl")))
static void gf_matmul_gfni_group(const uint8_t* mat, int r0, int nrows, int k,
                                 const uint8_t* const* in_rows,
                                 uint8_t* const* out_rows, size_t n) {
  int use_nt = n >= WN_GFNI_NT_MIN;
  for (int r = 0; use_nt && r < nrows; r++)
    if (((uintptr_t)out_rows[r0 + r]) & 63) use_nt = 0;
  size_t col = 0;
  for (; col + 256 <= n; col += 256) {
    __m512i acc[4][4];
    for (int r = 0; r < nrows; r++)
      acc[r][0] = acc[r][1] = acc[r][2] = acc[r][3] =
          _mm512_setzero_si512();
    for (int j = 0; j < k; j++) {
      const uint8_t* src = in_rows[j] + col;
      __m512i v0 = _mm512_loadu_si512((const void*)src);
      __m512i v1 = _mm512_loadu_si512((const void*)(src + 64));
      __m512i v2 = _mm512_loadu_si512((const void*)(src + 128));
      __m512i v3 = _mm512_loadu_si512((const void*)(src + 192));
      for (int r = 0; r < nrows; r++) {
        uint8_t c = mat[(size_t)(r0 + r) * k + j];
        if (c == 0) continue;
        __m512i A = _mm512_set1_epi64((long long)GF_AFFINE[c]);
        acc[r][0] = _mm512_xor_si512(
            acc[r][0], _mm512_gf2p8affine_epi64_epi8(v0, A, 0));
        acc[r][1] = _mm512_xor_si512(
            acc[r][1], _mm512_gf2p8affine_epi64_epi8(v1, A, 0));
        acc[r][2] = _mm512_xor_si512(
            acc[r][2], _mm512_gf2p8affine_epi64_epi8(v2, A, 0));
        acc[r][3] = _mm512_xor_si512(
            acc[r][3], _mm512_gf2p8affine_epi64_epi8(v3, A, 0));
      }
    }
    if (use_nt) {
      for (int r = 0; r < nrows; r++) {
        uint8_t* dst = out_rows[r0 + r] + col;
        _mm512_stream_si512((__m512i*)dst, acc[r][0]);
        _mm512_stream_si512((__m512i*)(dst + 64), acc[r][1]);
        _mm512_stream_si512((__m512i*)(dst + 128), acc[r][2]);
        _mm512_stream_si512((__m512i*)(dst + 192), acc[r][3]);
      }
    } else {
      for (int r = 0; r < nrows; r++) {
        uint8_t* dst = out_rows[r0 + r] + col;
        _mm512_storeu_si512((void*)dst, acc[r][0]);
        _mm512_storeu_si512((void*)(dst + 64), acc[r][1]);
        _mm512_storeu_si512((void*)(dst + 128), acc[r][2]);
        _mm512_storeu_si512((void*)(dst + 192), acc[r][3]);
      }
    }
  }
  if (use_nt) _mm_sfence();  // NT stores are weakly ordered; fence before
                             // the buffers are handed to the writers
  // 128-byte remainder block keeps the vector path for mid-size tails
  for (; col + 128 <= n; col += 128) {
    __m512i acc[4][2];
    for (int r = 0; r < nrows; r++)
      acc[r][0] = acc[r][1] = _mm512_setzero_si512();
    for (int j = 0; j < k; j++) {
      const uint8_t* src = in_rows[j] + col;
      __m512i v0 = _mm512_loadu_si512((const void*)src);
      __m512i v1 = _mm512_loadu_si512((const void*)(src + 64));
      for (int r = 0; r < nrows; r++) {
        uint8_t c = mat[(size_t)(r0 + r) * k + j];
        if (c == 0) continue;
        __m512i A = _mm512_set1_epi64((long long)GF_AFFINE[c]);
        acc[r][0] = _mm512_xor_si512(
            acc[r][0], _mm512_gf2p8affine_epi64_epi8(v0, A, 0));
        acc[r][1] = _mm512_xor_si512(
            acc[r][1], _mm512_gf2p8affine_epi64_epi8(v1, A, 0));
      }
    }
    for (int r = 0; r < nrows; r++) {
      uint8_t* dst = out_rows[r0 + r] + col;
      _mm512_storeu_si512((void*)dst, acc[r][0]);
      _mm512_storeu_si512((void*)(dst + 64), acc[r][1]);
    }
  }
  // scalar tail (< 128 bytes)
  for (; col < n; col++) {
    for (int r = 0; r < nrows; r++) {
      uint8_t a = 0;
      for (int j = 0; j < k; j++) {
        uint8_t c = mat[(size_t)(r0 + r) * k + j];
        if (c) a ^= GF_MUL[c][in_rows[j][col]];
      }
      out_rows[r0 + r][col] = a;
    }
  }
}

__attribute__((target("xsave")))
static int detect_gfni(void) {
  // GFNI (leaf 7 ECX bit 8) + AVX512F (EBX bit 16) + AVX512BW (EBX bit 30)
  unsigned a, b, c, d;
  if (!__get_cpuid_count(7, 0, &a, &b, &c, &d)) return 0;
  if (!((c >> 8) & 1)) return 0;
  if (!((b >> 16) & 1) || !((b >> 30) & 1)) return 0;
  // OS must enable ZMM state (XCR0 bits 5:7 via OSXSAVE)
  if (!__get_cpuid(1, &a, &b, &c, &d) || !((c >> 27) & 1)) return 0;
  uint64_t xcr0 = _xgetbv(0);
  return (xcr0 & 0xE6) == 0xE6;
}
#endif

// 0 = auto (best available), 1 = force AVX2 split-table, 2 = force scalar,
// 3 = force GFNI (falls back to auto-best when the host lacks it).  The AVX2
// force keeps the klauspost-equivalent baseline testable on GFNI hosts.
static int gf_impl_force = 0;

void wn_gf_set_impl(int impl) { gf_impl_force = impl; }

int wn_gf_impl(void) {
#if defined(__x86_64__)
  static int has_gfni = -1;
  if (has_gfni < 0) has_gfni = detect_gfni();
#if defined(__AVX2__)
  int best = has_gfni ? 3 : 1;  // 3 = gfni+avx512
#else
  int best = has_gfni ? 3 : 2;
#endif
  switch (gf_impl_force) {
    case 1: return 1;
    case 2: return 2;
    case 3: return has_gfni ? 3 : best;
    default: return best;
  }
#else
  (void)gf_impl_force;
  return 2;
#endif
}

// Shared ptr-based core used by both entry points.
static void gf_matmul_rows(const uint8_t* mat, int rows, int k,
                           const uint8_t* const* in_rows,
                           uint8_t* const* out_rows, size_t n) {
#if defined(__x86_64__)
  if (wn_gf_impl() == 3) {
    for (int r0 = 0; r0 < rows; r0 += 4) {
      int nrows = rows - r0 < 4 ? rows - r0 : 4;
      gf_matmul_gfni_group(mat, r0, nrows, k, in_rows, out_rows, n);
    }
    return;
  }
#endif
#if defined(__AVX2__)
  if (wn_gf_impl() != 2) {
    for (int r0 = 0; r0 < rows; r0 += 4) {
      int nrows = rows - r0 < 4 ? rows - r0 : 4;
      gf_matmul_avx2_group(mat, r0, nrows, k, in_rows, out_rows, n);
    }
    return;
  }
#endif
  // Cache-blocked scalar fallback: 16KB column panels keep the k input
  // sub-blocks resident in L2 across all output rows.
  const size_t BLK = 16 * 1024;
  for (size_t col = 0; col < n; col += BLK) {
    size_t w = n - col < BLK ? n - col : BLK;
    for (int r = 0; r < rows; r++) {
      uint8_t* dst = out_rows[r] + col;
      int first = 1;
      for (int j = 0; j < k; j++) {
        uint8_t c = mat[(size_t)r * k + j];
        if (c == 0) continue;
        gf_mul_slice_scalar(c, in_rows[j] + col, dst, w, !first);
        first = 0;
      }
      if (first) memset(dst, 0, w);
    }
  }
}

void wn_gf_matmul(const uint8_t* mat, int rows, int k, const uint8_t* in,
                  uint8_t* out, size_t n) {
  wn_gf_init();
  const uint8_t* in_rows[256];
  uint8_t* out_rows[256];
  for (int j = 0; j < k; j++) in_rows[j] = in + (size_t)j * n;
  for (int r = 0; r < rows; r++) out_rows[r] = out + (size_t)r * n;
  gf_matmul_rows(mat, rows, k, in_rows, out_rows, n);
}

// Same matmul but over scattered row pointers (avoids staging copies when
// shards live in separate buffers / an mmap'd .dat).
void wn_gf_matmul_ptrs(const uint8_t* mat, int rows, int k,
                       const uint8_t* const* in_rows, uint8_t* const* out_rows,
                       size_t n) {
  wn_gf_init();
  gf_matmul_rows(mat, rows, k, in_rows, out_rows, n);
}

// ---------------------------------------------------------------------------
// CRC32C (Castagnoli), reflected, init/xorout 0xFFFFFFFF
// ---------------------------------------------------------------------------

static uint32_t CRC32C_TABLE[256];
static int crc_initialized = 0;

__attribute__((unused)) static void crc_init(void) {
  if (crc_initialized) return;
  for (uint32_t i = 0; i < 256; i++) {
    uint32_t c = i;
    for (int j = 0; j < 8; j++)
      c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : (c >> 1);
    CRC32C_TABLE[i] = c;
  }
  crc_initialized = 1;
}

uint32_t wn_crc32c(const uint8_t* p, size_t n, uint32_t crc) {
  crc = ~crc;
#if defined(__SSE4_2__)
  while (n >= 8) {
    crc = (uint32_t)_mm_crc32_u64(crc, *(const uint64_t*)p);
    p += 8;
    n -= 8;
  }
  while (n--) crc = _mm_crc32_u8(crc, *p++);
#else
  crc_init();
  while (n--) crc = (crc >> 8) ^ CRC32C_TABLE[(crc ^ *p++) & 0xFF];
#endif
  return ~crc;
}

// ---------------------------------------------------------------------------
// AES-256 (key expansion + block encrypt), CTR and GCM modes
// ---------------------------------------------------------------------------

static const uint8_t SBOX[256] = {
    0x63,0x7c,0x77,0x7b,0xf2,0x6b,0x6f,0xc5,0x30,0x01,0x67,0x2b,0xfe,0xd7,0xab,0x76,
    0xca,0x82,0xc9,0x7d,0xfa,0x59,0x47,0xf0,0xad,0xd4,0xa2,0xaf,0x9c,0xa4,0x72,0xc0,
    0xb7,0xfd,0x93,0x26,0x36,0x3f,0xf7,0xcc,0x34,0xa5,0xe5,0xf1,0x71,0xd8,0x31,0x15,
    0x04,0xc7,0x23,0xc3,0x18,0x96,0x05,0x9a,0x07,0x12,0x80,0xe2,0xeb,0x27,0xb2,0x75,
    0x09,0x83,0x2c,0x1a,0x1b,0x6e,0x5a,0xa0,0x52,0x3b,0xd6,0xb3,0x29,0xe3,0x2f,0x84,
    0x53,0xd1,0x00,0xed,0x20,0xfc,0xb1,0x5b,0x6a,0xcb,0xbe,0x39,0x4a,0x4c,0x58,0xcf,
    0xd0,0xef,0xaa,0xfb,0x43,0x4d,0x33,0x85,0x45,0xf9,0x02,0x7f,0x50,0x3c,0x9f,0xa8,
    0x51,0xa3,0x40,0x8f,0x92,0x9d,0x38,0xf5,0xbc,0xb6,0xda,0x21,0x10,0xff,0xf3,0xd2,
    0xcd,0x0c,0x13,0xec,0x5f,0x97,0x44,0x17,0xc4,0xa7,0x7e,0x3d,0x64,0x5d,0x19,0x73,
    0x60,0x81,0x4f,0xdc,0x22,0x2a,0x90,0x88,0x46,0xee,0xb8,0x14,0xde,0x5e,0x0b,0xdb,
    0xe0,0x32,0x3a,0x0a,0x49,0x06,0x24,0x5c,0xc2,0xd3,0xac,0x62,0x91,0x95,0xe4,0x79,
    0xe7,0xc8,0x37,0x6d,0x8d,0xd5,0x4e,0xa9,0x6c,0x56,0xf4,0xea,0x65,0x7a,0xae,0x08,
    0xba,0x78,0x25,0x2e,0x1c,0xa6,0xb4,0xc6,0xe8,0xdd,0x74,0x1f,0x4b,0xbd,0x8b,0x8a,
    0x70,0x3e,0xb5,0x66,0x48,0x03,0xf6,0x0e,0x61,0x35,0x57,0xb9,0x86,0xc1,0x1d,0x9e,
    0xe1,0xf8,0x98,0x11,0x69,0xd9,0x8e,0x94,0x9b,0x1e,0x87,0xe9,0xce,0x55,0x28,0xdf,
    0x8c,0xa1,0x89,0x0d,0xbf,0xe6,0x42,0x68,0x41,0x99,0x2d,0x0f,0xb0,0x54,0xbb,0x16};

static const uint8_t RCON[15] = {0x01,0x02,0x04,0x08,0x10,0x20,0x40,0x80,
                                 0x1b,0x36,0x6c,0xd8,0xab,0x4d,0x9a};

typedef struct {
  uint8_t rk[15][16];  // 14 rounds + initial, AES-256
} aes256_key;

static void aes256_expand(const uint8_t key[32], aes256_key* ks) {
  uint8_t w[60][4];
  memcpy(w, key, 32);
  for (int i = 8; i < 60; i++) {
    uint8_t t[4];
    memcpy(t, w[i - 1], 4);
    if (i % 8 == 0) {
      uint8_t tmp = t[0];
      t[0] = (uint8_t)(SBOX[t[1]] ^ RCON[i / 8 - 1]);
      t[1] = SBOX[t[2]];
      t[2] = SBOX[t[3]];
      t[3] = SBOX[tmp];
    } else if (i % 8 == 4) {
      for (int j = 0; j < 4; j++) t[j] = SBOX[t[j]];
    }
    for (int j = 0; j < 4; j++) w[i][j] = (uint8_t)(w[i - 8][j] ^ t[j]);
  }
  memcpy(ks->rk, w, 240);
}

static uint8_t xtime(uint8_t x) {
  return (uint8_t)((x << 1) ^ ((x >> 7) * 0x1B));
}

static void aes_block_soft(const aes256_key* ks, const uint8_t in[16],
                           uint8_t out[16]) {
  uint8_t s[16];
  for (int i = 0; i < 16; i++) s[i] = (uint8_t)(in[i] ^ ks->rk[0][i]);
  for (int round = 1; round <= 14; round++) {
    uint8_t t[16];
    // SubBytes + ShiftRows
    for (int c = 0; c < 4; c++)
      for (int r = 0; r < 4; r++)
        t[4 * c + r] = SBOX[s[4 * ((c + r) & 3) + r]];
    if (round < 14) {
      // MixColumns
      for (int c = 0; c < 4; c++) {
        uint8_t a0 = t[4 * c], a1 = t[4 * c + 1], a2 = t[4 * c + 2],
                a3 = t[4 * c + 3];
        s[4 * c] = (uint8_t)(xtime(a0) ^ xtime(a1) ^ a1 ^ a2 ^ a3);
        s[4 * c + 1] = (uint8_t)(a0 ^ xtime(a1) ^ xtime(a2) ^ a2 ^ a3);
        s[4 * c + 2] = (uint8_t)(a0 ^ a1 ^ xtime(a2) ^ xtime(a3) ^ a3);
        s[4 * c + 3] = (uint8_t)(xtime(a0) ^ a0 ^ a1 ^ a2 ^ xtime(a3));
      }
    } else {
      memcpy(s, t, 16);
    }
    for (int i = 0; i < 16; i++) s[i] ^= ks->rk[round][i];
  }
  memcpy(out, s, 16);
}

#if defined(__AES__)
static int has_aesni(void) {
  unsigned a, b, c, d;
  if (!__get_cpuid(1, &a, &b, &c, &d)) return 0;
  return (c >> 25) & 1;
}

static void aes_block_ni(const aes256_key* ks, const uint8_t in[16],
                         uint8_t out[16]) {
  __m128i v = _mm_loadu_si128((const __m128i*)in);
  v = _mm_xor_si128(v, _mm_loadu_si128((const __m128i*)ks->rk[0]));
  for (int r = 1; r < 14; r++)
    v = _mm_aesenc_si128(v, _mm_loadu_si128((const __m128i*)ks->rk[r]));
  v = _mm_aesenclast_si128(v, _mm_loadu_si128((const __m128i*)ks->rk[14]));
  _mm_storeu_si128((__m128i*)out, v);
}
#endif

static void aes_block(const aes256_key* ks, const uint8_t in[16],
                      uint8_t out[16]) {
#if defined(__AES__)
  static int use_ni = -1;
  if (use_ni < 0) use_ni = has_aesni();
  if (use_ni) {
    aes_block_ni(ks, in, out);
    return;
  }
#endif
  aes_block_soft(ks, in, out);
}

static void ctr_inc(uint8_t ctr[16]) {
  for (int i = 15; i >= 12; i--)
    if (++ctr[i]) break;
}

// CTR over a pre-expanded schedule; AES-NI path runs 4 blocks in flight to
// cover the aesenc latency chain.
static void aes256_ctr_ks(const aes256_key* ks, const uint8_t iv[16],
                          const uint8_t* in, uint8_t* out, size_t n) {
  uint8_t ctr[16];
  memcpy(ctr, iv, 16);
  size_t off = 0;
#if defined(__AES__)
  static int use_ni = -1;
  if (use_ni < 0) use_ni = has_aesni();
  if (use_ni) {
    while (n - off >= 64) {
      __m128i b[4];
      for (int j = 0; j < 4; j++) {
        b[j] = _mm_loadu_si128((const __m128i*)ctr);
        ctr_inc(ctr);
      }
      const __m128i rk0 = _mm_loadu_si128((const __m128i*)ks->rk[0]);
      for (int j = 0; j < 4; j++) b[j] = _mm_xor_si128(b[j], rk0);
      for (int r = 1; r < 14; r++) {
        const __m128i rk = _mm_loadu_si128((const __m128i*)ks->rk[r]);
        for (int j = 0; j < 4; j++) b[j] = _mm_aesenc_si128(b[j], rk);
      }
      const __m128i rkl = _mm_loadu_si128((const __m128i*)ks->rk[14]);
      for (int j = 0; j < 4; j++) {
        b[j] = _mm_aesenclast_si128(b[j], rkl);
        __m128i v = _mm_loadu_si128((const __m128i*)(in + off + 16 * j));
        _mm_storeu_si128((__m128i*)(out + off + 16 * j),
                         _mm_xor_si128(v, b[j]));
      }
      off += 64;
    }
  }
#endif
  uint8_t ksblk[16];
  while (off < n) {
    aes_block(ks, ctr, ksblk);
    size_t chunk = n - off < 16 ? n - off : 16;
    for (size_t i = 0; i < chunk; i++)
      out[off + i] = (uint8_t)(in[off + i] ^ ksblk[i]);
    off += chunk;
    ctr_inc(ctr);
  }
}

// CTR keystream XOR: out = in ^ AES-CTR(key, iv).  iv is the 16-byte
// initial counter block; the low 32 bits big-endian increment per block.
void wn_aes256_ctr(const uint8_t key[32], const uint8_t iv[16],
                   const uint8_t* in, uint8_t* out, size_t n) {
  aes256_key ks;
  aes256_expand(key, &ks);
  aes256_ctr_ks(&ks, iv, in, out, n);
}

// -- GHASH over GF(2^128) ---------------------------------------------------

typedef struct {
  uint64_t hi, lo;
} be128;

static be128 load_be128(const uint8_t* p) {
  be128 r;
  r.hi = r.lo = 0;
  for (int i = 0; i < 8; i++) r.hi = (r.hi << 8) | p[i];
  for (int i = 8; i < 16; i++) r.lo = (r.lo << 8) | p[i];
  return r;
}

static void store_be128(be128 v, uint8_t* p) {
  for (int i = 7; i >= 0; i--) {
    p[i] = (uint8_t)v.hi;
    v.hi >>= 8;
  }
  for (int i = 15; i >= 8; i--) {
    p[i] = (uint8_t)v.lo;
    v.lo >>= 8;
  }
}

// Shoup-style 16x256 GHASH tables, built from the bit-level reference above
// by linearity: entry [i][b] = (byte b at position i) * H.  Build cost is
// 128 mulx steps + ~33k 128-bit xors (~us), then each block is 16 lookups.
typedef struct {
  be128 t[16][256];
} ghash_tables;

static void ghash_precompute(const uint8_t h[16], ghash_tables* tb) {
  // P[p] = u^p * H, where u^p*H is p applications of the mulx step used by
  // ghash_mul's scan (bit p counts from byte 0's MSB).
  be128 P[128];
  be128 v = load_be128(h);
  for (int p = 0; p < 128; p++) {
    P[p] = v;
    int lsb = (int)(v.lo & 1);
    v.lo = (v.lo >> 1) | (v.hi << 63);
    v.hi >>= 1;
    if (lsb) v.hi ^= 0xE100000000000000ull;
  }
  for (int i = 0; i < 16; i++) {
    for (int b = 0; b < 256; b++) {
      be128 z = {0, 0};
      for (int j = 0; j < 8; j++) {
        if (b & (1 << j)) {
          const be128* p = &P[8 * i + (7 - j)];
          z.hi ^= p->hi;
          z.lo ^= p->lo;
        }
      }
      tb->t[i][b] = z;
    }
  }
}

static be128 ghash_mul_tab(const ghash_tables* tb, be128 x) {
  uint8_t bytes[16];
  store_be128(x, bytes);
  be128 z = {0, 0};
  for (int i = 0; i < 16; i++) {
    const be128* e = &tb->t[i][bytes[i]];
    z.hi ^= e->hi;
    z.lo ^= e->lo;
  }
  return z;
}

static void ghash_update(const ghash_tables* tb, be128* y, const uint8_t* p,
                         size_t len) {
  uint8_t blk[16];
  for (size_t off = 0; off < len; off += 16) {
    size_t c = len - off < 16 ? len - off : 16;
    const uint8_t* src = p + off;
    if (c < 16) {
      memset(blk, 0, 16);
      memcpy(blk, src, c);
      src = blk;
    }
    be128 x = load_be128(src);
    y->hi ^= x.hi;
    y->lo ^= x.lo;
    *y = ghash_mul_tab(tb, *y);
  }
}

// Small mutex-guarded table cache keyed on H: per-chunk keys re-seal many
// blocks, and repeated small seals with one key shouldn't pay the 64KB
// table build every call.
static std::mutex ghash_cache_mu;
static struct {
  uint8_t h[16];
  ghash_tables tb;
  int valid;
} ghash_cache[4];
static int ghash_cache_next = 0;

static void ghash(const uint8_t h[16], const uint8_t* aad, size_t aad_len,
                  const uint8_t* ct, size_t ct_len, uint8_t out[16]) {
  ghash_tables tb;
  {
    std::lock_guard<std::mutex> g(ghash_cache_mu);
    int hit = -1;
    for (int i = 0; i < 4; i++)
      if (ghash_cache[i].valid && memcmp(ghash_cache[i].h, h, 16) == 0)
        hit = i;
    if (hit < 0) {
      hit = ghash_cache_next;
      ghash_cache_next = (ghash_cache_next + 1) & 3;
      ghash_precompute(h, &ghash_cache[hit].tb);
      memcpy(ghash_cache[hit].h, h, 16);
      ghash_cache[hit].valid = 1;
    }
    memcpy(&tb, &ghash_cache[hit].tb, sizeof(tb));
  }
  be128 y = {0, 0};
  ghash_update(&tb, &y, aad, aad_len);
  ghash_update(&tb, &y, ct, ct_len);
  be128 lens;
  lens.hi = (uint64_t)aad_len * 8;
  lens.lo = (uint64_t)ct_len * 8;
  y.hi ^= lens.hi;
  y.lo ^= lens.lo;
  y = ghash_mul_tab(&tb, y);
  store_be128(y, out);
}

// AES-256-GCM seal: out = ciphertext(n bytes) with 16-byte tag written to
// `tag`.  12-byte nonce (the Go stdlib default the reference uses).
void wn_aes256_gcm_seal(const uint8_t key[32], const uint8_t nonce[12],
                        const uint8_t* aad, size_t aad_len, const uint8_t* in,
                        uint8_t* out, size_t n, uint8_t tag[16]) {
  aes256_key ks;
  aes256_expand(key, &ks);
  uint8_t h[16] = {0}, zero[16] = {0};
  aes_block(&ks, zero, h);
  uint8_t j0[16];
  memcpy(j0, nonce, 12);
  j0[12] = j0[13] = j0[14] = 0;
  j0[15] = 1;
  // CTR starts at J0+1
  uint8_t ctr0[16];
  memcpy(ctr0, j0, 16);
  ctr_inc(ctr0);
  aes256_ctr_ks(&ks, ctr0, in, out, n);
  uint8_t s[16];
  ghash(h, aad, aad_len, out, n, s);
  uint8_t ek[16];
  aes_block(&ks, j0, ek);
  for (int i = 0; i < 16; i++) tag[i] = (uint8_t)(s[i] ^ ek[i]);
}

// Returns 0 on success, -1 on tag mismatch (out untouched on mismatch).
int wn_aes256_gcm_open(const uint8_t key[32], const uint8_t nonce[12],
                       const uint8_t* aad, size_t aad_len, const uint8_t* in,
                       uint8_t* out, size_t n, const uint8_t tag[16]) {
  aes256_key ks;
  aes256_expand(key, &ks);
  uint8_t h[16] = {0}, zero[16] = {0};
  aes_block(&ks, zero, h);
  uint8_t j0[16];
  memcpy(j0, nonce, 12);
  j0[12] = j0[13] = j0[14] = 0;
  j0[15] = 1;
  uint8_t s[16];
  ghash(h, aad, aad_len, in, n, s);
  uint8_t ek[16];
  aes_block(&ks, j0, ek);
  uint8_t expect[16];
  for (int i = 0; i < 16; i++) expect[i] = (uint8_t)(s[i] ^ ek[i]);
  uint8_t diff = 0;
  for (int i = 0; i < 16; i++) diff |= (uint8_t)(expect[i] ^ tag[i]);
  if (diff) return -1;
  uint8_t ctr0[16];
  memcpy(ctr0, j0, 16);
  ctr_inc(ctr0);
  aes256_ctr_ks(&ks, ctr0, in, out, n);
  return 0;
}

}  // extern "C"

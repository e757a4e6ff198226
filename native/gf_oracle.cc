// GF(2^8) oracle — independent C++ implementation of the matrix constructions
// and codec math in ceph_tpu/gf, used as (a) the bit-exactness referee for the
// JAX/Pallas path and (b) the CPU throughput baseline the TPU must beat.
//
// Plays the role of the reference's native jerasure/gf-complete/ISA-L stack
// (reference: src/erasure-code/jerasure/jerasure/src/{reed_sol.c,cauchy.c,
// jerasure.c,galois.c}, src/isa-l).  Algorithms are re-implemented from their
// documented behavior; field is GF(2^8) mod 0x11D as in jerasure w=8 / ISA-L.
//
// Parity semantics: byte-wise GF(2^8) matrix multiply for every technique
// (ISA-L's ec_encode_data convention).  jerasure's bitmatrix techniques
// produce packetsize-dependent layouts instead; byte-wise is the
// layout-independent formulation and equals jerasure for reed_sol_van.
//
// The fast path (gfo_encode_fast) is the ISA-L analog: 4-bit split tables,
// SSSE3 PSHUFB when available — this is the number the "10x on one v5e chip"
// target is measured against (BASELINE.md).
//
// Exposed via a plain C ABI for ctypes (no pybind11 in this image).

#include <cstdint>
#include <cstring>
#include <vector>

#if defined(__SSSE3__)
#include <tmmintrin.h>
#endif
#if defined(__AVX2__)
#include <immintrin.h>
#endif

namespace {

constexpr int GF_POLY = 0x11D;

struct Tables {
  uint8_t exp[512];
  int log[256];
  uint8_t inv[256];
  uint8_t mul[256][256];
  Tables() {
    int x = 1;
    for (int i = 0; i < 255; ++i) {
      exp[i] = (uint8_t)x;
      log[x] = i;
      x <<= 1;
      if (x & 0x100) x ^= GF_POLY;
    }
    for (int i = 255; i < 512; ++i) exp[i] = exp[i - 255];
    log[0] = 0;
    for (int a = 0; a < 256; ++a)
      for (int b = 0; b < 256; ++b)
        mul[a][b] = (a && b) ? exp[log[a] + log[b]] : 0;
    inv[0] = 0;
    for (int a = 1; a < 256; ++a) inv[a] = exp[(255 - log[a]) % 255];
  }
};

const Tables T;

inline int gmul(int a, int b) { return T.mul[a & 0xff][b & 0xff]; }
inline int gdiv(int a, int b) {
  if (b == 0) return -1;
  if (a == 0) return 0;
  return T.exp[(T.log[a] - T.log[b] + 255) % 255];
}

}  // namespace

extern "C" {

int gfo_mul(int a, int b) { return gmul(a, b); }
int gfo_div(int a, int b) { return gdiv(a, b); }

void gfo_mul_table(uint8_t* out) { std::memcpy(out, T.mul, 256 * 256); }

// reed_sol.c :: reed_sol_big_vandermonde_distribution_matrix (w=8), returning
// the bottom m rows (reed_sol_vandermonde_coding_matrix).
int gfo_vandermonde(int k, int m, uint8_t* out) {
  const int rows = k + m, cols = k;
  if (rows >= 256 || rows <= cols) return -1;
  std::vector<int> d((size_t)rows * cols, 0);
  auto at = [&](int r, int c) -> int& { return d[(size_t)r * cols + c]; };
  // reed_sol_extended_vandermonde_matrix: [1,0..0], powers of i, [0..0,1]
  at(0, 0) = 1;
  at(rows - 1, cols - 1) = 1;
  for (int i = 1; i < rows - 1; ++i) {
    at(i, 0) = 1;
    for (int j = 1; j < cols; ++j) at(i, j) = gmul(at(i, j - 1), i);
  }
  for (int i = 1; i < cols; ++i) {
    int j = i;
    while (j < rows && at(j, i) == 0) ++j;
    if (j == rows) return -2;
    if (j != i)
      for (int c = 0; c < cols; ++c) std::swap(at(i, c), at(j, c));
    if (at(i, i) != 1) {
      const int inv = gdiv(1, at(i, i));
      for (int r = 0; r < rows; ++r) at(r, i) = gmul(inv, at(r, i));
    }
    for (int j2 = 0; j2 < cols; ++j2) {
      const int tmp = at(i, j2);
      if (j2 != i && tmp != 0)
        for (int r = 0; r < rows; ++r) at(r, j2) ^= gmul(tmp, at(r, i));
    }
  }
  // row k all ones: scale each column below the identity
  for (int j = 0; j < cols; ++j) {
    const int tmp = at(cols, j);
    if (tmp == 0) return -3;
    if (tmp != 1) {
      const int inv = gdiv(1, tmp);
      for (int r = cols; r < rows; ++r) at(r, j) = gmul(inv, at(r, j));
    }
  }
  // each later row's first element 1: scale the row
  for (int i = cols + 1; i < rows; ++i) {
    const int tmp = at(i, 0);
    if (tmp == 0) return -3;
    if (tmp != 1) {
      const int inv = gdiv(1, tmp);
      for (int j = 0; j < cols; ++j) at(i, j) = gmul(at(i, j), inv);
    }
  }
  for (int i = 0; i < m; ++i)
    for (int j = 0; j < k; ++j) out[i * k + j] = (uint8_t)at(cols + i, j);
  return 0;
}

// cauchy.c :: cauchy_original_coding_matrix: M[i][j] = 1/(i ^ (m+j)).
int gfo_cauchy_original(int k, int m, uint8_t* out) {
  if (k + m > 256) return -1;
  for (int i = 0; i < m; ++i)
    for (int j = 0; j < k; ++j) out[i * k + j] = T.inv[i ^ (m + j)];
  return 0;
}

// cauchy.c :: cauchy_n_ones — ones in the 8x8 bitmatrix of multiply-by-n.
int gfo_n_ones(int n) {
  int total = 0, e = n & 0xff;
  for (int x = 0; x < 8; ++x) {
    total += __builtin_popcount(e);
    e = gmul(e, 2);
  }
  return total;
}

// cauchy.c :: cauchy_improve_coding_matrix + cauchy_good_general_coding_matrix
// (no m==2 precomputed-best special case; see ceph_tpu/gf/matrix.py note).
int gfo_cauchy_good(int k, int m, uint8_t* out) {
  if (gfo_cauchy_original(k, m, out) != 0) return -1;
  for (int j = 0; j < k; ++j) {
    if (out[j] != 1) {
      const int inv = gdiv(1, out[j]);
      for (int i = 0; i < m; ++i) out[i * k + j] = (uint8_t)gmul(out[i * k + j], inv);
    }
  }
  for (int i = 1; i < m; ++i) {
    uint8_t* row = out + (size_t)i * k;
    int bno = 0;
    for (int j = 0; j < k; ++j) bno += gfo_n_ones(row[j]);
    int bno_index = -1;
    for (int j = 0; j < k; ++j) {
      if (row[j] != 1) {
        const int inv = gdiv(1, row[j]);
        int tno = 0;
        for (int x = 0; x < k; ++x) tno += gfo_n_ones(gmul(row[x], inv));
        if (tno < bno) {
          bno = tno;
          bno_index = j;
        }
      }
    }
    if (bno_index != -1) {
      const int inv = gdiv(1, row[bno_index]);
      for (int j = 0; j < k; ++j) row[j] = (uint8_t)gmul(row[j], inv);
    }
  }
  return 0;
}

// jerasure.c :: jerasure_invert_matrix (Gauss-Jordan over GF(2^8)).
int gfo_invert(const uint8_t* in, int n, uint8_t* out) {
  std::vector<int> a(in, in + (size_t)n * n);
  std::vector<int> b((size_t)n * n, 0);
  for (int i = 0; i < n; ++i) b[(size_t)i * n + i] = 1;
  auto A = [&](int r, int c) -> int& { return a[(size_t)r * n + c]; };
  auto B = [&](int r, int c) -> int& { return b[(size_t)r * n + c]; };
  for (int i = 0; i < n; ++i) {
    if (A(i, i) == 0) {
      int r = i + 1;
      while (r < n && A(r, i) == 0) ++r;
      if (r == n) return -1;  // singular
      for (int c = 0; c < n; ++c) {
        std::swap(A(i, c), A(r, c));
        std::swap(B(i, c), B(r, c));
      }
    }
    if (A(i, i) != 1) {
      const int pinv = gdiv(1, A(i, i));
      for (int c = 0; c < n; ++c) {
        A(i, c) = gmul(A(i, c), pinv);
        B(i, c) = gmul(B(i, c), pinv);
      }
    }
    for (int r = 0; r < n; ++r) {
      const int f = A(r, i);
      if (r != i && f != 0)
        for (int c = 0; c < n; ++c) {
          A(r, c) ^= gmul(f, A(i, c));
          B(r, c) ^= gmul(f, B(i, c));
        }
    }
  }
  for (size_t i = 0; i < (size_t)n * n; ++i) out[i] = (uint8_t)b[i];
  return 0;
}

// Scalar byte-wise matrix apply: rows x n matrix over chunks [n][len].
void gfo_apply(const uint8_t* mat, int rows, int n, const uint8_t* chunks,
               long len, uint8_t* out) {
  for (int i = 0; i < rows; ++i) {
    uint8_t* dst = out + (size_t)i * len;
    std::memset(dst, 0, (size_t)len);
    for (int j = 0; j < n; ++j) {
      const uint8_t e = mat[i * n + j];
      if (e == 0) continue;
      const uint8_t* src = chunks + (size_t)j * len;
      const uint8_t* mrow = T.mul[e];
      if (e == 1) {
        for (long s = 0; s < len; ++s) dst[s] ^= src[s];
      } else {
        for (long s = 0; s < len; ++s) dst[s] ^= mrow[src[s]];
      }
    }
  }
}

void gfo_encode(const uint8_t* coding, int k, int m, const uint8_t* data,
                long len, uint8_t* parity) {
  gfo_apply(coding, m, k, data, len, parity);
}

// Fast CPU path — the ISA-L analog (reference: src/isa-l ec_encode_data):
// per-(i,j) 4-bit split tables applied 16 bytes at a time with PSHUFB.
#if defined(__SSSE3__)
[[maybe_unused]] static void apply_fast_ssse3(
    const uint8_t* mat, int rows, int n,
    const uint8_t* chunks, long len, uint8_t* out) {
  // Build split tables: lo[b] = e*(b), hi[b] = e*(b<<4) for b in 0..15.
  std::vector<uint8_t> tbl((size_t)rows * n * 32);
  for (int i = 0; i < rows; ++i)
    for (int j = 0; j < n; ++j) {
      uint8_t* t = tbl.data() + ((size_t)i * n + j) * 32;
      const int e = mat[i * n + j];
      for (int b = 0; b < 16; ++b) {
        t[b] = (uint8_t)gmul(e, b);
        t[16 + b] = (uint8_t)gmul(e, b << 4);
      }
    }
  const __m128i mask0f = _mm_set1_epi8(0x0f);
  const long vlen = len & ~15L;
  for (int i = 0; i < rows; ++i) {
    uint8_t* dst = out + (size_t)i * len;
    std::memset(dst, 0, (size_t)len);
    for (int j = 0; j < n; ++j) {
      const int e = mat[i * n + j];
      if (e == 0) continue;
      const uint8_t* src = chunks + (size_t)j * len;
      const uint8_t* t = tbl.data() + ((size_t)i * n + j) * 32;
      const __m128i tlo = _mm_loadu_si128((const __m128i*)t);
      const __m128i thi = _mm_loadu_si128((const __m128i*)(t + 16));
      for (long s = 0; s < vlen; s += 16) {
        const __m128i d = _mm_loadu_si128((const __m128i*)(src + s));
        const __m128i lo = _mm_and_si128(d, mask0f);
        const __m128i hi = _mm_and_si128(_mm_srli_epi64(d, 4), mask0f);
        const __m128i p =
            _mm_xor_si128(_mm_shuffle_epi8(tlo, lo), _mm_shuffle_epi8(thi, hi));
        __m128i acc = _mm_loadu_si128((__m128i*)(dst + s));
        _mm_storeu_si128((__m128i*)(dst + s), _mm_xor_si128(acc, p));
      }
      const uint8_t* mrow = T.mul[e];
      for (long s = vlen; s < len; ++s) dst[s] ^= mrow[src[s]];
    }
  }
}
#endif

#if defined(__AVX2__)
// ISA-L's actual formulation (reference: src/isa-l :: ec_encode_data AVX2
// gf_vect_mad loops): 4-bit split tables broadcast to both 128-bit lanes,
// 32 bytes per VPSHUFB pair.  This is the honest "beat ISA-L" baseline —
// the SSSE3 path above understates what ISA-L reaches on this host.
static void apply_fast_avx2(const uint8_t* mat, int rows, int n,
                            const uint8_t* chunks, long len, uint8_t* out) {
  std::vector<uint8_t> tbl((size_t)rows * n * 32);
  for (int i = 0; i < rows; ++i)
    for (int j = 0; j < n; ++j) {
      uint8_t* t = tbl.data() + ((size_t)i * n + j) * 32;
      const int e = mat[i * n + j];
      for (int b = 0; b < 16; ++b) {
        t[b] = (uint8_t)gmul(e, b);
        t[16 + b] = (uint8_t)gmul(e, b << 4);
      }
    }
  const __m256i mask0f = _mm256_set1_epi8(0x0f);
  const long vlen = len & ~31L;
  for (int i = 0; i < rows; ++i) {
    uint8_t* dst = out + (size_t)i * len;
    std::memset(dst, 0, (size_t)len);
    for (int j = 0; j < n; ++j) {
      const int e = mat[i * n + j];
      if (e == 0) continue;
      const uint8_t* src = chunks + (size_t)j * len;
      const uint8_t* t = tbl.data() + ((size_t)i * n + j) * 32;
      const __m256i tlo = _mm256_broadcastsi128_si256(
          _mm_loadu_si128((const __m128i*)t));
      const __m256i thi = _mm256_broadcastsi128_si256(
          _mm_loadu_si128((const __m128i*)(t + 16)));
      for (long s = 0; s < vlen; s += 32) {
        const __m256i d = _mm256_loadu_si256((const __m256i*)(src + s));
        const __m256i lo = _mm256_and_si256(d, mask0f);
        const __m256i hi =
            _mm256_and_si256(_mm256_srli_epi64(d, 4), mask0f);
        const __m256i p = _mm256_xor_si256(_mm256_shuffle_epi8(tlo, lo),
                                           _mm256_shuffle_epi8(thi, hi));
        __m256i acc = _mm256_loadu_si256((__m256i*)(dst + s));
        _mm256_storeu_si256((__m256i*)(dst + s), _mm256_xor_si256(acc, p));
      }
      const uint8_t* mrow = T.mul[e];
      for (long s = vlen; s < len; ++s) dst[s] ^= mrow[src[s]];
    }
  }
}
#endif

// Returns 2 for AVX2, 1 for SSSE3, 0 for scalar fallback.
int gfo_apply_fast(const uint8_t* mat, int rows, int n, const uint8_t* chunks,
                   long len, uint8_t* out) {
#if defined(__AVX2__)
  apply_fast_avx2(mat, rows, n, chunks, len, out);
  return 2;
#elif defined(__SSSE3__)
  apply_fast_ssse3(mat, rows, n, chunks, len, out);
  return 1;
#else
  gfo_apply(mat, rows, n, chunks, len, out);
  return 0;
#endif
}

int gfo_encode_fast(const uint8_t* coding, int k, int m, const uint8_t* data,
                    long len, uint8_t* parity) {
  return gfo_apply_fast(coding, m, k, data, len, parity);
}

// Decode: rebuild data chunks from the first k available shard rows of the
// systematic generator [I_k ; coding] (jerasure_make_decoding_matrix shape).
int gfo_decode(const uint8_t* coding, int k, int m, const int* avail_rows,
               int n_avail, const uint8_t* shards, long len, uint8_t* data_out) {
  if (n_avail < k) return -1;
  std::vector<uint8_t> sub((size_t)k * k);
  for (int r = 0; r < k; ++r) {
    const int row = avail_rows[r];
    if (row < 0 || row >= k + m) return -2;
    for (int c = 0; c < k; ++c)
      sub[(size_t)r * k + c] =
          (row < k) ? (uint8_t)(row == c ? 1 : 0) : coding[(row - k) * k + c];
  }
  std::vector<uint8_t> dm((size_t)k * k);
  if (gfo_invert(sub.data(), k, dm.data()) != 0) return -3;
  gfo_apply_fast(dm.data(), k, k, shards, len, data_out);
  return 0;
}

}  // extern "C"

// Host-side quantile sketch + bin assignment, the DMatrix-construction hot
// path. Mirrors the role of the reference's SketchOnDMatrix
// (src/common/hist_util.cc:32-69) + GHistIndexMatrix::PushBatch
// (src/data/gradient_index.cc): the semantics here are defined by
// xgboost_tpu/data/quantile.py (cuts_from_summaries / search_bin) — this is
// the native fast path for the same computation, used by sketch_matrix()
// and BinnedMatrix.from_dense() when the library is available.
//
// Single-core speed comes from an LSD radix sort over order-preserving u32
// float keys (4 passes, no comparisons) and a branchless lower_bound in the
// binning sweep; OpenMP parallelises per-feature (sketch) and per-row-block
// (binning) when cores are available.

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <limits>
#include <vector>

#ifdef _OPENMP
#include <omp.h>
#endif

namespace {

// Order-preserving float -> u32 key (IEEE754 totally ordered; -0.0 must be
// normalised to +0.0 by the caller so equal floats map to equal keys).
inline uint32_t F2U(float f) {
  uint32_t u;
  std::memcpy(&u, &f, 4);
  return (u & 0x80000000u) ? ~u : (u | 0x80000000u);
}

inline float U2F(uint32_t u) {
  u = (u & 0x80000000u) ? (u & 0x7FFFFFFFu) : ~u;
  float f;
  std::memcpy(&f, &u, 4);
  return f;
}

// LSD radix sort of keys (optionally carrying a float payload), 4x8-bit.
void RadixSort(std::vector<uint32_t>& keys, std::vector<float>* payload) {
  const size_t n = keys.size();
  std::vector<uint32_t> tmp(n);
  std::vector<float> ptmp(payload ? n : 0);
  uint32_t* src = keys.data();
  uint32_t* dst = tmp.data();
  float* psrc = payload ? payload->data() : nullptr;
  float* pdst = payload ? ptmp.data() : nullptr;
  size_t count[256];
  for (int shift = 0; shift < 32; shift += 8) {
    std::memset(count, 0, sizeof(count));
    for (size_t i = 0; i < n; ++i) ++count[(src[i] >> shift) & 0xFF];
    size_t pos = 0;
    for (int b = 0; b < 256; ++b) {
      const size_t c = count[b];
      count[b] = pos;
      pos += c;
    }
    if (payload) {
      for (size_t i = 0; i < n; ++i) {
        const size_t p = count[(src[i] >> shift) & 0xFF]++;
        dst[p] = src[i];
        pdst[p] = psrc[i];
      }
      std::swap(psrc, pdst);
    } else {
      for (size_t i = 0; i < n; ++i) dst[count[(src[i] >> shift) & 0xFF]++] = src[i];
    }
    std::swap(src, dst);
  }
  // 4 passes = even number of swaps: results are back in the input vectors.
}

// Same LSD radix sort carrying a u32 index payload (for f64 weight gathers).
void RadixSortIdx(std::vector<uint32_t>& keys, std::vector<uint32_t>& idx) {
  const size_t n = keys.size();
  std::vector<uint32_t> tmp(n), itmp(n);
  uint32_t* src = keys.data();
  uint32_t* dst = tmp.data();
  uint32_t* isrc = idx.data();
  uint32_t* idst = itmp.data();
  size_t count[256];
  for (int shift = 0; shift < 32; shift += 8) {
    std::memset(count, 0, sizeof(count));
    for (size_t i = 0; i < n; ++i) ++count[(src[i] >> shift) & 0xFF];
    size_t pos = 0;
    for (int b = 0; b < 256; ++b) {
      const size_t c = count[b];
      count[b] = pos;
      pos += c;
    }
    for (size_t i = 0; i < n; ++i) {
      const size_t p = count[(src[i] >> shift) & 0xFF]++;
      dst[p] = src[i];
      idst[p] = isrc[i];
    }
    std::swap(src, dst);
    std::swap(isrc, idst);
  }
}

// Branchless lower_bound: first index in [0, len) with arr[i] >= v, or len.
inline int32_t LowerBound(const float* arr, int32_t len, float v) {
  const float* base = arr;
  int32_t n = len;
  while (n > 1) {
    const int32_t half = n / 2;
    base = (base[half - 1] < v) ? base + half : base;
    n -= half;
  }
  return static_cast<int32_t>(base - arr) + (len > 0 && *base < v);
}

// Exact analogue of the numeric branch of cuts_from_summaries(): from the
// sorted unique (value, total-weight) summary of one feature, emit cut
// points at evenly spaced weighted ranks. All arithmetic in double, cast to
// float only on output, matching numpy.
void CutsFromSummary(const std::vector<double>& uniq,
                     const std::vector<double>& wsum, int max_bin,
                     std::vector<float>* out_cuts, float* out_min) {
  const size_t k = uniq.size();
  if (k == 0) {
    out_cuts->push_back(std::numeric_limits<float>::infinity());
    *out_min = 0.0f;
    return;
  }
  const double vmin = uniq.front(), vmax = uniq.back();
  std::vector<double> pts;
  if (k <= static_cast<size_t>(max_bin)) {
    pts = uniq;
  } else {
    std::vector<double> cum(k);
    double acc = 0.0;
    for (size_t i = 0; i < k; ++i) {
      acc += wsum[i];
      cum[i] = acc;
    }
    const double total = cum.back();
    pts.reserve(max_bin);
    int64_t prev = -1;
    for (int i = 1; i <= max_bin; ++i) {
      const double rank = (static_cast<double>(i) / max_bin) * total;
      int64_t idx = std::lower_bound(cum.begin(), cum.end(), rank) - cum.begin();
      if (idx > static_cast<int64_t>(k) - 1) idx = static_cast<int64_t>(k) - 1;
      if (idx < 0) idx = 0;
      if (idx != prev) {  // np.unique of a non-decreasing index sequence
        pts.push_back(uniq[idx]);
        prev = idx;
      }
    }
  }
  const double last = vmax + (std::abs(vmax) * 1e-5 + 1e-5);
  // unique(concat(pts[:-1], [last])): pts is sorted unique and last > all of
  // pts[:-1], so the result is just pts[:-1] followed by last.
  for (size_t i = 0; i + 1 < pts.size(); ++i)
    out_cuts->push_back(static_cast<float>(pts[i]));
  out_cuts->push_back(static_cast<float>(last));
  *out_min = static_cast<float>(vmin - (std::abs(vmin) * 1e-5 + 1e-5));
}

}  // namespace

extern "C" {

// Sketch all features of a dense row-major [n, nf] float32 matrix (NaN =
// missing). Writes, per feature f, up to max_bin cut values into
// out_values[f * max_bin ...], the count into out_counts[f], and the
// feature's min sentinel into out_min_vals[f]. weights ([n] float64) may be
// null. skip ([nf] uint8) may be null; features with skip[f] != 0 (e.g.
// categorical, whose cuts the host derives directly) are left untouched
// with out_counts[f] = 0.
void xtpu_sketch_cuts(const float* X, int64_t n, int64_t nf,
                      const double* weights, const uint8_t* skip, int max_bin,
                      float* out_values, int32_t* out_counts,
                      float* out_min_vals) {
#pragma omp parallel for schedule(dynamic)
  for (int64_t f = 0; f < nf; ++f) {
    if (skip != nullptr && skip[f]) {
      out_counts[f] = 0;
      out_min_vals[f] = 0.0f;
      continue;
    }
    // gather non-missing column values as sortable keys (+ weight payload
    // indices; the f64 weights ride outside the radix sort)
    std::vector<uint32_t> keys;
    keys.reserve(n);
    std::vector<double> wsrc;
    if (weights != nullptr) wsrc.reserve(n);
    for (int64_t r = 0; r < n; ++r) {
      float v = X[r * nf + f];
      if (std::isnan(v)) continue;
      v += 0.0f;  // -0.0 -> +0.0 so equal floats share one key
      keys.push_back(F2U(v));
      if (weights != nullptr) wsrc.push_back(weights[r]);
    }
    // radix-sort an index payload so tie weights accumulate in full f64
    std::vector<uint32_t> order;
    if (weights != nullptr) {
      order.resize(keys.size());
      for (size_t i = 0; i < order.size(); ++i) order[i] = i;
      RadixSortIdx(keys, order);
    } else {
      RadixSort(keys, nullptr);
    }
    std::vector<double> uniq, wsum;
    // counted first and reserved exactly: grown by push_back, two vectors of
    // up to n doubles leave a chain of freed blocks a column in each thread's
    // arena, and 220 columns of 7.3M rows ran a 40 GiB host out of memory
    size_t n_uniq = keys.empty() ? 0 : 1;
    for (size_t i = 1; i < keys.size(); ++i) n_uniq += keys[i] != keys[i - 1];
    uniq.reserve(n_uniq);
    wsum.reserve(n_uniq);
    for (size_t i = 0; i < keys.size();) {
      size_t j = i;
      double acc = 0.0;
      while (j < keys.size() && keys[j] == keys[i]) {
        if (weights != nullptr) acc += wsrc[order[j]];
        ++j;
      }
      uniq.push_back(static_cast<double>(U2F(keys[i])));
      wsum.push_back(weights != nullptr ? acc : static_cast<double>(j - i));
      i = j;
    }
    std::vector<float> cuts;
    cuts.reserve(max_bin);
    float mn = 0.0f;
    CutsFromSummary(uniq, wsum, max_bin, &cuts, &mn);
    out_counts[f] = static_cast<int32_t>(cuts.size());
    out_min_vals[f] = mn;
    std::memcpy(out_values + f * max_bin, cuts.data(),
                cuts.size() * sizeof(float));
  }
}

// How many elements of X[0:count] are NaN.
int64_t xtpu_count_nan(const float* X, int64_t count) {
  int64_t found = 0;
#pragma omp parallel for schedule(static) reduction(+ : found)
  for (int64_t i = 0; i < count; ++i) {
    found += std::isnan(X[i]) ? 1 : 0;
  }
  return found;
}

// Vectorized SearchBin (quantile.py HistogramCuts.search_bin + the missing
// mapping done in BinnedMatrix.from_dense): local bin = lower_bound of the
// feature's cuts, clamped into the last real bin; NaN -> missing_bin.
// out_dtype: 0 = uint8, 1 = uint16, 2 = int32.

#if defined(__AVX512F__)
#include <immintrin.h>

// 16 rows of one feature at a time: every lane binary-searches the SAME cut
// array (same trip count), probes gathered per step. ~6x the scalar
// branchless loop on one core (the scalar chain is latency-bound).
static void SearchBinBlock16U8(const float* X, int64_t r0, int64_t nf,
                               const float* cut_values,
                               const int32_t* cut_ptrs, int32_t missing_bin,
                               uint8_t* out) {
  alignas(64) int32_t tmp[16];
  const __m512i lane = _mm512_setr_epi32(0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10,
                                         11, 12, 13, 14, 15);
  const __m512i stride = _mm512_mullo_epi32(
      lane, _mm512_set1_epi32(static_cast<int32_t>(nf)));
  for (int64_t f = 0; f < nf; ++f) {
    const int32_t lo = cut_ptrs[f];
    const int32_t len = cut_ptrs[f + 1] - lo;
    const float* cuts = cut_values + lo;
    const __m512 v = _mm512_i32gather_ps(stride, X + r0 * nf + f, 4);
    const __mmask16 nan = _mm512_cmp_ps_mask(v, v, _CMP_UNORD_Q);
    if (len <= 0) {  // empty cut range: match the scalar path (b = -1,
                     // i.e. clamp of 0 into len-1), NaN -> missing_bin;
                     // and never gather from the empty cut array
      uint8_t* o = out + r0 * nf + f;
      alignas(64) int32_t nm[16];
      _mm512_store_si512(reinterpret_cast<__m512i*>(nm),
                         _mm512_mask_mov_epi32(
                             _mm512_set1_epi32(-1), nan,
                             _mm512_set1_epi32(missing_bin)));
      for (int i = 0; i < 16; ++i) o[i * nf] = static_cast<uint8_t>(nm[i]);
      continue;
    }
    __m512i b = _mm512_setzero_si512();
    int32_t m = len;
    while (m > 1) {
      const int32_t half = m / 2;
      const __m512i probe =
          _mm512_add_epi32(b, _mm512_set1_epi32(half - 1));
      const __m512 c = _mm512_i32gather_ps(probe, cuts, 4);
      const __mmask16 lt = _mm512_cmp_ps_mask(c, v, _CMP_LT_OQ);
      b = _mm512_mask_add_epi32(b, lt, b, _mm512_set1_epi32(half));
      m -= half;
    }
    const __m512 cb = _mm512_i32gather_ps(b, cuts, 4);
    const __mmask16 inc = _mm512_cmp_ps_mask(cb, v, _CMP_LT_OQ);
    b = _mm512_mask_add_epi32(b, inc, b, _mm512_set1_epi32(1));
    b = _mm512_min_epi32(b, _mm512_set1_epi32(len - 1));
    b = _mm512_mask_mov_epi32(b, nan, _mm512_set1_epi32(missing_bin));
    _mm512_store_si512(reinterpret_cast<__m512i*>(tmp), b);
    uint8_t* o = out + r0 * nf + f;
    for (int i = 0; i < 16; ++i) o[i * nf] = static_cast<uint8_t>(tmp[i]);
  }
}
#endif  // __AVX512F__

void xtpu_search_bin(const float* X, int64_t n, int64_t nf,
                     const float* cut_values, const int32_t* cut_ptrs,
                     int32_t missing_bin, int32_t out_dtype, void* out) {
  int64_t r_start = 0;
#if defined(__AVX512F__)
  if (out_dtype == 0 && nf > 0) {
    const int64_t blocks = n / 16;
#pragma omp parallel for schedule(static)
    for (int64_t blk = 0; blk < blocks; ++blk) {
      SearchBinBlock16U8(X, blk * 16, nf, cut_values, cut_ptrs, missing_bin,
                         static_cast<uint8_t*>(out));
    }
    r_start = blocks * 16;  // ragged tail falls through to the scalar loop
  }
#endif
#pragma omp parallel for schedule(static)
  for (int64_t r = r_start; r < n; ++r) {
    const float* row = X + r * nf;
    for (int64_t f = 0; f < nf; ++f) {
      const int32_t lo = cut_ptrs[f];
      const int32_t len = cut_ptrs[f + 1] - lo;
      const float v = row[f];
      int32_t b;
      if (std::isnan(v)) {
        b = missing_bin;
      } else {
        b = LowerBound(cut_values + lo, len, v);
        if (b > len - 1) b = len - 1;
      }
      const int64_t o = r * nf + f;
      if (out_dtype == 0)
        static_cast<uint8_t*>(out)[o] = static_cast<uint8_t>(b);
      else if (out_dtype == 1)
        static_cast<uint16_t*>(out)[o] = static_cast<uint16_t>(b);
      else
        static_cast<int32_t*>(out)[o] = b;
    }
  }
}

}  // extern "C"

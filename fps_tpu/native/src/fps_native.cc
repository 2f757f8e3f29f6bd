// Native host-side ingest kernels for fps_tpu.
//
// The reference's ingest rides Flink's JVM source operators; this framework's
// ingest is host-side Python/numpy (fps_tpu/core/ingest.py), whose two hot
// loops are worth native code on the TPU VM host:
//   * dataset file parsing (np.loadtxt is ~50x slower than a tight scanner
//     on MovieLens-20M-sized rating files), and
//   * skip-gram pair generation with frequent-word subsampling and a
//     dynamic window (a per-token branchy loop, word2vec's ingest shape).
//
// Exposed as a tiny C ABI (no pybind11 in this image) consumed via ctypes —
// see fps_tpu/native/__init__.py, which builds this file on demand with g++
// and falls back to the numpy implementations when no compiler is present.

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>

namespace {

// splitmix64 — deterministic, seedable, fast.
struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed + 0x9E3779B97F4A7C15ULL) {}
  uint64_t next() {
    uint64_t z = (s += 0x9E3779B97F4A7C15ULL);
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
  }
  // uniform in [0, 1)
  double uniform() { return (next() >> 11) * (1.0 / 9007199254740992.0); }
  // uniform integer in [1, hi]
  int one_to(int hi) { return 1 + static_cast<int>(next() % hi); }
};

}  // namespace

extern "C" {

namespace {

inline bool is_digit(char c) { return c >= '0' && c <= '9'; }

// Parse an unsigned int at p; advances p. Returns -1 if no digits.
inline long parse_uint(const char*& p, const char* end) {
  if (p >= end || !is_digit(*p)) return -1;
  long v = 0;
  while (p < end && is_digit(*p)) v = v * 10 + (*p++ - '0');
  return v;
}

// Parse a simple decimal (digits[.digits]); advances p. NaN if no digits.
inline float parse_decimal(const char*& p, const char* end) {
  long ip = parse_uint(p, end);
  if (ip < 0) return -1.0f;
  double v = static_cast<double>(ip);
  if (p < end && *p == '.') {
    ++p;
    double scale = 0.1;
    while (p < end && is_digit(*p)) {
      v += (*p++ - '0') * scale;
      scale *= 0.1;
    }
  }
  return static_cast<float>(v);
}

inline void skip_sep(const char*& p, const char* end) {
  while (p < end && (*p == '\t' || *p == ',' || *p == ' ')) ++p;
}

inline void skip_line(const char*& p, const char* end) {
  while (p < end && *p != '\n') ++p;
  if (p < end) ++p;
}

}  // namespace

// Count newline-terminated lines in a file (capacity sizing for
// fps_parse_ratings — keeps the whole "how many rows might this file have"
// question on the native side, one warm-cache read instead of a Python
// chunk loop). Returns -1 if the file cannot be read.
long fps_count_lines(const char* path) {
  FILE* f = fopen(path, "rb");
  if (!f) return -1;
  const size_t bufsz = 1 << 18;  // heap: callers may run on small-stack threads
  char* buf = static_cast<char*>(malloc(bufsz));
  if (!buf) {
    fclose(f);
    return -1;
  }
  long lines = 0;
  size_t got;
  char last = '\n';
  while ((got = fread(buf, 1, bufsz, f)) > 0) {
    for (size_t i = 0; i < got; ++i)
      if (buf[i] == '\n') ++lines;
    last = buf[got - 1];
  }
  free(buf);
  fclose(f);
  if (last != '\n') ++lines;  // unterminated final line
  return lines;
}

// Parse a ratings file: lines of "user sep item sep rating [sep extra...]"
// with sep in {tab, comma, space}. '#'-leading lines are comments and are
// skipped anywhere (np.loadtxt convention). Other non-digit-leading lines
// are treated as skippable headers ONLY before the first data row; after
// data has started they count in *malformed, as do lines that start like
// data but fail mid-parse. A file that yields ZERO data rows but had
// header-skipped lines also reports them as malformed — a quoted-field csv
// must error, not parse to an empty dataset. user/item are written verbatim
// (caller re-indexes). Returns rows written, or -1 if the file cannot be
// read. Writes at most cap rows. Whole-file buffered manual scanner —
// per-line stdio + strtol measured ~7x slower on ML-20M files.
long fps_parse_ratings(const char* path, int32_t* users, int32_t* items,
                       float* ratings, long cap, long* malformed) {
  FILE* f = fopen(path, "rb");
  if (!f) return -1;
  if (fseek(f, 0, SEEK_END) != 0) {
    fclose(f);
    return -1;
  }
  long size = ftell(f);
  if (size < 0 || fseek(f, 0, SEEK_SET) != 0) {
    fclose(f);
    return -1;
  }
  char* buf = static_cast<char*>(malloc(size + 1));
  if (!buf) {
    fclose(f);
    return -1;
  }
  long got = static_cast<long>(fread(buf, 1, size, f));
  fclose(f);
  const char* p = buf;
  const char* end = buf + got;
  long headers = 0;
  long n = 0;
  long bad = 0;
  while (n < cap && p < end) {
    while (p < end && *p == ' ') ++p;
    if (p >= end) break;
    if (*p == '\n' || (*p == '\r' && (p + 1 >= end || p[1] == '\n'))) {
      skip_line(p, end);  // empty line (LF or CRLF)
      continue;
    }
    if (*p == '#') {  // comment line, valid anywhere
      skip_line(p, end);
      continue;
    }
    if (!is_digit(*p)) {
      if (n == 0) {
        ++headers;  // header line before any data
      } else {
        ++bad;  // non-data line mid-file: corrupt, not a header
      }
      skip_line(p, end);
      continue;
    }
    long u = parse_uint(p, end);
    skip_sep(p, end);
    long i = parse_uint(p, end);
    skip_sep(p, end);
    float r = parse_decimal(p, end);
    if (u < 0 || i < 0 || r < 0.0f) {  // malformed data line
      ++bad;
      skip_line(p, end);
      continue;
    }
    users[n] = static_cast<int32_t>(u);
    items[n] = static_cast<int32_t>(i);
    ratings[n] = r;
    ++n;
    skip_line(p, end);
  }
  free(buf);
  if (n == 0 && headers > 0) bad += headers;  // all-header file: not data
  if (malformed) *malformed = bad;
  return n;
}

namespace {

// Signed decimal with optional exponent ("-1", "+0.25", "1e-3"); advances p.
// Returns false if nothing parseable at p.
inline bool parse_signed(const char*& p, const char* end, double* out) {
  double sign = 1.0;
  if (p < end && (*p == '+' || *p == '-')) {
    if (*p == '-') sign = -1.0;
    ++p;
  }
  if (p >= end || (!is_digit(*p) && *p != '.')) return false;
  double v = 0.0;
  bool digits = false;  // "." / "-." must fail like Python float("."), not
                        // parse as 0.0 — native and fallback loaders must
                        // classify degenerate tokens identically.
  while (p < end && is_digit(*p)) {
    v = v * 10.0 + (*p++ - '0');
    digits = true;
  }
  if (p < end && *p == '.') {
    ++p;
    double scale = 0.1;
    while (p < end && is_digit(*p)) {
      v += (*p++ - '0') * scale;
      scale *= 0.1;
      digits = true;
    }
  }
  if (!digits) return false;
  if (p < end && (*p == 'e' || *p == 'E')) {
    ++p;
    double esign = 1.0;
    if (p < end && (*p == '+' || *p == '-')) {
      if (*p == '-') esign = -1.0;
      ++p;
    }
    if (p >= end || !is_digit(*p)) return false;
    double e = 0.0;
    while (p < end && is_digit(*p)) e = e * 10.0 + (*p++ - '0');
    // Cap: beyond ~1e310 the scale is inf/0 anyway, and an O(e) loop on a
    // hostile exponent ("1e2000000000") must not hang the parser.
    long ecap = e > 310.0 ? 310 : static_cast<long>(e);
    double scale = 1.0;
    for (long k = 0; k < ecap; ++k) scale *= 10.0;
    v = esign > 0 ? v * scale : v / scale;
  }
  *out = sign * v;
  return true;
}

// FNV-1a 64-bit over bytes, finalized with splitmix64 — the categorical
// feature hash. fps_tpu/utils/datasets.py's fallback reimplements this
// bit-for-bit; the two must stay in sync.
inline uint64_t hash_bytes(uint64_t seed, const char* s, long len) {
  uint64_t h = 1469598103934665603ULL ^ seed;
  for (long i = 0; i < len; ++i) {
    h ^= static_cast<unsigned char>(s[i]);
    h *= 1099511628211ULL;
  }
  uint64_t z = h + 0x9E3779B97F4A7C15ULL;
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
  return z ^ (z >> 31);
}

inline char* read_whole_file(const char* path, long* out_len) {
  FILE* f = fopen(path, "rb");
  if (!f) return nullptr;
  // An unseekable path (pipe, directory) must surface as an I/O error, not
  // as a valid empty dataset: ftell returns -1 there.
  if (fseek(f, 0, SEEK_END) != 0) {
    fclose(f);
    return nullptr;
  }
  long size = ftell(f);
  if (size < 0 || fseek(f, 0, SEEK_SET) != 0) {
    fclose(f);
    return nullptr;
  }
  char* buf = static_cast<char*>(malloc(size + 1));
  if (!buf) {
    fclose(f);
    return nullptr;
  }
  *out_len = static_cast<long>(fread(buf, 1, size, f));
  fclose(f);
  return buf;
}

}  // namespace

// svmlight/RCV1 scanner, pass 1: rows and the max feature count of any
// line, so the caller can size the padded (rows, nnz) arrays. Lines:
//   <label> <idx>:<val> <idx>:<val> ... [# comment]
// '#'-leading lines are comments; blank lines skipped. Returns data-looking
// row count, or -1 if the file cannot be read.
long fps_svmlight_dims(const char* path, long* max_nnz) {
  long size = 0;
  char* buf = read_whole_file(path, &size);
  if (!buf) return -1;
  const char* p = buf;
  const char* end = buf + size;
  long rows = 0, mx = 0;
  while (p < end) {
    while (p < end && (*p == ' ' || *p == '\r')) ++p;
    if (p >= end) break;
    if (*p == '\n' || *p == '#') {
      skip_line(p, end);
      continue;
    }
    long nnz = 0;
    const char* q = p;
    while (q < end && *q != '\n' && *q != '#') {
      if (*q == ':') ++nnz;
      ++q;
    }
    if (nnz > mx) mx = nnz;
    ++rows;
    skip_line(p, end);
  }
  free(buf);
  *max_nnz = mx;
  return rows;
}

// svmlight/RCV1 scanner, pass 2: fill CALLER-ZEROED padded row-major
// (cap_rows x nnz_cap) id/value arrays (pad slots stay id 0 / value 0 —
// inactive by the models' x != 0 convention) plus float labels. Feature
// ids are written verbatim (svmlight is conventionally 1-based; the caller
// re-indexes). Rows with more than nnz_cap features keep the FIRST nnz_cap
// and count in *truncated. Structurally malformed data lines (unparseable
// label, broken idx:val token) count in *malformed — callers refuse the
// file rather than silently drop lines, matching fps_parse_ratings.
long fps_parse_svmlight(const char* path, float* labels, int32_t* ids,
                        float* vals, long cap_rows, long nnz_cap,
                        long* truncated, long* malformed) {
  long size = 0;
  char* buf = read_whole_file(path, &size);
  if (!buf) return -1;
  const char* p = buf;
  const char* end = buf + size;
  long n = 0, bad = 0, trunc = 0;
  while (n < cap_rows && p < end) {
    while (p < end && (*p == ' ' || *p == '\r')) ++p;
    if (p >= end) break;
    if (*p == '\n' || *p == '#') {
      skip_line(p, end);
      continue;
    }
    double label;
    if (!parse_signed(p, end, &label)) {
      ++bad;
      skip_line(p, end);
      continue;
    }
    long nnz = 0;
    bool ok = true;
    while (p < end && *p != '\n' && *p != '#' && *p != '\r') {
      while (p < end && *p == ' ') ++p;
      if (p >= end || *p == '\n' || *p == '#' || *p == '\r') break;
      long idx = parse_uint(p, end);
      if (idx < 0 || p >= end || *p != ':') {
        ok = false;
        break;
      }
      ++p;  // ':'
      double v;
      if (!parse_signed(p, end, &v)) {
        ok = false;
        break;
      }
      if (nnz < nnz_cap) {
        ids[n * nnz_cap + nnz] = static_cast<int32_t>(idx);
        vals[n * nnz_cap + nnz] = static_cast<float>(v);
        ++nnz;
      } else {
        ++trunc;
        // keep scanning to validate the rest of the line
      }
    }
    if (!ok) {
      ++bad;
      // wipe any partial row
      for (long k = 0; k < nnz_cap; ++k) {
        ids[n * nnz_cap + k] = 0;
        vals[n * nnz_cap + k] = 0.0f;
      }
      skip_line(p, end);
      continue;
    }
    labels[n] = static_cast<float>(label);
    ++n;
    skip_line(p, end);
  }
  free(buf);
  if (truncated) *truncated = trunc;
  if (malformed) *malformed = bad;
  return n;
}

// Criteo click-logs scanner: one example per line,
//   <label> \t I1..I13 (ints, may be empty/negative) \t C1..C26 (hex tokens,
//   may be empty)
// Numeric column j (0-based) with value x >= 0 becomes feature id j with
// value log1p(x); negative or empty numerics are treated as missing.
// Categorical column j with token s becomes feature id
//   13 + hash(j, s) % (num_features - 13)      (FNV-1a + splitmix64)
// with value 1.0. Output is CALLER-ZEROED row-major (cap_rows x 39) with a
// FIXED-SLOT layout: numeric column j always sits at slot j (id j, value 0
// when missing — inactive by the models' x != 0 convention), categoricals
// append from slot 13; absent fields leave inactive pads. Lines with a
// non-0/1 label or a wrong field count are malformed. Returns rows, or -1
// on IO error.
long fps_parse_criteo(const char* path, float* labels, int32_t* ids,
                      float* vals, long cap_rows, long num_features,
                      long* malformed) {
  long size = 0;
  char* buf = read_whole_file(path, &size);
  if (!buf) return -1;
  const long kNum = 13, kCat = 26, kNnz = kNum + kCat;
  const long cat_space = num_features - kNum;
  const char* p = buf;
  const char* end = buf + size;
  long n = 0, bad = 0;
  while (n < cap_rows && p < end) {
    if (*p == '\n') {
      ++p;
      continue;
    }
    if (*p == '\r' && (p + 1 >= end || p[1] == '\n')) {
      p += (p + 1 < end) ? 2 : 1;  // blank CRLF line, skip like the fallback
      continue;
    }
    const char* line_end = p;
    while (line_end < end && *line_end != '\n') ++line_end;
    const char* le = line_end;
    if (le > p && le[-1] == '\r') --le;

    bool ok = true;
    // label
    long label = parse_uint(p, le);
    if (label != 0 && label != 1) ok = false;
    // FIXED-SLOT layout: numeric column j always occupies batch slot j
    // (id j; value 0 = inactive when missing/negative), so slot<->id is
    // deterministic for the dense head — models exploit it by pulling and
    // pushing the 13 numeric weights densely (LogRegConfig.dense_features)
    // instead of paying 13 scatter rows per example. Categorical features
    // append from slot 13 in field order; absent cats leave inactive pads.
    long nnz = kNum;  // cat slots start after the fixed numeric head
    long field = 0;
    while (ok && field < kNnz) {
      if (p >= le || *p != '\t') {
        ok = false;
        break;
      }
      ++p;  // tab
      const char* fs = p;
      while (p < le && *p != '\t') ++p;
      long flen = p - fs;
      if (field < kNum) {
        ids[n * kNnz + field] = static_cast<int32_t>(field);
        vals[n * kNnz + field] = 0.0f;  // inactive unless present below
      }
      if (flen == 0) {
        ++field;
        continue;  // missing value
      }
      if (field < kNum) {
        const char* q = fs;
        double v;
        if (!parse_signed(q, fs + flen, &v) || q != fs + flen) {
          ok = false;
          break;
        }
        if (v >= 0.0) {
          // log1p, cheap enough inline
          double x = v, r = 0.0;
          r = __builtin_log1p(x);
          vals[n * kNnz + field] = static_cast<float>(r);
        }
      } else {
        uint64_t h = hash_bytes(static_cast<uint64_t>(field), fs, flen);
        ids[n * kNnz + nnz] =
            static_cast<int32_t>(kNum + static_cast<long>(h % cat_space));
        vals[n * kNnz + nnz] = 1.0f;
        ++nnz;
      }
      ++field;
    }
    if (ok && (field != kNnz || p != le)) ok = false;
    if (!ok) {
      ++bad;
      for (long k = 0; k < kNnz; ++k) {
        ids[n * kNnz + k] = 0;
        vals[n * kNnz + k] = 0.0f;
      }
      p = line_end < end ? line_end + 1 : end;
      continue;
    }
    labels[n] = static_cast<float>(label);
    ++n;
    p = line_end < end ? line_end + 1 : end;
  }
  free(buf);
  if (malformed) *malformed = bad;
  return n;
}

// Skip-gram pair generation over a token segment, mirroring
// fps_tpu/models/word2vec.py's skipgram_chunks inner loop:
//   1. drop position t with probability 1 - keep_p[token[t]]  (subsampling)
//   2. per kept position, draw half-width h ~ U{1..window}
//   3. for d in 1..h with t+d kept-in-range: emit (kept[t], kept[t+d]) and
//      (kept[t+d], kept[t])  (both directions, distance gated by the LEFT
//      element's half-width, exactly like the numpy implementation)
// Deterministic for a given seed. Returns pairs written (<= cap).
long fps_skipgram_pairs(const int32_t* tokens, long n, int window,
                        uint64_t seed, const float* keep_p, int32_t vocab,
                        int32_t* centers, int32_t* contexts, long cap) {
  if (n <= 0 || window <= 0) return 0;
  Rng rng(seed);
  // Pass 1: subsample into a kept buffer (indices compacted).
  int32_t* kept = static_cast<int32_t*>(malloc(sizeof(int32_t) * n));
  if (!kept) return -1;
  long m = 0;
  for (long t = 0; t < n; ++t) {
    int32_t tok = tokens[t];
    double kp = (keep_p && tok >= 0 && tok < vocab) ? keep_p[tok] : 1.0;
    if (kp >= 1.0 || rng.uniform() < kp) kept[m++] = tok;
  }
  long out = 0;
  for (long t = 0; t < m && out < cap; ++t) {
    int h = rng.one_to(window);
    for (int d = 1; d <= h && t + d < m; ++d) {
      if (out + 2 > cap) break;
      centers[out] = kept[t];
      contexts[out] = kept[t + d];
      ++out;
      centers[out] = kept[t + d];
      contexts[out] = kept[t];
      ++out;
    }
  }
  free(kept);
  return out;
}

}  // extern "C"

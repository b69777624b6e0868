// nebula_native — C-ABI native kernels for the host (CPU) plane.
//
// The reference implements its storage scan path, row/key codec, and
// bulk loaders in C++ (src/storage, src/codec [UNVERIFIED — empty
// reference mount, SURVEY §0]).  In the TPU-first rebuild the device
// compute path is XLA-generated native code; the pieces that still
// merit handwritten C++ are the host-side bulk-data kernels feeding
// HBM: CSV ingest, COO→padded-CSR assembly (the sort+indptr hot loop
// of the snapshot builder), and the binary row codec used for bulk
// export.  Exposed via a plain C ABI consumed with ctypes
// (nebula_tpu/native/__init__.py), with Python/NumPy fallbacks.
//
// Build: g++ -O3 -shared -fPIC -std=c++17 -o libnebula_native.so nebula_native.cc
#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <cstdlib>
#include <numeric>
#include <string>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// CSV edge/vertex ingest
//
// Parses a delimited text file of records.  Column types:
//   0 = int64, 1 = float64, 2 = string (FNV-1a 64-bit hash; the Python
//       side resolves hashes to pool codes), 3 = skip.
// Values land column-major into caller-allocated buffers (int64/double
// per column, capacity max_rows).  Returns rows parsed, -1 on I/O
// error, -2 if the file holds more than max_rows rows, or -3 on a
// malformed record (short row, or an int/float field that does not
// parse) — a bulk loader must fail loudly, never silently skip/zero.
// ---------------------------------------------------------------------------

static inline uint64_t fnv1a(const char* s, size_t n) {
    uint64_t h = 1469598103934665603ull;
    for (size_t i = 0; i < n; i++) {
        h ^= (unsigned char)s[i];
        h *= 1099511628211ull;
    }
    return h;
}

long long csv_ingest(const char* path, char delim, int skip_header,
                     int n_cols, const int* col_types,
                     long long max_rows, int64_t** int_cols,
                     double** dbl_cols) {
    FILE* f = std::fopen(path, "rb");
    if (!f) return -1;
    std::vector<char> buf(1 << 20);
    std::string line;
    line.reserve(4096);
    long long row = 0;
    bool first = true;
    bool malformed = false;
    int c;
    std::string cur;
    std::vector<std::string> fields;
    auto flush_line = [&]() -> bool {
        if (cur.empty() && fields.empty()) return true;
        fields.push_back(cur);
        cur.clear();
        if (first && skip_header) {
            first = false;
            fields.clear();
            return true;
        }
        first = false;
        if ((int)fields.size() != n_cols) {
            malformed = true;   // short OR over-long record (field shift)
            fields.clear();
            return false;
        }
        if (row >= max_rows) { fields.clear(); return false; }
        for (int i = 0; i < n_cols; i++) {
            const std::string& s = fields[i];
            char* end = nullptr;
            switch (col_types[i]) {
                case 0:
                    errno = 0;
                    int_cols[i][row] = std::strtoll(s.c_str(), &end, 10);
                    if (end == s.c_str() || *end != '\0' ||
                        errno == ERANGE) {   // reject silent clamping too
                        malformed = true;
                        fields.clear();
                        return false;
                    }
                    break;
                case 1:
                    errno = 0;
                    dbl_cols[i][row] = std::strtod(s.c_str(), &end);
                    if (end == s.c_str() || *end != '\0' ||
                        errno == ERANGE) {
                        malformed = true;
                        fields.clear();
                        return false;
                    }
                    break;
                case 2: int_cols[i][row] = (int64_t)fnv1a(s.data(), s.size()); break;
                default: break;
            }
        }
        row++;
        fields.clear();
        return true;
    };
    bool keep = true;
    while (keep) {
        size_t n = std::fread(buf.data(), 1, buf.size(), f);
        if (n == 0) break;
        for (size_t i = 0; i < n && keep; i++) {
            c = buf[i];
            if (c == '\n') {
                keep = flush_line();
            } else if (c == '\r') {
                // ignore
            } else if (c == delim) {
                fields.push_back(cur);
                cur.clear();
            } else {
                cur.push_back((char)c);
            }
        }
    }
    if (keep) flush_line();
    std::fclose(f);
    if (malformed) return -3;
    if (!keep) return -2;          // max_rows exceeded
    return row;
}

// ---------------------------------------------------------------------------
// COO → padded per-part CSR (the snapshot builder's hot loop)
//
// Inputs: n_edges COO entries with dense src/dst ids (dense % P = owner
// part, dense / P = local row), rank.  Emits, for the part-major padded
// layout (P, vmax+1)/(P, emax):
//   perm      (n_edges)    — input index in output slot order, so the
//                            caller gathers property columns with one
//                            numpy fancy-index per column
//   indptr    (P, vmax+1)
//   nbr,rank  (P, emax)    — -1 / 0 padded
// Sort order per part: (local_src, rank, dst) — matching the host
// get_neighbors iteration order for integer vids.
// Returns emax (max edges in any part), or -1 on error.
// ---------------------------------------------------------------------------

long long build_csr(long long n_edges, int P, long long vmax,
                    const int64_t* src_dense, const int64_t* dst_dense,
                    const int64_t* rank, const int64_t* dst_key,
                    int64_t* perm, int32_t* indptr,
                    int32_t* nbr, int32_t* rank_out,
                    long long emax_cap) {
    std::vector<int64_t> order(n_edges);
    std::iota(order.begin(), order.end(), 0);
    std::sort(order.begin(), order.end(),
              [&](int64_t a, int64_t b) {
                  int pa = (int)(src_dense[a] % P), pb = (int)(src_dense[b] % P);
                  if (pa != pb) return pa < pb;
                  int64_t la = src_dense[a] / P, lb = src_dense[b] / P;
                  if (la != lb) return la < lb;
                  if (rank[a] != rank[b]) return rank[a] < rank[b];
                  // dst_key: caller-provided neighbor order (vid value
                  // for int spaces, sorted-string ordinal otherwise)
                  if (dst_key[a] != dst_key[b]) return dst_key[a] < dst_key[b];
                  return a < b;
              });
    // validate + per-part counts (an out-of-range local index must be a
    // clean error, not a write past the indptr row)
    std::vector<long long> pcount(P, 0);
    for (long long i = 0; i < n_edges; i++) {
        if (src_dense[i] < 0 || src_dense[i] / P >= vmax) return -1;
        pcount[src_dense[i] % P]++;
    }
    long long emax = 1;
    for (int p = 0; p < P; p++) emax = std::max(emax, pcount[p]);
    if (emax > emax_cap) return -1;

    // fill
    std::vector<long long> ppos(P, 0);
    const long long stride_i = vmax + 1;
    for (int p = 0; p < P; p++)
        for (long long v = 0; v <= vmax; v++) indptr[p * stride_i + v] = 0;
    for (long long k = 0; k < n_edges; k++) {
        int64_t e = order[k];
        int p = (int)(src_dense[e] % P);
        int64_t local = src_dense[e] / P;
        long long slot = ppos[p]++;
        perm[p * emax_cap + slot] = e;
        nbr[p * emax_cap + slot] = (int32_t)dst_dense[e];
        rank_out[p * emax_cap + slot] = (int32_t)rank[e];
        indptr[p * stride_i + local + 1]++;
    }
    for (int p = 0; p < P; p++) {
        int32_t acc = 0;
        for (long long v = 1; v <= vmax; v++) {
            acc += indptr[p * stride_i + v];
            indptr[p * stride_i + v] = acc;
        }
    }
    return emax;
}

// ---------------------------------------------------------------------------
// Binary row codec (RowWriterV2/RowReaderWrapper analog)
//
// Fixed little-endian layout per row:
//   u16 schema_version | u16 n_props | per prop:
//     u8 kind (0=null,1=int64,2=double,3=bool,4=str) |
//     int64/double/u8 | (str: u32 len + bytes)
// Encode: caller passes parallel arrays describing one row; returns
// bytes written or -1 if the buffer is too small.  Used for bulk export
// and WAL-compaction payloads.
// ---------------------------------------------------------------------------

long long row_encode(int version, int n_props, const int* kinds,
                     const int64_t* ivals, const double* dvals,
                     const char** svals, const int* slens,
                     unsigned char* out, long long cap) {
    long long need = 4;
    for (int i = 0; i < n_props; i++) {
        need += 1;
        if (kinds[i] == 1) need += 8;
        else if (kinds[i] == 2) need += 8;
        else if (kinds[i] == 3) need += 1;
        else if (kinds[i] == 4) need += 4 + slens[i];
    }
    if (need > cap) return -1;
    unsigned char* w = out;
    uint16_t v16 = (uint16_t)version, n16 = (uint16_t)n_props;
    std::memcpy(w, &v16, 2); w += 2;
    std::memcpy(w, &n16, 2); w += 2;
    for (int i = 0; i < n_props; i++) {
        *w++ = (unsigned char)kinds[i];
        if (kinds[i] == 1) { std::memcpy(w, &ivals[i], 8); w += 8; }
        else if (kinds[i] == 2) { std::memcpy(w, &dvals[i], 8); w += 8; }
        else if (kinds[i] == 3) { *w++ = (unsigned char)(ivals[i] != 0); }
        else if (kinds[i] == 4) {
            uint32_t l = (uint32_t)slens[i];
            std::memcpy(w, &l, 4); w += 4;
            std::memcpy(w, svals[i], l); w += l;
        }
    }
    return (long long)(w - out);
}

// Decode: fills kinds/ivals/dvals and, for strings, offsets+lengths
// into the input buffer (zero-copy).  Returns n_props or -1.
long long row_decode(const unsigned char* in, long long len,
                     int* version, int* kinds, int64_t* ivals,
                     double* dvals, long long* soffs, int* slens,
                     int max_props) {
    if (len < 4) return -1;
    uint16_t v16, n16;
    std::memcpy(&v16, in, 2);
    std::memcpy(&n16, in + 2, 2);
    if (n16 > max_props) return -1;
    const unsigned char* r = in + 4;
    const unsigned char* end = in + len;
    for (int i = 0; i < n16; i++) {
        if (r >= end) return -1;
        int k = *r++;
        kinds[i] = k;
        if (k == 1) { if (r + 8 > end) return -1; std::memcpy(&ivals[i], r, 8); r += 8; }
        else if (k == 2) { if (r + 8 > end) return -1; std::memcpy(&dvals[i], r, 8); r += 8; }
        else if (k == 3) { if (r + 1 > end) return -1; ivals[i] = *r++; }
        else if (k == 4) {
            uint32_t l;
            if (r + 4 > end) return -1;
            std::memcpy(&l, r, 4); r += 4;
            if (r + l > end) return -1;
            soffs[i] = (long long)(r - in);
            slens[i] = (int)l;
            r += l;
        } else if (k != 0) return -1;
    }
    *version = v16;
    return n16;
}

// A fetched property column's 32-bit halves (device.py split_halves)
// joined into its 64-bit host column: out[i] = lo[i] | hi[i] << 32, one
// pass at a copy's rate.  numpy has no one-pass interleave: two strided
// stores a row cost twice the copy (runtime.py _join_halves).
//
// The pass also answers the decode's one question about the column
// (csr.py decode_prop_column_np): does any joined slot hold the NULL
// sentinel?  For a float column (is_float) that is a NaN of any
// pattern, exponent all ones and a mantissa bit set; for an integer
// one INT_NULL, the sign bit alone.  Both are tested on the halves in
// 32-bit arithmetic, which the compiler vectorises without 64-bit
// compares, and OR-ed into one word: no branch in the loop.  Returns 1
// if some slot does, else 0.
int join_halves(const uint32_t* lo, const uint32_t* hi, uint64_t* out,
                long long n, int is_float) {
    uint32_t hit = 0;
    if (is_float) {
        for (long long i = 0; i < n; ++i) {
            uint32_t l = lo[i], h = hi[i];
            out[i] = (uint64_t)l | ((uint64_t)h << 32);
            int32_t a = (int32_t)(h & 0x7FFFFFFFu);
            hit |= (uint32_t)(a > 0x7FF00000) |
                   ((uint32_t)(a == 0x7FF00000) & (uint32_t)(l != 0));
        }
    } else {
        for (long long i = 0; i < n; ++i) {
            uint32_t l = lo[i], h = hi[i];
            out[i] = (uint64_t)l | ((uint64_t)h << 32);
            hit |= (uint32_t)(h == 0x80000000u) & (uint32_t)(l == 0);
        }
    }
    return hit != 0;
}

}  // extern "C"

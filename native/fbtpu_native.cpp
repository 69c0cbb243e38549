// fbtpu_native — msgpack hot-path scanner + batch staging.
//
// The C++ data-plane shim promised by SURVEY §7 ("msgpack chunk codec +
// staging buffers"): the reference keeps its hot loops in C
// (lib/msgpack-c, src/flb_mp.c record counting at
// src/flb_input_chunk.c:3041); this is the TPU build's equivalent. The
// Python codec (fluentbit_tpu/codec/msgpack.py) remains the semantic
// reference; this library accelerates three operations on the ingest
// path:
//
//   fbtpu_count_records  — count top-level msgpack objects (no decode)
//   fbtpu_scan_offsets   — per-record byte offsets (raw span slicing)
//   fbtpu_stage_field    — fill the [B, L] u8 staging matrix + lengths
//                          with each record's top-level string field
//                          (feeds the DFA/sketch kernels directly, no
//                          Python-object round trip)
//
// Exposed via ctypes (no pybind11 in this image). All functions return
// -1 on malformed input; the caller falls back to the Python codec.

#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <new>
#include <thread>
#include <vector>

#if defined(__SSE2__) || defined(_M_X64) || defined(__x86_64__)
#include <emmintrin.h>
#define FBTPU_HAVE_SSE2 1
#endif

// W-way interleaved DFA over extracted values: W independent
// state-transition chains hide the dependent-load latency that caps a
// scalar table walk. DEAD(0)/ACC(1) rows and the EOL class are all
// absorbing in these tables (regex/dfa.py construction), so rows
// shorter than the block's max length just spin on EOL — branch-free.
#define FBTPU_DFA_LANES 8

static void dfa_run_block(const int16_t *trans, const int32_t *cmap,
                          int32_t C, int32_t start,
                          const uint8_t *const *vals,
                          const uint32_t *lens, int nrows,
                          uint8_t *out) {
    const int W = FBTPU_DFA_LANES;
    int32_t eol = cmap[256];
    int32_t s[W];
    const uint8_t *v[W];
    uint32_t l[W], maxlen = 0;
    for (int j = 0; j < W; j++) {
        if (j < nrows && vals[j] != nullptr) {
            v[j] = vals[j];
            l[j] = lens[j];
            s[j] = start;
            if (l[j] > maxlen) maxlen = l[j];
        } else {
            v[j] = nullptr;
            l[j] = 0;
            s[j] = 0;  // DEAD: missing/non-string value never matches
        }
    }
    for (uint32_t pos = 0; pos <= maxlen; pos++) {
        int32_t c[W], acc = 0;
        for (int j = 0; j < W; j++)
            c[j] = pos < l[j] ? cmap[v[j][pos]] : eol;
        for (int j = 0; j < W; j++) {
            s[j] = trans[s[j] * C + c[j]];
            acc |= s[j];
        }
        // states are non-negative, so OR <= 1 iff every chain is in
        // {DEAD, ACC} — all absorbed, result final
        if (acc <= 1) break;
    }
    // every live row consumed >= 1 EOL symbol inside the loop (pos runs
    // to maxlen inclusive), and an early break means all chains were
    // already absorbed — the final states are final
    for (int j = 0; j < W && j < nrows; j++)
        out[j] = (uint8_t)(s[j] == 1);
}

// k>=2 variant: trans_k[s, c1*C^(k-1) + ... + ck] tables pre-composed
// host-side (GrepTables packs them while S*C^k fits the budget) cut the
// dependent-load chain k-fold — k bytes per step, EOL^k absorbing.
template <int K>
static void dfa_run_block_k(const int16_t *transk, const int32_t *cmap,
                            int32_t C, int32_t start,
                            const uint8_t *const *vals,
                            const uint32_t *lens, int nrows,
                            uint8_t *out) {
    const int W = FBTPU_DFA_LANES;
    int32_t eol = cmap[256];
    int32_t Ck = 1;
    for (int b = 0; b < K; b++) Ck *= C;
    int32_t s[W];
    const uint8_t *v[W];
    uint32_t l[W], maxlen = 0;
    for (int j = 0; j < W; j++) {
        if (j < nrows && vals[j] != nullptr) {
            v[j] = vals[j];
            l[j] = lens[j];
            s[j] = start;
            if (l[j] > maxlen) maxlen = l[j];
        } else {
            v[j] = nullptr;
            l[j] = 0;
            s[j] = 0;
        }
    }
    // pos <= maxlen guarantees every row sees >= 1 EOL symbol: the
    // step group containing index l always runs (l <= maxlen), and pad
    // positions inside a group read as EOL
    for (uint32_t pos = 0; pos <= maxlen; pos += K) {
        int32_t c[W], acc = 0;
        for (int j = 0; j < W; j++) {
            int32_t cc = 0;
            for (int b = 0; b < K; b++) {
                int32_t cb = pos + b < l[j] ? cmap[v[j][pos + b]] : eol;
                cc = cc * C + cb;
            }
            c[j] = cc;
        }
        for (int j = 0; j < W; j++) {
            s[j] = transk[s[j] * Ck + c[j]];
            acc |= s[j];
        }
        if (acc <= 1) break;
    }
    for (int j = 0; j < W && j < nrows; j++)
        out[j] = (uint8_t)(s[j] == 1);
}



extern "C" {

// ---------------------------------------------------------------------
// msgpack skip: advance over one object, headers only
// ---------------------------------------------------------------------

static const uint8_t *skip_obj(const uint8_t *p, const uint8_t *end,
                               int depth) {
    if (p >= end || depth > 64) return nullptr;
    uint8_t b = *p++;
    uint32_t n;
    if (b <= 0x7f || b >= 0xe0) return p;                 // fixint
    if ((b & 0xe0) == 0xa0) {                             // fixstr
        n = b & 0x1f;
        return p + n <= end ? p + n : nullptr;
    }
    if ((b & 0xf0) == 0x90) {                             // fixarray
        n = b & 0x0f;
        for (uint32_t i = 0; i < n; i++) {
            p = skip_obj(p, end, depth + 1);
            if (!p) return nullptr;
        }
        return p;
    }
    if ((b & 0xf0) == 0x80) {                             // fixmap
        n = b & 0x0f;
        for (uint32_t i = 0; i < 2 * n; i++) {
            p = skip_obj(p, end, depth + 1);
            if (!p) return nullptr;
        }
        return p;
    }
    switch (b) {
    case 0xc0: case 0xc2: case 0xc3: return p;            // nil/bool
    case 0xcc: case 0xd0: return p + 1 <= end ? p + 1 : nullptr;
    case 0xcd: case 0xd1: return p + 2 <= end ? p + 2 : nullptr;
    case 0xce: case 0xd2: case 0xca: return p + 4 <= end ? p + 4 : nullptr;
    case 0xcf: case 0xd3: case 0xcb: return p + 8 <= end ? p + 8 : nullptr;
    case 0xd9: case 0xc4:                                 // str8/bin8
        if (p + 1 > end) return nullptr;
        n = p[0]; p += 1;
        return p + n <= end ? p + n : nullptr;
    case 0xda: case 0xc5:                                 // str16/bin16
        if (p + 2 > end) return nullptr;
        n = ((uint32_t)p[0] << 8) | p[1]; p += 2;
        return p + n <= end ? p + n : nullptr;
    case 0xdb: case 0xc6:                                 // str32/bin32
        if (p + 4 > end) return nullptr;
        n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
          | ((uint32_t)p[2] << 8) | p[3]; p += 4;
        return p + n <= end ? p + n : nullptr;
    case 0xdc:                                            // array16
        if (p + 2 > end) return nullptr;
        n = ((uint32_t)p[0] << 8) | p[1]; p += 2;
        for (uint32_t i = 0; i < n; i++) {
            p = skip_obj(p, end, depth + 1);
            if (!p) return nullptr;
        }
        return p;
    case 0xdd:                                            // array32
        if (p + 4 > end) return nullptr;
        n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
          | ((uint32_t)p[2] << 8) | p[3]; p += 4;
        for (uint32_t i = 0; i < n; i++) {
            p = skip_obj(p, end, depth + 1);
            if (!p) return nullptr;
        }
        return p;
    case 0xde:                                            // map16
        if (p + 2 > end) return nullptr;
        n = ((uint32_t)p[0] << 8) | p[1]; p += 2;
        for (uint32_t i = 0; i < 2 * n; i++) {
            p = skip_obj(p, end, depth + 1);
            if (!p) return nullptr;
        }
        return p;
    case 0xdf:                                            // map32
        if (p + 4 > end) return nullptr;
        n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
          | ((uint32_t)p[2] << 8) | p[3]; p += 4;
        for (uint32_t i = 0; i < 2 * n; i++) {
            p = skip_obj(p, end, depth + 1);
            if (!p) return nullptr;
        }
        return p;
    case 0xd4: return p + 2 <= end ? p + 2 : nullptr;     // fixext1
    case 0xd5: return p + 3 <= end ? p + 3 : nullptr;     // fixext2
    case 0xd6: return p + 5 <= end ? p + 5 : nullptr;     // fixext4
    case 0xd7: return p + 9 <= end ? p + 9 : nullptr;     // fixext8
    case 0xd8: return p + 17 <= end ? p + 17 : nullptr;   // fixext16
    case 0xc7:                                            // ext8
        if (p + 2 > end) return nullptr;
        n = p[0]; p += 2;
        return p + n <= end ? p + n : nullptr;
    case 0xc8:                                            // ext16
        if (p + 3 > end) return nullptr;
        n = ((uint32_t)p[0] << 8) | p[1]; p += 3;
        return p + n <= end ? p + n : nullptr;
    case 0xc9:                                            // ext32
        if (p + 5 > end) return nullptr;
        n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
          | ((uint32_t)p[2] << 8) | p[3]; p += 5;
        return p + n <= end ? p + n : nullptr;
    }
    return nullptr;
}

// helpers: read container headers at p (returns elem count, advances)
static const uint8_t *read_array_hdr(const uint8_t *p, const uint8_t *end,
                                     uint32_t *n) {
    if (p >= end) return nullptr;
    uint8_t b = *p++;
    if ((b & 0xf0) == 0x90) { *n = b & 0x0f; return p; }
    if (b == 0xdc) {
        if (p + 2 > end) return nullptr;
        *n = ((uint32_t)p[0] << 8) | p[1];
        return p + 2;
    }
    if (b == 0xdd) {
        if (p + 4 > end) return nullptr;
        *n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
           | ((uint32_t)p[2] << 8) | p[3];
        return p + 4;
    }
    return nullptr;
}

static const uint8_t *read_map_hdr(const uint8_t *p, const uint8_t *end,
                                   uint32_t *n) {
    if (p >= end) return nullptr;
    uint8_t b = *p++;
    if ((b & 0xf0) == 0x80) { *n = b & 0x0f; return p; }
    if (b == 0xde) {
        if (p + 2 > end) return nullptr;
        *n = ((uint32_t)p[0] << 8) | p[1];
        return p + 2;
    }
    if (b == 0xdf) {
        if (p + 4 > end) return nullptr;
        *n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
           | ((uint32_t)p[2] << 8) | p[3];
        return p + 4;
    }
    return nullptr;
}

static const uint8_t *read_str_hdr(const uint8_t *p, const uint8_t *end,
                                   uint32_t *n) {
    if (p >= end) return nullptr;
    uint8_t b = *p++;
    if ((b & 0xe0) == 0xa0) { *n = b & 0x1f; return p; }
    if (b == 0xd9) {
        if (p + 1 > end) return nullptr;
        *n = p[0];
        return p + 1;
    }
    if (b == 0xda) {
        if (p + 2 > end) return nullptr;
        *n = ((uint32_t)p[0] << 8) | p[1];
        return p + 2;
    }
    if (b == 0xdb) {
        if (p + 4 > end) return nullptr;
        *n = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
           | ((uint32_t)p[2] << 8) | p[3];
        return p + 4;
    }
    return nullptr;
}

// ---------------------------------------------------------------------
// public API
// ---------------------------------------------------------------------

long long fbtpu_count_records(const uint8_t *buf, long long len) {
    const uint8_t *p = buf, *end = buf + len;
    long long count = 0;
    while (p < end) {
        p = skip_obj(p, end, 0);
        if (!p) return -1;
        count++;
    }
    return count;
}

// offsets[count+1]: record i spans [offsets[i], offsets[i+1])
long long fbtpu_scan_offsets(const uint8_t *buf, long long len,
                             long long *offsets, long long max_records) {
    const uint8_t *p = buf, *end = buf + len;
    long long count = 0;
    while (p < end) {
        if (count >= max_records) return -2;  // caller buffer too small
        offsets[count] = p - buf;
        p = skip_obj(p, end, 0);
        if (!p) return -1;
        count++;
    }
    offsets[count] = len;
    return count;
}

// One record's field extraction: stage the top-level string field
// `key` of the record at rec_start into out_row[max_len]. Returns the
// staged length, -1 missing/non-string/non-map, -2 oversize. When the
// record is the common 2-element [[ts, meta], body] shape, *rec_end_out
// gets the end discovered by the pair walk (sparing the caller a
// second full skip_obj walk); otherwise it is left untouched.
static inline int32_t stage_one_record(const uint8_t *rec_start,
                                       const uint8_t *end,
                                       const uint8_t *key, long long keylen,
                                       uint8_t *out_row, long long max_len,
                                       const uint8_t **rec_end_out) {
    uint32_t outer;
    const uint8_t *q = read_array_hdr(rec_start, end, &outer);
    int32_t flen = -1;
    if (q && outer >= 2) {
        // skip the header element (array [ts, meta] or scalar ts)
        const uint8_t *body = skip_obj(q, end, 0);
        if (body) {
            uint32_t pairs;
            const uint8_t *kv = read_map_hdr(body, end, &pairs);
            if (kv) {
                // scan ALL pairs: duplicate map keys are legal
                // msgpack, and the Python dict decode keeps the
                // LAST occurrence — so must we
                const uint8_t *hit = nullptr;
                uint32_t hit_len = 0;
                int hit_kind = 0;  // 0 none, 1 string, 2 non-string
                for (uint32_t i = 0; i < pairs && kv; i++) {
                    uint32_t klen;
                    const uint8_t *kstr = read_str_hdr(kv, end, &klen);
                    const uint8_t *val;
                    bool match = false;
                    if (kstr) {
                        val = kstr + klen;
                        if (val > end) { kv = nullptr; break; }
                        match = ((long long)klen == keylen &&
                                 memcmp(kstr, key, klen) == 0);
                    } else {
                        val = skip_obj(kv, end, 0);  // non-str key
                        if (!val) { kv = nullptr; break; }
                    }
                    if (match) {
                        uint32_t vlen;
                        const uint8_t *vstr =
                            read_str_hdr(val, end, &vlen);
                        if (vstr && vstr + vlen <= end) {
                            hit = vstr;
                            hit_len = vlen;
                            hit_kind = 1;
                        } else {
                            hit_kind = 2;  // non-string value
                        }
                    }
                    kv = skip_obj(val, end, 0);
                }
                if (hit_kind == 1) {
                    if ((long long)hit_len > max_len) {
                        flen = -2;  // overflow row
                    } else {
                        memcpy(out_row, hit, hit_len);
                        flen = (int32_t)hit_len;
                    }
                }
                if (kv && outer == 2 && rec_end_out)
                    *rec_end_out = kv;  // pair walk ended at record end
            }
        }
    }
    return flen;
}

// Stage each record's top-level string field `key` into out[B][max_len].
// Records are [[ts, meta], body] (V2) or [ts, body] (legacy); non-map
// bodies and missing/non-string values get length -1; oversize -2.
// offsets: optional record offsets out (B+1) or NULL.
long long fbtpu_stage_field(const uint8_t *buf, long long buflen,
                            const uint8_t *key, long long keylen,
                            uint8_t *out, int32_t *lengths,
                            long long max_records, long long max_len,
                            long long *offsets) {
    const uint8_t *p = buf, *end = buf + buflen;
    long long rec = 0;
    while (p < end) {
        if (rec >= max_records) return -2;
        if (offsets) offsets[rec] = p - buf;
        const uint8_t *rec_start = p;
        const uint8_t *rec_end = nullptr;
        lengths[rec] = stage_one_record(rec_start, end, key, keylen,
                                        out + rec * max_len, max_len,
                                        &rec_end);
        p = rec_end ? rec_end : skip_obj(rec_start, end, 0);
        if (!p) return -1;
        rec++;
    }
    if (offsets) offsets[rec] = buflen;
    return rec;
}

// ---------------------------------------------------------------------
// Numeric column staging (fbtpu-flux): each record's top-level NUMERIC
// field `key` → out[i] double + kinds[i] (0 missing/non-numeric,
// 1 integer, 2 float). msgpack bools are NOT numeric (mirrors the
// Python aggregate rule `isinstance(v, (int, float)) and not bool`,
// stream_processor._Agg.add); strings are NOT parsed — the exact
// Python evaluation path skips numeric-looking strings, and the flux
// plane must stay bit-identical to it. int64/uint64 → double uses the
// same IEEE round-to-nearest Python's float(int) applies.
// ---------------------------------------------------------------------

static inline int read_numeric(const uint8_t *p, const uint8_t *end,
                               double *out) {
    if (p >= end) return 0;
    uint8_t b = *p++;
    if (b <= 0x7f) { *out = (double)b; return 1; }            // pos fixint
    if (b >= 0xe0) { *out = (double)(int8_t)b; return 1; }    // neg fixint
    switch (b) {
    case 0xcc: if (p + 1 > end) return 0;
        *out = (double)p[0]; return 1;
    case 0xcd: if (p + 2 > end) return 0;
        *out = (double)(((uint32_t)p[0] << 8) | p[1]); return 1;
    case 0xce: if (p + 4 > end) return 0;
        *out = (double)(((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
                        | ((uint32_t)p[2] << 8) | p[3]);
        return 1;
    case 0xcf: {
        if (p + 8 > end) return 0;
        uint64_t v = 0;
        for (int i = 0; i < 8; i++) v = (v << 8) | p[i];
        *out = (double)v;
        return 1;
    }
    case 0xd0: if (p + 1 > end) return 0;
        *out = (double)(int8_t)p[0]; return 1;
    case 0xd1: if (p + 2 > end) return 0;
        *out = (double)(int16_t)(((uint16_t)p[0] << 8) | p[1]); return 1;
    case 0xd2: if (p + 4 > end) return 0;
        *out = (double)(int32_t)(((uint32_t)p[0] << 24)
                                 | ((uint32_t)p[1] << 16)
                                 | ((uint32_t)p[2] << 8) | p[3]);
        return 1;
    case 0xd3: {
        if (p + 8 > end) return 0;
        uint64_t v = 0;
        for (int i = 0; i < 8; i++) v = (v << 8) | p[i];
        *out = (double)(int64_t)v;
        return 1;
    }
    case 0xca: {
        if (p + 4 > end) return 0;
        uint32_t v = ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
                   | ((uint32_t)p[2] << 8) | p[3];
        float f;
        memcpy(&f, &v, 4);
        *out = (double)f;
        return 2;
    }
    case 0xcb: {
        if (p + 8 > end) return 0;
        uint64_t v = 0;
        for (int i = 0; i < 8; i++) v = (v << 8) | p[i];
        double d;
        memcpy(&d, &v, 8);
        *out = d;
        return 2;
    }
    }
    return 0;
}

long long fbtpu_stage_field_f64(const uint8_t *buf, long long buflen,
                                const uint8_t *key, long long keylen,
                                double *out, uint8_t *kinds,
                                long long max_records, long long *offsets) {
    const uint8_t *p = buf, *end = buf + buflen;
    long long rec = 0;
    while (p < end) {
        if (rec >= max_records) return -2;
        if (offsets) offsets[rec] = p - buf;
        const uint8_t *rec_start = p;
        out[rec] = 0.0;
        kinds[rec] = 0;
        uint32_t outer;
        const uint8_t *q = read_array_hdr(rec_start, end, &outer);
        const uint8_t *rec_end = nullptr;
        if (q && outer >= 2) {
            const uint8_t *body = skip_obj(q, end, 0);
            if (body) {
                uint32_t pairs;
                const uint8_t *kv = read_map_hdr(body, end, &pairs);
                if (kv) {
                    // scan ALL pairs: duplicate keys keep the LAST
                    // occurrence, same as the dict decode / stage_field
                    for (uint32_t i = 0; i < pairs && kv; i++) {
                        uint32_t klen;
                        const uint8_t *kstr = read_str_hdr(kv, end, &klen);
                        const uint8_t *val;
                        bool match = false;
                        if (kstr) {
                            val = kstr + klen;
                            if (val > end) { kv = nullptr; break; }
                            match = ((long long)klen == keylen &&
                                     memcmp(kstr, key, klen) == 0);
                        } else {
                            val = skip_obj(kv, end, 0);
                            if (!val) { kv = nullptr; break; }
                        }
                        if (match) {
                            double v;
                            int kind = read_numeric(val, end, &v);
                            if (kind) {
                                out[rec] = v;
                                kinds[rec] = (uint8_t)kind;
                            } else {
                                kinds[rec] = 0;  // last occurrence rules
                            }
                        }
                        kv = skip_obj(val, end, 0);
                    }
                    if (kv && outer == 2) rec_end = kv;
                }
            }
        }
        p = rec_end ? rec_end : skip_obj(rec_start, end, 0);
        if (!p) return -1;
        rec++;
    }
    if (offsets) offsets[rec] = buflen;
    return rec;
}

// ---------------------------------------------------------------------
// Typed group-key staging (fbtpu-flux): each record's top-level field
// `key` as a GROUP BY key → out[i] int64 + kinds[i], flux/state.py's
// KEY_* codes: 0 missing (no such key, a non-map body, or nil: the
// exact path's `_get_key` gives None for all three), 1 a string (the
// string stager carries its bytes), 2 an integer that int64 holds
// exactly, 3 anything else — a float, a
// bool, a uint64 past INT64_MAX, bin, ext, a nested value — which the
// batched path cannot key exactly and declines on. Duplicate map keys
// keep the LAST occurrence, as the dict decode does.
// ---------------------------------------------------------------------

static inline int read_key_i64(const uint8_t *p, const uint8_t *end,
                               int64_t *out) {
    if (p >= end) return 3;
    uint8_t b = *p++;
    if (b <= 0x7f) { *out = (int64_t)b; return 2; }           // pos fixint
    if (b >= 0xe0) { *out = (int64_t)(int8_t)b; return 2; }   // neg fixint
    if ((b & 0xe0) == 0xa0 || b == 0xd9 || b == 0xda || b == 0xdb)
        return 1;                                             // str
    uint64_t v = 0;
    switch (b) {
    case 0xc0: return 0;                                      // nil
    case 0xcc: if (p + 1 > end) return 3;
        *out = (int64_t)p[0]; return 2;
    case 0xcd: if (p + 2 > end) return 3;
        *out = (int64_t)(((uint32_t)p[0] << 8) | p[1]); return 2;
    case 0xce: if (p + 4 > end) return 3;
        *out = (int64_t)(((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16)
                         | ((uint32_t)p[2] << 8) | p[3]);
        return 2;
    case 0xcf:
        if (p + 8 > end) return 3;
        for (int i = 0; i < 8; i++) v = (v << 8) | p[i];
        if (v > (uint64_t)INT64_MAX) return 3;    // no int64 holds it
        *out = (int64_t)v;
        return 2;
    case 0xd0: if (p + 1 > end) return 3;
        *out = (int64_t)(int8_t)p[0]; return 2;
    case 0xd1: if (p + 2 > end) return 3;
        *out = (int64_t)(int16_t)(((uint16_t)p[0] << 8) | p[1]); return 2;
    case 0xd2: if (p + 4 > end) return 3;
        *out = (int64_t)(int32_t)(((uint32_t)p[0] << 24)
                                  | ((uint32_t)p[1] << 16)
                                  | ((uint32_t)p[2] << 8) | p[3]);
        return 2;
    case 0xd3:
        if (p + 8 > end) return 3;
        for (int i = 0; i < 8; i++) v = (v << 8) | p[i];
        *out = (int64_t)v;
        return 2;
    }
    return 3;
}

long long fbtpu_stage_field_i64(const uint8_t *buf, long long buflen,
                                const uint8_t *key, long long keylen,
                                int64_t *out, uint8_t *kinds,
                                long long max_records) {
    const uint8_t *p = buf, *end = buf + buflen;
    long long rec = 0;
    while (p < end) {
        if (rec >= max_records) return -2;
        const uint8_t *rec_start = p;
        out[rec] = 0;
        kinds[rec] = 0;
        uint32_t outer;
        const uint8_t *q = read_array_hdr(rec_start, end, &outer);
        const uint8_t *rec_end = nullptr;
        if (q && outer >= 2) {
            const uint8_t *body = skip_obj(q, end, 0);
            if (body) {
                uint32_t pairs;
                const uint8_t *kv = read_map_hdr(body, end, &pairs);
                if (kv) {
                    for (uint32_t i = 0; i < pairs && kv; i++) {
                        uint32_t klen;
                        const uint8_t *kstr = read_str_hdr(kv, end, &klen);
                        const uint8_t *val;
                        bool match = false;
                        if (kstr) {
                            val = kstr + klen;
                            if (val > end) { kv = nullptr; break; }
                            match = ((long long)klen == keylen &&
                                     memcmp(kstr, key, klen) == 0);
                        } else {
                            val = skip_obj(kv, end, 0);
                            if (!val) { kv = nullptr; break; }
                        }
                        if (match) {
                            int64_t v = 0;
                            int kind = read_key_i64(val, end, &v);
                            out[rec] = kind == 2 ? v : 0;
                            kinds[rec] = (uint8_t)kind;
                        }
                        kv = skip_obj(val, end, 0);
                    }
                    if (kv && outer == 2) rec_end = kv;
                }
            }
        }
        p = rec_end ? rec_end : skip_obj(rec_start, end, 0);
        if (!p) return -1;
        rec++;
    }
    return rec;
}

// ---------------------------------------------------------------------
// Host-pinned sketch updates (fbtpu-flux): the bit-identical C twins of
// the device HLL/count-min kernels (fluentbit_tpu/ops/sketch.py), used
// while the backend is still attaching (or pinned to CPU). Hash is
// finalized FNV-1a 32 + murmur3 fmix32, exactly _hash32_cpu.
// ---------------------------------------------------------------------

static inline uint32_t fnv1a_mix32(const uint8_t *v, int32_t len) {
    uint32_t h = 0x811C9DC5u;
    for (int32_t i = 0; i < len; i++)
        h = (h ^ v[i]) * 0x01000193u;
    h ^= h >> 16; h *= 0x85EBCA6Bu;
    h ^= h >> 13; h *= 0xC2B2AE35u;
    h ^= h >> 16;
    return h;
}

// rows with lengths[i] < 0 are skipped (missing/overflow markers)
void fbtpu_hll_update(const uint8_t *batch, const int32_t *lengths,
                      long long B, long long L, int32_t p,
                      int32_t *registers) {
    int32_t max_rank = 32 - p + 1;
    for (long long i = 0; i < B; i++) {
        int32_t len = lengths[i];
        if (len < 0) continue;
        uint32_t h = fnv1a_mix32(batch + i * L, len);
        uint32_t idx = h >> (32 - p);
        uint32_t rest = (uint32_t)(h << p);
        int32_t nlz = rest ? __builtin_clz(rest) : 32;
        int32_t rank = nlz + 1 < max_rank ? nlz + 1 : max_rank;
        if (rank > registers[idx]) registers[idx] = rank;
    }
}

// table is [depth, width] of elem_size-byte signed counters (4 or 8 —
// CountMin keys its dtype off jax_enable_x64); weight 1 per valid row.
long long fbtpu_cms_update(const uint8_t *batch, const int32_t *lengths,
                           long long B, long long L, int32_t depth,
                           int32_t width, void *table, int32_t elem_size) {
    if (elem_size != 4 && elem_size != 8) return -1;
    for (long long i = 0; i < B; i++) {
        int32_t len = lengths[i];
        if (len < 0) continue;
        uint32_t h1 = fnv1a_mix32(batch + i * L, len);
        uint32_t h2 = h1;
        h2 ^= h2 >> 16; h2 *= 0x85EBCA6Bu;
        h2 ^= h2 >> 13; h2 *= 0xC2B2AE35u;
        h2 ^= h2 >> 16;
        h2 |= 1u;
        for (int32_t r = 0; r < depth; r++) {
            uint32_t col = (uint32_t)(h1 + (uint32_t)r * h2)
                           % (uint32_t)width;
            if (elem_size == 4)
                ((int32_t *)table)[(long long)r * width + col] += 1;
            else
                ((int64_t *)table)[(long long)r * width + col] += 1;
        }
    }
    return 0;
}

// ---------------------------------------------------------------------
// Threaded staging: phase 1 is the serial boundary walk (record i+1's
// start depends on record i's end — inherently sequential, but it only
// skips headers), phase 2 fans the per-record field extraction +
// row memcpy out over a PERSISTENT worker pool. Per-chunk thread spawn
// would eat the win at bench chunk rates (~6k dispatches/s), so the
// pool parks workers on a condvar between jobs; dispatch is one
// notify_all + one condvar wait for the caller.
// ---------------------------------------------------------------------

}  // extern "C" — the pool below needs C++ linkage (templates)

namespace {

// generic slice-parallel job: fn(ctx, slice_idx) for slices 1..n-1 on
// pool workers, slice 0 on the caller's thread
typedef void (*pool_fn)(const void *ctx, int slice);

struct PoolJob {
    pool_fn fn;
    const void *ctx;
    int n_slices;
};

struct WorkPool {
    std::mutex m;
    std::condition_variable cv_work;
    std::condition_variable cv_done;
    uint64_t gen = 0;
    int remaining = 0;
    int n_workers = 0;
    PoolJob job{};

    void worker(int idx) {
        uint64_t seen = 0;
        for (;;) {
            PoolJob j;
            {
                std::unique_lock<std::mutex> lk(m);
                cv_work.wait(lk, [&] { return gen != seen; });
                seen = gen;
                j = job;
            }
            // slice 0 runs on the caller's thread; workers take 1..n
            if (idx + 1 < j.n_slices) j.fn(j.ctx, idx + 1);
            {
                std::lock_guard<std::mutex> lk(m);
                if (--remaining == 0) cv_done.notify_one();
            }
        }
    }

    // start exactly once; pool size is fixed at first use (daemon
    // threads, process lifetime — the .so is never unloaded)
    void ensure(int want_workers) {
        std::lock_guard<std::mutex> lk(m);
        if (n_workers > 0) return;
        n_workers = want_workers;
        for (int i = 0; i < want_workers; i++)
            std::thread([this, i] { worker(i); }).detach();
    }

    // serializes dispatch: threaded inputs may enter concurrently, and
    // the pool's job/remaining slots are single-occupancy. Waiters
    // queue here; each dispatch still fans out over every worker.
    std::mutex run_m;

    void run(pool_fn fn, const void *ctx, int n_slices) {
        std::lock_guard<std::mutex> run_lk(run_m);
        {
            std::lock_guard<std::mutex> lk(m);
            job = PoolJob{fn, ctx, n_slices};
            remaining = n_workers;
            gen++;
        }
        cv_work.notify_all();
        fn(ctx, 0);
        std::unique_lock<std::mutex> lk(m);
        cv_done.wait(lk, [&] { return remaining == 0; });
    }
};

// deliberately never destroyed: detached workers may be parked in
// cv_work.wait at process exit, and destroying a condvar/mutex with
// waiters is UB — a static instance's destructor would run exactly
// then. Placement-new into static storage: no destructor is ever
// registered, and no heap allocation can fail before main()
alignas(WorkPool) unsigned char g_pool_storage[sizeof(WorkPool)];
WorkPool &g_pool = *new (g_pool_storage) WorkPool;

// FBTPU_DFA_THREADS: unset → all cores (capped 16); 0 or negative →
// threading disabled (1). The ONE parser for every threaded path.
// FBTPU_THREADS_NO_HW_CAP lifts the core clamp so single-core CI can
// still EXERCISE the pool dispatch paths (oversubscribed but correct).
int pool_threads_wanted() {
    unsigned hw = std::thread::hardware_concurrency();
    const char *env = getenv("FBTPU_DFA_THREADS");
    long want;
    if (env != nullptr) {
        want = strtol(env, nullptr, 10);
        if (want <= 0) return 1;
    } else {
        want = hw ? (long)hw : 1;
    }
    if (hw && want > (long)hw
            && getenv("FBTPU_THREADS_NO_HW_CAP") == nullptr)
        want = hw;
    if (want > 16) want = 16;
    return (int)want;
}

struct StageJob {
    const uint8_t *buf;
    const uint8_t *end;
    const uint8_t *key;
    long long keylen;
    uint8_t *out;
    int32_t *lengths;
    const long long *offsets;
    long long n_rec;
    long long max_len;
    long long slice;  // records per slice
    int n_slices;
};

static void stage_run_slice(const StageJob &j, int sx) {
    long long lo = (long long)sx * j.slice;
    long long hi = lo + j.slice < j.n_rec ? lo + j.slice : j.n_rec;
    for (long long r = lo; r < hi; r++)
        j.lengths[r] = stage_one_record(j.buf + j.offsets[r], j.end,
                                        j.key, j.keylen,
                                        j.out + r * j.max_len, j.max_len,
                                        nullptr);
}

static void stage_slice_adapter(const void *ctx, int sx) {
    stage_run_slice(*(const StageJob *)ctx, sx);
}

}  // namespace

extern "C" {

// How many slices a stage call requesting `nthreads` would ACTUALLY
// fan out to after the hardware/16-way caps — the introspection probe
// behind fluentbit_tpu.native.stage_threads_effective(), so the bench
// RESULT records the real slice count instead of the env request.
int32_t fbtpu_stage_effective_threads(int32_t nthreads) {
    unsigned hw = std::thread::hardware_concurrency();
    if (hw && nthreads > (int32_t)hw
            && getenv("FBTPU_THREADS_NO_HW_CAP") == nullptr)
        nthreads = (int32_t)hw;
    if (nthreads > 16) nthreads = 16;
    if (nthreads < 2) return 1;
    int pool = pool_threads_wanted();
    return nthreads < pool ? nthreads : pool;
}

// Threaded fbtpu_stage_field. offsets is REQUIRED (n+1 entries filled
// by the phase-1 scan). nthreads counts total slices including the
// caller's; the pool is sized on first call and later calls are capped
// to it. Falls back to the serial walk for small batches where the
// dispatch handshake would dominate.
long long fbtpu_stage_field_mt(const uint8_t *buf, long long buflen,
                               const uint8_t *key, long long keylen,
                               uint8_t *out, int32_t *lengths,
                               long long max_records, long long max_len,
                               long long *offsets, int nthreads) {
    unsigned hw = std::thread::hardware_concurrency();
    if (hw && nthreads > (int)hw
            && getenv("FBTPU_THREADS_NO_HW_CAP") == nullptr)
        nthreads = (int)hw;
    if (nthreads > 16) nthreads = 16;
    if (nthreads < 2)
        // single-core host: the fused one-walk serial path beats the
        // two-phase split (no separate boundary scan)
        return fbtpu_stage_field(buf, buflen, key, keylen, out, lengths,
                                 max_records, max_len, offsets);
    long long n = fbtpu_scan_offsets(buf, buflen, offsets, max_records);
    if (n < 0) return n;
    if (n < 1024) {
        StageJob j{buf, buf + buflen, key, keylen, out, lengths,
                   offsets, n, max_len, n, 1};
        stage_run_slice(j, 0);
        return n;
    }
    // pool is sized once to the machine-wide cap; each dispatch caps
    // its own slice count (workers past n_slices no-op), so one
    // caller's thread request never inflates another's
    g_pool.ensure(pool_threads_wanted() - 1);
    int slices = g_pool.n_workers + 1;
    if (slices > nthreads) slices = nthreads;
    long long slice = (n + slices - 1) / slices;
    StageJob j{buf, buf + buflen, key, keylen, out, lengths,
               offsets, n, max_len, slice,
               (int)((n + slice - 1) / slice)};
    g_pool.run(stage_slice_adapter, &j, j.n_slices);
    return n;
}

// ---------------------------------------------------------------------
// One-pass grep: field extraction + DFA execution straight off chunk
// bytes. The host-side twin of the device kernel (fluentbit_tpu/ops/
// grep.py): identical table semantics (DEAD=0 / ACC=1 absorbing, bytes
// then one EOL step), so verdicts are bit-exact with both the jax
// kernel and the Python regex engine. Used when the device backend is
// not attached (or is the jax CPU backend, which a table-driven C loop
// beats by orders of magnitude) — reference precedent: the hot filter
// loop is host-native C in fluent-bit (plugins/filter_grep/grep.c:286).
//
//   keys_cat/key_offs : n_keys concatenated field names
//   key_of_rule       : rule r matches field keys[key_of_rule[r]]
//   trans_cat/troffs  : per-rule [S*C] int32 transition tables
//   cmaps             : [R][257] byte->class maps (entry 256 = EOL)
//   starts, ncls      : per-rule start state / class count
//   match_out         : [R][max_records] u8 verdict matrix
//   offsets           : record byte offsets (max_records+1)
// Returns record count, -1 malformed, -2 capacity exceeded.
// ---------------------------------------------------------------------

#define FBTPU_MAX_KEYS 64

long long fbtpu_grep_match_v2(const uint8_t *buf, long long buflen,
                           const uint8_t *keys_cat,
                           const long long *key_offs, long long n_keys,
                           const int32_t *key_of_rule, long long n_rules,
                           const int16_t *trans_cat,
                           const long long *troffs,
                           const int32_t *cmaps, const int32_t *starts,
                           const int32_t *ncls,
                           uint8_t *match_out, long long max_records,
                           long long *offsets) {
    if (n_keys > FBTPU_MAX_KEYS) return -1;
    const uint8_t *p = buf, *end = buf + buflen;
    long long rec = 0;
    // phase 1: one msgpack walk extracts every key's (ptr, len) per
    // record into scratch, so phase 2 can run each rule's DFA over
    // contiguous rows with FBTPU_DFA_LANES-way interleaving
    const uint8_t **vals = new const uint8_t *[n_keys * max_records];
    uint32_t *vlens = new uint32_t[n_keys * max_records];
    while (p < end) {
        if (rec >= max_records) {
            delete[] vals;
            delete[] vlens;
            return -2;
        }
        if (offsets) offsets[rec] = p - buf;
        const uint8_t *rec_start = p;
        for (long long kx = 0; kx < n_keys; kx++)
            vals[kx * max_records + rec] = nullptr;
        uint32_t outer;
        const uint8_t *rec_end = nullptr;
        const uint8_t *q = read_array_hdr(p, end, &outer);
        if (q && outer >= 2) {
            const uint8_t *body = skip_obj(q, end, 0);
            if (body) {
                uint32_t pairs;
                const uint8_t *kv = read_map_hdr(body, end, &pairs);
                if (kv) {
                    // one map walk resolves every rule's field; LAST
                    // duplicate occurrence wins (dict-decode parity)
                    for (uint32_t i = 0; i < pairs && kv; i++) {
                        uint32_t klen;
                        const uint8_t *kstr = read_str_hdr(kv, end, &klen);
                        const uint8_t *val;
                        long long match_kx = -1;
                        if (kstr) {
                            val = kstr + klen;
                            if (val > end) { kv = nullptr; break; }
                            for (long long kx = 0; kx < n_keys; kx++) {
                                long long kl =
                                    key_offs[kx + 1] - key_offs[kx];
                                if (kl == (long long)klen &&
                                    memcmp(kstr, keys_cat + key_offs[kx],
                                           klen) == 0) {
                                    match_kx = kx;
                                    break;
                                }
                            }
                        } else {
                            val = skip_obj(kv, end, 0);  // non-str key
                            if (!val) { kv = nullptr; break; }
                        }
                        if (match_kx >= 0) {
                            uint32_t vlen;
                            const uint8_t *vstr =
                                read_str_hdr(val, end, &vlen);
                            long long slot =
                                match_kx * max_records + rec;
                            if (vstr && vstr + vlen <= end) {
                                vals[slot] = vstr;
                                vlens[slot] = vlen;
                            } else {
                                vals[slot] = nullptr;  // non-string
                            }
                        }
                        kv = skip_obj(val, end, 0);
                    }
                    // the pair walk ended exactly at the map's end: for
                    // the common [[ts, meta], body] shape that IS the
                    // record end — reuse it instead of re-walking the
                    // whole record with skip_obj
                    if (kv && outer == 2) rec_end = kv;
                }
            }
        }
        p = rec_end ? rec_end : skip_obj(rec_start, end, 0);
        if (!p) {
            delete[] vals;
            delete[] vlens;
            return -1;
        }
        rec++;
    }
    if (offsets) offsets[rec] = buflen;
    // phase 2: per-rule interleaved DFA sweep. Rows are independent, so
    // large batches fan out across host threads (the ctypes caller has
    // already released the GIL). FBTPU_DFA_THREADS caps the fan-out.
    auto sweep = [&](long long r, long long lo, long long hi) {
        const int16_t *trans = trans_cat + troffs[r];
        const int32_t *cmap = cmaps + r * 257;
        const uint8_t *const *kv = vals + key_of_rule[r] * max_records;
        const uint32_t *kl = vlens + key_of_rule[r] * max_records;
        uint8_t *out = match_out + r * max_records;
        // ncls encodes C and the super-step k: C + 1000*(k-1)
        int32_t enc = ncls[r];
        int k = enc / 1000 + 1;
        int32_t C = enc % 1000;
        for (long long i = lo; i < hi; i += FBTPU_DFA_LANES) {
            int nrows = (int)(hi - i < FBTPU_DFA_LANES
                              ? hi - i : FBTPU_DFA_LANES);
            if (k == 4)
                dfa_run_block_k<4>(trans, cmap, C, starts[r],
                                   kv + i, kl + i, nrows, out + i);
            else if (k == 3)
                dfa_run_block_k<3>(trans, cmap, C, starts[r],
                                   kv + i, kl + i, nrows, out + i);
            else if (k == 2)
                dfa_run_block_k<2>(trans, cmap, C, starts[r],
                                   kv + i, kl + i, nrows, out + i);
            else
                dfa_run_block(trans, cmap, C, starts[r],
                              kv + i, kl + i, nrows, out + i);
        }
    };
    int nthreads = rec >= 4096 ? pool_threads_wanted() : 1;
    if (nthreads <= 1) {
        for (long long r = 0; r < n_rules; r++) sweep(r, 0, rec);
    } else {
        // split rows into nthreads slices (lane-aligned), all rules in
        // each slice — one spawn wave regardless of rule count
        std::thread workers[16];
        long long step = (rec + nthreads - 1) / nthreads;
        step = ((step + FBTPU_DFA_LANES - 1) / FBTPU_DFA_LANES)
               * FBTPU_DFA_LANES;
        int spawned = 0;
        for (int t = 0; t < nthreads; t++) {
            long long lo = (long long)t * step;
            if (lo >= rec) break;
            long long hi = lo + step < rec ? lo + step : rec;
            workers[spawned++] = std::thread([&sweep, n_rules, lo, hi] {
                for (long long r = 0; r < n_rules; r++)
                    sweep(r, lo, hi);
            });
        }
        for (int t = 0; t < spawned; t++) workers[t].join();
    }
    delete[] vals;
    delete[] vlens;
    return rec;
}

// Copy the records whose keep[i] != 0 into out, preserving order.
// offsets has n+1 entries (from fbtpu_stage_field / fbtpu_scan_offsets).
// Returns bytes written; out must hold buflen bytes (worst case).
long long fbtpu_compact(const uint8_t *buf, long long buflen,
                        const long long *offsets, const uint8_t *keep,
                        long long n, uint8_t *out) {
    long long w = 0;
    for (long long i = 0; i < n; i++) {
        if (!keep[i]) continue;
        long long a = offsets[i], b = offsets[i + 1];
        if (a < 0 || b > buflen || b < a) return -1;
        memcpy(out + w, buf + a, (size_t)(b - a));
        w += b - a;
    }
    return w;
}

// ---------------------------------------------------------------------
// Fused grep filter: one pass over chunk bytes doing field extraction,
// accelerated DFA matching, verdict, and run-coalesced compaction.
//
// The DFA acceleration exploits the dominant shape of log-matching
// automata (apache2-style "[^ ]* ... [^\]]* ... .*$" skeletons): most
// live states SELF-LOOP on nearly every byte and leave only on one or
// two delimiter bytes. The Python side (native.GrepFilterTables)
// precomputes, per state, the escape-byte set; states with <=2 escape
// bytes carry an accel word and the runtime skips straight to the next
// escape byte with a 16-lane SIMD compare instead of walking the
// transition table byte-by-byte. Self-loop skipping is exact (the
// state is unchanged by skipped bytes, by construction), so verdicts
// stay bit-identical to the table walk, the jax kernel, and the Python
// regex engine.
//
// accel[s] encoding: bits 0-1 = 0 none / 1 one escape byte / 2 two /
// 3 no escape bytes at all (skip to end); bits 8-15 byte1; 16-23 byte2.
// ---------------------------------------------------------------------

// ---------------------------------------------------------------------
// Super-symbol prepass + interleaved walk (the fused filter's matcher).
//
// A DFA walk is a serial dependency chain; the classic fix (8
// interleaved lanes, dfa_run_block above) leaves the per-step class
// lookups and k-byte combines INSIDE the latency-bound loop. Splitting
// the work makes both halves fast:
//   A. prepass — per record, byte classes combine into k-byte
//      super-symbols in a branchless, position-independent loop the
//      CPU can run at superscalar width;
//   B. walk — per 8-lane block, each step is exactly one scratch load
//      and one dependent table load: s = T[s*Ck + sym].
// Pad steps use the absorbing EOL super-symbol, so lanes of different
// lengths stay in lockstep with no branches.
// ---------------------------------------------------------------------

#define FBTPU_PRE_LANES 16

// ---------------------------------------------------------------------
// Escape-byte accelerated scalar matcher (the accel[s] design in the
// fused-filter comment above): a state that leaves only on one or two
// bytes skips straight to the next escape byte with memchr / a 16-wide
// SIMD compare; a state with NO escape bytes is fixed until EOL. Exact:
// skipped bytes provably keep the state unchanged.
//   accel[s]: bits 0-1 kind (0 step / 1 one byte / 2 two / 3 fixed),
//   bits 8-15 byte1, 16-23 byte2.
// ---------------------------------------------------------------------

static inline uint32_t scan_one_byte(const uint8_t *v, uint32_t i,
                                     uint32_t len, uint8_t b1) {
#ifdef FBTPU_HAVE_SSE2
    __m128i m1 = _mm_set1_epi8((char)b1);
    while (i + 16 <= len) {
        __m128i x = _mm_loadu_si128((const __m128i *)(v + i));
        int mask = _mm_movemask_epi8(_mm_cmpeq_epi8(x, m1));
        if (mask) return i + (uint32_t)__builtin_ctz((unsigned)mask);
        i += 16;
    }
#endif
    for (; i < len; i++)
        if (v[i] == b1) return i;
    return 0xFFFFFFFFu;
}

static inline uint32_t scan_two_bytes(const uint8_t *v, uint32_t i,
                                      uint32_t len, uint8_t b1,
                                      uint8_t b2) {
#ifdef FBTPU_HAVE_SSE2
    __m128i m1 = _mm_set1_epi8((char)b1), m2 = _mm_set1_epi8((char)b2);
    while (i + 16 <= len) {
        __m128i x = _mm_loadu_si128((const __m128i *)(v + i));
        int mask = _mm_movemask_epi8(
            _mm_or_si128(_mm_cmpeq_epi8(x, m1), _mm_cmpeq_epi8(x, m2)));
        if (mask) return i + (uint32_t)__builtin_ctz((unsigned)mask);
        i += 16;
    }
#endif
    for (; i < len; i++)
        if (v[i] == b1 || v[i] == b2) return i;
    return 0xFFFFFFFFu;
}

// One record through the tables with skipping — a HYBRID walk:
// skippy states (<=2 escape bytes) jump via memchr/SIMD; dense states
// step through the k-composed table (4 bytes per dependent load when
// the pair-class table is available) so a skip-poor stretch costs no
// more than the lockstep engine's per-byte work. DEAD(0) and ACC(1)
// are absorbing; the trailing EOL step is safe from either.
static inline uint8_t dfa_accel_match(const int16_t *bt,
                                      const int32_t *cmap, int32_t C,
                                      int32_t start,
                                      const uint32_t *accel,
                                      const int16_t *transk,
                                      const uint16_t *cmap2,
                                      int k, int32_t Ck,
                                      const uint8_t *v, uint32_t len) {
    int32_t s = start;
    int32_t C2 = C * C;
    uint32_t i = 0;
    while (i < len) {
        uint32_t a = accel[s];
        uint32_t kind = a & 3u;
        if (kind == 0u) {
            // dense state: composed 4-byte step when possible
            if (k == 4 && cmap2 != nullptr && i + 4 <= len) {
                uint16_t w0, w1;
                memcpy(&w0, v + i, 2);
                memcpy(&w1, v + i + 2, 2);
                s = transk[s * Ck + (int32_t)cmap2[w0] * C2 + cmap2[w1]];
                i += 4;
            } else {
                s = bt[s * C + cmap[v[i]]];
                i++;
            }
            if (s <= 1) break;
            continue;
        }
        if (kind == 3u) {
            i = len;  // state cannot change before EOL
            break;
        }
        if (kind == 1u) {
            i = scan_one_byte(v, i, len, (uint8_t)((a >> 8) & 0xffu));
            if (i == 0xFFFFFFFFu) { i = len; break; }
        } else {  // kind == 2
            i = scan_two_bytes(v, i, len, (uint8_t)((a >> 8) & 0xffu),
                               (uint8_t)((a >> 16) & 0xffu));
            if (i == 0xFFFFFFFFu) { i = len; break; }
        }
        s = bt[s * C + cmap[v[i]]];  // step on the escape byte
        i++;
        if (s <= 1) break;  // absorbed
    }
    s = bt[s * C + cmap[256]];  // EOL step
    return (uint8_t)(s == 1);
}

// cmap2 (optional, even k only): 64K-entry byte-PAIR class table
// cmap2[b0 + (b1<<8)] = class(b0)*C + class(b1) — one load classifies
// two bytes, and for k=4 two pair-lookups make a whole super-symbol:
// sym = p01*C^2 + p23. Halves the prepass load count, which dominates
// the matcher once the walk is down to two loads per step.
static void dfa_prepass_block(const int16_t *transk, const int32_t *cmap,
                              const uint16_t *cmap2,
                              int32_t C, int k, int32_t Ck, int32_t start,
                              const uint8_t *const *vals,
                              const uint32_t *lens, int nrows,
                              uint8_t *out, uint16_t *syms) {
    const int W = FBTPU_PRE_LANES;
    int32_t eol = cmap[256];
    int32_t eol_super = 0;
    for (int b = 0; b < k; b++) eol_super = eol_super * C + eol;
    long long steps[W];
    long long max_steps = 0;
    for (int j = 0; j < W; j++) {
        long long len =
            (j < nrows && vals[j] != nullptr) ? (long long)lens[j] : -1LL;
        if (len < 0) {
            steps[j] = 0;  // missing/non-string: stays DEAD
        } else {
            steps[j] = len / k + 1;  // >=1 trailing EOL symbol
            if (steps[j] > max_steps) max_steps = steps[j];
        }
    }
    // phase A: branchless super-symbol build. Scratch layout is
    // [step][lane] so phase B's 8 lane loads per step share one cache
    // line instead of touching 8 strided rows.
    for (int j = 0; j < W; j++) {
        if (steps[j] == 0) {
            // lane is DEAD from the start (missing/non-string field or
            // j >= nrows). Phase B still LOADS this lane's column every
            // step, so it must hold valid symbols (< Ck) — fill with the
            // absorbing EOL super-symbol. Leaving it uninitialized reads
            // garbage that can index past the transition table.
            uint16_t *col = syms + j;
            for (long long i = 0; i < max_steps; i++)
                col[i * W] = (uint16_t)eol_super;
            continue;
        }
        uint16_t *col = syms + j;
        const uint8_t *v = vals[j];
        long long len = lens[j];
        long long full = len / k;  // groups with no pad byte
        long long i = 0;
        if (cmap2 != nullptr && k == 4) {
            int32_t C2 = C * C;
            for (; i < full; i++) {
                const uint8_t *g = v + i * 4;
                uint16_t w0, w1;
                memcpy(&w0, g, 2);      // little-endian: b0 + (b1<<8)
                memcpy(&w1, g + 2, 2);
                col[i * W] = (uint16_t)(cmap2[w0] * C2 + cmap2[w1]);
            }
        } else if (cmap2 != nullptr && k == 2) {
            for (; i < full; i++) {
                uint16_t w0;
                memcpy(&w0, v + i * 2, 2);
                col[i * W] = cmap2[w0];
            }
        } else {
            for (; i < full; i++) {
                long long base = i * k;
                int32_t cc = cmap[v[base]];
                for (int b = 1; b < k; b++)
                    cc = cc * C + cmap[v[base + b]];
                col[i * W] = (uint16_t)cc;
            }
        }
        for (; i < steps[j]; i++) {  // tail group: pad with EOL
            long long base = i * k;
            int32_t cc = 0;
            for (int b = 0; b < k; b++) {
                long long idx = base + b;
                cc = cc * C + (idx < len ? cmap[v[idx]] : eol);
            }
            col[i * W] = (uint16_t)cc;
        }
        for (; i < max_steps; i++) col[i * W] = (uint16_t)eol_super;
    }
    // phase B: lockstep walk — 2 loads per lane-step
    int32_t s[W];
    for (int j = 0; j < W; j++)
        s[j] = steps[j] ? start : 0;
    const uint16_t *row = syms;
    for (long long i = 0; i < max_steps; i++, row += W) {
        int32_t acc = 0;
        for (int j = 0; j < W; j++) {
            s[j] = transk[s[j] * Ck + row[j]];
            acc |= s[j];
        }
        if (acc <= 1) break;  // all lanes absorbed (DEAD/ACC)
    }
    for (int j = 0; j < W && j < nrows; j++)
        out[j] = (uint8_t)(s[j] == 1);
}

// slice-parallel jobs for the fused filter's phase 2 (records within
// a rule are independent; mrow writes are disjoint per slice)
struct GrepAccelJob {
    const int16_t *bt;
    const int32_t *cmap;
    const uint32_t *accel;
    const int16_t *transk;
    const uint16_t *cmap2;
    int32_t C;
    int k;
    int32_t Ck;
    int32_t start;
    const uint8_t *const *kv;
    const uint32_t *kl;
    uint8_t *mrow;
    long long n_rec;
    long long slice;
    int n_slices;
};

static void grep_accel_slice(const void *ctx, int sx) {
    const GrepAccelJob *j = (const GrepAccelJob *)ctx;
    long long lo = (long long)sx * j->slice;
    long long hi = lo + j->slice < j->n_rec ? lo + j->slice : j->n_rec;
    for (long long i = lo; i < hi; i++)
        j->mrow[i] = j->kv[i] != nullptr
            ? dfa_accel_match(j->bt, j->cmap, j->C, j->start, j->accel,
                              j->transk, j->cmap2, j->k, j->Ck,
                              j->kv[i], j->kl[i])
            : 0;
}

struct GrepBlockJob {
    const int16_t *trans;
    const int32_t *cmap;
    const uint16_t *cmap2;
    int32_t C;
    int k;
    int32_t Ck;
    int32_t start;
    long long max_vlen;
    const uint8_t *const *kv;
    const uint32_t *kl;
    const int32_t *ord;
    uint8_t *mrow;
    long long n_rec;
    long long slice;  // records per slice (multiple of FBTPU_PRE_LANES)
    int n_slices;
};

static void grep_block_slice(const void *ctx, int sx) {
    const GrepBlockJob *j = (const GrepBlockJob *)ctx;
    // per-worker prepass scratch (grows to the chunk's longest value)
    static thread_local uint16_t *syms = nullptr;
    static thread_local long long syms_cap = 0;
    long long need = FBTPU_PRE_LANES * (j->max_vlen / j->k + 2);
    if (need > syms_cap) {
        delete[] syms;
        syms = new uint16_t[need];
        syms_cap = need;
    }
    long long lo = (long long)sx * j->slice;
    long long hi = lo + j->slice < j->n_rec ? lo + j->slice : j->n_rec;
    const uint8_t *bv[FBTPU_PRE_LANES];
    uint32_t bl[FBTPU_PRE_LANES];
    uint8_t bm[FBTPU_PRE_LANES];
    for (long long i = lo; i < hi; i += FBTPU_PRE_LANES) {
        int nrows = (int)(hi - i < FBTPU_PRE_LANES
                          ? hi - i : FBTPU_PRE_LANES);
        for (int jj = 0; jj < nrows; jj++) {
            bv[jj] = j->kv[j->ord[i + jj]];
            bl[jj] = j->kl[j->ord[i + jj]];
        }
        dfa_prepass_block(j->trans, j->cmap, j->cmap2, j->C, j->k,
                          j->Ck, j->start, bv, bl, nrows, bm, syms);
        for (int jj = 0; jj < nrows; jj++)
            j->mrow[j->ord[i + jj]] = bm[jj];
    }
}

#define FBTPU_OP_LEGACY 0
#define FBTPU_OP_AND 1
#define FBTPU_OP_OR 2

// Verdict semantics are grep.c's (plugins/filter_grep/grep.c:167-284 in
// the reference; same logic as plugins/filter_grep.py keep_record /
// keep_mask):
//  legacy — first matching rule decides (!exclude), a non-matching
//           keep-rule decides EXCLUDE, fallthrough keeps;
//  AND/OR — all/any rules match, verdict = found XOR exclude (rule
//           kinds are uniform in these modes, enforced at config time).
//
// Three phases over chunk bytes:
//   1. one msgpack walk extracts every key's (ptr, len) per record
//   2. per rule, the interleaved accel matcher fills a match row
//   3. verdict + run-coalesced compaction (contiguous kept records
//      collapse into single memcpys; an all-kept chunk copies nothing
//      and the caller reuses the input buffer)
//
// out_info[0]=n_records, out_info[1]=n_kept, out_info[2]=1 if `out`
// holds the compacted bytes (0 = every record kept, out untouched).
// Returns bytes written, -1 malformed, -2 capacity exceeded.
long long fbtpu_grep_filter(const uint8_t *buf, long long buflen,
                            const uint8_t *keys_cat,
                            const long long *key_offs, long long n_keys,
                            const int32_t *key_of_rule, long long n_rules,
                            const int16_t *trans_cat,
                            const long long *troffs,
                            const int32_t *cmaps, const int32_t *starts,
                            const int32_t *ncls,
                            const uint16_t *cmap2_cat,
                            const long long *cm2offs,
                            const int16_t *btrans_cat,
                            const long long *btroffs,
                            const uint32_t *accel_cat,
                            const long long *aoffs,
                            const uint8_t *rule_exclude, int32_t op_mode,
                            long long max_records,
                            uint8_t *out, long long *out_info) {
    if (n_keys > FBTPU_MAX_KEYS) return -1;
    const uint8_t *p = buf, *end = buf + buflen;
    long long n_rec = 0;
    // ---- phase 1: extraction walk ----
    // thread-local growable scratch: the fused filter runs per chunk on
    // the ingest hot path, so per-call new[]/delete[] of multi-MB
    // arrays (and the page faults behind them) must not recur
    static thread_local const uint8_t **vals = nullptr;
    static thread_local uint32_t *vlens = nullptr;
    static thread_local long long *offsets = nullptr;
    static thread_local uint8_t *match = nullptr;
    static thread_local long long cap_vals = 0, cap_offs = 0, cap_match = 0;
    if (n_keys * max_records > cap_vals) {
        delete[] vals; delete[] vlens;
        cap_vals = n_keys * max_records;
        vals = new const uint8_t *[cap_vals];
        vlens = new uint32_t[cap_vals];
    }
    if (max_records + 1 > cap_offs) {
        delete[] offsets;
        cap_offs = max_records + 1;
        offsets = new long long[cap_offs];
    }
    if (n_rules * max_records > cap_match) {
        delete[] match;
        cap_match = n_rules * max_records;
        match = new uint8_t[cap_match];
    }
    while (p < end) {
        if (n_rec >= max_records) return -2;
        offsets[n_rec] = p - buf;
        const uint8_t *rec_start = p;
        for (long long kx = 0; kx < n_keys; kx++)
            vals[kx * max_records + n_rec] = nullptr;
        uint32_t outer;
        const uint8_t *rec_end = nullptr;
        const uint8_t *q = read_array_hdr(p, end, &outer);
        if (q && outer >= 2) {
            const uint8_t *body = skip_obj(q, end, 0);
            if (body) {
                uint32_t pairs;
                const uint8_t *kv = read_map_hdr(body, end, &pairs);
                if (kv) {
                    // one map walk resolves every rule's field; LAST
                    // duplicate occurrence wins (dict-decode parity)
                    for (uint32_t i = 0; i < pairs && kv; i++) {
                        uint32_t klen;
                        const uint8_t *kstr = read_str_hdr(kv, end, &klen);
                        const uint8_t *val;
                        long long match_kx = -1;
                        if (kstr) {
                            val = kstr + klen;
                            if (val > end) { kv = nullptr; break; }
                            for (long long kx = 0; kx < n_keys; kx++) {
                                long long kl =
                                    key_offs[kx + 1] - key_offs[kx];
                                if (kl == (long long)klen &&
                                    memcmp(kstr, keys_cat + key_offs[kx],
                                           klen) == 0) {
                                    match_kx = kx;
                                    break;
                                }
                            }
                        } else {
                            val = skip_obj(kv, end, 0);  // non-str key
                            if (!val) { kv = nullptr; break; }
                        }
                        if (match_kx >= 0) {
                            uint32_t vlen;
                            const uint8_t *vstr =
                                read_str_hdr(val, end, &vlen);
                            long long slot = match_kx * max_records + n_rec;
                            if (vstr && vstr + vlen <= end) {
                                vals[slot] = vstr;
                                vlens[slot] = vlen;
                            } else {
                                vals[slot] = nullptr;  // non-string
                            }
                        }
                        kv = skip_obj(val, end, 0);
                    }
                    if (kv && outer == 2) rec_end = kv;
                }
            }
        }
        p = rec_end ? rec_end : skip_obj(rec_start, end, 0);
        if (!p) return -1;
        n_rec++;
    }
    offsets[n_rec] = buflen;
    // ---- phase 2: per-rule prepass + lockstep walk ----
    // scratch sized to the longest value in the chunk
    long long max_vlen = 0;
    for (long long kx = 0; kx < n_keys; kx++)
        for (long long i = 0; i < n_rec; i++)
            if (vals[kx * max_records + i] != nullptr &&
                (long long)vlens[kx * max_records + i] > max_vlen)
                max_vlen = vlens[kx * max_records + i];
    // length-sorted processing order (per key): blocks of 16 lanes pad
    // every lane to the block's longest value, so feeding blocks
    // length-homogeneous records removes the padding waste of mixed
    // traffic. Counting sort over 64-byte length buckets; match rows
    // are written through the order array, so output order is intact.
    static thread_local int32_t *order = nullptr;
    static thread_local long long order_cap = 0;
    if (n_keys * n_rec > order_cap) {
        delete[] order;
        order_cap = n_keys * n_rec;
        order = new int32_t[order_cap];
    }
    bool order_built[FBTPU_MAX_KEYS] = {false};
    const int N_BUCKETS = 64;
    for (long long r = 0; r < n_rules; r++) {
        if (aoffs != nullptr && aoffs[r] >= 0)
            continue;  // accel rules don't use the sorted order
        long long kx = key_of_rule[r];
        if (!order_built[kx]) {
            order_built[kx] = true;
            int32_t *ord = order + kx * n_rec;
            const uint8_t *const *kv = vals + kx * max_records;
            const uint32_t *kl = vlens + kx * max_records;
            long long counts[N_BUCKETS + 1] = {0};
            auto bucket = [&](long long i) -> int {
                if (kv[i] == nullptr) return 0;
                long long b = kl[i] / 64 + 1;
                return b > N_BUCKETS ? N_BUCKETS : (int)b;
            };
            for (long long i = 0; i < n_rec; i++) counts[bucket(i)]++;
            long long pos = 0;
            long long starts_b[N_BUCKETS + 1];
            for (int b = 0; b <= N_BUCKETS; b++) {
                starts_b[b] = pos;
                pos += counts[b];
            }
            for (long long i = 0; i < n_rec; i++)
                ord[starts_b[bucket(i)]++] = (int32_t)i;
        }
    }
    // records are independent within a rule, so each rule's matcher
    // fans out over LANE-ALIGNED record slices on the worker pool when
    // the host has cores to spend (the per-worker prepass scratch is
    // thread_local inside the slice fns). A 1-core host keeps the
    // single-slice path with zero dispatch overhead.
    int p2_threads = n_rec >= 4096 ? pool_threads_wanted() : 1;
    if (p2_threads > 1) g_pool.ensure(pool_threads_wanted() - 1);
    for (long long r = 0; n_rec > 0 && r < n_rules; r++) {
        const int32_t *cmap = cmaps + r * 257;
        if (aoffs != nullptr && aoffs[r] >= 0) {
            // skip-friendly DFA: escape-byte hybrid matcher (memchr /
            // SIMD skips in self-loop states, composed 4-byte steps in
            // dense ones)
            int32_t enc_a = ncls[r];
            GrepAccelJob aj;
            aj.bt = btrans_cat + btroffs[r];
            aj.cmap = cmap;
            aj.accel = accel_cat + aoffs[r];
            aj.transk = trans_cat + troffs[r];
            aj.cmap2 = cm2offs[r] >= 0 ? cmap2_cat + cm2offs[r] : nullptr;
            aj.C = enc_a % 1000;
            aj.k = enc_a / 1000 + 1;
            aj.Ck = 1;
            for (int b = 0; b < aj.k; b++) aj.Ck *= aj.C;
            aj.start = starts[r];
            aj.kv = vals + key_of_rule[r] * max_records;
            aj.kl = vlens + key_of_rule[r] * max_records;
            aj.mrow = match + r * max_records;
            aj.n_rec = n_rec;
            int slices = p2_threads > 1 ? g_pool.n_workers + 1 : 1;
            if (slices > p2_threads) slices = p2_threads;
            aj.slice = (n_rec + slices - 1) / slices;
            aj.n_slices = (int)((n_rec + aj.slice - 1) / aj.slice);
            if (aj.n_slices > 1)
                g_pool.run(grep_accel_slice, &aj, aj.n_slices);
            else
                grep_accel_slice(&aj, 0);
            continue;
        }
        int32_t enc = ncls[r];
        GrepBlockJob bj;
        bj.trans = trans_cat + troffs[r];
        bj.cmap = cmap;
        bj.cmap2 = cm2offs[r] >= 0 ? cmap2_cat + cm2offs[r] : nullptr;
        // ncls encodes C and the super-step k: C + 1000*(k-1)
        bj.k = enc / 1000 + 1;
        bj.C = enc % 1000;
        bj.Ck = 1;
        for (int b = 0; b < bj.k; b++) bj.Ck *= bj.C;
        bj.start = starts[r];
        bj.max_vlen = max_vlen;
        bj.kv = vals + key_of_rule[r] * max_records;
        bj.kl = vlens + key_of_rule[r] * max_records;
        bj.ord = order + key_of_rule[r] * n_rec;
        bj.mrow = match + r * max_records;
        bj.n_rec = n_rec;
        int slices = p2_threads > 1 ? g_pool.n_workers + 1 : 1;
        if (slices > p2_threads) slices = p2_threads;
        long long per = (n_rec + slices - 1) / slices;
        // lane-aligned slices: blocks of FBTPU_PRE_LANES stay whole
        per = ((per + FBTPU_PRE_LANES - 1) / FBTPU_PRE_LANES)
              * FBTPU_PRE_LANES;
        bj.slice = per;
        bj.n_slices = (int)((n_rec + per - 1) / per);
        if (bj.n_slices > 1)
            g_pool.run(grep_block_slice, &bj, bj.n_slices);
        else
            grep_block_slice(&bj, 0);
    }
    // ---- phase 3: verdict + run-coalesced compaction ----
    long long n_keep = 0, w = 0, run_s = 0, run_e = 0;
    for (long long i = 0; i < n_rec; i++) {
        int keep;
        if (n_rules == 0) {
            keep = 1;
        } else if (op_mode == FBTPU_OP_LEGACY) {
            keep = 1;  // fallthrough keeps
            for (long long r = 0; r < n_rules; r++) {
                if (match[r * max_records + i]) {
                    keep = !rule_exclude[r];
                    break;
                }
                if (!rule_exclude[r]) { keep = 0; break; }
            }
        } else {
            int found = (op_mode == FBTPU_OP_AND);
            for (long long r = 0; r < n_rules; r++) {
                found = match[r * max_records + i];
                if (op_mode == FBTPU_OP_OR && found) break;
                if (op_mode == FBTPU_OP_AND && !found) break;
            }
            keep = rule_exclude[0] ? !found : found;
        }
        if (keep) {
            n_keep++;
            long long rs = offsets[i], re = offsets[i + 1];
            if (rs == run_e) {
                run_e = re;  // contiguous keep: extend the pending run
            } else {
                if (run_e > run_s) {
                    memcpy(out + w, buf + run_s, (size_t)(run_e - run_s));
                    w += run_e - run_s;
                }
                run_s = rs;
                run_e = re;
            }
        }
    }
    out_info[0] = n_rec;
    out_info[1] = n_keep;
    if (n_keep == n_rec) {
        out_info[2] = 0;  // nothing dropped: caller reuses the input
        return 0;
    }
    if (run_e > run_s) {
        memcpy(out + w, buf + run_s, (size_t)(run_e - run_s));
        w += run_e - run_s;
    }
    out_info[2] = 1;
    return w;
}


}  // extern "C"

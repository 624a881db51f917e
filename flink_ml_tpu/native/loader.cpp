// Native data-plane loaders.
//
// The reference's only native boundary is the math kernel (netlib BLAS via
// flink-ml-lib, BLAS.java:28-41); on TPU that role is played by XLA.  The
// runtime component that still deserves native code here is ingestion: CSV
// and LibSVM parsing is pure host CPU work on the training path (SURVEY.md
// §7.1 'bounded sources'), and the Python fallbacks are interpreter-bound.
//
// Exposed via a plain C ABI consumed with ctypes (no pybind11 in this
// environment):
//   fml_read_csv     -> one malloc'd buffer: rows joined by \x1e, cells by
//                       \x1f (RFC-4180 quoting handled here; Python does two
//                       C-speed splits to materialize cells)
//   fml_read_libsvm  -> CSR triplet buffers (labels / indptr / indices /
//                       values) ready to wrap as numpy arrays
//   fml_free         -> release any buffer returned by the calls above
//
// Streaming handles (the out-of-core path — bounded memory, one chunk of
// rows per call, files never fully materialized):
//   fml_open_libsvm_stream / fml_next_libsvm_chunk / fml_close_libsvm_stream
//       -> per-chunk CSR triplets, identical row semantics to fml_read_libsvm
//   fml_open_csv_stream / fml_next_csv_doubles / fml_close_csv_stream
//       -> per-chunk (rows x arity) double matrix for all-numeric schemas
//          (RFC-4180 quoting honored; empty/null cells parse as NaN); the
//          common dense-ML case skips per-cell Python entirely

#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

namespace {

// Read a whole file into a string; empty string on failure (len 0).
static bool read_file(const char* path, std::string& out) {
    FILE* f = std::fopen(path, "rb");
    if (!f) return false;
    std::fseek(f, 0, SEEK_END);
    long size = std::ftell(f);
    std::fseek(f, 0, SEEK_SET);
    if (size < 0) {
        std::fclose(f);
        return false;
    }
    out.resize(static_cast<size_t>(size));
    size_t got = size ? std::fread(&out[0], 1, static_cast<size_t>(size), f) : 0;
    std::fclose(f);
    out.resize(got);
    return true;
}

static const size_t NPOS = static_cast<size_t>(-1);

// Incremental file reader: a bounded buffer of not-yet-consumed bytes.
struct TextStream {
    FILE* f = nullptr;
    std::string buf;
    size_t pos = 0;  // consumed prefix
    bool eof = false;

    bool refill() {
        if (eof) return false;
        if (pos > (1u << 20)) {  // compact so memory stays ~one block
            buf.erase(0, pos);
            pos = 0;
        }
        char tmp[1 << 16];
        size_t got = std::fread(tmp, 1, sizeof tmp, f);
        if (got == 0) {
            eof = true;
            return false;
        }
        buf.append(tmp, got);
        return true;
    }
};

// End (exclusive) of the first COMPLETE row at `from`, honoring RFC-4180
// quoting (newlines inside quoted cells are data); `next_pos` receives the
// offset past the row terminator.  NPOS = the buffer holds no complete row
// yet (caller refills) — boundary-ambiguous cases ("" split across a block
// edge, trailing \r) are treated as incomplete until eof.
static size_t find_row_end(const std::string& s, size_t from, bool eof,
                           size_t& next_pos) {
    bool in_quotes = false;
    size_t i = from;
    const size_t n = s.size();
    while (i < n) {
        char c = s[i];
        if (in_quotes) {
            if (c == '"') {
                if (i + 1 >= n) {
                    if (!eof) return NPOS;  // could be the first of ""
                    in_quotes = false;
                    ++i;
                    continue;
                }
                if (s[i + 1] == '"') {
                    i += 2;
                    continue;
                }
                in_quotes = false;
                ++i;
                continue;
            }
            ++i;
            continue;
        }
        if (c == '"') {
            in_quotes = true;
            ++i;
            continue;
        }
        if (c == '\n') {
            next_pos = i + 1;
            return i;
        }
        if (c == '\r') {
            if (i + 1 >= n && !eof) return NPOS;  // \r\n may span blocks
            next_pos = (i + 1 < n && s[i + 1] == '\n') ? i + 2 : i + 1;
            return i;
        }
        ++i;
    }
    return NPOS;
}

// One CSV row [p, e) -> doubles.  Empty / "null" cells parse as NaN.
// Returns false on a non-numeric cell.
static bool parse_double_cells(const char* p, const char* e, char delim,
                               std::vector<double>& out, int64_t* count) {
    int64_t c = 0;
    std::string cell;
    while (true) {
        cell.clear();
        if (p < e && *p == '"') {
            ++p;
            while (p < e) {
                if (*p == '"') {
                    if (p + 1 < e && p[1] == '"') {
                        cell.push_back('"');
                        p += 2;
                    } else {
                        ++p;
                        break;
                    }
                } else {
                    cell.push_back(*p++);
                }
            }
        } else {
            while (p < e && *p != delim) cell.push_back(*p++);
        }
        size_t b = cell.find_first_not_of(" \t");
        size_t t = cell.find_last_not_of(" \t");
        std::string trimmed =
            (b == std::string::npos) ? std::string() : cell.substr(b, t - b + 1);
        double v;
        if (trimmed.empty() || trimmed == "null" || trimmed == "NULL" ||
            trimmed == "Null") {
            v = std::nan("");
        } else {
            // strtod accepts forms Python's float() rejects (hex floats,
            // nan(payload)); reject those so the stream and read() agree —
            // legitimate decimals never contain 'x'/'X'/'('
            if (trimmed.find_first_of("xX(") != std::string::npos) return false;
            char* after = nullptr;
            v = std::strtod(trimmed.c_str(), &after);
            if (after != trimmed.c_str() + trimmed.size()) return false;
        }
        out.push_back(v);
        ++c;
        if (p < e && *p == delim) {
            ++p;
            continue;
        }
        break;
    }
    *count = c;
    return true;
}

// One LibSVM line [p, stop) into the accumulators.  Returns 0 = row added,
// 1 = blank/comment-only (skip), -2 = parse error.  Shared by the whole-file
// reader and the streaming chunk reader so their row semantics cannot drift.
static int parse_libsvm_line(const char* p, const char* stop, int64_t offset,
                             std::vector<double>& labels,
                             std::vector<int64_t>& indices,
                             std::vector<double>& values, int64_t* max_idx) {
    const char* hash =
        static_cast<const char*>(std::memchr(p, '#', static_cast<size_t>(stop - p)));
    if (hash) stop = hash;
    while (p < stop && (*p == ' ' || *p == '\t' || *p == '\r')) ++p;
    if (p >= stop) return 1;
    char* next = nullptr;
    double label = std::strtod(p, &next);
    if (next == p) return -2;
    labels.push_back(label);
    p = next;
    for (;;) {
        while (p < stop && (*p == ' ' || *p == '\t' || *p == '\r')) ++p;
        if (p >= stop) break;
        char* colon = nullptr;
        long long idx = std::strtoll(p, &colon, 10);
        if (colon == p || colon >= stop || *colon != ':') return -2;
        // the value must start right after ':' within this line — strtod's
        // own whitespace-skipping would otherwise walk past the newline and
        // silently consume the next line's label
        const char* vstart = colon + 1;
        if (vstart >= stop || *vstart == ' ' || *vstart == '\t' ||
            *vstart == '\r' || *vstart == '\n') {
            return -2;
        }
        char* after = nullptr;
        double val = std::strtod(vstart, &after);
        if (after == vstart || after > stop) return -2;
        int64_t j = static_cast<int64_t>(idx) - offset;
        if (j < 0) return -2;
        indices.push_back(j);
        values.push_back(val);
        if (j > *max_idx) *max_idx = j;
        p = after;
    }
    return 0;
}

template <typename T>
static T* copy_out(const std::vector<T>& v) {
    auto* out = static_cast<T*>(std::malloc(sizeof(T) * (v.empty() ? 1 : v.size())));
    if (out && !v.empty()) std::memcpy(out, v.data(), sizeof(T) * v.size());
    return out;
}

struct CsvStream {
    TextStream ts;
    char delim;
    bool skip_pending;
};

struct LibsvmStream {
    TextStream ts;
    int64_t offset;
};

}  // namespace

extern "C" {

void fml_free(void* p) { std::free(p); }

// Parse CSV with RFC-4180 double-quote semantics.  Returns a buffer of
// rows separated by \x1e whose cells are separated by \x1f, or nullptr on
// I/O error (*out_len = 0) or when the data itself contains the separator
// control bytes 0x1E/0x1F (*out_len = -2: legal in quoted cells but not
// representable in this transport — the caller falls back to the pure
// parser).  Otherwise *out_len receives the buffer length.
char* fml_read_csv(const char* path, char delim, int skip_header,
                   int64_t* out_len) {
    *out_len = 0;
    std::string data;
    if (!read_file(path, data)) return nullptr;
    if (data.find('\x1e') != std::string::npos ||
        data.find('\x1f') != std::string::npos) {
        *out_len = -2;
        return nullptr;
    }

    std::string out;
    out.reserve(data.size() + data.size() / 8);

    size_t i = 0;
    const size_t n = data.size();
    bool row_started = false;
    bool skipping = skip_header != 0;

    while (i < n) {
        // parse one cell
        std::string cell;
        if (data[i] == '"') {
            ++i;
            while (i < n) {
                if (data[i] == '"') {
                    if (i + 1 < n && data[i + 1] == '"') {
                        cell.push_back('"');
                        i += 2;
                    } else {
                        ++i;
                        break;
                    }
                } else {
                    cell.push_back(data[i++]);
                }
            }
        } else {
            while (i < n && data[i] != delim && data[i] != '\n' && data[i] != '\r') {
                cell.push_back(data[i++]);
            }
        }
        if (!skipping) {
            if (row_started) out.push_back('\x1f');
            out += cell;
            row_started = true;
        }
        // cell terminator
        if (i < n && data[i] == delim) {
            ++i;
            continue;
        }
        // row terminator (handle \r\n and \n)
        if (i < n && data[i] == '\r') ++i;
        if (i < n && data[i] == '\n') ++i;
        if (skipping) {
            skipping = false;
        } else if (row_started) {
            out.push_back('\x1e');
            row_started = false;
        }
    }
    if (row_started) out.push_back('\x1e');

    char* buf = static_cast<char*>(std::malloc(out.size() ? out.size() : 1));
    if (!buf) return nullptr;
    std::memcpy(buf, out.data(), out.size());
    *out_len = static_cast<int64_t>(out.size());
    return buf;
}

// Parse LibSVM/SVMlight text into CSR buffers.  '#' starts a comment.
// Returns 0 on success, -1 on I/O error, -2 on parse error.
int fml_read_libsvm(const char* path, int zero_based, double** out_labels,
                    int64_t** out_indptr, int64_t** out_indices,
                    double** out_values, int64_t* out_rows, int64_t* out_nnz,
                    int64_t* out_max_idx) {
    std::string data;
    if (!read_file(path, data)) return -1;

    std::vector<double> labels;
    std::vector<int64_t> indptr(1, 0);
    std::vector<int64_t> indices;
    std::vector<double> values;
    int64_t max_idx = -1;
    const int64_t offset = zero_based ? 0 : 1;

    const char* p = data.c_str();
    const char* end = p + data.size();
    while (p < end) {
        const char* line_end = static_cast<const char*>(
            std::memchr(p, '\n', static_cast<size_t>(end - p)));
        if (!line_end) line_end = end;
        int rc = parse_libsvm_line(p, line_end, offset, labels, indices,
                                   values, &max_idx);
        if (rc == -2) return -2;
        if (rc == 0) indptr.push_back(static_cast<int64_t>(indices.size()));
        p = (line_end < end) ? line_end + 1 : end;
    }

    const size_t nr = labels.size();
    const size_t nz = indices.size();
    auto* lab = static_cast<double*>(std::malloc(sizeof(double) * (nr ? nr : 1)));
    auto* ptr = static_cast<int64_t*>(std::malloc(sizeof(int64_t) * (nr + 1)));
    auto* ind = static_cast<int64_t*>(std::malloc(sizeof(int64_t) * (nz ? nz : 1)));
    auto* val = static_cast<double*>(std::malloc(sizeof(double) * (nz ? nz : 1)));
    if (!lab || !ptr || !ind || !val) {
        std::free(lab); std::free(ptr); std::free(ind); std::free(val);
        return -1;
    }
    if (nr) std::memcpy(lab, labels.data(), sizeof(double) * nr);
    std::memcpy(ptr, indptr.data(), sizeof(int64_t) * (nr + 1));
    if (nz) std::memcpy(ind, indices.data(), sizeof(int64_t) * nz);
    if (nz) std::memcpy(val, values.data(), sizeof(double) * nz);
    *out_labels = lab;
    *out_indptr = ptr;
    *out_indices = ind;
    *out_values = val;
    *out_rows = static_cast<int64_t>(nr);
    *out_nnz = static_cast<int64_t>(nz);
    *out_max_idx = max_idx;
    return 0;
}

// -- streaming (out-of-core) handles -----------------------------------------

void* fml_open_libsvm_stream(const char* path, int zero_based) {
    FILE* f = std::fopen(path, "rb");
    if (!f) return nullptr;
    auto* s = new LibsvmStream;
    s->ts.f = f;
    s->offset = zero_based ? 0 : 1;
    return s;
}

// Up to max_rows rows as CSR triplets (caller frees all four buffers with
// fml_free).  Returns rows read (0 = end of file), -1 = alloc failure,
// -2 = parse error.
int64_t fml_next_libsvm_chunk(void* handle, int64_t max_rows,
                              double** out_labels, int64_t** out_indptr,
                              int64_t** out_indices, double** out_values,
                              int64_t* out_nnz, int64_t* out_max_idx) {
    auto* s = static_cast<LibsvmStream*>(handle);
    std::vector<double> labels;
    std::vector<int64_t> indptr(1, 0);
    std::vector<int64_t> indices;
    std::vector<double> values;
    int64_t max_idx = -1;

    while (static_cast<int64_t>(labels.size()) < max_rows) {
        const std::string& b = s->ts.buf;
        const char* base = b.c_str();
        const void* nl = (s->ts.pos < b.size())
            ? std::memchr(base + s->ts.pos, '\n', b.size() - s->ts.pos)
            : nullptr;
        size_t line_end, next_pos;
        if (nl != nullptr) {
            line_end = static_cast<const char*>(nl) - base;
            next_pos = line_end + 1;
        } else if (!s->ts.eof) {
            if (!s->ts.refill() && s->ts.pos >= s->ts.buf.size()) break;
            continue;
        } else if (s->ts.pos < b.size()) {
            line_end = b.size();  // final unterminated line
            next_pos = line_end;
        } else {
            break;  // fully consumed
        }
        int rc = parse_libsvm_line(base + s->ts.pos, base + line_end,
                                   s->offset, labels, indices, values,
                                   &max_idx);
        if (rc == -2) return -2;
        if (rc == 0) indptr.push_back(static_cast<int64_t>(indices.size()));
        s->ts.pos = next_pos;
    }

    *out_labels = copy_out(labels);
    *out_indptr = copy_out(indptr);
    *out_indices = copy_out(indices);
    *out_values = copy_out(values);
    if (!*out_labels || !*out_indptr || !*out_indices || !*out_values) {
        std::free(*out_labels);
        std::free(*out_indptr);
        std::free(*out_indices);
        std::free(*out_values);
        return -1;
    }
    *out_nnz = static_cast<int64_t>(values.size());
    *out_max_idx = max_idx;
    return static_cast<int64_t>(labels.size());
}

void fml_close_libsvm_stream(void* handle) {
    auto* s = static_cast<LibsvmStream*>(handle);
    if (s) {
        if (s->ts.f) std::fclose(s->ts.f);
        delete s;
    }
}

void* fml_open_csv_stream(const char* path, char delim, int skip_header) {
    FILE* f = std::fopen(path, "rb");
    if (!f) return nullptr;
    auto* s = new CsvStream;
    s->ts.f = f;
    s->delim = delim;
    s->skip_pending = skip_header != 0;
    return s;
}

// Up to max_rows rows of an all-numeric CSV as one (rows x arity) row-major
// double buffer (caller frees with fml_free).  Returns rows read (0 = end
// of file), -1 = alloc failure, -2 = non-numeric cell or arity mismatch
// (the Python caller falls back to the pure parser, skipping the rows this
// handle already delivered).
int64_t fml_next_csv_doubles(void* handle, int64_t max_rows, int64_t arity,
                             double** out) {
    auto* s = static_cast<CsvStream*>(handle);
    std::vector<double> vals;
    vals.reserve(static_cast<size_t>(max_rows * arity));
    int64_t rows = 0;

    while (rows < max_rows) {
        size_t next_pos = 0;
        size_t row_end = find_row_end(s->ts.buf, s->ts.pos, s->ts.eof, next_pos);
        if (row_end == NPOS) {
            if (s->ts.refill()) continue;
            if (s->ts.pos >= s->ts.buf.size()) break;
            row_end = s->ts.buf.size();  // final unterminated row
            next_pos = row_end;
        }
        const char* b = s->ts.buf.c_str() + s->ts.pos;
        const char* e = s->ts.buf.c_str() + row_end;
        // the header skip consumes physical row 0 even when blank (the pure
        // parser enumerates csv.reader rows, so a blank first line IS the
        // skipped header) — check before the blank-line skip
        if (s->skip_pending) {
            s->skip_pending = false;
            s->ts.pos = next_pos;
            continue;
        }
        if (b == e) {  // blank line: skipped, like csv.reader's empty row
            s->ts.pos = next_pos;
            continue;
        }
        int64_t count = 0;
        if (!parse_double_cells(b, e, s->delim, vals, &count)) return -2;
        if (count != arity) return -2;
        ++rows;
        s->ts.pos = next_pos;
    }

    *out = copy_out(vals);
    if (!*out) return -1;
    return rows;
}

void fml_close_csv_stream(void* handle) {
    auto* s = static_cast<CsvStream*>(handle);
    if (s) {
        if (s->ts.f) std::fclose(s->ts.f);
        delete s;
    }
}

// Adds one to counts[id] for each of the n ids, all in [0, dim); returns
// the place of the first id outside it (counted up to there), or -1.
// Holds no lock: callers count chunks of one column on threads, each into
// counts of its own.
int64_t fml_count_ids(const int32_t* ids, int64_t n, int64_t dim,
                      int64_t* counts) {
    for (int64_t i = 0; i < n; ++i) {
        const int32_t id = ids[i];
        if (id < 0 || id >= dim) return i;
        ++counts[id];
    }
    return -1;
}

}  // extern "C"

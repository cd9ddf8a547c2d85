// Shared C ABI declarations for the native core (parse.cc + reader.cc).
//
// All result buffers are malloc'd and freed with the matching dmlc_free_*;
// Python loads these via ctypes (no pybind11 in this image).

#ifndef DMLC_TPU_NATIVE_API_H_
#define DMLC_TPU_NATIVE_API_H_

#include <cstdint>

// The wire formats this core reads (recordio frames, indexed .idx offsets)
// are little-endian, and the frame loads are memcpy-native by design (the
// hot path must not pay per-load byte swaps on the LE hosts we target).
// Refuse to BUILD on a big-endian target rather than corrupt data at
// runtime — the compile-time analog of the reference's s390x CI guard
// (scripts/travis/travis_script.sh:62-66, endian.h DMLC_IO_NO_ENDIAN_SWAP).
#if defined(__BYTE_ORDER__) && (__BYTE_ORDER__ != __ORDER_LITTLE_ENDIAN__)
#error "dmlc_tpu native core requires a little-endian host (LE wire format)"
#endif

extern "C" {

// One parsed CSR block (libsvm / libfm). Free with dmlc_free_block.
struct CsrBlockResult {
  int64_t n_rows;
  int64_t nnz;
  int64_t* offset;    // [n_rows + 1]
  float* label;       // [n_rows]
  float* weight;      // [n_rows] or null
  int64_t* qid;       // [n_rows] or null
  uint64_t* index;    // [nnz]
  uint64_t* field;    // [nnz] or null (libfm)
  float* value;       // [nnz] or null (all-binary)
  char* error;        // null on success
};

// Dense libsvm result: x laid out row-major [n_rows, n_cols].
struct DenseResult {
  int64_t n_rows;
  int64_t n_cols;
  float* x;       // [n_rows, n_cols]; bf16 (uint16) payload when x_bf16 = 1
  float* label;   // [n_rows]
  float* weight;  // [n_rows] or null
  char* error;    // null on success
  int32_t needs_csr;  // 1 = data needs the CSR path (e.g. qid rows); error is
                      // also set. Explicit flag so callers never route on
                      // error-message wording.
  int32_t x_bf16;     // 1 = x holds bfloat16 (the TPU-native ingest format:
                      // half the host->HBM bytes, MXU-preferred operand)
  // 1 = x is [n_rows, n_cols + 2] with label in column n_cols and weight
  // in column n_cols + 1 (label/weight pointers are then NULL): ONE
  // device_put per batch instead of three arrays — measured 2x on the
  // per-array put overhead (benchmarks/bench_transfer_floor.py aux leg).
  // Only emitted in batch-repack mode on request (pack_aux); in bf16 mode
  // the aux columns are bf16 too, so callers opt in only when their
  // labels/weights are bf16-exact.
  int32_t packed_aux;
};

// Dense CSV result: cells laid out row-major [n_rows, n_cols].
struct CsvResult {
  int64_t n_rows;
  int64_t n_cols;
  float* cells;
  char* error;
};

// Dense CSV result with integer cells (csv_parser.h's int32_t / int64_t
// instantiations, data.cc): row-major [n_rows, n_cols] of int32_t (bits ==
// 32) or int64_t (bits == 64). A cell that is no whole decimal number, or
// lies outside the type's range, is an error; nothing passes through a
// float on the way.
struct CsvIntResult {
  int64_t n_rows;
  int64_t n_cols;
  void* cells;
  int32_t bits;
  char* error;
};

// CsvIntResult of a scan with hashed cells (``hash_bins``; docs/data.md,
// "Hashed cells"): every cell but the label's and the weight's is
// FNV-1a-64(position byte, the cell's bytes) mod hash_bins, the label and
// weight cells whole numbers. empty_cells counts the hashed cells that had
// no bytes (each a value of its column, never an error).
struct CsvHashedResult {
  int64_t n_rows;
  int64_t n_cols;
  void* cells;
  int32_t bits;
  int64_t empty_cells;
  char* error;
};

// CSV result with the label/weight columns split out during the single
// merge-copy pass: values holds ONLY the feature cells, row-major
// [n_rows, n_feat_cols], so the RowBlock wrapper needs zero further copies
// (the synthetic per-row 0..k-1 index/offset skeleton is format-implied
// and cached host-side). The reference's CSV path re-walks cells in its
// consumer (csv_parser.h:120-121); splitting here keeps the whole parse
// one pass over the bytes.
struct CsvSplitResult {
  int64_t n_rows;
  int64_t n_feat_cols;  // columns minus label/weight columns
  float* values;        // [n_rows, n_feat_cols]
  float* label;         // [n_rows], or NULL when label_col < 0
  float* weight;        // [n_rows], or NULL when weight_col < 0
  char* error;          // null on success
};

// Sparse batch in device-ready COO layout (the BCOO host half): coords are
// int32 (row, col) pairs — on KDD-shaped data the coordinate array
// dominates transfer bytes, so int32 halves host->HBM traffic vs int64 —
// padded out to rows_padded/nnz_padded with OUT-OF-BOUNDS entries
// (rows_padded, num_col), which every jax BCOO op masks. values may be
// NULL with values_elided=1 when every real value is 1.0f (binary-feature
// corpora): the consumer synthesizes ones on device, saving 4 B/nnz of
// transfer. qid/field are not carried (BCOO interop drops them, matching
// the Python convert path). Free with dmlc_free_coo.
struct CooResult {
  int64_t n_rows;       // real rows
  int64_t nnz;          // real entries
  int64_t rows_padded;  // label/weight length (>= n_rows)
  int64_t nnz_padded;   // coords rows / values length (>= nnz)
  int32_t* coords;      // [nnz_padded, 2] row-major (row, col), or
                        // [nnz_padded] cols-only when csr_wire
  float* values;        // [nnz_padded] or NULL when values_elided
  float* label;         // [rows_padded], zeros past n_rows
  float* weight;        // [rows_padded], zeros past n_rows
  char* error;          // null on success
  int32_t values_elided;
  // CSR wire format (csr_wire=1): coords carries ONLY the column ids and
  // row_ptr is [rows_padded + 1] with row i spanning entries
  // [row_ptr[i], row_ptr[i+1]); pad rows all point at nnz (real), so an
  // on-device prefix-sum rebuild maps every pad entry to the OOB row
  // rows_padded. Halves the coordinate transfer bytes (4 B/nnz instead of
  // 8) at the cost of one tiny [rows+1] array and a cheap device-side
  // scatter+cumsum.
  int32_t csr_wire;
  int32_t* row_ptr;     // [rows_padded + 1] when csr_wire, else NULL
};

// Parse a text chunk (fmt: 0 = libsvm, 3 = libfm) straight to COO.
// row_bucket/nnz_bucket quantize the padded dims UP to bucket multiples so
// batch shapes REPEAT across chunks (a novel-shape device_put costs a fresh
// transfer plan and a recompile downstream);
// 0 disables. elide_unit enables the all-ones value elision. csr_wire
// emits the cols+row_ptr wire layout (see CooResult). Requires
// max(num_col, chunk rows) + 1 < 2^31 (int32 coords); callers guard.
CooResult* dmlc_parse_coo(const char* data, int64_t len, int nthread,
                          int indexing_mode, int fmt, int64_t num_col,
                          int64_t row_bucket, int64_t nnz_bucket,
                          int32_t elide_unit, int32_t csr_wire);
void dmlc_free_coo(CooResult* r);

// A batch of RecordIO record payloads: record i is
// data[offsets[i] : offsets[i+1]]. Free with dmlc_free_records.
struct RecordBatchResult {
  int64_t n_records;
  int64_t data_len;   // == offsets[n_records]
  char* data;         // concatenated payloads
  int64_t* offsets;   // [n_records + 1]
  char* error;        // null on success
};

// Extract every record from a span of RecordIO bytes that starts at a
// record head and contains only whole records (recordio.cc:53-82 framing:
// magic/lrecord cells, cflag 0|1|2|3 multi-part reassembly with the magic
// re-inserted between parts). Pure function — safe to feed spans read from
// any source (local chunk, cloud stream, indexed batch).
RecordBatchResult* dmlc_recordio_extract(const char* data, int64_t len);
void dmlc_free_records(RecordBatchResult* r);

CsrBlockResult* dmlc_parse_libsvm(const char* data, int64_t len, int nthread,
                                  int indexing_mode);
CsrBlockResult* dmlc_parse_libfm(const char* data, int64_t len, int nthread,
                                 int indexing_mode);
DenseResult* dmlc_parse_libsvm_dense(const char* data, int64_t len, int nthread,
                                     int64_t num_col, int indexing_mode);
CsvResult* dmlc_parse_csv(const char* data, int64_t len, int nthread, char delim);
CsvIntResult* dmlc_parse_csv_int(const char* data, int64_t len, int nthread,
                                 char delim, int32_t bits);
CsvHashedResult* dmlc_parse_csv_hashed(const char* data, int64_t len,
                                       int nthread, char delim, int32_t bits,
                                       int32_t label_col, int32_t weight_col,
                                       int64_t hash_bins);
CsvSplitResult* dmlc_parse_csv_split(const char* data, int64_t len, int nthread,
                                     char delim, int32_t label_col,
                                     int32_t weight_col);

void dmlc_free_block(CsrBlockResult* r);
void dmlc_free_dense(DenseResult* r);
void dmlc_free_csv(CsvResult* r);
void dmlc_free_csv_int(CsvIntResult* r);
void dmlc_free_csv_hashed(CsvHashedResult* r);
void dmlc_free_csv_split(CsvSplitResult* r);

int dmlc_native_abi_version();

// ---------------- streaming reader (reader.cc) ----------------
//
// A native read->chunk->parse pipeline over a byte-range partition of local
// text files: producer thread loads record-aligned chunks (the reference's
// InputSplitBase/LineSplitter invariants), parses each with worker threads,
// and queues parsed blocks for the consumer. Formats: 0=libsvm (CSR),
// 1=libsvm dense, 2=csv, 3=libfm, 4=recordio (binary records: 4-byte
// partition alignment, magic-head boundary seeks, no newline injection at
// file joins; results are RecordBatchResult).

// batch_rows > 0 (dense libsvm, or csv with num_col > 0): repack parsed
// rows into exact [batch_rows, num_col] dense blocks off the consumer
// thread (final block may be short). For csv, label_col/weight_col (-1 =
// absent) are split out and the remaining cells padded/truncated to
// num_col; results then carry format 1 (dense). out_bf16 = 1 converts x
// to bfloat16 (round-to-nearest-even) DURING the repack copy — the same
// single pass, half the output bytes.
// Formats 6 (libsvm -> COO) and 7 (libfm -> COO) emit CooResult blocks:
// one device-ready COO batch per chunk, with row_bucket/nnz_bucket shape
// quantization and optional unit-value elision (see dmlc_parse_coo).
void* dmlc_reader_create(const char** paths, const int64_t* sizes,
                         int32_t nfiles, int64_t part_index, int64_t num_parts,
                         int32_t format, int64_t num_col, int32_t indexing_mode,
                         char delim, int32_t nthread, int64_t chunk_bytes,
                         int32_t queue_depth, int64_t batch_rows,
                         int32_t label_col, int32_t weight_col,
                         int32_t out_bf16, int64_t row_bucket,
                         int64_t nnz_bucket, int32_t elide_unit,
                         int32_t csr_wire, int32_t pack_aux);
// Next parsed block; NULL at end-of-partition or on reader error (check
// dmlc_reader_error). Parse errors ride the result's own error field.
// Blocks with zero rows are never returned. `fmt_out` (may be NULL)
// receives the format of THIS result: a reader created with format 1
// (libsvm dense) downgrades permanently to format 0 (CSR) when it meets
// data the dense scanner cannot express (qid rows), so the tag can differ
// from the requested format.
void* dmlc_reader_next(void* handle, int32_t* fmt_out);
void dmlc_reader_before_first(void* handle);
int64_t dmlc_reader_bytes_read(void* handle);

// ---------------- indexed recordio reader (reader.cc) ----------------
//
// Record-count partitioned reader over an external index (sorted record
// start offsets, global over the concatenated files): batched contiguous
// reads when shuffle=0, per-epoch shuffled per-record seeks when
// shuffle=1 (mt19937_64 seeded with `seed`; each before_first draws the
// next epoch's permutation). Results are RecordBatchResult (payloads
// extracted, multi-part reassembled). Mirrors indexed_recordio_split.cc
// (ResetPartition :12-41, NextBatchEx :159-212, BeforeFirst :221-233).
void* dmlc_indexed_reader_create(const char** paths, const int64_t* sizes,
                                 int32_t nfiles, const int64_t* index_offsets,
                                 int64_t n_index, int64_t part_index,
                                 int64_t num_parts, int64_t batch_records,
                                 int32_t shuffle, uint64_t seed,
                                 int32_t queue_depth);
void* dmlc_indexed_reader_next(void* handle);  // RecordBatchResult*
void dmlc_indexed_reader_before_first(void* handle);
// Native resume: land in epoch `epochs` (counting before_first calls) at
// record `records` of the partition — missing epoch permutations are drawn
// (pure rng replay, no I/O) and the producer starts at the record cursor.
void dmlc_indexed_reader_skip(void* handle, int64_t epochs, int64_t records);
int64_t dmlc_indexed_reader_bytes_read(void* handle);
const char* dmlc_indexed_reader_error(void* handle);
void dmlc_indexed_reader_destroy(void* handle);
// Non-NULL when the reader itself failed (open/seek/IO); owned by the handle.
const char* dmlc_reader_error(void* handle);
void dmlc_reader_destroy(void* handle);

// ---------------- push-mode reader (chunk feeder) ----------------
//
// Same chunk->parse->queue pipeline, but bytes are PUSHED by the caller
// instead of read from local files — the path by which remote streams
// (S3/GCS/HTTP range reads in Python) reach the native parser. The caller
// owns partitioning (byte range + record-boundary adjustment + newline
// injection at text file joins, which the Python input-split engine
// already does for every filesystem); the feeder owns record-aligned
// chunking, threaded parsing, and batch repack. Push blocks (GIL released
// via ctypes) when the internal byte queue is full — natural backpressure.

void* dmlc_feeder_create(int32_t format, int64_t num_col,
                         int32_t indexing_mode, char delim, int32_t nthread,
                         int64_t chunk_bytes, int32_t queue_depth,
                         int64_t batch_rows, int32_t label_col,
                         int32_t weight_col, int32_t out_bf16,
                         int64_t row_bucket, int64_t nnz_bucket,
                         int32_t elide_unit, int32_t csr_wire,
                         int32_t pack_aux);
// 0 = accepted; -1 = reader stopped/failed (check dmlc_feeder_error).
int32_t dmlc_feeder_push(void* handle, const char* data, int64_t len);
// Signal end of input: the pipeline flushes its tail and then next()
// returns NULL at end of stream.
void dmlc_feeder_finish(void* handle);
// Unblock + fail any in-flight push and drain the pipeline to EOF. The
// caller MUST abort and join its feed thread before calling
// dmlc_feeder_before_first or dmlc_feeder_destroy.
void dmlc_feeder_abort(void* handle);
// Record a feed-side failure (remote read error in the feeding thread) and
// end the stream; queued results drain, then next() returns NULL with the
// error set.
void dmlc_feeder_fail(void* handle, const char* msg);
void* dmlc_feeder_next(void* handle, int32_t* fmt_out);
// Reset for a new epoch: the caller must re-feed from the start.
void dmlc_feeder_before_first(void* handle);
int64_t dmlc_feeder_bytes_read(void* handle);
const char* dmlc_feeder_error(void* handle);
void dmlc_feeder_destroy(void* handle);

}  // extern "C"

#endif  // DMLC_TPU_NATIVE_API_H_

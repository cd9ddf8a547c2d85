// C++ smoke test for the native core, runnable under TSan/ASan
// (the reference's CI runs its gtest binary under ThreadSanitizer,
// scripts/travis/travis_script.sh:53-60; this is the equivalent seam for
// the rebuilt core — the full behavioral suite lives in tests/ via pytest).

#include <cassert>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "../src/api.h"
#include "../src/buffer_pool.h"

static int failures = 0;
#define CHECK_TRUE(cond)                                        \
  do {                                                          \
    if (!(cond)) {                                              \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__,        \
                   __LINE__, #cond);                            \
      ++failures;                                               \
    }                                                           \
  } while (0)

int main() {
  // libsvm CSR parse across threads
  const char* text =
      "1 0:1.5 3:2.5\n0 1:0.5\n1 2:3.0 4:4.5 5:1e-2\n";
  CsrBlockResult* b =
      dmlc_parse_libsvm(text, static_cast<int64_t>(strlen(text)), 2, 0);
  CHECK_TRUE(b != nullptr);
  CHECK_TRUE(b->error == nullptr);
  CHECK_TRUE(b->n_rows == 3);
  CHECK_TRUE(b->nnz == 6);
  CHECK_TRUE(b->offset[3] == 6);
  dmlc_free_block(b);

  // dense scan + qid downgrade flag
  DenseResult* d = dmlc_parse_libsvm_dense(text,
                                           static_cast<int64_t>(strlen(text)),
                                           2, 6, 0);
  CHECK_TRUE(d != nullptr && d->error == nullptr && d->n_rows == 3);
  CHECK_TRUE(d->x[0] == 1.5f && d->x[3] == 2.5f);
  dmlc_free_dense(d);
  const char* qid_text = "1 qid:3 0:1\n";
  DenseResult* dq = dmlc_parse_libsvm_dense(
      qid_text, static_cast<int64_t>(strlen(qid_text)), 1, 4, 0);
  CHECK_TRUE(dq != nullptr && dq->needs_csr == 1);
  dmlc_free_dense(dq);

  // csv
  const char* csv = "1,2.5,3\n4,5.5,6\n";
  CsvResult* c = dmlc_parse_csv(csv, static_cast<int64_t>(strlen(csv)), 2, ',');
  CHECK_TRUE(c != nullptr && c->error == nullptr);
  CHECK_TRUE(c->n_rows == 2 && c->n_cols == 3 && c->cells[1] == 2.5f);
  dmlc_free_csv(c);

  // csv, integer cells: ids a float32 would round stay whole; a cell past
  // the type's range, or no whole number, is an error
  const char* icsv = "1\t16777217\t2147483647\n0\t-2147483648\t7\n";
  CsvIntResult* ic = dmlc_parse_csv_int(
      icsv, static_cast<int64_t>(strlen(icsv)), 2, '\t', 32);
  CHECK_TRUE(ic != nullptr && ic->error == nullptr && ic->bits == 32);
  const int32_t* cells32 = static_cast<const int32_t*>(ic->cells);
  CHECK_TRUE(ic->n_rows == 2 && ic->n_cols == 3 && cells32[1] == 16777217 &&
             cells32[2] == 2147483647 && cells32[4] == -2147483647 - 1);
  dmlc_free_csv_int(ic);
  const char* wide = "9223372036854775807,-9223372036854775808\n";
  CsvIntResult* iw = dmlc_parse_csv_int(
      wide, static_cast<int64_t>(strlen(wide)), 1, ',', 64);
  CHECK_TRUE(iw != nullptr && iw->error == nullptr && iw->n_cols == 2 &&
             static_cast<const int64_t*>(iw->cells)[0] ==
                 9223372036854775807LL);
  dmlc_free_csv_int(iw);
  for (const char* bad : {"1,2147483648\n", "1,2.5\n", "1,x\n", "1,\n"}) {
    CsvIntResult* ib = dmlc_parse_csv_int(
        bad, static_cast<int64_t>(strlen(bad)), 1, ',', 32);
    CHECK_TRUE(ib != nullptr && ib->error != nullptr);
    dmlc_free_csv_int(ib);
  }
  CsvIntResult* i16 = dmlc_parse_csv_int("1\n", 2, 1, ',', 16);
  CHECK_TRUE(i16 != nullptr && i16->error != nullptr);
  dmlc_free_csv_int(i16);

  // csv, hashed cells: every cell but the label's is FNV-1a-64(position
  // byte, the cell's bytes) mod bins; an empty cell is a value of its column
  const char* hcsv = "1\t68fd1e64\t-1\t\n0\t\t\t\r\n";
  CsvHashedResult* hc = dmlc_parse_csv_hashed(
      hcsv, static_cast<int64_t>(strlen(hcsv)), 2, '\t', 32, /*label_col=*/0,
      /*weight_col=*/-1, /*hash_bins=*/1000000);
  CHECK_TRUE(hc != nullptr && hc->error == nullptr);
  CHECK_TRUE(hc->n_rows == 2 && hc->n_cols == 4 && hc->empty_cells == 4);
  const int32_t* hcells = static_cast<const int32_t*>(hc->cells);
  CHECK_TRUE(hcells[0] == 1 && hcells[1] == 587123 && hcells[2] == 459430 &&
             hcells[3] == 423877);
  CHECK_TRUE(hcells[4] == 0 && hcells[5] == 167455 && hcells[7] == 423877);
  dmlc_free_csv_hashed(hc);
  for (const char* bad : {"1\ta\tb\n0\ta\n", "\ta\tb\n", "x\ta\n"}) {
    CsvHashedResult* hb = dmlc_parse_csv_hashed(
        bad, static_cast<int64_t>(strlen(bad)), 1, '\t', 32, 0, -1, 10);
    CHECK_TRUE(hb != nullptr && hb->error != nullptr);
    dmlc_free_csv_hashed(hb);
  }
  CsvHashedResult* h0 = dmlc_parse_csv_hashed("1\ta\n", 4, 1, '\t', 32, 0, -1, 0);
  CHECK_TRUE(h0 != nullptr && h0->error != nullptr);
  dmlc_free_csv_hashed(h0);

  // csv split: label mid-column, weight last — features are the two runs
  // around them; the sanitizers watch the run-wise memcpy bounds here
  const char* csv2 = "1,9,2.5,3,0.5\n4,8,5.5,6,0.25\n";
  CsvSplitResult* s = dmlc_parse_csv_split(
      csv2, static_cast<int64_t>(strlen(csv2)), 2, ',', /*label_col=*/1,
      /*weight_col=*/4);
  CHECK_TRUE(s != nullptr && s->error == nullptr);
  CHECK_TRUE(s->n_rows == 2 && s->n_feat_cols == 3);
  CHECK_TRUE(s->values[0] == 1.0f && s->values[1] == 2.5f &&
             s->values[2] == 3.0f && s->values[3] == 4.0f);
  CHECK_TRUE(s->label[0] == 9.0f && s->label[1] == 8.0f);
  CHECK_TRUE(s->weight[0] == 0.5f && s->weight[1] == 0.25f);
  dmlc_free_csv_split(s);
  // guard rails: equal columns and out-of-range columns must error, not
  // write out of bounds
  CsvSplitResult* s2 = dmlc_parse_csv_split(
      csv2, static_cast<int64_t>(strlen(csv2)), 1, ',', 2, 2);
  CHECK_TRUE(s2 != nullptr && s2->error != nullptr);
  dmlc_free_csv_split(s2);
  CsvSplitResult* s3 = dmlc_parse_csv_split(
      csv2, static_cast<int64_t>(strlen(csv2)), 1, ',', 9, -1);
  CHECK_TRUE(s3 != nullptr && s3->error != nullptr);
  dmlc_free_csv_split(s3);

  // streaming reader over a temp file, exercised twice (before_first)
  char path[] = "/tmp/dmlc_tpu_smoke_XXXXXX";
  int fd = mkstemp(path);
  CHECK_TRUE(fd >= 0);
  FILE* f = fdopen(fd, "w");
  for (int i = 0; i < 1000; ++i) std::fprintf(f, "%d 0:%d.5 1:2\n", i % 2, i);
  fclose(f);
  long size = 0;
  {
    FILE* g = fopen(path, "rb");
    fseek(g, 0, SEEK_END);
    size = ftell(g);
    fclose(g);
  }
  const char* paths[] = {path};
  int64_t sizes[] = {size};
  void* r = dmlc_reader_create(paths, sizes, 1, 0, 1, /*fmt=*/0, 0, 0, ',',
                               2, 4096, 2, /*batch_rows=*/0,
                               /*label_col=*/-1, /*weight_col=*/-1,
                               /*out_bf16=*/0, /*row_bucket=*/0,
                               /*nnz_bucket=*/0, /*elide_unit=*/0,
                               /*csr_wire=*/0, /*pack_aux=*/0);
  CHECK_TRUE(r != nullptr);
  for (int pass = 0; pass < 2; ++pass) {
    int64_t rows = 0;
    while (true) {
      int32_t fmt = 0;
      void* res = dmlc_reader_next(r, &fmt);
      if (!res) break;
      CsrBlockResult* blk = static_cast<CsrBlockResult*>(res);
      CHECK_TRUE(blk->error == nullptr);
      rows += blk->n_rows;
      dmlc_free_block(blk);
    }
    CHECK_TRUE(dmlc_reader_error(r) == nullptr);
    CHECK_TRUE(rows == 1000);
    dmlc_reader_before_first(r);
  }
  dmlc_reader_destroy(r);
  remove(path);

  // indexed recordio reader: sequential, shuffled epochs, native skip —
  // all under the sanitizer (producer thread + per-record seeks)
  {
    char rpath[] = "/tmp/dmlc_tpu_smoke_rec_XXXXXX";
    int rfd = mkstemp(rpath);
    CHECK_TRUE(rfd >= 0);
    FILE* rf = fdopen(rfd, "wb");
    const uint32_t magic = 0xced7230a;
    int64_t offsets[64];
    for (int i = 0; i < 64; ++i) {
      offsets[i] = static_cast<int64_t>(ftell(rf));
      uint32_t len = 8 + static_cast<uint32_t>(i % 4);
      uint32_t lrec = len;  // cflag 0
      fwrite(&magic, 4, 1, rf);
      fwrite(&lrec, 4, 1, rf);
      char payload[12] = {0};
      payload[0] = static_cast<char>(i);
      fwrite(payload, 1, len, rf);
      size_t pad = (4 - len % 4) % 4;
      char zeros[4] = {0, 0, 0, 0};
      fwrite(zeros, 1, pad, rf);
    }
    int64_t fsize = static_cast<int64_t>(ftell(rf));
    fclose(rf);
    const char* rpaths[1] = {rpath};
    for (int shuffle = 0; shuffle < 2; ++shuffle) {
      void* ir = dmlc_indexed_reader_create(
          rpaths, &fsize, 1, offsets, 64, /*part=*/0, /*nparts=*/1,
          /*batch_records=*/7, shuffle, /*seed=*/3, /*queue_depth=*/2);
      CHECK_TRUE(ir != nullptr);
      for (int pass = 0; pass < 2; ++pass) {
        int64_t recs = 0;
        while (true) {
          void* res = dmlc_indexed_reader_next(ir);
          if (!res) break;
          RecordBatchResult* rb = static_cast<RecordBatchResult*>(res);
          CHECK_TRUE(rb->error == nullptr);
          recs += rb->n_records;
          dmlc_free_records(rb);
        }
        CHECK_TRUE(dmlc_indexed_reader_error(ir) == nullptr);
        CHECK_TRUE(recs == 64);
        dmlc_indexed_reader_before_first(ir);
      }
      // native skip: land mid-epoch, count only the suffix
      dmlc_indexed_reader_skip(ir, /*epochs=*/2, /*records=*/50);
      CHECK_TRUE(dmlc_indexed_reader_error(ir) == nullptr);
      int64_t rest = 0;
      while (true) {
        void* res = dmlc_indexed_reader_next(ir);
        if (!res) break;
        RecordBatchResult* rb = static_cast<RecordBatchResult*>(res);
        rest += rb->n_records;
        dmlc_free_records(rb);
      }
      CHECK_TRUE(rest == 14);
      dmlc_indexed_reader_destroy(ir);
    }
    remove(rpath);
  }

  // text -> COO: one-shot parse with bucket padding + unit elision, and
  // the streaming reader in COO mode (format 7), all under the sanitizer
  {
    const char* fm = "1 0:10:1 1:20:1\n0 2:30:1\n";
    CooResult* co = dmlc_parse_coo(fm, static_cast<int64_t>(strlen(fm)),
                                   /*nthread=*/2, /*indexing_mode=*/0,
                                   /*fmt=*/3, /*num_col=*/100,
                                   /*row_bucket=*/4, /*nnz_bucket=*/8,
                                   /*elide_unit=*/1, /*csr_wire=*/0);
    CHECK_TRUE(co != nullptr && co->error == nullptr);
    CHECK_TRUE(co->n_rows == 2 && co->nnz == 3);
    CHECK_TRUE(co->rows_padded == 4 && co->nnz_padded == 8);
    CHECK_TRUE(co->values_elided == 1 && co->values == nullptr);
    CHECK_TRUE(co->csr_wire == 0 && co->row_ptr == nullptr);
    CHECK_TRUE(co->coords[0] == 0 && co->coords[1] == 10);
    CHECK_TRUE(co->coords[4] == 1 && co->coords[5] == 30);
    CHECK_TRUE(co->coords[6] == 4 && co->coords[7] == 100);  // OOB pad
    CHECK_TRUE(co->weight[1] == 1.0f && co->weight[2] == 0.0f);
    dmlc_free_coo(co);

    // CSR wire: cols-only coords + row_ptr with pad rows pinned at nnz
    CooResult* cw = dmlc_parse_coo(fm, static_cast<int64_t>(strlen(fm)),
                                   /*nthread=*/2, /*indexing_mode=*/0,
                                   /*fmt=*/3, /*num_col=*/100,
                                   /*row_bucket=*/4, /*nnz_bucket=*/8,
                                   /*elide_unit=*/1, /*csr_wire=*/1);
    CHECK_TRUE(cw != nullptr && cw->error == nullptr);
    CHECK_TRUE(cw->csr_wire == 1 && cw->row_ptr != nullptr);
    CHECK_TRUE(cw->coords[0] == 10 && cw->coords[1] == 20 &&
               cw->coords[2] == 30);
    CHECK_TRUE(cw->coords[3] == 100 && cw->coords[7] == 100);  // OOB pad
    CHECK_TRUE(cw->row_ptr[0] == 0 && cw->row_ptr[1] == 2 &&
               cw->row_ptr[2] == 3);
    CHECK_TRUE(cw->row_ptr[3] == 3 && cw->row_ptr[4] == 3);  // pad rows
    dmlc_free_coo(cw);

    char cpath[] = "/tmp/dmlc_tpu_smoke_coo_XXXXXX";
    int cfd = mkstemp(cpath);
    CHECK_TRUE(cfd >= 0);
    FILE* cf = fdopen(cfd, "w");
    for (int i = 0; i < 500; ++i)
      std::fprintf(cf, "%d 0:%d:1 1:%d:2.5\n", i % 2, i % 97, i % 89);
    long csize;
    fflush(cf);
    csize = ftell(cf);
    fclose(cf);
    const char* cpaths[] = {cpath};
    int64_t csizes[] = {csize};
    void* cr = dmlc_reader_create(cpaths, csizes, 1, 0, 1, /*fmt=*/7,
                                  /*num_col=*/128, 0, ',', 2, 4096, 2, 0,
                                  -1, -1, 0, /*row_bucket=*/64,
                                  /*nnz_bucket=*/256, /*elide_unit=*/1,
                                  /*csr_wire=*/0, /*pack_aux=*/0);
    CHECK_TRUE(cr != nullptr);
    for (int pass = 0; pass < 2; ++pass) {
      int64_t rows = 0, nnz = 0;
      while (true) {
        int32_t fmt = 7;
        void* res = dmlc_reader_next(cr, &fmt);
        if (!res) break;
        CHECK_TRUE(fmt == 7);
        CooResult* blk = static_cast<CooResult*>(res);
        CHECK_TRUE(blk->error == nullptr);
        CHECK_TRUE(blk->values_elided == 0);  // 2.5 values present
        CHECK_TRUE(blk->rows_padded % 64 == 0);
        CHECK_TRUE(blk->nnz_padded % 256 == 0);
        rows += blk->n_rows;
        nnz += blk->nnz;
        dmlc_free_coo(blk);
      }
      CHECK_TRUE(dmlc_reader_error(cr) == nullptr);
      CHECK_TRUE(rows == 500 && nnz == 1000);
      dmlc_reader_before_first(cr);
    }
    dmlc_reader_destroy(cr);
    remove(cpath);
  }

  // buffer pool (memory.h analog): same-size blocks recycle, depth and
  // byte caps hold, trim drains. Recycling checks only apply when the
  // pool is enabled — under DMLC_TPU_POOL=0 (the documented leak-triage
  // mode) every release goes straight to free() by design.
  {
    using dmlc_tpu::dmlc_pool_alloc;
    using dmlc_tpu::dmlc_pool_free;
    using dmlc_tpu::pool_detail::kMaxFreePerSize;
    using dmlc_tpu::pool_detail::kMinPooledBytes;
    const bool pooling = dmlc_tpu::pool_detail::pool().enabled;
    dmlc_tpu::dmlc_pool_trim();
    const size_t big = 1u << 20;
    void* a = dmlc_pool_alloc(big);
    CHECK_TRUE(a != nullptr);
    memset(a, 7, big);  // sanitizers watch the full payload
    dmlc_pool_free(a);
    if (pooling) {
      CHECK_TRUE(dmlc_tpu::dmlc_pool_cached_bytes() == big);
      void* b = dmlc_pool_alloc(big);
      CHECK_TRUE(b == a);  // recycled, not re-mmapped
      CHECK_TRUE(dmlc_tpu::dmlc_pool_cached_bytes() == 0);
      dmlc_pool_free(b);
      dmlc_tpu::dmlc_pool_trim();
    }
    void* small = dmlc_pool_alloc(kMinPooledBytes / 2);  // below threshold
    dmlc_pool_free(small);
    CHECK_TRUE(dmlc_tpu::dmlc_pool_cached_bytes() == 0);
    // per-size depth cap: free more than kMaxFreePerSize blocks of one
    // pooled size, cache stays capped at the configured depth
    const size_t sz = 2 * kMinPooledBytes;
    const size_t n_many = kMaxFreePerSize + 4;
    std::vector<void*> many;
    for (size_t i = 0; i < n_many; ++i) many.push_back(dmlc_pool_alloc(sz));
    for (void* p : many) dmlc_pool_free(p);
    CHECK_TRUE(dmlc_tpu::dmlc_pool_cached_bytes() <=
               kMaxFreePerSize * sz);
    dmlc_tpu::dmlc_pool_trim();
    CHECK_TRUE(dmlc_tpu::dmlc_pool_cached_bytes() == 0);
  }

  CHECK_TRUE(dmlc_native_abi_version() == 17);
  if (failures == 0) std::printf("native_smoke: all checks passed\n");
  return failures == 0 ? 0 : 1;
}

// Native data-plane core: packed-row layout + ragged-batch collation.
//
// Role: the hot host-side loops of the input pipeline (the reference keeps
// its data plane on torch's C++ via torchdata/tokenizers; here the inner
// loops that copy tokens into rows and pad ragged batches are plain C++
// behind ctypes, with the numpy implementations in automodel_tpu/datasets/
// as the semantic reference and the path where there is no compiler).
// Nothing here decides anything: which documents share a packed row is
// decided in Python (packed_sequence.py:best_fit_rows), once, for both
// layouts.  Single-threaded on purpose: dataloading shares one host core
// with the dispatch loop, so memory-bandwidth-efficient tight loops beat
// thread fan-out here.
//
// ABI: C, int32 everywhere (token ids and lengths), row-major buffers
// allocated by the caller (numpy).  Functions return 0 on success.

#include <cstdint>
#include <cstring>

extern "C" {

// Row layout of whole-document packing (split_across_pack=false).  WHICH
// documents share a row is decided in one place,
// automodel_tpu/datasets/llm/packed_sequence.py:best_fit_rows (best fit,
// longest first, inside a window of the dataset); the caller hands the
// documents over already in row order with the number each row holds, and
// this writes the rows: input_ids / labels / position_ids (restarting per
// document) / segment_ids (1-based per document, dense per row; 0 =
// padding), each row padded to pack_size.
//
//   lengths[sum(counts)] : token count of each document, in row order
//   counts[n_rows]       : documents in each row
//   ids, labels          : the documents' tokens, concatenated in row order
//   pack_size            : slots per row
//   pad_id               : fill for input_ids (labels pad with ignore_index)
//   out_*                : [n_rows, pack_size]
//
// Returns 0, or -1 if a row's documents exceed pack_size.
int32_t am_pack_rows(
    const int32_t* lengths, const int32_t* counts, int64_t n_rows,
    const int32_t* ids, const int32_t* labels,
    int64_t pack_size, int32_t pad_id, int32_t ignore_index,
    int32_t* out_ids, int32_t* out_labels,
    int32_t* out_pos, int32_t* out_seg) {
  int64_t doc = 0;          // next document
  int64_t src = 0;          // read offset into ids/labels
  for (int64_t r = 0; r < n_rows; ++r) {
    int32_t* ids_row = out_ids + r * pack_size;
    int32_t* lab_row = out_labels + r * pack_size;
    int32_t* pos_row = out_pos + r * pack_size;
    int32_t* seg_row = out_seg + r * pack_size;
    int64_t fill = 0;       // slots used in this row
    for (int32_t seg = 1; seg <= counts[r]; ++seg, ++doc) {
      const int64_t len = lengths[doc];
      if (len < 0 || fill + len > pack_size) return -1;
      std::memcpy(ids_row + fill, ids + src, len * sizeof(int32_t));
      std::memcpy(lab_row + fill, labels + src, len * sizeof(int32_t));
      for (int64_t i = 0; i < len; ++i) {
        pos_row[fill + i] = static_cast<int32_t>(i);
        seg_row[fill + i] = seg;
      }
      src += len;
      fill += len;
    }
    for (int64_t i = fill; i < pack_size; ++i) {
      ids_row[i] = pad_id;
      lab_row[i] = ignore_index;
      // pad positions keep counting (python layout parity; they are
      // attention-masked via segment 0 either way)
      pos_row[i] = static_cast<int32_t>(i);
      seg_row[i] = 0;
    }
  }
  return 0;
}

// Pad a ragged batch of int32 rows into a [n_rows, max_len] buffer.
// rows are concatenated in `flat` with `lengths` per row; cells beyond a
// row's length are `pad_value`.
int32_t am_collate_pad(
    const int32_t* flat, const int32_t* lengths, int64_t n_rows,
    int64_t max_len, int32_t pad_value, int32_t* out) {
  int64_t src = 0;
  for (int64_t r = 0; r < n_rows; ++r) {
    const int64_t len = lengths[r];
    if (len > max_len) return -1;
    int32_t* row = out + r * max_len;
    std::memcpy(row, flat + src, len * sizeof(int32_t));
    for (int64_t i = len; i < max_len; ++i) row[i] = pad_value;
    src += len;
  }
  return 0;
}

}  // extern "C"

// One-pass host-path kernel for the packed verify hot loop.
//
// Role: after round 8 removed every payload copy from ingest, the packed
// submit/harvest path still spent ~3.6 us/txn in Python/NumPy glue: the
// strided 8B tag gather + dedup query + mask arithmetic on submit
// (disco/pipeline.py submit_packed_rows) and the verdict masking +
// conditional tag insert + per-txn wire tobytes() loop on harvest
// (_finish_rows).  These two entry points fuse each side into a single C
// call per FRAG, reusing the tcache exported by txnparse.cpp (same .so,
// resolved at link) so the dedup window is shared with every other path.
//
// Submit: strided tag gather straight off the dcache row view + one
// fd_tcache_query_batch over the transactions' first rows (QUERY only —
// tags are inserted at harvest iff the txn verifies, the
// FD_TCACHE_INSERT-at-publish contract).
//
// Harvest: verdict masking (every row of a txn ok, & !dup & live),
// conditional fd_tcache_insert_batch_dedup over the passing tags, and wire
// reconstruction (k | sig_0[64] .. sig_k-1[64] | msg[len], via per-row
// memcpy) into a caller-provided arena with an offsets table.  The arena
// is sized by the caller; if the passing wires do not fit, the call
// returns -(needed bytes) WITHOUT touching the tcache so the caller can
// grow the arena and retry with identical semantics.
//
// C ABI (ctypes): flat arrays only.  Row layout (disco/dcache.py packed
// rows): msg[ml] | sig[64] | pub[32] | len_le32[4]; dedup tag = low 64
// bits of the signature = row[ml:ml+8] LE; tag 0 marks a dead lane.
//
// The len word also marks the row's transaction (tango/ring.py): bits
// 0-15 the message length, 16-23 the row's signature index i, 24-31 the
// transaction's signature count less one.  A transaction of k signatures
// is k contiguous rows, each holding the whole message; its first row has
// i = 0 and runs to the next such row.  Its first row's tag is its dedup
// tag, and it passes only if it has k rows and every one of them passes.
// A frame of single-signature rows has words with zero high bits.

#include <cstdint>
#include <cstring>

#define API extern "C" __attribute__((visibility("default")))

// txnparse.cpp exports (same shared library)
extern "C" void fd_tcache_query_batch(void *h, const uint64_t *tags, int n,
                                      uint8_t *hit);
extern "C" void fd_tcache_insert_batch_dedup(void *h, const uint64_t *tags,
                                             int n, uint8_t *dup);

namespace {

constexpr int kSigSz = 64;
constexpr int kLenOff = kSigSz + 32;  // len_le32 sits after sig|pub
constexpr int kMaxBatch = 1 << 16;    // passing-set scratch bound per frag

inline uint64_t row_tag(const uint8_t *row, int ml) {
  uint64_t t;
  std::memcpy(&t, row + ml, 8);  // low 64 bits of sig, LE host
  return t;
}

inline uint32_t row_word(const uint8_t *row, int ml) {
  uint32_t w;
  std::memcpy(&w, row + ml + kLenOff, 4);
  return w;
}

inline int word_len(uint32_t w, int ml) {
  // defensive clamp: a torn/garbage row must not drive memcpy off the lane
  int l = (int)(w & 0xFFFFu);
  return l > ml ? ml : l;
}

inline int word_idx(uint32_t w) { return (int)((w >> 16) & 0xFFu); }
inline int word_nsig(uint32_t w) { return (int)(w >> 24) + 1; }

}  // namespace

// Submit side: gather the dedup tag of every lane (strided — `rows` is a
// dcache view whose row pitch is the bucket stride, not ml+100) and run
// one batched tcache QUERY over the transactions' first rows.  tag_out[i]
// and dup_out[i] are those of row i's transaction (tag 0 = dead lane, as
// are rows before the first transaction's); dup = its tag is already in
// the dedup window.  counts = {transactions, transactions of two or more
// signatures, message bytes of the n rows, dup transactions}.  Returns the
// dup count.  tcache may be null (dedup off): dup_out zeroed.
API int64_t fd_hostpath_submit_rows(const uint8_t *rows, int64_t row_stride,
                                    int n, int ml, void *tcache,
                                    uint64_t *tag_out, uint8_t *dup_out,
                                    int64_t *counts) {
  counts[0] = counts[1] = counts[2] = counts[3] = 0;
  // n is a frag's meta.sz, a u16: below kMaxBatch
  if (n <= 0 || n > kMaxBatch) return 0;
  int64_t bytes = 0;
  uint32_t marked = 0;
  for (int i = 0; i < n; i++) {
    const uint8_t *row = rows + (int64_t)i * row_stride;
    uint32_t w = row_word(row, ml);
    tag_out[i] = row_tag(row, ml);
    bytes += w & 0xFFFFu;
    marked |= w >> 16;
  }
  counts[2] = bytes;
  if (!marked) {  // every row its own transaction
    counts[0] = n;
    if (!tcache) {
      std::memset(dup_out, 0, (size_t)n);
      return 0;
    }
    fd_tcache_query_batch(tcache, tag_out, n, dup_out);
    int64_t ndup = 0;
    for (int i = 0; i < n; i++) ndup += dup_out[i];
    counts[3] = ndup;
    return ndup;
  }
  static thread_local uint64_t first_tag[kMaxBatch];
  static thread_local uint8_t first_dup[kMaxBatch];
  int m = 0;
  int64_t multi = 0;
  for (int i = 0; i < n; i++) {
    uint32_t w = row_word(rows + (int64_t)i * row_stride, ml);
    if (word_idx(w)) continue;
    first_tag[m++] = tag_out[i];
    multi += word_nsig(w) > 1;
  }
  if (tcache && m)
    fd_tcache_query_batch(tcache, first_tag, m, first_dup);
  else
    std::memset(first_dup, 0, (size_t)m);
  int64_t ndup = 0;
  for (int j = 0; j < m; j++) ndup += first_dup[j];
  int j = -1;
  for (int i = 0; i < n; i++) {
    if (!word_idx(row_word(rows + (int64_t)i * row_stride, ml))) j++;
    tag_out[i] = j < 0 ? 0 : first_tag[j];
    dup_out[i] = j < 0 ? 0 : first_dup[j];
  }
  counts[0] = m;
  counts[1] = multi;
  counts[3] = ndup;
  return ndup;
}

// Harvest side: one pass over the verdict, one transaction at a time.
// Inputs are the submit-time tag/dup arrays plus the device verdict ok[i]
// (1 = signature valid).
//
//   live    = tag != 0 & !dup                   (of the first row)
//   pass    = has its k rows & ok on every row
//   passing = live & pass                       (candidates for publish)
//   vfail   = live & !pass                      (counted, never published)
//
// Passing tags are inserted via fd_tcache_insert_batch_dedup (dup2[j]=1
// iff already present, including earlier transactions of the same batch —
// those are dropped as harvest-time dups).  Survivor wires are written
// back-to-back into `arena`, byte for byte as sent:  arena[offs[j] ..
// offs[j+1]] = k | sig_0[64] .. sig_k-1[64] | msg[len_j] (k < 128 is its
// one-byte compact-u16), with offs having k+1 entries and keep_tag[j] the
// survivor's tag.  counts = {verify_fail, dup2_drops, passing}.  Returns
// the survivor count, or -(needed bytes) if arena_cap is too small — in
// that case NOTHING was inserted into the tcache and the call can be
// retried verbatim with a larger arena.
API int64_t fd_hostpath_finish_rows(const uint8_t *rows, int64_t row_stride,
                                    int n, int ml, const uint8_t *ok,
                                    const uint64_t *tag, const uint8_t *dup,
                                    void *tcache, uint8_t *arena,
                                    int64_t arena_cap, int64_t *offs,
                                    uint64_t *keep_tag, int64_t *counts) {
  counts[0] = counts[1] = counts[2] = 0;
  if (n <= 0 || n > kMaxBatch) {
    offs[0] = 0;
    return n <= 0 ? 0 : -1;
  }

  static thread_local int pass_idx[kMaxBatch];
  static thread_local int pass_nsig[kMaxBatch];
  static thread_local uint64_t pass_tag[kMaxBatch];
  static thread_local uint8_t dup2[kMaxBatch];

  int np = 0;
  int64_t vfail = 0, need = 0;
  int r = 0;
  while (r < n) {
    uint32_t w = row_word(rows + (int64_t)r * row_stride, ml);
    if (word_idx(w)) {  // before the first transaction: no transaction
      r++;
      continue;
    }
    int e = r + 1;
    while (e < n && word_idx(row_word(rows + (int64_t)e * row_stride, ml)))
      e++;
    if (tag[r] && !dup[r]) {  // not a dead lane or a submit-time dup
      bool pass = e - r == word_nsig(w);
      for (int i = r; i < e; i++) pass = pass && ok[i];
      if (!pass) {
        vfail++;
      } else {
        pass_idx[np] = r;
        pass_nsig[np] = e - r;
        pass_tag[np] = tag[r];
        np++;
        need += 1 + (int64_t)kSigSz * (e - r) + word_len(w, ml);
      }
    }
    r = e;
  }
  counts[0] = vfail;
  counts[2] = np;
  if (need > arena_cap) return -need;  // tcache untouched: retry-safe

  if (tcache && np)
    fd_tcache_insert_batch_dedup(tcache, pass_tag, np, dup2);
  else
    std::memset(dup2, 0, (size_t)np);

  int64_t k = 0, o = 0;
  offs[0] = 0;
  for (int j = 0; j < np; j++) {
    if (dup2[j]) continue;  // harvest-time dup (raced within the window)
    const uint8_t *row = rows + (int64_t)pass_idx[j] * row_stride;
    int nsig = pass_nsig[j];
    int len = word_len(row_word(row, ml), ml);
    arena[o++] = (uint8_t)nsig;
    for (int i = 0; i < nsig; i++, o += kSigSz)
      std::memcpy(arena + o, row + (int64_t)i * row_stride + ml, kSigSz);
    std::memcpy(arena + o, row, (size_t)len);
    o += len;
    keep_tag[k] = pass_tag[j];
    offs[++k] = o;
  }
  counts[1] = np - k;
  return k;
}

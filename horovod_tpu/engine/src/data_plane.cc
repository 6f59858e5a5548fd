#include "data_plane.h"

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstdlib>
#include <cstring>
#include <map>
#include <vector>

#include "half.h"
#include "message.h"

namespace hvdtpu {

namespace {

template <typename T>
void CombineTyped(T* acc, const T* src, int64_t n, ReduceKind kind) {
  switch (kind) {
    case ReduceKind::SUM:
    case ReduceKind::AVERAGE:
      for (int64_t i = 0; i < n; ++i) acc[i] += src[i];
      break;
    case ReduceKind::MIN:
      for (int64_t i = 0; i < n; ++i) acc[i] = std::min(acc[i], src[i]);
      break;
    case ReduceKind::MAX:
      for (int64_t i = 0; i < n; ++i) acc[i] = std::max(acc[i], src[i]);
      break;
    case ReduceKind::PRODUCT:
      for (int64_t i = 0; i < n; ++i) acc[i] *= src[i];
      break;
    case ReduceKind::ADASUM:
      break;  // handled separately
  }
}

// Hot-path half/bf16 combine: blocked bulk convert to fp32 (F16C or
// branch-free autovectorized loops, half.cc), a tight fused reduce the
// compiler vectorizes, bulk convert back. The reduce switch is hoisted to
// block granularity — the inner loops carry no branches.
void CombineHalf(uint16_t* acc, const uint16_t* src, int64_t n,
                 ReduceKind kind, bool bf16) {
  if (kind == ReduceKind::SUM || kind == ReduceKind::AVERAGE) {
    // The hot case delegates to the ONE blocked sum kernel (half.cc) the
    // compression paths also use — one implementation to fix, not three.
    if (bf16) {
      Bfloat16SumInto(acc, src, static_cast<size_t>(n));
    } else {
      HalfSumInto(acc, src, static_cast<size_t>(n));
    }
    return;
  }
  constexpr int64_t kBlock = 2048;  // 2 x 8 KB fp32 staging: L1-resident
  float a[kBlock], b[kBlock];
  for (int64_t base = 0; base < n; base += kBlock) {
    const int64_t m = std::min(kBlock, n - base);
    if (bf16) {
      Bfloat16ToFloatN(acc + base, a, m);
      Bfloat16ToFloatN(src + base, b, m);
    } else {
      HalfToFloatN(acc + base, a, m);
      HalfToFloatN(src + base, b, m);
    }
    switch (kind) {
      case ReduceKind::SUM:
      case ReduceKind::AVERAGE:
        break;  // handled above
      case ReduceKind::MIN:
        for (int64_t i = 0; i < m; ++i) a[i] = std::min(a[i], b[i]);
        break;
      case ReduceKind::MAX:
        for (int64_t i = 0; i < m; ++i) a[i] = std::max(a[i], b[i]);
        break;
      case ReduceKind::PRODUCT:
        for (int64_t i = 0; i < m; ++i) a[i] *= b[i];
        break;
      case ReduceKind::ADASUM:
        break;  // handled separately
    }
    if (bf16) {
      FloatToBfloat16N(a, acc + base, m);
    } else {
      FloatToHalfN(a, acc + base, m);
    }
  }
}

void Combine(void* acc, const void* src, int64_t n, DataType dtype,
             ReduceKind kind) {
  switch (dtype) {
    case DataType::FLOAT32:
      CombineTyped(static_cast<float*>(acc),
                   static_cast<const float*>(src), n, kind);
      break;
    case DataType::FLOAT64:
      CombineTyped(static_cast<double*>(acc),
                   static_cast<const double*>(src), n, kind);
      break;
    case DataType::INT32:
      CombineTyped(static_cast<int32_t*>(acc),
                   static_cast<const int32_t*>(src), n, kind);
      break;
    case DataType::INT64:
      CombineTyped(static_cast<int64_t*>(acc),
                   static_cast<const int64_t*>(src), n, kind);
      break;
    case DataType::UINT8:
      CombineTyped(static_cast<uint8_t*>(acc),
                   static_cast<const uint8_t*>(src), n, kind);
      break;
    case DataType::INT8:
      CombineTyped(static_cast<int8_t*>(acc),
                   static_cast<const int8_t*>(src), n, kind);
      break;
    case DataType::UINT16:
      CombineTyped(static_cast<uint16_t*>(acc),
                   static_cast<const uint16_t*>(src), n, kind);
      break;
    case DataType::INT16:
      CombineTyped(static_cast<int16_t*>(acc),
                   static_cast<const int16_t*>(src), n, kind);
      break;
    case DataType::FLOAT16:
      CombineHalf(static_cast<uint16_t*>(acc),
                  static_cast<const uint16_t*>(src), n, kind, false);
      break;
    case DataType::BFLOAT16:
      CombineHalf(static_cast<uint16_t*>(acc),
                  static_cast<const uint16_t*>(src), n, kind, true);
      break;
    case DataType::BOOL:
      // logical OR for sum-like, AND for min/product
      CombineTyped(static_cast<uint8_t*>(acc),
                   static_cast<const uint8_t*>(src), n, kind);
      break;
  }
}

// Convert any float dtype to a double working vector (Adasum + scaling).
void ToDouble(const void* src, int64_t n, DataType dtype, double* out) {
  switch (dtype) {
    case DataType::FLOAT32: {
      auto* p = static_cast<const float*>(src);
      for (int64_t i = 0; i < n; ++i) out[i] = p[i];
      break;
    }
    case DataType::FLOAT64:
      std::memcpy(out, src, n * sizeof(double));
      break;
    case DataType::FLOAT16: {
      auto* p = static_cast<const uint16_t*>(src);
      for (int64_t i = 0; i < n; ++i) out[i] = HalfToFloat(p[i]);
      break;
    }
    case DataType::BFLOAT16: {
      auto* p = static_cast<const uint16_t*>(src);
      for (int64_t i = 0; i < n; ++i) out[i] = Bfloat16ToFloat(p[i]);
      break;
    }
    default:
      break;
  }
}

void FromDouble(const double* src, int64_t n, DataType dtype, void* out) {
  switch (dtype) {
    case DataType::FLOAT32: {
      auto* p = static_cast<float*>(out);
      for (int64_t i = 0; i < n; ++i) p[i] = static_cast<float>(src[i]);
      break;
    }
    case DataType::FLOAT64:
      std::memcpy(out, src, n * sizeof(double));
      break;
    case DataType::FLOAT16: {
      auto* p = static_cast<uint16_t*>(out);
      for (int64_t i = 0; i < n; ++i) {
        p[i] = FloatToHalf(static_cast<float>(src[i]));
      }
      break;
    }
    case DataType::BFLOAT16: {
      auto* p = static_cast<uint16_t*>(out);
      for (int64_t i = 0; i < n; ++i) {
        p[i] = FloatToBfloat16(static_cast<float>(src[i]));
      }
      break;
    }
    default:
      break;
  }
}

bool IsFloatType(DataType dtype) {
  return dtype == DataType::FLOAT16 || dtype == DataType::BFLOAT16 ||
         dtype == DataType::FLOAT32 || dtype == DataType::FLOAT64;
}

template <typename T>
void ScaleTyped(T* p, int64_t n, double factor) {
  for (int64_t i = 0; i < n; ++i) {
    p[i] = static_cast<T>(p[i] * factor);
  }
}

void ScaleBuffer(void* buf, int64_t n, DataType dtype, double factor) {
  if (factor == 1.0) return;
  if (IsFloatType(dtype)) {
    std::vector<double> tmp(n);
    ToDouble(buf, n, dtype, tmp.data());
    for (auto& v : tmp) v *= factor;
    FromDouble(tmp.data(), n, dtype, buf);
    return;
  }
  switch (dtype) {
    case DataType::INT32:
      ScaleTyped(static_cast<int32_t*>(buf), n, factor);
      break;
    case DataType::INT64:
      ScaleTyped(static_cast<int64_t*>(buf), n, factor);
      break;
    case DataType::INT16:
      ScaleTyped(static_cast<int16_t*>(buf), n, factor);
      break;
    case DataType::UINT16:
      ScaleTyped(static_cast<uint16_t*>(buf), n, factor);
      break;
    case DataType::INT8:
      ScaleTyped(static_cast<int8_t*>(buf), n, factor);
      break;
    case DataType::UINT8:
    case DataType::BOOL:
      ScaleTyped(static_cast<uint8_t*>(buf), n, factor);
      break;
    default:
      break;
  }
}

// Pairwise Adasum combine over double vectors
// (reference math: adasum.h — a' = (1 - a.b/2||a||²)a + (1 - a.b/2||b||²)b).
void AdasumPair(std::vector<double>& a, const std::vector<double>& b) {
  double dot = 0, na = 0, nb = 0;
  for (size_t i = 0; i < a.size(); ++i) {
    dot += a[i] * b[i];
    na += a[i] * a[i];
    nb += b[i] * b[i];
  }
  double ac = na == 0 ? 1.0 : 1.0 - dot / (2.0 * na);
  double bc = nb == 0 ? 1.0 : 1.0 - dot / (2.0 * nb);
  for (size_t i = 0; i < a.size(); ++i) a[i] = ac * a[i] + bc * b[i];
}

}  // namespace

DataPlane::DataPlane(std::shared_ptr<ControllerTransport> transport)
    : transport_(std::move(transport)) {
  // Below this, star latency wins; above it, ring bandwidth wins
  // (reference knob analog: HOROVOD_FUSION_THRESHOLD sizing). This env
  // read is only the session seed: the engine re-applies routing via
  // SetRouting from the cycle-fenced TunedParams broadcast, so the tuner
  // can move the threshold at a cycle boundary on every rank at once.
  ring_threshold_ = 1 << 20;
  if (const char* env = std::getenv("HOROVOD_RING_THRESHOLD_BYTES")) {
    if (*env) ring_threshold_ = std::atoll(env);
  }
  if (const char* env = std::getenv("HOROVOD_DATA_FAULT_INJECT")) {
    const std::string faults(env);
    fault_truncate_star_allgatherv_ =
        faults.find("truncate_star_allgatherv") != std::string::npos;
    fault_truncate_ring_alltoallv_ =
        faults.find("truncate_ring_alltoallv") != std::string::npos;
    fault_truncate_rd_bundle_ =
        faults.find("truncate_rd_bundle") != std::string::npos;
    fault_truncate_hier_chunk_ =
        faults.find("truncate_hier_chunk") != std::string::npos;
    fault_truncate_hier_allgather_ =
        faults.find("truncate_hier_allgather") != std::string::npos;
  }
}

Status DataPlane::EnsureTopology() {
  if (topology_ready_ || host_id_ < 0 || transport_->size() == 1) {
    return Status::OK();
  }
  // 8 bytes/rank on the star, once per session. All ranks hit their first
  // data-plane op in lockstep (response order is globally agreed), so the
  // exchange is uniformly placed — and sessions without host ids skip it
  // entirely, keeping their wire traffic (and fault-injection frame
  // numbering) byte-identical to before.
  std::vector<int64_t> ids;
  auto st = ExchangeInt64(host_id_, &ids);
  if (!st.ok()) return st;
  host_ids_.assign(ids.begin(), ids.end());
  std::map<int32_t, std::vector<int>> groups;
  for (int r = 0; r < transport_->size(); ++r) {
    groups[host_ids_[r]].push_back(r);
  }
  host_groups_.clear();
  for (auto& kv : groups) host_groups_.push_back(kv.second);
  topology_ready_ = true;
  return Status::OK();
}

void DataPlane::CountWire(int dst, int64_t nbytes) {
  if (metrics_ == nullptr || nbytes <= 0) return;
  const bool inter = topology_ready_ &&
                     host_ids_[dst] != host_ids_[transport_->rank()];
  auto& c = inter ? metrics_->data_interhost_bytes
                  : metrics_->data_intrahost_bytes;
  c.fetch_add(nbytes, std::memory_order_relaxed);
}

Status DataPlane::CanonicalReduce(
    const std::vector<std::string>& contributions, int64_t num_elements,
    DataType dtype, ReduceKind kind, void* out) const {
  const int size = transport_->size();
  const int64_t nbytes = num_elements * DataTypeSize(dtype);
  for (int r = 0; r < size; ++r) {
    if (static_cast<int64_t>(contributions[r].size()) != nbytes) {
      return Status::Unknown(
          "canonical reduce contribution size mismatch (rank " +
          std::to_string(r) + ": " +
          std::to_string(contributions[r].size()) + " bytes, expected " +
          std::to_string(nbytes) + ")");
    }
  }
  if (!topology_ready_ || host_groups_.size() <= 1) {
    // Flat: the historical sequential rank-order chain — single-host
    // results stay bit-identical across versions.
    std::memcpy(out, contributions[0].data(), nbytes);
    for (int r = 1; r < size; ++r) {
      Combine(out, contributions[r].data(), num_elements, dtype, kind);
    }
    return Status::OK();
  }
  // Two-level canonical order: per-host partials folded in local rank
  // order, then host partials folded in host-id order — exactly the chain
  // the hierarchical route computes, so star == rd == hier bit-for-bit.
  std::string partial;
  bool first_host = true;
  for (const auto& group : host_groups_) {
    partial.assign(contributions[group[0]]);
    for (size_t i = 1; i < group.size(); ++i) {
      Combine(&partial[0], contributions[group[i]].data(), num_elements,
              dtype, kind);
    }
    if (first_host) {
      std::memcpy(out, partial.data(), nbytes);
      first_host = false;
    } else {
      Combine(out, partial.data(), num_elements, dtype, kind);
    }
  }
  return Status::OK();
}

Status DataPlane::RingAllreduce(void* buffer, int64_t num_elements,
                                DataType dtype, ReduceKind kind) {
  const int size = transport_->size();
  const int rank = transport_->rank();
  const int64_t es = DataTypeSize(dtype);
  char* buf = static_cast<char*>(buffer);
  // chunk c covers counts[c] elements at offs[c]
  std::vector<int64_t> counts(size), offs(size);
  const int64_t base = num_elements / size;
  const int64_t rem = num_elements % size;
  int64_t off = 0;
  for (int c = 0; c < size; ++c) {
    counts[c] = base + (c < rem ? 1 : 0);
    offs[c] = off;
    off += counts[c];
  }
  // reduce-scatter: after step s each rank's chunk (rank-s-1) holds s+2
  // contributions; rank ends owning fully-reduced chunk (rank+1)%size
  const int next = (rank + 1) % size;
  std::string incoming;
  for (int s = 0; s < size - 1; ++s) {
    const int sc = ((rank - s) % size + size) % size;
    const int rc = ((rank - s - 1) % size + size) % size;
    CountWire(next, counts[sc] * es);
    auto st = transport_->RingExchange(buf + offs[sc] * es, counts[sc] * es,
                                       &incoming);
    if (!st.ok()) return st;
    Combine(buf + offs[rc] * es, incoming.data(), counts[rc], dtype, kind);
  }
  // allgather: circulate the reduced chunks
  for (int s = 0; s < size - 1; ++s) {
    const int sc = ((rank + 1 - s) % size + size) % size;
    const int rc = ((rank - s) % size + size) % size;
    CountWire(next, counts[sc] * es);
    auto st = transport_->RingExchange(buf + offs[sc] * es, counts[sc] * es,
                                       &incoming);
    if (!st.ok()) return st;
    std::memcpy(buf + offs[rc] * es, incoming.data(), counts[rc] * es);
  }
  ++ring_ops_;
  return Status::OK();
}

namespace {

// Even chunk partition with the remainder spread over the first chunks
// (the ring allreduce's layout, reused by the hierarchical phases).
void PartitionElements(int64_t num_elements, int parts,
                       std::vector<int64_t>* counts,
                       std::vector<int64_t>* offs) {
  counts->assign(parts, 0);
  offs->assign(parts, 0);
  const int64_t base = num_elements / parts;
  const int64_t rem = num_elements % parts;
  int64_t off = 0;
  for (int c = 0; c < parts; ++c) {
    (*counts)[c] = base + (c < rem ? 1 : 0);
    (*offs)[c] = off;
    off += (*counts)[c];
  }
}

}  // namespace

Status DataPlane::RecursiveDoublingAllreduce(void* buffer,
                                             int64_t num_elements,
                                             DataType dtype,
                                             ReduceKind kind) {
  // Latency route: a distance-doubling allgather of rank-tagged RAW
  // contributions (log2(p) pairwise exchanges, no rank-0 hub), then ONE
  // local reduction in the canonical order — bit-exact with the star.
  // Wire cost is (p-1)*nbytes per rank, fine for the sub-express-lane
  // payloads this route is gated to; the win is the critical path:
  // log2(p) pairwise hops instead of p-1 serialized receives at rank 0.
  //
  // Bundle wire format (validated before use — a truncated or corrupt
  // frame must fail the op, not hand the reducer garbage):
  //   [u32 count][count x i32 rank][count x payload(nbytes each)]
  const int size = transport_->size();
  const int rank = transport_->rank();
  const int64_t nbytes = num_elements * DataTypeSize(dtype);
  std::vector<std::string> contrib(size);
  std::vector<bool> have(size, false);
  contrib[rank].assign(static_cast<const char*>(buffer), nbytes);
  have[rank] = true;

  int m = 1;
  while (m * 2 <= size) m *= 2;
  const int extra = size - m;  // ranks [m, size) fold into [0, extra)

  auto pack = [&](std::string* wire) {
    uint32_t count = 0;
    for (int r = 0; r < size; ++r) count += have[r] ? 1 : 0;
    wire->clear();
    wire->reserve(sizeof(count) + count * (sizeof(int32_t) + nbytes));
    wire->append(reinterpret_cast<const char*>(&count), sizeof(count));
    for (int r = 0; r < size; ++r) {
      if (!have[r]) continue;
      int32_t r32 = r;
      wire->append(reinterpret_cast<const char*>(&r32), sizeof(r32));
    }
    for (int r = 0; r < size; ++r) {
      if (have[r]) wire->append(contrib[r]);
    }
    if (fault_truncate_rd_bundle_ && !wire->empty()) {
      wire->pop_back();  // test-only: exercise the receiver's size check
    }
  };
  auto merge = [&](const std::string& in) -> Status {
    uint32_t count = 0;
    if (in.size() < sizeof(count)) {
      return Status::Unknown("recursive-doubling bundle truncated");
    }
    std::memcpy(&count, in.data(), sizeof(count));
    if (count == 0 || count > static_cast<uint32_t>(size)) {
      return Status::Unknown("recursive-doubling bundle corrupt count " +
                             std::to_string(count));
    }
    const size_t expected =
        sizeof(count) +
        static_cast<size_t>(count) * (sizeof(int32_t) + nbytes);
    if (in.size() != expected) {
      return Status::Unknown(
          "recursive-doubling bundle size mismatch (" +
          std::to_string(in.size()) + " bytes, expected " +
          std::to_string(expected) + " for " + std::to_string(count) +
          " contributions)");
    }
    const char* ranks_p = in.data() + sizeof(count);
    const char* data_p = ranks_p + count * sizeof(int32_t);
    for (uint32_t i = 0; i < count; ++i) {
      int32_t r = 0;
      std::memcpy(&r, ranks_p + i * sizeof(int32_t), sizeof(r));
      if (r < 0 || r >= size || have[r]) {
        return Status::Unknown(
            "recursive-doubling bundle corrupt contribution rank " +
            std::to_string(r));
      }
      contrib[r].assign(data_p + static_cast<size_t>(i) * nbytes, nbytes);
      have[r] = true;
    }
    return Status::OK();
  };

  std::string wire, incoming;
  if (rank >= m) {
    // Fold-in pre-step: ship the contribution to the core partner, then
    // wait for the fully-reduced vector (post-step).
    pack(&wire);
    CountWire(rank - m, static_cast<int64_t>(wire.size()));
    auto st = transport_->PeerSend(rank - m, wire.data(), wire.size());
    if (!st.ok()) return st;
    st = transport_->PeerRecv(rank - m, &incoming);
    if (!st.ok()) return st;
    if (static_cast<int64_t>(incoming.size()) != nbytes) {
      return Status::Unknown(
          "recursive-doubling fold-in result size mismatch (" +
          std::to_string(incoming.size()) + " bytes, expected " +
          std::to_string(nbytes) + ")");
    }
    std::memcpy(buffer, incoming.data(), nbytes);
    ++rd_ops_;
    return Status::OK();
  }
  if (rank < extra) {
    auto st = transport_->PeerRecv(rank + m, &incoming);
    if (!st.ok()) return st;
    st = merge(incoming);
    if (!st.ok()) return st;
  }
  for (int dist = 1; dist < m; dist <<= 1) {
    const int partner = rank ^ dist;
    pack(&wire);
    CountWire(partner, static_cast<int64_t>(wire.size()));
    auto st = transport_->PeerExchange(partner, wire.data(), wire.size(),
                                       &incoming);
    if (!st.ok()) return st;
    st = merge(incoming);
    if (!st.ok()) return st;
  }
  for (int r = 0; r < size; ++r) {
    if (!have[r]) {
      return Status::Unknown(
          "recursive doubling left missing contribution from rank " +
          std::to_string(r));
    }
  }
  auto st = CanonicalReduce(contrib, num_elements, dtype, kind, buffer);
  if (!st.ok()) return st;
  if (rank < extra) {
    CountWire(rank + m, nbytes);
    st = transport_->PeerSend(rank + m, buffer, nbytes);
    if (!st.ok()) return st;
  }
  ++rd_ops_;
  return Status::OK();
}

Status DataPlane::HierarchicalAllreduce(void* buffer, int64_t num_elements,
                                        DataType dtype, ReduceKind kind) {
  // Two-level route (arXiv:1810.11112): only the leaders' phase crosses
  // hosts, so inter-host wire bytes shrink by roughly the local fan-in
  // vs any flat algorithm whose links cross host boundaries. Reduction
  // order is the canonical order (intra-host chains in local rank order,
  // hosts folded in host-id order) — bit-exact with the star/rd paths.
  const int rank = transport_->rank();
  const int64_t es = DataTypeSize(dtype);
  const int64_t nbytes = num_elements * es;
  char* buf = static_cast<char*>(buffer);
  const int H = static_cast<int>(host_groups_.size());
  int h = -1, j = -1;
  for (int hi = 0; hi < H && h < 0; ++hi) {
    for (size_t idx = 0; idx < host_groups_[hi].size(); ++idx) {
      if (host_groups_[hi][idx] == rank) {
        h = hi;
        j = static_cast<int>(idx);
        break;
      }
    }
  }
  if (h < 0) return Status::Unknown("rank missing from locality map");
  const std::vector<int>& g = host_groups_[h];
  const int L = static_cast<int>(g.size());
  std::vector<int64_t> counts_l, offs_l;
  PartitionElements(num_elements, L, &counts_l, &offs_l);
  std::string incoming;

  // Phase 1 — intra-host pairwise reduce-scatter of RAW contributions
  // (round t is a cyclic shift: send chunk (j+t) to member j+t, receive
  // our chunk from member j-t — a permutation per round, deadlock-free).
  // Raw chunks let the owner reduce in exact local rank order.
  std::vector<std::string> raw(L);
  for (int t = 1; t < L; ++t) {
    const int si = (j + t) % L;
    const int ri = (j - t + L) % L;
    int64_t send_len = counts_l[si] * es;
    if (fault_truncate_hier_chunk_ && t == 1 && send_len > 0) {
      --send_len;  // test-only: exercise the receiver's size check
    }
    CountWire(g[si], send_len);
    auto st = transport_->PeerShift(g[si], g[ri], buf + offs_l[si] * es,
                                    send_len, &incoming);
    if (!st.ok()) return st;
    if (static_cast<int64_t>(incoming.size()) != counts_l[j] * es) {
      return Status::Unknown(
          "hierarchical intra-host chunk size mismatch (" +
          std::to_string(incoming.size()) + " bytes from local rank " +
          std::to_string(ri) + ", expected " +
          std::to_string(counts_l[j] * es) + ")");
    }
    raw[ri] = std::move(incoming);
  }
  // Reduce my chunk j over the host's members in local rank order.
  auto local_src = [&](int i) -> const char* {
    return i == j ? buf + offs_l[j] * es : raw[i].data();
  };
  std::string accj(local_src(0), counts_l[j] * es);
  for (int i = 1; i < L; ++i) {
    Combine(&accj[0], local_src(i), counts_l[j], dtype, kind);
  }

  // Phase 2 — chunk gather to the local leader (g[0]), assembling the
  // full host-partial vector there. Leaders are required (not per-chunk
  // owners) because hosts may have UNEVEN local sizes (3+5): their chunk
  // partitions don't align across hosts, but full vectors at leaders do.
  std::string partial;
  if (j == 0) {
    partial.resize(nbytes);
    std::memcpy(&partial[offs_l[0] * es], accj.data(), accj.size());
    for (int i = 1; i < L; ++i) {
      auto st = transport_->PeerRecv(g[i], &incoming);
      if (!st.ok()) return st;
      if (static_cast<int64_t>(incoming.size()) != counts_l[i] * es) {
        return Status::Unknown(
            "hierarchical leader-gather chunk size mismatch (" +
            std::to_string(incoming.size()) + " bytes from local rank " +
            std::to_string(i) + ", expected " +
            std::to_string(counts_l[i] * es) + ")");
      }
      std::memcpy(&partial[offs_l[i] * es], incoming.data(),
                  incoming.size());
    }
  } else {
    CountWire(g[0], static_cast<int64_t>(accj.size()));
    auto st = transport_->PeerSend(g[0], accj.data(), accj.size());
    if (!st.ok()) return st;
  }

  // Phase 3 — inter-host allreduce among the H leaders: pairwise
  // reduce-scatter of raw host partials (chunked by H, reduced in host-id
  // order), then a chunk allgather — ring above the ring threshold,
  // recursive-doubling (latency-optimal) below it.
  if (j == 0 && H > 1) {
    std::vector<int> leaders(H);
    for (int hi = 0; hi < H; ++hi) leaders[hi] = host_groups_[hi][0];
    std::vector<int64_t> counts_h, offs_h;
    PartitionElements(num_elements, H, &counts_h, &offs_h);
    std::vector<std::string> raw_h(H);
    for (int t = 1; t < H; ++t) {
      const int sh = (h + t) % H;
      const int rh = (h - t + H) % H;
      CountWire(leaders[sh], counts_h[sh] * es);
      auto st = transport_->PeerShift(leaders[sh], leaders[rh],
                                      partial.data() + offs_h[sh] * es,
                                      counts_h[sh] * es, &incoming);
      if (!st.ok()) return st;
      if (static_cast<int64_t>(incoming.size()) != counts_h[h] * es) {
        return Status::Unknown(
            "hierarchical inter-host chunk size mismatch (" +
            std::to_string(incoming.size()) + " bytes from host " +
            std::to_string(rh) + ", expected " +
            std::to_string(counts_h[h] * es) + ")");
      }
      raw_h[rh] = std::move(incoming);
    }
    auto host_src = [&](int i) -> const char* {
      return i == h ? partial.data() + offs_h[h] * es : raw_h[i].data();
    };
    std::string acch(host_src(0), counts_h[h] * es);
    for (int i = 1; i < H; ++i) {
      Combine(&acch[0], host_src(i), counts_h[h], dtype, kind);
    }
    std::memcpy(&partial[offs_h[h] * es], acch.data(), acch.size());
    if (nbytes >= ring_threshold_) {
      // Ring allgather around the leader circle (bandwidth regime).
      const int lnext = leaders[(h + 1) % H];
      const int lprev = leaders[(h - 1 + H) % H];
      for (int t = 0; t < H - 1; ++t) {
        const int sc = (h - t + H) % H;
        const int rc = (h - t - 1 + H) % H;
        CountWire(lnext, counts_h[sc] * es);
        auto st = transport_->PeerShift(lnext, lprev,
                                        partial.data() + offs_h[sc] * es,
                                        counts_h[sc] * es, &incoming);
        if (!st.ok()) return st;
        if (static_cast<int64_t>(incoming.size()) != counts_h[rc] * es) {
          return Status::Unknown(
              "hierarchical leader-allgather chunk size mismatch (" +
              std::to_string(incoming.size()) + " bytes, expected " +
              std::to_string(counts_h[rc] * es) + ")");
        }
        std::memcpy(&partial[offs_h[rc] * es], incoming.data(),
                    incoming.size());
      }
    } else {
      // Recursive-doubling allgather of host-tagged chunks (latency
      // regime): log2(H) bundle exchanges, fold-in for non-pow2 H.
      // Bundle: [u32 count][count x (i32 host_idx, i64 len)][payloads].
      auto st = [&]() -> Status {
        std::vector<bool> have_c(H, false);
        have_c[h] = true;
        int m2 = 1;
        while (m2 * 2 <= H) m2 *= 2;
        const int extra2 = H - m2;
        // exclude: a chunk the receiver is known to hold already (the
        // fold-in post-step returns everything EXCEPT the extra
        // leader's own chunk — a duplicate would trip the receiver's
        // corruption check, which treats re-delivery as a corrupt wire).
        auto pack = [&](std::string* wire, int exclude) {
          uint32_t count = 0;
          for (int i = 0; i < H; ++i) {
            count += (have_c[i] && i != exclude) ? 1 : 0;
          }
          wire->clear();
          wire->append(reinterpret_cast<const char*>(&count),
                       sizeof(count));
          for (int i = 0; i < H; ++i) {
            if (!have_c[i] || i == exclude) continue;
            int32_t idx = i;
            int64_t len = counts_h[i] * es;
            wire->append(reinterpret_cast<const char*>(&idx), sizeof(idx));
            wire->append(reinterpret_cast<const char*>(&len), sizeof(len));
          }
          for (int i = 0; i < H; ++i) {
            if (have_c[i] && i != exclude) {
              wire->append(partial.data() + offs_h[i] * es,
                           counts_h[i] * es);
            }
          }
          if (fault_truncate_hier_allgather_ && !wire->empty()) {
            wire->pop_back();  // test-only: exercise the size validation
          }
        };
        auto merge = [&](const std::string& in) -> Status {
          uint32_t count = 0;
          if (in.size() < sizeof(count)) {
            return Status::Unknown("hierarchical allgather bundle "
                                   "truncated");
          }
          std::memcpy(&count, in.data(), sizeof(count));
          if (count == 0 || count > static_cast<uint32_t>(H)) {
            return Status::Unknown(
                "hierarchical allgather bundle corrupt count " +
                std::to_string(count));
          }
          constexpr size_t kHdr = sizeof(int32_t) + sizeof(int64_t);
          size_t data_off = sizeof(count) + count * kHdr;
          if (in.size() < data_off) {
            return Status::Unknown("hierarchical allgather bundle header "
                                   "truncated");
          }
          const char* p = in.data() + sizeof(count);
          for (uint32_t i = 0; i < count; ++i) {
            int32_t idx = 0;
            int64_t len = 0;
            std::memcpy(&idx, p, sizeof(idx));
            p += sizeof(idx);
            std::memcpy(&len, p, sizeof(len));
            p += sizeof(len);
            if (idx < 0 || idx >= H || have_c[idx] ||
                len != counts_h[idx] * es ||
                data_off + static_cast<size_t>(len) > in.size()) {
              return Status::Unknown(
                  "hierarchical allgather bundle corrupt entry (host " +
                  std::to_string(idx) + ", " + std::to_string(len) +
                  " bytes)");
            }
            std::memcpy(&partial[offs_h[idx] * es], in.data() + data_off,
                        len);
            have_c[idx] = true;
            data_off += len;
          }
          if (data_off != in.size()) {
            return Status::Unknown(
                "hierarchical allgather bundle trailing bytes");
          }
          return Status::OK();
        };
        std::string wire2, inc2;
        if (h >= m2) {
          pack(&wire2, -1);
          CountWire(leaders[h - m2],
                    static_cast<int64_t>(wire2.size()));
          auto s2 = transport_->PeerSend(leaders[h - m2], wire2.data(),
                                         wire2.size());
          if (!s2.ok()) return s2;
          s2 = transport_->PeerRecv(leaders[h - m2], &inc2);
          if (!s2.ok()) return s2;
          return merge(inc2);
        }
        if (h < extra2) {
          auto s2 = transport_->PeerRecv(leaders[h + m2], &inc2);
          if (!s2.ok()) return s2;
          s2 = merge(inc2);
          if (!s2.ok()) return s2;
        }
        for (int dist = 1; dist < m2; dist <<= 1) {
          const int partner = h ^ dist;
          pack(&wire2, -1);
          CountWire(leaders[partner],
                    static_cast<int64_t>(wire2.size()));
          auto s2 = transport_->PeerExchange(leaders[partner], wire2.data(),
                                             wire2.size(), &inc2);
          if (!s2.ok()) return s2;
          s2 = merge(inc2);
          if (!s2.ok()) return s2;
        }
        if (h < extra2) {
          pack(&wire2, h + m2);
          CountWire(leaders[h + m2],
                    static_cast<int64_t>(wire2.size()));
          auto s2 = transport_->PeerSend(leaders[h + m2], wire2.data(),
                                         wire2.size());
          if (!s2.ok()) return s2;
        }
        for (int i = 0; i < H; ++i) {
          if (!have_c[i]) {
            return Status::Unknown(
                "hierarchical allgather left missing chunk for host " +
                std::to_string(i));
          }
        }
        return Status::OK();
      }();
      if (!st.ok()) return st;
    }
  }

  // Phase 4 — intra-host distribute: the leader scatters result chunks
  // (local partition), then a local ring allgather circulates them so
  // per-link intra-host traffic stays O(nbytes) instead of the leader
  // pushing L-1 full copies.
  std::vector<std::string> chunks(L);
  if (j == 0) {
    for (int i = 1; i < L; ++i) {
      CountWire(g[i], counts_l[i] * es);
      auto st = transport_->PeerSend(g[i], partial.data() + offs_l[i] * es,
                                     counts_l[i] * es);
      if (!st.ok()) return st;
    }
    chunks[0].assign(partial.data() + offs_l[0] * es, counts_l[0] * es);
  } else {
    auto st = transport_->PeerRecv(g[0], &chunks[j]);
    if (!st.ok()) return st;
    if (static_cast<int64_t>(chunks[j].size()) != counts_l[j] * es) {
      return Status::Unknown(
          "hierarchical scatter chunk size mismatch (" +
          std::to_string(chunks[j].size()) + " bytes, expected " +
          std::to_string(counts_l[j] * es) + ")");
    }
  }
  if (L > 1) {
    const int gnext = g[(j + 1) % L];
    const int gprev = g[(j - 1 + L) % L];
    for (int t = 0; t < L - 1; ++t) {
      const int sc = (j - t + L) % L;
      const int rc = (j - t - 1 + L) % L;
      CountWire(gnext, static_cast<int64_t>(chunks[sc].size()));
      auto st = transport_->PeerShift(gnext, gprev, chunks[sc].data(),
                                      chunks[sc].size(), &incoming);
      if (!st.ok()) return st;
      if (static_cast<int64_t>(incoming.size()) != counts_l[rc] * es) {
        return Status::Unknown(
            "hierarchical intra-host allgather chunk size mismatch (" +
            std::to_string(incoming.size()) + " bytes, expected " +
            std::to_string(counts_l[rc] * es) + ")");
      }
      chunks[rc] = std::move(incoming);
    }
  }
  for (int i = 0; i < L; ++i) {
    std::memcpy(buf + offs_l[i] * es, chunks[i].data(), chunks[i].size());
  }
  ++hier_ops_;
  return Status::OK();
}

Status DataPlane::RingBcast(void* buffer, int64_t nbytes, int32_t root) {
  const int size = transport_->size();
  const int rank = transport_->rank();
  const int next = (rank + 1) % size;
  const int64_t kChunk = 1 << 20;
  char* buf = static_cast<char*>(buffer);
  const bool tail = (rank + 1) % size == root;  // last relay before root
  for (int64_t off = 0; off < nbytes; off += kChunk) {
    const int64_t n = std::min(kChunk, nbytes - off);
    if (rank == root) {
      CountWire(next, n);
      auto st = transport_->RingSend(std::string(buf + off, n));
      if (!st.ok()) return st;
    } else {
      std::string chunk;
      auto st = transport_->RingRecv(&chunk);
      if (!st.ok()) return st;
      if (static_cast<int64_t>(chunk.size()) != n) {
        return Status::Unknown("ring bcast chunk size mismatch");
      }
      std::memcpy(buf + off, chunk.data(), n);
      if (!tail) {
        CountWire(next, n);
        st = transport_->RingSend(chunk);
        if (!st.ok()) return st;
      }
    }
  }
  ++ring_ops_;
  return Status::OK();
}

Status DataPlane::AllreduceImpl(void* buffer, int64_t num_elements,
                                DataType dtype, ReduceKind kind,
                                double prescale, double postscale) {
  const int size = transport_->size();
  const int rank = transport_->rank();
  const int64_t nbytes = num_elements * DataTypeSize(dtype);
  if (kind == ReduceKind::ADASUM && !IsFloatType(dtype)) {
    return Status::InvalidArgument(
        "Adasum requires a floating-point dtype, got " +
        std::string(DataTypeName(dtype)));
  }
  auto st = EnsureTopology();
  if (!st.ok()) return st;
  if (prescale != 1.0) ScaleBuffer(buffer, num_elements, dtype, prescale);
  if (size > 1) {
    // Algorithm selection — every operand of these conditions is either
    // negotiated metadata (identical on all ranks) or a cycle-fenced
    // routing knob, so all ranks take the same branch with no extra
    // traffic. Adasum keeps the star's binary combine tree.
    const bool small_rd = kind != ReduceKind::ADASUM &&
                          small_algo_ == kSmallTensorRecursiveDoubling &&
                          nbytes < small_max_bytes_;
    const bool hier = !small_rd && kind != ReduceKind::ADASUM &&
                      hierarchical_ && MultiHost() &&
                      nbytes >= small_max_bytes_;
    const bool ring = !small_rd && !hier && kind != ReduceKind::ADASUM &&
                      nbytes >= ring_threshold_ && num_elements >= size;
    if (small_rd) {
      st = RecursiveDoublingAllreduce(buffer, num_elements, dtype, kind);
      if (!st.ok()) return st;
    } else if (hier) {
      st = HierarchicalAllreduce(buffer, num_elements, dtype, kind);
      if (!st.ok()) return st;
    } else if (ring) {
      st = RingAllreduce(buffer, num_elements, dtype, kind);
      if (!st.ok()) return st;
    } else {
      std::string mine(static_cast<const char*>(buffer), nbytes);
      if (rank != 0) CountWire(0, nbytes);
      std::vector<std::string> all;
      st = transport_->Gather(mine, rank == 0 ? &all : nullptr);
      if (!st.ok()) return st;
      std::string result;
      if (rank == 0) {
        if (kind == ReduceKind::ADASUM && IsFloatType(dtype)) {
          // Binary-tree pairwise combine — the same reduction tree VHDD
          // produces (level l pairs r with r^2^l).
          std::vector<std::vector<double>> vecs(size);
          for (int r = 0; r < size; ++r) {
            vecs[r].resize(num_elements);
            ToDouble(all[r].data(), num_elements, dtype, vecs[r].data());
          }
          for (int level = 1; level < size; level <<= 1) {
            for (int r = 0; r + level < size; r += 2 * level) {
              AdasumPair(vecs[r], vecs[r + level]);
            }
          }
          result.resize(nbytes);
          FromDouble(vecs[0].data(), num_elements, dtype, result.data());
        } else {
          result.resize(nbytes);
          st = CanonicalReduce(all, num_elements, dtype, kind, &result[0]);
          if (!st.ok()) return st;
        }
        for (int r = 1; r < size; ++r) {
          CountWire(r, static_cast<int64_t>(result.size()));
        }
      }
      st = transport_->Bcast(&result);
      if (!st.ok()) return st;
      std::memcpy(buffer, result.data(), nbytes);
    }
  }
  if (kind == ReduceKind::AVERAGE) {
    ScaleBuffer(buffer, num_elements, dtype, 1.0 / size);
  }
  if (postscale != 1.0) ScaleBuffer(buffer, num_elements, dtype, postscale);
  return Status::OK();
}

Status DataPlane::ExchangeInt64(int64_t mine, std::vector<int64_t>* all) {
  const int size = transport_->size();
  std::string m(reinterpret_cast<const char*>(&mine), sizeof(mine));
  std::vector<std::string> gathered;
  auto st = transport_->Gather(m, transport_->rank() == 0 ? &gathered
                                                          : nullptr);
  if (!st.ok()) return st;
  std::string packed;
  if (transport_->rank() == 0) {
    for (auto& p : gathered) packed.append(p);
  }
  st = transport_->Bcast(&packed);
  if (!st.ok()) return st;
  if (packed.size() != static_cast<size_t>(size) * sizeof(int64_t)) {
    return Status::Unknown("int64 exchange size mismatch");
  }
  all->resize(size);
  std::memcpy(all->data(), packed.data(), packed.size());
  return Status::OK();
}

Status DataPlane::RingAllgatherv(const void* in,
                                 const std::vector<int64_t>& sizes,
                                 std::string* out) {
  const int size = transport_->size();
  const int rank = transport_->rank();
  // Rotate blobs around the ring: step s sends the blob received at step
  // s-1 (starting with our own), so every blob travels each link exactly
  // once — per-link traffic is O(total bytes), with no rank-0 relay.
  std::vector<std::string> blobs(size);
  blobs[rank].assign(static_cast<const char*>(in), sizes[rank]);
  for (int s = 0; s < size - 1; ++s) {
    const int send_r = ((rank - s) % size + size) % size;
    const int recv_r = ((rank - s - 1) % size + size) % size;
    std::string incoming;
    CountWire((rank + 1) % size,
              static_cast<int64_t>(blobs[send_r].size()));
    auto st = transport_->RingExchange(blobs[send_r].data(),
                                       blobs[send_r].size(), &incoming);
    if (!st.ok()) return st;
    if (static_cast<int64_t>(incoming.size()) != sizes[recv_r]) {
      return Status::Unknown("ring allgatherv blob size mismatch");
    }
    blobs[recv_r] = std::move(incoming);
  }
  int64_t total = 0;
  for (auto s : sizes) total += s;
  out->clear();
  out->reserve(total);
  for (int r = 0; r < size; ++r) out->append(blobs[r]);
  ++ring_ops_;
  return Status::OK();
}

Status DataPlane::AllgathervImpl(const void* in, int64_t in_bytes,
                                 std::string* out,
                                 std::vector<int64_t>* rank_bytes) {
  const int size = transport_->size();
  auto st = EnsureTopology();
  if (!st.ok()) return st;
  // Per-rank sizes ride the star first (8 bytes each): every rank needs
  // them for the output layout, and all ranks must take the same
  // star-or-ring branch.
  st = ExchangeInt64(in_bytes, rank_bytes);
  if (!st.ok()) return st;
  int64_t total = 0;
  for (auto s : *rank_bytes) total += s;
  if (size > 1 && total >= ring_threshold_) {
    return RingAllgatherv(in, *rank_bytes, out);
  }
  std::string mine(static_cast<const char*>(in), in_bytes);
  if (transport_->rank() != 0) CountWire(0, in_bytes);
  std::vector<std::string> all;
  st = transport_->Gather(mine, transport_->rank() == 0 ? &all : nullptr);
  if (!st.ok()) return st;
  std::string packed;
  if (transport_->rank() == 0) {
    packed.reserve(total);
    for (auto& p : all) packed.append(p);
    if (fault_truncate_star_allgatherv_ && !packed.empty()) {
      packed.pop_back();  // test-only: simulate a truncated broadcast
    }
    for (int r = 1; r < size; ++r) {
      CountWire(r, static_cast<int64_t>(packed.size()));
    }
  }
  st = transport_->Bcast(&packed);
  if (!st.ok()) return st;
  // A truncated/corrupt Bcast would hand callers rank_bytes offsets running
  // past the payload consumed via hvdtpu_data_fetch — validate like the
  // ring path validates each blob.
  if (static_cast<int64_t>(packed.size()) != total) {
    return Status::Unknown("star allgatherv payload size mismatch");
  }
  *out = std::move(packed);
  return Status::OK();
}

Status DataPlane::BcastImpl(void* buffer, int64_t nbytes, int32_t root) {
  auto tst = EnsureTopology();
  if (!tst.ok()) return tst;
  const int size = transport_->size();
  if (size > 1 && nbytes >= ring_threshold_) {
    return RingBcast(buffer, nbytes, root);
  }
  // Star topology with rank-0 hub: non-zero roots relay through rank 0.
  const int rank = transport_->rank();
  if (root != 0) {
    std::string mine;
    if (rank == root) {
      mine.assign(static_cast<const char*>(buffer), nbytes);
      CountWire(0, nbytes);
    }
    std::vector<std::string> all;
    auto st = transport_->Gather(mine, rank == 0 ? &all : nullptr);
    if (!st.ok()) return st;
    std::string payload;
    if (rank == 0) {
      payload = all[root];
      for (int r = 1; r < size; ++r) {
        CountWire(r, static_cast<int64_t>(payload.size()));
      }
    }
    st = transport_->Bcast(&payload);
    if (!st.ok()) return st;
    std::memcpy(buffer, payload.data(),
                std::min<int64_t>(nbytes, payload.size()));
    return Status::OK();
  }
  std::string payload;
  if (rank == 0) {
    payload.assign(static_cast<const char*>(buffer), nbytes);
    for (int r = 1; r < size; ++r) CountWire(r, nbytes);
  }
  auto st = transport_->Bcast(&payload);
  if (!st.ok()) return st;
  if (rank != 0) {
    std::memcpy(buffer, payload.data(),
                std::min<int64_t>(nbytes, payload.size()));
  }
  return Status::OK();
}

Status DataPlane::RingAlltoallv(const void* in,
                                const std::vector<int64_t>& send_bytes,
                                std::string* out,
                                std::vector<int64_t>* recv_bytes) {
  const int size = transport_->size();
  const int rank = transport_->rank();
  const char* src_data = static_cast<const char*>(in);
  // Entry-relay bundle: every chunk is tagged (src, dst) and rides the
  // ring until its destination extracts it — chunk (s -> d) travels
  // (d - s) mod size hops, so per-link traffic averages total/2 with no
  // rank-0 funnel. All ranks run exactly size-1 lockstep exchanges
  // (possibly with empty bundles), so the ring cannot skew.
  //
  // The bundle lives in wire format end-to-end:
  //   [u32 count][count x (i32 src, i32 dst, i64 len)][payloads...]
  // Each hop splices the incoming buffer in one pass — delivered chunks
  // copy out, kept chunks copy straight into the next outgoing buffer —
  // so per-hop work is O(bytes still in flight), not the
  // O(world x total_bytes) a deserialize-reserialize round trip costs.
  constexpr size_t kEntryHdr = 2 * sizeof(int32_t) + sizeof(int64_t);
  auto append_hdr = [](std::string* wire, int32_t src, int32_t dst,
                       int64_t len) {
    wire->append(reinterpret_cast<const char*>(&src), sizeof(src));
    wire->append(reinterpret_cast<const char*>(&dst), sizeof(dst));
    wire->append(reinterpret_cast<const char*>(&len), sizeof(len));
  };
  std::vector<std::string> received(size);
  std::string wire;
  {
    uint32_t count = static_cast<uint32_t>(size > 0 ? size - 1 : 0);
    int64_t payload_total = 0, off = 0;
    for (int d = 0; d < size; ++d) {
      if (d != rank) payload_total += send_bytes[d];
    }
    wire.reserve(sizeof(count) + count * kEntryHdr + payload_total);
    wire.append(reinterpret_cast<const char*>(&count), sizeof(count));
    for (int d = 0; d < size; ++d) {
      if (d == rank) {
        received[rank].assign(src_data + off, send_bytes[d]);
      } else {
        append_hdr(&wire, rank, d, send_bytes[d]);
      }
      off += send_bytes[d];
    }
    off = 0;
    for (int d = 0; d < size; ++d) {
      if (d != rank) wire.append(src_data + off, send_bytes[d]);
      off += send_bytes[d];
    }
  }

  for (int s = 0; s < size - 1; ++s) {
    if (fault_truncate_ring_alltoallv_ && s == 0 &&
        wire.size() > sizeof(uint32_t)) {
      wire.pop_back();  // test-only: simulate a corrupt relay payload
    }
    std::string incoming;
    CountWire((rank + 1) % size, static_cast<int64_t>(wire.size()));
    auto st = transport_->RingExchange(wire.data(), wire.size(), &incoming);
    if (!st.ok()) return st;
    uint32_t count = 0;
    if (incoming.size() < sizeof(count)) {
      return Status::Unknown("ring alltoallv truncated bundle");
    }
    std::memcpy(&count, incoming.data(), sizeof(count));
    size_t hdr = sizeof(count);
    size_t data_off = hdr + count * kEntryHdr;
    if (incoming.size() < data_off) {
      return Status::Unknown("ring alltoallv truncated bundle header");
    }
    // One pass: validate headers, deliver our chunks, splice the rest.
    std::string next;
    uint32_t kept = 0;
    next.append(reinterpret_cast<const char*>(&kept), sizeof(kept));
    int64_t kept_payload = 0;
    struct Span {
      size_t off;
      int64_t len;
    };
    std::vector<Span> kept_spans;
    kept_spans.reserve(count);
    for (uint32_t i = 0; i < count; ++i) {
      int32_t src = 0, dst = 0;
      int64_t len = 0;
      std::memcpy(&src, incoming.data() + hdr, sizeof(src));
      hdr += sizeof(src);
      std::memcpy(&dst, incoming.data() + hdr, sizeof(dst));
      hdr += sizeof(dst);
      std::memcpy(&len, incoming.data() + hdr, sizeof(len));
      hdr += sizeof(len);
      if (src < 0 || src >= size || dst < 0 || dst >= size || len < 0 ||
          data_off + static_cast<size_t>(len) > incoming.size()) {
        return Status::Unknown("ring alltoallv corrupt entry");
      }
      if (dst == rank) {
        received[src].assign(incoming.data() + data_off, len);
      } else {
        append_hdr(&next, src, dst, len);
        kept_spans.push_back({data_off, len});
        kept_payload += len;
        ++kept;
      }
      data_off += len;
    }
    next.reserve(next.size() + kept_payload);
    for (const auto& span : kept_spans) {
      next.append(incoming.data() + span.off, span.len);
    }
    std::memcpy(&next[0], &kept, sizeof(kept));
    wire = std::move(next);
  }
  if (wire.size() > sizeof(uint32_t)) {
    return Status::Unknown("ring alltoallv left undelivered chunks");
  }
  recv_bytes->resize(size);
  int64_t total = 0;
  for (int r = 0; r < size; ++r) {
    (*recv_bytes)[r] = static_cast<int64_t>(received[r].size());
    total += (*recv_bytes)[r];
  }
  out->clear();
  out->reserve(total);
  for (int r = 0; r < size; ++r) out->append(received[r]);
  ++ring_ops_;
  return Status::OK();
}

Status DataPlane::AlltoallvImpl(const void* in,
                                const std::vector<int64_t>& send_bytes,
                                std::string* out,
                                std::vector<int64_t>* recv_bytes) {
  const int size = transport_->size();
  const int rank = transport_->rank();
  auto tst = EnsureTopology();
  if (!tst.ok()) return tst;
  // Uniform star-or-ring decision on the global total (per-rank totals
  // ride the star first — 8 bytes each).
  int64_t my_total = 0;
  for (int64_t sz : send_bytes) my_total += sz;
  std::vector<int64_t> totals;
  auto status = ExchangeInt64(my_total, &totals);
  if (!status.ok()) return status;
  int64_t grand = 0;
  for (auto t : totals) grand += t;
  if (size > 1 && grand >= ring_threshold_) {
    return RingAlltoallv(in, send_bytes, out, recv_bytes);
  }
  // Pack [i64 sizes...][data] and gather at root; root reshuffles and
  // scatters each rank its incoming chunks in source-rank order.
  std::string mine;
  for (int64_t sz : send_bytes) {
    mine.append(reinterpret_cast<const char*>(&sz), sizeof(sz));
  }
  int64_t total = 0;
  for (int64_t sz : send_bytes) total += sz;
  mine.append(static_cast<const char*>(in), total);

  if (rank != 0) CountWire(0, static_cast<int64_t>(mine.size()));
  std::vector<std::string> all;
  auto st = transport_->Gather(mine, rank == 0 ? &all : nullptr);
  if (!st.ok()) return st;

  std::vector<std::string> outgoing;
  if (rank == 0) {
    // per source rank: sizes + chunk offsets
    std::vector<std::vector<int64_t>> sizes(size);
    std::vector<size_t> data_off(size);
    for (int src = 0; src < size; ++src) {
      sizes[src].resize(size);
      std::memcpy(sizes[src].data(), all[src].data(),
                  size * sizeof(int64_t));
      data_off[src] = size * sizeof(int64_t);
    }
    outgoing.resize(size);
    for (int dst = 0; dst < size; ++dst) {
      std::string& pkt = outgoing[dst];
      for (int src = 0; src < size; ++src) {
        pkt.append(reinterpret_cast<const char*>(&sizes[src][dst]),
                   sizeof(int64_t));
      }
      for (int src = 0; src < size; ++src) {
        size_t off = data_off[src];
        for (int d = 0; d < dst; ++d) off += sizes[src][d];
        pkt.append(all[src].data() + off, sizes[src][dst]);
      }
    }
  }
  if (rank == 0) {
    for (int r = 1; r < size; ++r) {
      CountWire(r, static_cast<int64_t>(outgoing[r].size()));
    }
  }
  std::string packet;
  st = transport_->Scatter(rank == 0 ? &outgoing : nullptr, &packet);
  if (!st.ok()) return st;
  recv_bytes->resize(size);
  std::memcpy(recv_bytes->data(), packet.data(), size * sizeof(int64_t));
  out->assign(packet.data() + size * sizeof(int64_t),
              packet.size() - size * sizeof(int64_t));
  return Status::OK();
}

// --- metric-recording wrappers ---------------------------------------------
// All data-plane calls run on the single callback thread, so the per-
// algorithm op counters' before/after deltas are a race-free way to
// attribute each op to the path (star/ring/rd/hier) that served it.

void DataPlane::RecordOp(std::atomic<int64_t> MetricsStore::*bytes_member,
                         int64_t nbytes, int64_t ring_ops_before,
                         int64_t rd_ops_before, int64_t hier_ops_before) {
  if (metrics_ == nullptr) return;
  (metrics_->*bytes_member).fetch_add(nbytes, std::memory_order_relaxed);
  if (ring_ops_ > ring_ops_before) {
    metrics_->data_ring_ops.fetch_add(1, std::memory_order_relaxed);
  } else if (rd_ops_ > rd_ops_before) {
    metrics_->data_rd_ops.fetch_add(1, std::memory_order_relaxed);
  } else if (hier_ops_ > hier_ops_before) {
    metrics_->data_hier_ops.fetch_add(1, std::memory_order_relaxed);
  } else {
    metrics_->data_star_ops.fetch_add(1, std::memory_order_relaxed);
  }
}

Status DataPlane::Allreduce(void* buffer, int64_t num_elements,
                            DataType dtype, ReduceKind kind, double prescale,
                            double postscale) {
  int64_t ring_before = ring_ops_, rd_before = rd_ops_,
          hier_before = hier_ops_;
  auto st = AllreduceImpl(buffer, num_elements, dtype, kind, prescale,
                          postscale);
  last_error_ = st.ok() ? "" : st.reason;
  if (st.ok()) {
    RecordOp(&MetricsStore::allreduce_bytes,
             num_elements * DataTypeSize(dtype), ring_before, rd_before,
             hier_before);
  }
  return st;
}

Status DataPlane::Allgatherv(const void* in, int64_t in_bytes,
                             std::string* out,
                             std::vector<int64_t>* rank_bytes) {
  int64_t ring_before = ring_ops_, rd_before = rd_ops_,
          hier_before = hier_ops_;
  auto st = AllgathervImpl(in, in_bytes, out, rank_bytes);
  last_error_ = st.ok() ? "" : st.reason;
  if (st.ok()) {
    RecordOp(&MetricsStore::allgather_bytes,
             static_cast<int64_t>(out->size()), ring_before, rd_before,
             hier_before);
  }
  return st;
}

Status DataPlane::Bcast(void* buffer, int64_t nbytes, int32_t root) {
  int64_t ring_before = ring_ops_, rd_before = rd_ops_,
          hier_before = hier_ops_;
  auto st = BcastImpl(buffer, nbytes, root);
  last_error_ = st.ok() ? "" : st.reason;
  if (st.ok()) {
    RecordOp(&MetricsStore::broadcast_bytes, nbytes, ring_before, rd_before,
             hier_before);
  }
  return st;
}

Status DataPlane::Alltoallv(const void* in,
                            const std::vector<int64_t>& send_bytes,
                            std::string* out,
                            std::vector<int64_t>* recv_bytes) {
  int64_t ring_before = ring_ops_, rd_before = rd_ops_,
          hier_before = hier_ops_;
  auto st = AlltoallvImpl(in, send_bytes, out, recv_bytes);
  last_error_ = st.ok() ? "" : st.reason;
  if (st.ok()) {
    RecordOp(&MetricsStore::alltoall_bytes,
             static_cast<int64_t>(out->size()), ring_before, rd_before,
             hier_before);
  }
  return st;
}

}  // namespace hvdtpu

// Host (CPU) data plane for eager collectives.
//
// Reference analog: the CPU op implementations —
// horovod/common/ops/mpi_operations.cc (MPI_Allreduce/Allgatherv/Bcast/
// Alltoallv on host buffers) and gloo_operations.cc. The TPU framework's hot
// path is in-XLA collectives over ICI; this plane serves the eager surface
// (broadcast_object, metric averaging, optimizer-state sync, CPU-staged
// tensors) the way the reference's MPI/Gloo CPU ops do.
//
// Topology-aware algorithm selection (allreduce):
// - sub-threshold latency class: the rank-0 star (one round trip), or a
//   log2(p)-step recursive-doubling route (small_tensor_algo=rd) that
//   removes the rank-0 hotspot (reference analog: MPICH/gloo
//   halving-doubling; MVAPICH characterization arXiv:1810.11112);
// - payloads >= ring_threshold take ring algorithms over neighbor p2p
//   links — O(bytes) traffic per rank independent of world size;
// - with HOROVOD_HIERARCHICAL_ALLREDUCE and a multi-host locality map, a
//   two-level route: intra-host reduce-scatter -> inter-host allreduce
//   among local leaders (ring >= threshold, recursive doubling below) ->
//   intra-host allgather, cutting inter-host wire traffic by roughly the
//   local fan-in (arXiv:1810.11112).
// All routing knobs are cycle-fenced: they ride the TunedParams broadcast
// and are applied by the engine between coordination cycles, so every rank
// routes a given collective identically (a split decision would deadlock
// the transports).
// Reduction math: typed kernels including fp16/bf16 accumulation (half.cc)
// and a binary-tree Adasum (reference: adasum_mpi.cc VHDD — same pairwise
// combination, tree order). The star, recursive-doubling, and hierarchical
// paths share ONE canonical reduction order (per-host partials in local
// rank order, then hosts in host-id order), so they are bit-exact with
// each other for every dtype.

#ifndef HVD_TPU_DATA_PLANE_H
#define HVD_TPU_DATA_PLANE_H

#include <memory>
#include <string>
#include <vector>

#include "common.h"
#include "metrics.h"
#include "transport.h"

namespace hvdtpu {

enum class ReduceKind : int32_t {
  SUM = 0,
  AVERAGE = 1,  // sum then scale by 1/size
  MIN = 2,
  MAX = 3,
  PRODUCT = 4,
  ADASUM = 5,
};

// Small-tensor allreduce route ids (TunedParams.small_tensor_algo).
constexpr int32_t kSmallTensorStar = 0;
constexpr int32_t kSmallTensorRecursiveDoubling = 1;

class DataPlane {
 public:
  explicit DataPlane(std::shared_ptr<ControllerTransport> transport);

  // Number of collectives served by the ring path (tests assert the ring
  // actually engaged for large payloads).
  int64_t ring_ops() const { return ring_ops_; }
  // Reason of the last failed op ("" if the last op succeeded): the
  // engine folds it into the handle error so a wire-validation failure
  // surfaces its specifics (which exchange, got/expected bytes), not
  // just a return code. Callback-thread only, like the ops themselves.
  const std::string& last_error() const { return last_error_; }
  // Recursive-doubling / hierarchical allreduces served (diagnostics).
  int64_t rd_ops() const { return rd_ops_; }
  int64_t hier_ops() const { return hier_ops_; }

  // Engine metrics sink: per-op payload bytes, per-algorithm routing
  // counters, and inter-host vs intra-host wire-byte attribution
  // (populated from the public entry points below).
  void set_metrics(MetricsStore* m) { metrics_ = m; }

  // Routing knobs — cycle-fenced: seeded from EngineOptions at Init and
  // re-applied by the engine after every SynchronizeParameters broadcast,
  // on the same background thread that runs the ops below, so a knob flip
  // can never split ranks across algorithms mid-collective.
  // small_tensor_max_bytes is the express-lane class boundary
  // (TunedParams.low_latency_threshold_bytes): payloads strictly below it
  // are eligible for the recursive-doubling route.
  void SetRouting(int64_t ring_threshold_bytes, bool hierarchical,
                  int32_t small_tensor_algo, int64_t small_tensor_max_bytes) {
    ring_threshold_ = ring_threshold_bytes;
    hierarchical_ = hierarchical;
    small_algo_ = small_tensor_algo;
    small_max_bytes_ = small_tensor_max_bytes;
  }
  int64_t ring_threshold() const { return ring_threshold_; }

  // This rank's host id from the launcher's topology records
  // (HOROVOD_CROSS_RANK / the hvdtpu_create_session host_id argument).
  // host_id < 0 means "no locality map": the plane stays flat and never
  // runs the topology exchange (existing single-host jobs keep their
  // exact wire traffic, including fault-injection frame numbering).
  // Loopback tests simulate multi-host grouping by passing distinct host
  // ids per in-process rank. Must be uniform across ranks: either every
  // rank supplies a host id or none does (launcher contract).
  void SetHostId(int32_t host_id) { host_id_ = host_id; }

  // Fast-abort fan-out on the data channel: best-effort abort frames to
  // every connected peer so a rank blocked in a data-plane receive fails
  // now instead of at the recv timeout (see
  // ControllerTransport::AbortPeers).
  void AbortPeers(const std::string& reason) {
    transport_->AbortPeers(reason);
  }

  // In-place allreduce over num_elements of dtype.
  Status Allreduce(void* buffer, int64_t num_elements, DataType dtype,
                   ReduceKind kind, double prescale, double postscale);

  // Gather per-rank byte blobs; every rank receives the concatenation in
  // rank order (sizes may differ — the allgatherv analog).
  Status Allgatherv(const void* in, int64_t in_bytes, std::string* out,
                    std::vector<int64_t>* rank_bytes);

  // Root's buffer replicated to all (in-place for non-roots).
  Status Bcast(void* buffer, int64_t nbytes, int32_t root);

  // Each rank sends send_splits[r] bytes to rank r from `in`; receives into
  // out (concatenated by source rank), recv sizes returned.
  Status Alltoallv(const void* in, const std::vector<int64_t>& send_bytes,
                   std::string* out, std::vector<int64_t>* recv_bytes);

 private:
  // The public ops above are thin metric-recording wrappers around these.
  Status AllreduceImpl(void* buffer, int64_t num_elements, DataType dtype,
                       ReduceKind kind, double prescale, double postscale);
  Status AllgathervImpl(const void* in, int64_t in_bytes, std::string* out,
                        std::vector<int64_t>* rank_bytes);
  Status BcastImpl(void* buffer, int64_t nbytes, int32_t root);
  Status AlltoallvImpl(const void* in,
                       const std::vector<int64_t>& send_bytes,
                       std::string* out, std::vector<int64_t>* recv_bytes);

  // O(bytes)-per-rank ring algorithms for payloads >= ring_threshold_:
  // reduce-scatter + allgather around the ring (allreduce), pipelined
  // chunk relay (bcast), blob rotation (allgatherv), and an entry-relay
  // bundle (alltoallv). No rank ever relays O(world * bytes) through one
  // link (reference analog: gloo ring ops, ops/gloo_operations.cc).
  Status RingAllreduce(void* buffer, int64_t num_elements, DataType dtype,
                       ReduceKind kind);
  Status RingBcast(void* buffer, int64_t nbytes, int32_t root);
  Status RingAllgatherv(const void* in, const std::vector<int64_t>& sizes,
                        std::string* out);
  Status RingAlltoallv(const void* in,
                       const std::vector<int64_t>& send_bytes,
                       std::string* out, std::vector<int64_t>* recv_bytes);

  // Latency-optimized log2(p) small-tensor allreduce: distance-doubling
  // allgather of tagged raw contributions (non-power-of-two handled by the
  // standard fold-in pre/post step), then one canonical-order local
  // reduction — bit-exact with the star path, no rank-0 hub.
  Status RecursiveDoublingAllreduce(void* buffer, int64_t num_elements,
                                    DataType dtype, ReduceKind kind);

  // Two-level topology-aware allreduce (HOROVOD_HIERARCHICAL_ALLREDUCE):
  // intra-host pairwise reduce-scatter -> chunk gather to the local leader
  // -> inter-host allreduce among leaders (pairwise reduce-scatter + ring
  // allgather >= ring_threshold, recursive-doubling allgather below) ->
  // intra-host chunk scatter + ring allgather. Reduction order is the
  // shared canonical order, so the result is bit-exact with star/rd.
  Status HierarchicalAllreduce(void* buffer, int64_t num_elements,
                               DataType dtype, ReduceKind kind);

  // One-time locality-map exchange (8 bytes/rank on the star): builds
  // host_groups_ (hosts in host-id order, members in rank order). Invoked
  // lazily from the first op of a session whose ranks carry host ids, so
  // flat sessions never pay it. All ranks reach their first data-plane op
  // in lockstep, so the exchange is uniformly placed.
  Status EnsureTopology();
  // True when a locality map exists and spans more than one host.
  bool MultiHost() const { return host_groups_.size() > 1; }

  // The one canonical reduction order shared by star / recursive-doubling
  // / hierarchical: fold each host's contributions sequentially in rank
  // order, then fold the host partials sequentially in host-id order.
  // With no locality map this is the plain sequential rank-order chain
  // (the historical star order — single-host results are bit-identical).
  // contributions[r] holds rank r's raw payload; result lands in `out`.
  Status CanonicalReduce(const std::vector<std::string>& contributions,
                         int64_t num_elements, DataType dtype,
                         ReduceKind kind, void* out) const;

  // Per-rank int64 exchange over the star (8 bytes/rank): gives every rank
  // the full vector so star-vs-ring decisions are uniform (a split
  // decision would deadlock the transports).
  Status ExchangeInt64(int64_t mine, std::vector<int64_t>* all);

  // Wire-byte attribution: logical payload bytes this rank sends to dst,
  // classified inter-host vs intra-host via the locality map (no map =
  // all intra-host, the single-host truth).
  void CountWire(int dst, int64_t nbytes);

  // Record one completed collective: payload bytes into `bytes_member`,
  // plus which algorithm (star/ring/rd/hier) served it.
  void RecordOp(std::atomic<int64_t> MetricsStore::*bytes_member,
                int64_t nbytes, int64_t ring_ops_before,
                int64_t rd_ops_before, int64_t hier_ops_before);

  std::shared_ptr<ControllerTransport> transport_;
  MetricsStore* metrics_ = nullptr;
  std::string last_error_;
  int64_t ring_threshold_;
  bool hierarchical_ = false;
  int32_t small_algo_ = kSmallTensorStar;
  int64_t small_max_bytes_ = 4096;
  int32_t host_id_ = -1;
  int64_t ring_ops_ = 0;
  int64_t rd_ops_ = 0;
  int64_t hier_ops_ = 0;
  // Locality map (EnsureTopology): per-rank host ids and the host groups
  // in canonical order. Empty until the exchange ran.
  bool topology_ready_ = false;
  std::vector<int32_t> host_ids_;
  std::vector<std::vector<int>> host_groups_;
  // Test-only fault injection (HOROVOD_DATA_FAULT_INJECT): corrupt a wire
  // payload so the negative paths of the size-validation checks are
  // exercisable from the multi-process tests. Never set in production.
  bool fault_truncate_star_allgatherv_ = false;
  bool fault_truncate_ring_alltoallv_ = false;
  bool fault_truncate_rd_bundle_ = false;
  bool fault_truncate_hier_chunk_ = false;
  bool fault_truncate_hier_allgather_ = false;
};

}  // namespace hvdtpu

#endif  // HVD_TPU_DATA_PLANE_H

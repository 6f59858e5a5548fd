// Collective flight recorder: always-on black-box event history.
//
// The reference's post-mortem story ends at the stall inspector's one-shot
// warning and whatever made it into logs before the process died. This
// recorder keeps the last HOROVOD_FLIGHT_RECORDER_SIZE per-collective
// events (enqueue → negotiate → fuse → exec → done, plus cycle sync
// anchors) in a fixed-size lock-free ring, so that when a job aborts,
// stalls, or desyncs, every surviving rank can dump the seconds before
// death as JSON (one file per rank in HOROVOD_FLIGHT_DIR) for the
// cross-rank analyzer (horovod_tpu/profiler/flight.py).
//
// Hot-path cost budget: one relaxed fetch_add to claim a slot, a handful
// of relaxed atomic stores, one release store to publish — no locks, no
// allocation (tensor names are truncated into a fixed in-slot array; the
// FNV-1a hash disambiguates truncated names across ranks). Readers
// (dump) use the per-slot sequence as a seqlock and skip torn slots: the
// dump is a best-effort black box, not a transactional snapshot. The
// slot fields are relaxed atomics because that is what makes the seqlock
// sound under the C++ memory model (Boehm, "Can seqlocks get along with
// programming language memory models?"): the writer's release fence
// orders the invalidation store before the (atomic) field stores, the
// reader's acquire fence orders the field loads before the re-check —
// with plain fields neither fence would constrain anything and TSan
// would rightly flag the race.

#ifndef HVD_TPU_FLIGHT_RECORDER_H
#define HVD_TPU_FLIGHT_RECORDER_H

#include <atomic>
#include <cstdint>
#include <string>
#include <vector>

#include "common.h"

namespace hvdtpu {

// Lifecycle phases of one collective as seen by one rank, plus CYCLE —
// a per-coordination-cycle anchor all ranks record after the same
// blocking exchange, which the analyzer uses to align per-rank
// steady clocks post hoc.
enum class FlightPhase : int32_t {
  ENQUEUE = 0,    // frontend submitted the tensor
  NEGOTIATE = 1,  // popped into a coordination cycle
  FUSE = 2,       // response received (aux = tensors in the fused batch)
  EXEC = 3,       // data-plane execution started
  DONE = 4,       // handle completed (status carries the failure class;
                  // aux = the response's exec-callback span in us, so the
                  // attribution engine can price each collective's exec
                  // without pairing EXEC/DONE across ring wrap)
  CYCLE = 5,      // coordination-cycle sync anchor (name empty)
  DESYNC = 6,     // signature/metadata mismatch error named this tensor
  STEP_BEGIN = 7, // frontend step-boundary mark (name empty, aux = step id)
  STEP_END = 8,   // frontend step-boundary mark (name empty, aux = step id)
};

const char* FlightPhaseName(FlightPhase p);

// FNV-1a over the tensor name — the stable cross-rank identity of a
// collective even when the in-slot name is truncated.
uint64_t FlightNameHash(const std::string& name);

class FlightRecorder {
 public:
  static constexpr size_t kNameBytes = 48;
  static constexpr int64_t kDefaultCapacity = 2048;

  static constexpr size_t kNameWords = kNameBytes / 8;

  struct Slot {
    // seqlock: 0 = never written (or mid-write); otherwise
    // event_index + 1, published with release after the fields below. A
    // reader seeing 0 or a changed value after its acquire-fenced copy
    // discards the slot.
    std::atomic<uint64_t> seq{0};
    std::atomic<int64_t> ts_us{0};  // steady clock since recorder creation
    std::atomic<uint64_t> name_hash{0};
    std::atomic<int64_t> cycle_id{-1};
    std::atomic<int64_t> payload_bytes{0};
    std::atomic<int64_t> aux{0};    // phase-specific (FUSE: batch size)
    std::atomic<int32_t> phase{0};
    std::atomic<int32_t> op_type{0};
    std::atomic<int32_t> dtype{0};
    std::atomic<int32_t> status{0};  // StatusType as int; 0 = OK
    // truncated NUL-padded name, packed into word-sized atomics
    std::atomic<uint64_t> name[kNameWords];
  };

  // capacity <= 0 disables recording entirely (Record becomes a cheap
  // early-out) — the bench's "off" configuration.
  explicit FlightRecorder(int64_t capacity = kDefaultCapacity);

  // HOROVOD_FLIGHT_RECORDER_SIZE, default kDefaultCapacity.
  static int64_t CapacityFromEnv();

  bool enabled() const { return !slots_.empty(); }
  int64_t capacity() const { return static_cast<int64_t>(slots_.size()); }
  int64_t recorded() const {
    return static_cast<int64_t>(next_.load(std::memory_order_relaxed));
  }

  void Record(FlightPhase phase, const std::string& name, uint64_t name_hash,
              int64_t cycle_id, int32_t op_type, int32_t dtype,
              int64_t payload_bytes, int32_t status = 0, int64_t aux = 0);

  // One JSON object: ring contents in event order plus enough metadata
  // for the analyzer to merge ranks (wall-clock anchor, trigger,
  // reason). Safe from any thread while writers keep recording.
  std::string DumpJson(int rank, int size, const std::string& trigger,
                       const std::string& reason) const;

  // DumpJson + write to <dir>/flight_rank<rank>.json (overwrite — the
  // latest trigger wins). Returns the JSON either way; empty dir skips
  // the file.
  std::string DumpToDir(const std::string& dir, int rank, int size,
                        const std::string& trigger,
                        const std::string& reason) const;

  // Write an already-serialized dump to <dir>/flight_rank<rank>.json
  // (write-then-rename so a visible file is always complete). Split out
  // so the C API can serialize once and write only on the call whose
  // caller buffer fits — file and returned JSON then always agree.
  static void WriteDumpFile(const std::string& dir, int rank,
                            const std::string& json);

  int64_t NowUs() const;

 private:
  std::vector<Slot> slots_;
  std::atomic<uint64_t> next_{0};
  std::chrono::steady_clock::time_point start_;
  int64_t origin_unix_us_ = 0;  // wall clock at construction
};

// ns per Record() call on this machine (tests/test_flight_recorder.py
// reads it). enabled=false times the disabled early-out.
double BenchFlightRecord(int64_t iters, bool enabled);

}  // namespace hvdtpu

#endif  // HVD_TPU_FLIGHT_RECORDER_H

// C ABI for ctypes (reference analog: horovod/common/operations.cc:710-898 —
// the horovod_* C functions loaded by common/basics.py).
//
// Session-based rather than singleton so one test process can host N engine
// instances coordinating over the loopback transport (the reference needs a
// real multi-process harness for this; SURVEY §7.2 calls out the
// single-process N-rank testability win).

#include <algorithm>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <string>

#include "engine.h"
#include "fault_injector.h"

using namespace hvdtpu;

namespace {

std::mutex g_mu;
std::map<int64_t, std::unique_ptr<Engine>> g_sessions;
int64_t g_next_session = 1;
thread_local std::string g_last_error;

Engine* GetSession(int64_t id) {
  std::lock_guard<std::mutex> lock(g_mu);
  auto it = g_sessions.find(id);
  return it == g_sessions.end() ? nullptr : it->second.get();
}

void SetError(const std::string& msg) { g_last_error = msg; }

}  // namespace

extern "C" {

// Bumped with any semantic change to the C ABI (new/removed symbols,
// changed return-code contracts). bindings.py refuses a prebuilt .so
// whose version doesn't match, so a stale library fails loudly instead
// of silently changing behavior.
// 6: hvdtpu_abort + hvdtpu_set_fault_spec; hvdtpu_wait can return
//    StatusType::CORRUPTED (6) for CRC-detected wire corruption.
// 7: hvdtpu_flight_dump + hvdtpu_bench_flight_record (collective flight
//    recorder); Request wire format carries a signature hash.
// 8: hvdtpu_step_begin/hvdtpu_step_end — frontend step-boundary marks
//    recorded into the flight ring (step-time attribution); DONE flight
//    events carry the response's exec-callback span (us) in aux.
// 9: hvdtpu_set_tuned_params / hvdtpu_get_tuned_params — runtime push of
//    cycle time / fusion threshold / cache / express-lane knobs through
//    the parameter-sync broadcast (HOROVOD_TUNE); TunedParams wire record
//    gains low_latency_threshold_bytes + express_lane.
// 10: topology-aware data plane — hvdtpu_create_session gains host_id
//     (launcher locality map; loopback multi-host simulation);
//     hvdtpu_set_tuned_params gains ring_threshold_bytes / hierarchical /
//     small_tensor_algo (cycle-fenced data-plane routing; TunedParams
//     wire record extended to match); hvdtpu_data_algo_ops exposes the
//     per-algorithm routing counters.
int32_t hvdtpu_abi_version() { return 10; }

namespace {

// Shared contract of the JSON-returning calls below: returns the full
// payload length in bytes (excluding the NUL terminator), or <0 on an
// invalid session. Up to len-1 bytes plus a NUL are written to buf; a
// return value >= len means the caller's buffer was too small — retry
// with a larger one (the snapshot is cheap to recompute).
int64_t CopyJson(const std::string& json, char* buf, int64_t len) {
  if (buf != nullptr && len > 0) {
    int64_t n = std::min<int64_t>(len - 1,
                                  static_cast<int64_t>(json.size()));
    std::memcpy(buf, json.data(), n);
    buf[n] = '\0';
  }
  return static_cast<int64_t>(json.size());
}

}  // namespace

// Runtime metrics snapshot (counters/gauges/histograms populated by the
// controller, tensor queue, response cache, data plane and stall
// inspector). JSON; see MetricsStore::SnapshotJson for the schema.
int64_t hvdtpu_metrics_snapshot(int64_t session, char* buf, int64_t len) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  return CopyJson(e->MetricsSnapshotJson(), buf, len);
}

// Machine-readable stall report: {"stalled":[{"tensor","ready","missing",
// "waited_sec"}...],"warning_sec":N}. Produced on the coordinator by the
// stall inspector's warning scan and broadcast to every rank, so any rank
// can name the missing ranks. Returns 0 (empty) before the first warning.
int64_t hvdtpu_last_stall_report(int64_t session, char* buf, int64_t len) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  return CopyJson(e->LastStallReport(), buf, len);
}

// Flight-recorder dump: the black-box JSON of the last
// HOROVOD_FLIGHT_RECORDER_SIZE collective events on this rank (see
// FlightRecorder::DumpJson for the schema). When `dir` is non-NULL and
// non-empty, also writes <dir>/flight_rank<R>.json (the analyzer's
// input) — only on a call whose caller buffer fits the payload, so the
// Python buffer-retry dance writes the file exactly once and the file
// always equals the returned JSON. Same buffer contract as the other
// JSON calls (CopyJson).
int64_t hvdtpu_flight_dump(int64_t session, const char* dir, char* buf,
                           int64_t len) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  std::string json = e->flight_recorder().DumpJson(
      e->rank(), e->size(), "api", "on-demand dump (hvdtpu_flight_dump)");
  bool fits = buf == nullptr ||
              len > static_cast<int64_t>(json.size());
  if (dir != nullptr && *dir != '\0' && fits) {
    FlightRecorder::WriteDumpFile(dir, e->rank(), json);
  }
  return CopyJson(json, buf, len);
}

// ns per FlightRecorder::Record call (tests/test_flight_recorder.py
// reads it); enabled=0 times the disabled early-out.
double hvdtpu_bench_flight_record(int64_t iters, int32_t enabled) {
  return BenchFlightRecord(iters, enabled != 0);
}

// Frontend step-boundary marks: STEP_BEGIN/STEP_END flight events whose
// aux carries the caller's step id. Driven by the Python step timer
// (horovod_tpu.metrics timed_step) around every train-step invocation so
// the attribution engine can decompose each step window into compute /
// exposed-comm / negotiation-stall / host time. One lock-free flight
// Record per call — cheap enough for every step. Returns 0, or -1 on an
// invalid session.
int32_t hvdtpu_step_begin(int64_t session, int64_t step_id) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  e->StepMark(/*begin=*/true, step_id);
  return 0;
}

int32_t hvdtpu_step_end(int64_t session, int64_t step_id) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  e->StepMark(/*begin=*/false, step_id);
  return 0;
}

// Frontend-tuner knob push: stage a TunedParams record for the next
// coordination cycle's parameter broadcast (every rank adopts at the
// same cycle boundary — rank-divergent fusion/express/routing partitions
// would desync the exec order or deadlock the data plane). Sentinels keep
// the current value: cycle_ms <= 0, fusion_bytes <= 0, low_latency_bytes
// < 0, cache/express < 0, ring_threshold_bytes <= 0, hierarchical < 0,
// small_tensor_algo < 0 (1 = recursive doubling, 0 = star). Effective on
// the coordinator; other ranks' pushes are ignored (they adopt via the
// broadcast). Returns 0, or nonzero with the reason via
// hvdtpu_last_error (multi-rank session without HOROVOD_TUNE=1).
int32_t hvdtpu_set_tuned_params(int64_t session, double cycle_ms,
                                int64_t fusion_bytes, int32_t cache_enabled,
                                int64_t low_latency_bytes,
                                int32_t express_lane,
                                int64_t ring_threshold_bytes,
                                int32_t hierarchical,
                                int32_t small_tensor_algo) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  TunedParams p = e->TunedSnapshot();
  if (cycle_ms > 0) p.cycle_time_ms = cycle_ms;
  if (fusion_bytes > 0) p.fusion_threshold_bytes = fusion_bytes;
  if (cache_enabled >= 0) p.cache_enabled = cache_enabled != 0 ? 1 : 0;
  if (low_latency_bytes >= 0) p.low_latency_threshold_bytes =
      low_latency_bytes;
  if (express_lane >= 0) p.express_lane = express_lane != 0 ? 1 : 0;
  if (ring_threshold_bytes > 0) p.ring_threshold_bytes =
      ring_threshold_bytes;
  if (hierarchical >= 0) p.hierarchical = hierarchical != 0 ? 1 : 0;
  if (small_tensor_algo >= 0) {
    if (small_tensor_algo != kSmallTensorStar &&
        small_tensor_algo != kSmallTensorRecursiveDoubling) {
      SetError("small_tensor_algo must be 0 (star) or 1 (recursive "
               "doubling)");
      return 1;
    }
    p.small_tensor_algo = static_cast<uint8_t>(small_tensor_algo);
  }
  auto st = e->SetTunedParams(p);
  if (!st.ok()) {
    SetError(st.reason);
    return 1;
  }
  return 0;
}

// Currently applied engine knobs as JSON (CopyJson buffer contract):
// {"cycle_time_ms","fusion_threshold_bytes","low_latency_threshold_bytes",
//  "ring_threshold_bytes","cache_enabled","tuning_active","express_lane",
//  "hierarchical","small_tensor_algo"}.
int64_t hvdtpu_get_tuned_params(int64_t session, char* buf, int64_t len) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  TunedParams p = e->TunedSnapshot();
  char json[384];
  std::snprintf(json, sizeof(json),
                "{\"cycle_time_ms\":%.6f,\"fusion_threshold_bytes\":%lld,"
                "\"low_latency_threshold_bytes\":%lld,"
                "\"ring_threshold_bytes\":%lld,\"cache_enabled\":%d,"
                "\"tuning_active\":%d,\"express_lane\":%d,"
                "\"hierarchical\":%d,\"small_tensor_algo\":%d}",
                p.cycle_time_ms,
                static_cast<long long>(p.fusion_threshold_bytes),
                static_cast<long long>(p.low_latency_threshold_bytes),
                static_cast<long long>(p.ring_threshold_bytes),
                static_cast<int>(p.cache_enabled),
                static_cast<int>(p.tuning_active),
                static_cast<int>(p.express_lane),
                static_cast<int>(p.hierarchical),
                static_cast<int>(p.small_tensor_algo));
  return CopyJson(json, buf, len);
}

// Collectives served by the ring data path (diagnostics/tests).
int64_t hvdtpu_data_ring_ops(int64_t session) {
  Engine* e = GetSession(session);
  if (!e || !e->data_plane()) return -1;
  return e->data_plane()->ring_ops();
}

// Collectives served by each data-plane routing algorithm:
// 0 = ring, 1 = recursive doubling, 2 = hierarchical (diagnostics/tests;
// star = total ops minus these, or read the metrics snapshot).
int64_t hvdtpu_data_algo_ops(int64_t session, int32_t algo) {
  Engine* e = GetSession(session);
  if (!e || !e->data_plane()) return -1;
  switch (algo) {
    case 0: return e->data_plane()->ring_ops();
    case 1: return e->data_plane()->rd_ops();
    case 2: return e->data_plane()->hier_ops();
    default: return -1;
  }
}

// Returns session id > 0, or <= 0 on failure (error via
// hvdtpu_last_error()). transport_kind: "loopback" or "tcp". host_id is
// this rank's host index from the launcher topology records (< 0 = no
// locality map — the data plane stays flat); loopback tests pass
// distinct host ids per in-process rank to simulate multi-host grouping.
int64_t hvdtpu_create_session(int32_t rank, int32_t size, int32_t local_rank,
                              int32_t local_size, int32_t host_id,
                              const char* transport_kind,
                              const char* group_or_addr, int32_t port,
                              int32_t data_port,
                              double timeout_sec, double cycle_time_ms,
                              int64_t fusion_threshold_bytes,
                              uint32_t cache_capacity,
                              int32_t cache_enabled,
                              double stall_warning_sec,
                              double stall_shutdown_sec,
                              int32_t stall_check_disable,
                              const char* timeline_path,
                              int32_t timeline_mark_cycles) {
  EngineOptions opts;
  opts.cycle_time_ms = cycle_time_ms;
  opts.fusion_threshold_bytes = fusion_threshold_bytes;
  opts.cache_capacity = cache_capacity;
  opts.cache_enabled = cache_enabled != 0;
  opts.stall_warning_time_sec = stall_warning_sec;
  opts.stall_shutdown_time_sec = stall_shutdown_sec;
  opts.stall_check_disable = stall_check_disable != 0;
  if (timeline_path != nullptr) opts.timeline_path = timeline_path;
  opts.timeline_mark_cycles = timeline_mark_cycles != 0;

  // Serving / low-latency mode knobs, straight from env like the autotune
  // family below (scope=cpp in the Python env registry). Read at session
  // creation so one process can host serving and training sessions with
  // different modes (tests flip the env between creates).
  const char* sm = std::getenv("HOROVOD_SERVING_MODE");
  opts.serving_mode = sm != nullptr && std::strcmp(sm, "0") != 0 &&
                      std::strcmp(sm, "") != 0;
  if (const char* v = std::getenv("HOROVOD_LOW_LATENCY_THRESHOLD")) {
    opts.low_latency_threshold_bytes = std::atoll(v);
  }
  if (const char* v = std::getenv("HOROVOD_SERVING_CYCLE_TIME")) {
    opts.serving_cycle_time_ms = std::atof(v);
  }

  // Data-plane routing seeds (cycle-fenced thereafter via the TunedParams
  // broadcast): the star-vs-ring boundary, the hierarchical allreduce
  // gate (the launcher's --hierarchical-allreduce flag, finally honored
  // by the engine), and the small-tensor route.
  opts.host_id = host_id;
  if (const char* v = std::getenv("HOROVOD_RING_THRESHOLD_BYTES")) {
    if (*v) opts.ring_threshold_bytes = std::atoll(v);
  }
  const char* ha = std::getenv("HOROVOD_HIERARCHICAL_ALLREDUCE");
  opts.hierarchical_allreduce = ha != nullptr && std::strcmp(ha, "0") != 0 &&
                                std::strcmp(ha, "") != 0;
  if (const char* v = std::getenv("HOROVOD_SMALL_TENSOR_ALGO")) {
    if (std::strcmp(v, "rd") == 0 ||
        std::strcmp(v, "recursive_doubling") == 0) {
      opts.small_tensor_algo = kSmallTensorRecursiveDoubling;
    } else if (std::strcmp(v, "star") == 0 || *v == '\0') {
      opts.small_tensor_algo = kSmallTensorStar;
    } else {
      SetError(std::string("HOROVOD_SMALL_TENSOR_ALGO must be 'star' or "
                           "'rd', got '") + v + "'");
      return -1;
    }
  }

  // Frontend-tuner parameter sync: HOROVOD_TUNE keeps the per-cycle
  // TunedParams broadcast alive so hvdtpu_set_tuned_params pushes reach
  // every rank at the same cycle boundary.
  const char* tn = std::getenv("HOROVOD_TUNE");
  opts.param_sync = tn != nullptr && std::strcmp(tn, "0") != 0 &&
                    std::strcmp(tn, "") != 0;

  // Autotune knobs come straight from env (reference parses these in C++
  // too, operations.cc:521-530 + utils/env_parser).
  const char* at = std::getenv("HOROVOD_AUTOTUNE");
  opts.autotune = at != nullptr && std::strcmp(at, "0") != 0 &&
                  std::strcmp(at, "") != 0;
  if (const char* v = std::getenv("HOROVOD_AUTOTUNE_LOG")) {
    opts.autotune_log_path = v;
  }
  if (const char* v = std::getenv("HOROVOD_AUTOTUNE_WARMUP_SAMPLES")) {
    opts.autotune_warmup_samples = std::atoi(v);
  }
  if (const char* v = std::getenv("HOROVOD_AUTOTUNE_STEPS")) {
    opts.autotune_steps = std::atoi(v);
  }
  if (const char* v = std::getenv("HOROVOD_AUTOTUNE_SAMPLE_CYCLES")) {
    opts.autotune_sample_cycles = std::atoi(v);
  }

  TransportConfig tcfg;
  tcfg.kind = transport_kind ? transport_kind : "loopback";
  if (tcfg.kind == "loopback") {
    tcfg.group = group_or_addr ? group_or_addr : "default";
  } else {
    tcfg.addr = group_or_addr ? group_or_addr : "127.0.0.1";
  }
  tcfg.port = port;
  tcfg.data_port = data_port;
  tcfg.timeout_sec = timeout_sec;

  auto engine = std::make_unique<Engine>(rank, size, local_rank, local_size,
                                         opts, tcfg);
  auto st = engine->Init();
  if (!st.ok()) {
    SetError(st.reason);
    return -1;
  }
  std::lock_guard<std::mutex> lock(g_mu);
  int64_t id = g_next_session++;
  g_sessions[id] = std::move(engine);
  return id;
}

int32_t hvdtpu_destroy_session(int64_t session) {
  std::unique_ptr<Engine> engine;
  {
    std::lock_guard<std::mutex> lock(g_mu);
    auto it = g_sessions.find(session);
    if (it == g_sessions.end()) return -1;
    engine = std::move(it->second);
    g_sessions.erase(it);
  }
  engine->Finalize();
  return 0;
}

int32_t hvdtpu_shutdown(int64_t session) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  e->RequestShutdown();
  return 0;
}

// Fast abort: fail every pending and future collective on EVERY rank
// within one coordination cycle (the abort flag + reason ride the next
// cycle's coordination exchange — same mechanism as the stall report).
// The session is unusable afterwards; elastic recovery re-inits.
int32_t hvdtpu_abort(int64_t session, const char* reason) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  e->Abort(reason ? reason : "");
  return 0;
}

// (Re)install a fault-injection spec (HOROVOD_FAULT_SPEC grammar — see
// fault_injector.h) for this process. Empty/NULL disables. Returns 0, or
// nonzero on a malformed spec (message via hvdtpu_last_error). Exposed so
// in-process loopback tests can switch specs without re-exec.
int32_t hvdtpu_set_fault_spec(const char* spec, uint64_t seed) {
  auto st = FaultInjector::Global().Configure(spec ? spec : "", seed);
  if (!st.ok()) {
    SetError(st.reason);
    return static_cast<int32_t>(st.type);
  }
  return 0;
}

int32_t hvdtpu_rank(int64_t session) {
  Engine* e = GetSession(session);
  return e ? e->rank() : -1;
}

int32_t hvdtpu_size(int64_t session) {
  Engine* e = GetSession(session);
  return e ? e->size() : -1;
}

int32_t hvdtpu_local_rank(int64_t session) {
  Engine* e = GetSession(session);
  return e ? e->local_rank() : -1;
}

int32_t hvdtpu_local_size(int64_t session) {
  Engine* e = GetSession(session);
  return e ? e->local_size() : -1;
}

int32_t hvdtpu_healthy(int64_t session) {
  Engine* e = GetSession(session);
  return e ? (e->healthy() ? 1 : 0) : -1;
}

int32_t hvdtpu_set_execute_callback(int64_t session, ExecuteFn fn,
                                    void* user_data) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  e->SetExecuteCallback(fn, user_data);
  return 0;
}

// op_type: 0=allreduce 1=allgather 2=broadcast 3=alltoall 5=barrier.
// Returns 0 and sets *handle, or nonzero (error via hvdtpu_last_error).
int32_t hvdtpu_enqueue(int64_t session, const char* name, int32_t op_type,
                       int32_t dtype, const int64_t* dims, int32_t ndims,
                       int32_t root_rank, int32_t reduce_op,
                       double prescale_factor, double postscale_factor,
                       int32_t group_id, int32_t group_size,
                       const int64_t* splits, int32_t nsplits,
                       int64_t* handle) {
  Engine* e = GetSession(session);
  if (!e) {
    SetError("invalid session");
    return -1;
  }
  TensorTableEntry entry;
  entry.name = name;
  entry.op_type = static_cast<OpType>(op_type);
  entry.dtype = static_cast<DataType>(dtype);
  entry.shape.dims.assign(dims, dims + ndims);
  entry.root_rank = root_rank;
  entry.reduce_op = reduce_op;
  entry.prescale_factor = prescale_factor;
  entry.postscale_factor = postscale_factor;
  entry.group_id = group_id;
  entry.group_size = group_size;
  if (splits != nullptr && nsplits > 0) {
    entry.splits.assign(splits, splits + nsplits);
  }
  auto st = e->EnqueueTensor(std::move(entry), handle);
  if (!st.ok()) {
    SetError(st.reason);
    return static_cast<int32_t>(st.type);
  }
  return 0;
}

int32_t hvdtpu_join(int64_t session, int64_t* handle) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  auto st = e->EnqueueJoin(handle);
  if (!st.ok()) {
    SetError(st.reason);
    return static_cast<int32_t>(st.type);
  }
  return 0;
}

// The last rank whose join completed the previous join epoch (reference:
// torch/mpi_ops.py:846+ return contract); -1 before any join completes.
int32_t hvdtpu_last_joined_rank(int64_t session) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  return e->last_joined_rank();
}

// Returns 1 done, 0 in-flight, <0 error. error_buf receives failure reason.
int32_t hvdtpu_poll(int64_t session, int64_t handle, char* error_buf,
                    int32_t error_buf_len) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  bool done = false;
  std::string err;
  auto st = e->PollHandle(handle, &done, &err);
  if (!st.ok()) {
    SetError(st.reason);
    return -1;
  }
  if (error_buf != nullptr && error_buf_len > 0) {
    std::strncpy(error_buf, err.c_str(), error_buf_len - 1);
    error_buf[error_buf_len - 1] = '\0';
  }
  return done ? 1 : 0;
}

// Returns 0 on success; nonzero failure with message in error_buf.
int32_t hvdtpu_wait(int64_t session, int64_t handle, double timeout_sec,
                    char* error_buf, int32_t error_buf_len) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  auto st = e->WaitHandle(handle, timeout_sec);
  if (error_buf != nullptr && error_buf_len > 0) {
    std::strncpy(error_buf, st.reason.c_str(), error_buf_len - 1);
    error_buf[error_buf_len - 1] = '\0';
  }
  return st.ok() ? 0 : static_cast<int32_t>(st.type);
}

int32_t hvdtpu_start_timeline(int64_t session, const char* path,
                              int32_t mark_cycles) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  // Coordinator-only (see Engine::Initialize): all ranks share the path.
  if (e->rank() != 0) return 0;
  e->timeline().Initialize(path, mark_cycles != 0);
  return 0;
}

int32_t hvdtpu_stop_timeline(int64_t session) {
  Engine* e = GetSession(session);
  if (!e) return -1;
  e->timeline().Shutdown();
  return 0;
}

// Frontend-phase markers nested inside the EXEC span (reference:
// timeline.h:102-154 — MEMCPY_IN_FUSION_BUFFER / COMMUNICATE /
// MEMCPY_OUT_FUSION_BUFFER ride the same per-tensor lane).
int32_t hvdtpu_timeline_activity_start(int64_t session, const char* name,
                                       const char* activity) {
  Engine* e = GetSession(session);
  if (!e || name == nullptr || activity == nullptr) return -1;
  e->timeline().ActivityStart(name, activity);
  return 0;
}

int32_t hvdtpu_timeline_activity_end(int64_t session, const char* name) {
  Engine* e = GetSession(session);
  if (!e || name == nullptr) return -1;
  e->timeline().ActivityEnd(name);
  return 0;
}

const char* hvdtpu_last_error() { return g_last_error.c_str(); }

// --- data plane (callback-thread only; see Engine::data_plane) -----------

namespace {
thread_local std::string g_scratch;
}

int32_t hvdtpu_data_allreduce(int64_t session, void* buffer,
                              int64_t num_elements, int32_t dtype,
                              int32_t kind, double prescale,
                              double postscale) {
  Engine* e = GetSession(session);
  if (!e || !e->data_plane()) return -1;
  auto st = e->data_plane()->Allreduce(
      buffer, num_elements, static_cast<DataType>(dtype),
      static_cast<ReduceKind>(kind), prescale, postscale);
  if (!st.ok()) {
    SetError(st.reason);
    return static_cast<int32_t>(st.type);
  }
  return 0;
}

// Gathers variable-size blobs; per-rank byte counts written to rank_bytes
// (length = size). Total bytes returned; fetch with hvdtpu_data_fetch.
int64_t hvdtpu_data_allgatherv(int64_t session, const void* in,
                               int64_t in_bytes, int64_t* rank_bytes) {
  Engine* e = GetSession(session);
  if (!e || !e->data_plane()) return -1;
  std::vector<int64_t> sizes;
  auto st = e->data_plane()->Allgatherv(in, in_bytes, &g_scratch, &sizes);
  if (!st.ok()) {
    SetError(st.reason);
    return -1;
  }
  for (size_t r = 0; r < sizes.size(); ++r) rank_bytes[r] = sizes[r];
  return static_cast<int64_t>(g_scratch.size());
}

int32_t hvdtpu_data_bcast(int64_t session, void* buffer, int64_t nbytes,
                          int32_t root) {
  Engine* e = GetSession(session);
  if (!e || !e->data_plane()) return -1;
  auto st = e->data_plane()->Bcast(buffer, nbytes, root);
  if (!st.ok()) {
    SetError(st.reason);
    return static_cast<int32_t>(st.type);
  }
  return 0;
}

int64_t hvdtpu_data_alltoallv(int64_t session, const void* in,
                              const int64_t* send_bytes, int32_t nsend,
                              int64_t* recv_bytes) {
  Engine* e = GetSession(session);
  if (!e || !e->data_plane()) return -1;
  std::vector<int64_t> sends(send_bytes, send_bytes + nsend);
  std::vector<int64_t> recvs;
  auto st = e->data_plane()->Alltoallv(in, sends, &g_scratch, &recvs);
  if (!st.ok()) {
    SetError(st.reason);
    return -1;
  }
  for (size_t r = 0; r < recvs.size(); ++r) recv_bytes[r] = recvs[r];
  return static_cast<int64_t>(g_scratch.size());
}

int32_t hvdtpu_data_fetch(int64_t session, void* dst, int64_t nbytes) {
  if (static_cast<size_t>(nbytes) > g_scratch.size()) return -1;
  std::memcpy(dst, g_scratch.data(), nbytes);
  return 0;
}

}  // extern "C"

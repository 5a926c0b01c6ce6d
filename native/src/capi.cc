// C API consumed by torchft_tpu/_native.py via ctypes. Strings cross the
// boundary as malloc'd char* (caller frees with tft_string_free); structured
// values as JSON. Status codes: 0 ok, 1 timeout (Python raises TimeoutError,
// mirroring the reference's gRPC-status mapping in src/lib.rs:321-333),
// 2 other error (Python raises RuntimeError with tft_last_error()).
#include <cstring>
#include <string>

#include "collectives.h"
#include "fault.h"
#include "json.h"
#include "lighthouse.h"
#include "manager.h"
#include "net.h"
#include "quorum.h"
#include "region.h"
#include "shm.h"
#include "store.h"
#include "wal.h"
#include "wire.h"

using namespace tft;

namespace {

thread_local std::string g_last_error;

constexpr int kOk = 0;
constexpr int kTimeout = 1;
constexpr int kError = 2;

char* dup_string(const std::string& s) {
  char* out = static_cast<char*>(malloc(s.size() + 1));
  memcpy(out, s.data(), s.size());
  out[s.size()] = '\0';
  return out;
}

char* dup_bytes(const std::string& s, size_t* len_out) {
  char* out = static_cast<char*>(malloc(s.size() ? s.size() : 1));
  memcpy(out, s.data(), s.size());
  *len_out = s.size();
  return out;
}

bool is_timeout(const torchft_tpu::ErrorResponse::Code code) {
  return code == torchft_tpu::ErrorResponse::DEADLINE_EXCEEDED ||
         code == torchft_tpu::ErrorResponse::CANCELLED;
}

// Runs fn, translating exceptions to status codes.
template <typename Fn>
int guarded(Fn&& fn) {
  try {
    fn();
    return kOk;
  } catch (const TimeoutError& e) {
    g_last_error = e.what();
    return kTimeout;
  } catch (const RpcError& e) {
    g_last_error = e.what();
    return is_timeout(e.code) ? kTimeout : kError;
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return kError;
  } catch (...) {
    g_last_error = "unknown error";
    return kError;
  }
}

} // namespace

extern "C" {

const char* tft_last_error() { return g_last_error.c_str(); }

void tft_string_free(char* s) { free(s); }

// ---- Lighthouse ----

// wal_dir ("" = no durability), peers ("" = no failover set; comma-
// separated other root endpoints), standby (1 = start passive) and
// takeover_ms (0 = default) are the durable-control-plane knobs — see
// native/src/lighthouse.h and docs/OPERATIONS.md "control-plane
// durability & failover".
void* tft_lighthouse_create(const char* bind, uint64_t min_replicas,
                            int64_t join_timeout_ms, int64_t quorum_tick_ms,
                            int64_t heartbeat_timeout_ms, const char* wal_dir,
                            int64_t snapshot_every, const char* peers,
                            int standby, int64_t takeover_ms) {
  Lighthouse* lh = nullptr;
  int rc = guarded([&] {
    LighthouseOpt opt;
    opt.min_replicas = min_replicas;
    opt.join_timeout_ms = join_timeout_ms;
    opt.quorum_tick_ms = quorum_tick_ms;
    opt.heartbeat_timeout_ms = heartbeat_timeout_ms;
    opt.wal_dir = wal_dir ? wal_dir : "";
    opt.snapshot_every = snapshot_every;
    opt.peers = peers ? peers : "";
    opt.standby = standby != 0;
    opt.takeover_ms = takeover_ms;
    lh = new Lighthouse(bind, opt);
  });
  return rc == kOk ? lh : nullptr;
}

// Whether this root is ACTIVE (serving) vs a passive warm standby.
int tft_lighthouse_active(void* handle) {
  return static_cast<Lighthouse*>(handle)->active() ? 1 : 0;
}

// Monotonic root epoch (0 = never active; fenced through the WAL).
int64_t tft_lighthouse_root_epoch(void* handle) {
  return static_cast<Lighthouse*>(handle)->root_epoch();
}

char* tft_lighthouse_address(void* handle) {
  return dup_string(static_cast<Lighthouse*>(handle)->address());
}

void tft_lighthouse_shutdown(void* handle) {
  static_cast<Lighthouse*>(handle)->shutdown();
}

void tft_lighthouse_destroy(void* handle) {
  delete static_cast<Lighthouse*>(handle);
}

int tft_lighthouse_heartbeat(const char* addr, const char* replica_id,
                             int64_t timeout_ms) {
  return guarded([&] {
    LighthouseClient client(addr, timeout_ms);
    client.heartbeat(replica_id, timeout_ms);
  });
}

int tft_lighthouse_status_json(void* handle, char** out) {
  return guarded(
      [&] { *out = dup_string(static_cast<Lighthouse*>(handle)->status_json()); });
}

// ---- RegionLighthouse ----

void* tft_region_create(const char* bind, const char* root_addr,
                        const char* region_id, int64_t digest_interval_ms,
                        int64_t heartbeat_timeout_ms, int64_t connect_timeout_ms) {
  RegionLighthouse* r = nullptr;
  int rc = guarded([&] {
    RegionOpt opt;
    if (digest_interval_ms > 0) opt.digest_interval_ms = digest_interval_ms;
    if (heartbeat_timeout_ms > 0) opt.heartbeat_timeout_ms = heartbeat_timeout_ms;
    if (connect_timeout_ms > 0) opt.connect_timeout_ms = connect_timeout_ms;
    r = new RegionLighthouse(bind, root_addr, region_id, opt);
  });
  return rc == kOk ? r : nullptr;
}

char* tft_region_address(void* handle) {
  return dup_string(static_cast<RegionLighthouse*>(handle)->address());
}

void tft_region_shutdown(void* handle) {
  static_cast<RegionLighthouse*>(handle)->shutdown();
}

void tft_region_destroy(void* handle) {
  delete static_cast<RegionLighthouse*>(handle);
}

int tft_region_status_json(void* handle, char** out) {
  return guarded([&] {
    *out = dup_string(static_cast<RegionLighthouse*>(handle)->status_json());
  });
}

// The region-side quorum cache: the last root quorum served locally with
// its refresh age (no root round trip per read).
int tft_region_quorum_json(void* handle, char** out) {
  return guarded([&] {
    *out = dup_string(static_cast<RegionLighthouse*>(handle)->quorum_json());
  });
}

// ---- LeaseClient (persistent lighthouse-protocol client) ----

// A LighthouseClient handle for batch lease renewal / heartbeat / depart
// over ONE persistent connection — the wire surface host-level renewal
// batchers and the control-plane tests' simulated groups ride.

void* tft_lease_client_create(const char* addr, int64_t connect_timeout_ms) {
  return new LighthouseClient(addr, connect_timeout_ms);
}

void tft_lease_client_destroy(void* handle) {
  delete static_cast<LighthouseClient*>(handle);
}

// entries_json: [{replica_id, ttl_ms, participating, member: {...}}, ...].
// Writes the lighthouse's current quorum_id to *quorum_id_out.
int tft_lease_client_renew(void* handle, const char* entries_json,
                           int64_t timeout_ms, int64_t* quorum_id_out) {
  return guarded([&] {
    std::vector<LeaseEntry> entries =
        lease_entries_from_json(Json::parse(entries_json));
    *quorum_id_out =
        static_cast<LighthouseClient*>(handle)->lease_renew(entries, timeout_ms);
  });
}

int tft_lease_client_heartbeat(void* handle, const char* replica_id,
                               int64_t timeout_ms) {
  return guarded([&] {
    static_cast<LighthouseClient*>(handle)->heartbeat(replica_id, timeout_ms);
  });
}

int tft_lease_client_depart(void* handle, const char* replica_id,
                            int64_t timeout_ms) {
  return guarded([&] {
    static_cast<LighthouseClient*>(handle)->depart(replica_id, timeout_ms);
  });
}

// ---- ManagerServer ----

// lighthouse_addr and root_addr may be COMMA-SEPARATED endpoint lists
// (root failover sets); region_probe_max bounds the demoted manager's
// region re-probes (0 = probe forever, the pre-durability behavior).
void* tft_manager_create(const char* replica_id, const char* lighthouse_addr,
                         const char* hostname, const char* bind,
                         const char* store_addr, uint64_t world_size,
                         int64_t heartbeat_interval_ms, int64_t connect_timeout_ms,
                         const char* root_addr, int64_t lease_ttl_ms,
                         const char* region, const char* host,
                         int64_t region_probe_max) {
  ManagerServer* m = nullptr;
  int rc = guarded([&] {
    m = new ManagerServer(replica_id, lighthouse_addr, hostname, bind, store_addr,
                          world_size, heartbeat_interval_ms, connect_timeout_ms,
                          root_addr ? root_addr : "", lease_ttl_ms,
                          region ? region : "", host ? host : "",
                          region_probe_max);
  });
  return rc == kOk ? m : nullptr;
}

// Whether the manager is currently demoted to direct-root registration
// (region failover active).
int tft_manager_using_root(void* handle) {
  return static_cast<ManagerServer*>(handle)->using_root_fallback() ? 1 : 0;
}

// Whether the bounded region re-probe gave up (region_probe_max
// consecutive failures while demoted) — the manager stays on the root.
int tft_manager_probe_given_up(void* handle) {
  return static_cast<ManagerServer*>(handle)->region_probe_given_up() ? 1 : 0;
}

// Publishes a member-health digest (JSON) carried on subsequent lease
// renewals into the lighthouse's per-member /status.json view.
int tft_manager_set_status(void* handle, const char* status_json) {
  return guarded([&] {
    static_cast<ManagerServer*>(handle)->set_status_json(
        status_json ? status_json : "");
  });
}

char* tft_manager_address(void* handle) {
  return dup_string(static_cast<ManagerServer*>(handle)->address());
}

void tft_manager_shutdown(void* handle) {
  static_cast<ManagerServer*>(handle)->shutdown();
}

void tft_manager_destroy(void* handle) {
  delete static_cast<ManagerServer*>(handle);
}

// ---- ManagerClient ----

void* tft_client_create(const char* addr, int64_t connect_timeout_ms) {
  return new ManagerClient(addr, connect_timeout_ms);
}

void tft_client_destroy(void* handle) {
  delete static_cast<ManagerClient*>(handle);
}

int tft_client_quorum(void* handle, int64_t rank, int64_t step,
                      const char* checkpoint_metadata, int shrink_only,
                      int force_reconfigure, int64_t timeout_ms,
                      char** result_json) {
  return guarded([&] {
    auto resp = static_cast<ManagerClient*>(handle)->quorum(
        rank, step, checkpoint_metadata, shrink_only != 0,
        force_reconfigure != 0, timeout_ms);
    *result_json = dup_string(quorum_response_to_json(resp).dump());
  });
}

int tft_client_checkpoint_metadata(void* handle, int64_t rank, int64_t timeout_ms,
                                   char** metadata_out) {
  return guarded([&] {
    *metadata_out = dup_string(
        static_cast<ManagerClient*>(handle)->checkpoint_metadata(rank, timeout_ms));
  });
}

int tft_client_should_commit(void* handle, int64_t rank, int64_t step,
                             int should_commit, int64_t timeout_ms, int* result) {
  return guarded([&] {
    *result = static_cast<ManagerClient*>(handle)->should_commit(
                  rank, step, should_commit != 0, timeout_ms)
                  ? 1
                  : 0;
  });
}

int tft_client_kill(void* handle, const char* msg) {
  return guarded([&] { static_cast<ManagerClient*>(handle)->kill(msg); });
}

// ---- Store ----

void* tft_store_create(const char* bind) {
  StoreServer* s = nullptr;
  int rc = guarded([&] { s = new StoreServer(bind); });
  return rc == kOk ? s : nullptr;
}

char* tft_store_address(void* handle) {
  return dup_string(static_cast<StoreServer*>(handle)->address());
}

int tft_store_port(void* handle) {
  return static_cast<StoreServer*>(handle)->port();
}

void tft_store_shutdown(void* handle) {
  static_cast<StoreServer*>(handle)->shutdown();
}

void tft_store_destroy(void* handle) {
  delete static_cast<StoreServer*>(handle);
}

void* tft_store_client_create(const char* addr, int64_t connect_timeout_ms) {
  StoreClient* c = nullptr;
  int rc = guarded([&] { c = new StoreClient(addr, connect_timeout_ms); });
  return rc == kOk ? c : nullptr;
}

void tft_store_client_destroy(void* handle) {
  delete static_cast<StoreClient*>(handle);
}

int tft_store_client_set(void* handle, const char* key, const char* value,
                         size_t value_len, int64_t timeout_ms) {
  return guarded([&] {
    static_cast<StoreClient*>(handle)->set(key, std::string(value, value_len),
                                           timeout_ms);
  });
}

int tft_store_client_get(void* handle, const char* key, int64_t timeout_ms,
                         char** value_out, size_t* value_len_out) {
  return guarded([&] {
    std::string v = static_cast<StoreClient*>(handle)->get(key, timeout_ms);
    *value_out = dup_bytes(v, value_len_out);
  });
}

int tft_store_client_add(void* handle, const char* key, int64_t delta,
                         int64_t timeout_ms, int64_t* value_out) {
  return guarded([&] {
    *value_out = static_cast<StoreClient*>(handle)->add(key, delta, timeout_ms);
  });
}

// ---- HostCollectives ----

void* tft_hc_create() { return new HostCollectives(); }

void tft_hc_destroy(void* handle) { delete static_cast<HostCollectives*>(handle); }

int tft_hc_configure(void* handle, const char* store_addr, int64_t rank,
                     int64_t world_size, int64_t timeout_ms, int64_t stripes) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->configure(store_addr, rank, world_size,
                                                     timeout_ms, stripes);
  });
}

// Configure with a REGION and/or HOST MAP: each *_json is a JSON array
// of one label per rank ("" = unlabeled; null/empty string = no map).
// With >= 2 distinct region labels the intra/inter tiers are built
// alongside the flat ring; with a host map grouping >= 2 co-hosted
// ranks the shared-memory HOST tier is built below them
// (TORCHFT_HC_SHM=0 falls it back to loopback TCP). stripes_inter
// (<= 0: = stripes) is the inter (leader) ring's connection count.
int tft_hc_configure_hier(void* handle, const char* store_addr, int64_t rank,
                          int64_t world_size, int64_t timeout_ms,
                          int64_t stripes, int64_t stripes_inter,
                          const char* regions_json, const char* hosts_json) {
  return guarded([&] {
    auto parse_labels = [](const char* js) {
      std::vector<std::string> out;
      if (js != nullptr && js[0] != '\0') {
        // Bound to a local: `Json::parse(...).as_array()` in the
        // range-for would destroy the temporary before the loop body
        // runs (the classic pre-C++23 range-for dangling reference).
        Json parsed = Json::parse(js);
        for (const auto& r : parsed.as_array()) out.push_back(r.as_string());
      }
      return out;
    };
    static_cast<HostCollectives*>(handle)->configure(
        store_addr, rank, world_size, timeout_ms, stripes,
        parse_labels(regions_json), stripes_inter, parse_labels(hosts_json));
  });
}

// Whether the last configure built a hierarchical topology (region
// and/or host tiers).
int64_t tft_hc_hier_capable(void* handle) {
  return static_cast<HostCollectives*>(handle)->hier_capable() ? 1 : 0;
}

// Host-tier transport of the last configure: 0 = no host tier, 1 =
// loopback TCP (TORCHFT_HC_SHM=0), 2 = shared-memory rings.
int64_t tft_hc_host_tier_transport(void* handle) {
  return static_cast<HostCollectives*>(handle)->host_tier_transport();
}

// abort() + deterministic release of every ring resource (sockets,
// listener, shm segments) without destroying the handle; a later
// configure rebuilds. The Python shutdown() path — segment lifetime must
// not ride garbage-collection timing.
int tft_hc_release(void* handle) {
  return guarded(
      [&] { static_cast<HostCollectives*>(handle)->release_rings(); });
}

// In-place two-tier allreduce (see HostCollectives::allreduce_hier).
// wire: 0 native across regions, 1 bf16 inter hop, 2 q8 inter hop.
int tft_hc_allreduce_hier(void* handle, void* data, size_t count, int dtype,
                          int op, int wire, int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->allreduce_hier(
        data, count, static_cast<Dtype>(dtype), static_cast<ReduceOp>(op),
        static_cast<HierWire>(wire), timeout_ms);
  });
}

// Phase/byte breakdown of the last hierarchical op as JSON (measured
// per-tier tx bytes; see HostCollectives::last_hier_json). Caller frees
// via tft_string_free.
int tft_hc_last_hier_json(void* handle, char** out) {
  return guarded([&] {
    *out = dup_string(static_cast<HostCollectives*>(handle)->last_hier_json());
  });
}

int tft_hc_allreduce(void* handle, void* data, size_t count, int dtype, int op,
                     int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->allreduce(
        data, count, static_cast<Dtype>(dtype), static_cast<ReduceOp>(op),
        timeout_ms);
  });
}

int tft_hc_allreduce_q8(void* handle, float* data, size_t count,
                        int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->allreduce_q8(data, count,
                                                        timeout_ms);
  });
}

int tft_hc_reduce_scatter(void* handle, void* data, size_t count, int dtype,
                          int op, void* shard_out, int64_t layout_stripes,
                          int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->reduce_scatter(
        data, count, static_cast<Dtype>(dtype), static_cast<ReduceOp>(op),
        shard_out, layout_stripes, timeout_ms);
  });
}

int tft_hc_reduce_scatter_q8(void* handle, float* data, size_t count,
                             float* shard_out, int grid_shard,
                             int64_t layout_stripes, int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->reduce_scatter_q8(
        data, count, shard_out, grid_shard != 0, layout_stripes, timeout_ms);
  });
}

int tft_hc_allgather_into(void* handle, const void* shard, void* data,
                          size_t count, int dtype, int64_t layout_stripes,
                          int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->allgather_into(
        shard, data, count, static_cast<Dtype>(dtype), layout_stripes,
        timeout_ms);
  });
}

// Writes up to `cap` (start, len) element pairs of rank `rank`'s shard into
// `out` (flattened pairs); returns the number of pairs, or -1 on error
// (tft_last_error set). Pure layout arithmetic once configured.
int64_t tft_hc_shard_ranges(void* handle, size_t count, size_t esize,
                            int64_t rank, int64_t layout_stripes, int64_t* out,
                            int64_t cap) {
  std::vector<std::pair<size_t, size_t>> ranges;
  int rc = guarded([&] {
    ranges = static_cast<HostCollectives*>(handle)->shard_ranges(
        count, esize, rank, layout_stripes);
  });
  if (rc != kOk) return -1;
  int64_t n = static_cast<int64_t>(ranges.size());
  for (int64_t i = 0; i < n && i < cap; i++) {
    out[2 * i] = static_cast<int64_t>(ranges[i].first);
    out[2 * i + 1] = static_cast<int64_t>(ranges[i].second);
  }
  return n;
}

// ---- persistent comm plans ----

// Builds a CommPlan for a leaf signature; returns the plan id (> 0) or -1
// with tft_last_error set. wire: 0 native dtypes, 1 bf16, 2 q8, 3 q8+EF.
int64_t tft_plan_build(void* handle, const int64_t* counts,
                       const int32_t* dtypes, int64_t n_leaves, int wire) {
  int64_t id = -1;
  int rc = guarded([&] {
    id = static_cast<HostCollectives*>(handle)->plan_build(
        counts, dtypes, n_leaves, static_cast<PlanWire>(wire));
  });
  return rc == kOk ? id : -1;
}

// One gradient sync over the plan: a single GIL-released call that packs
// leaf_in, rides the striped ring, and unpacks (dividing when
// has_divisor) into leaf_out. Both pointer arrays are n_leaves long, in
// signature order.
int tft_plan_execute(void* handle, int64_t plan_id, const void* const* leaf_in,
                     void* const* leaf_out, double divisor, int has_divisor,
                     int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->plan_execute(
        plan_id, leaf_in, leaf_out, divisor, has_divisor != 0, timeout_ms);
  });
}

// Builds a PREPACKED CommPlan: execute takes per-GROUP wire buffers the
// caller (the device-side Pallas pack) already encoded, so the pack stage
// is a straight decode. Same wire contract as tft_plan_build — prepacked
// and plain plans of one signature interoperate in one ring.
int64_t tft_plan_build_pre(void* handle, const int64_t* counts,
                           const int32_t* dtypes, int64_t n_leaves, int wire) {
  int64_t id = -1;
  int rc = guarded([&] {
    id = static_cast<HostCollectives*>(handle)->plan_build(
        counts, dtypes, n_leaves, static_cast<PlanWire>(wire),
        /*prepacked=*/true);
  });
  return rc == kOk ? id : -1;
}

// Builds a HIERARCHICAL CommPlan: execute (tft_plan_execute) runs the
// two-tier schedule — intra reduce-scatter/allgather, inter ring among
// region leaders at `wire` (bf16/q8/q8+EF applied at the slow hop ONLY;
// staging and the intra tier stay native width), chunk-pipelined intra
// broadcast. Requires a region-map configure (tft_hc_configure_hier) at
// execute time; the signature hash bakes the hier geometry in, so a hier
// plan meeting a flat plan errors instead of desyncing.
int64_t tft_plan_build_hier(void* handle, const int64_t* counts,
                            const int32_t* dtypes, int64_t n_leaves,
                            int wire) {
  int64_t id = -1;
  int rc = guarded([&] {
    id = static_cast<HostCollectives*>(handle)->plan_build(
        counts, dtypes, n_leaves, static_cast<PlanWire>(wire),
        /*prepacked=*/false, /*hier=*/true);
  });
  return rc == kOk ? id : -1;
}

// One gradient sync over a prepacked plan: group_in[g] is group g's wire
// payload (g.count staging-dtype elements — int8 codes for q8 wires),
// group_aux[g] its per-leaf f32 scale sidecar (q8 only; may be null
// otherwise). Both arrays are n_groups long in plan group order;
// leaf_out is n_leaves long in signature order.
int tft_plan_execute_pre(void* handle, int64_t plan_id,
                         const void* const* group_in,
                         const void* const* group_aux, void* const* leaf_out,
                         double divisor, int has_divisor, int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->plan_execute_pre(
        plan_id, group_in, group_aux, leaf_out, divisor, has_divisor != 0,
        timeout_ms);
  });
}

// ---- sharded comm plans (per-step ZeRO) ----

// Builds a SHARDED CommPlan: the fused allreduce split at the
// reduce-scatter boundary so the caller can update only the 1/W shard it
// owns and allgather the updated params. f32 leaves only; rs_wire
// (0 native, 1 bf16, 2 q8) encodes the grad leg — the owner's shard
// lands full f32 regardless — and ag_wire (0 native, 1 bf16) the param
// leg. Returns the plan id (> 0) or -1 with tft_last_error set.
int64_t tft_plan_build_sharded(void* handle, const int64_t* counts,
                               const int32_t* dtypes, int64_t n_leaves,
                               int rs_wire, int ag_wire) {
  int64_t id = -1;
  int rc = guarded([&] {
    id = static_cast<HostCollectives*>(handle)->plan_build_sharded(
        counts, dtypes, n_leaves, static_cast<PlanWire>(rs_wire),
        static_cast<PlanWire>(ag_wire));
  });
  return rc == kOk ? id : -1;
}

// Grad leg of a sharded plan: packs leaf_in (n_leaves, signature order),
// rides the reduce-scatter phase, compacts the rank-owned shard into
// shard_out (tft_plan_sharded_meta's shard_count f32 elements) with the
// divisor applied to the shard only.
int tft_plan_execute_rs(void* handle, int64_t plan_id,
                        const void* const* leaf_in, float* shard_out,
                        double divisor, int has_divisor, int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->plan_execute_rs(
        plan_id, leaf_in, shard_out, divisor, has_divisor != 0, timeout_ms);
  });
}

// Param leg of a sharded plan: scatters shard_in (the updated shard,
// same layout) back, rides the allgather phase at the plan's ag wire and
// unpacks into leaf_out (n_leaves, signature order), no divisor.
int tft_plan_execute_ag(void* handle, int64_t plan_id, const float* shard_in,
                        void* const* leaf_out, int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->plan_execute_ag(
        plan_id, shard_in, leaf_out, timeout_ms);
  });
}

// out3[0] = this rank's shard element count, out3[1] = the plan's stripe
// partition (pass it to tft_hc_shard_ranges as layout_stripes), out3[2]
// = total flat element count.
int tft_plan_sharded_meta(void* handle, int64_t plan_id, int64_t* out3) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->plan_sharded_meta(plan_id, out3);
  });
}

int tft_plan_free(void* handle, int64_t plan_id) {
  return guarded(
      [&] { static_cast<HostCollectives*>(handle)->plan_free(plan_id); });
}

int tft_plan_reset_feedback(void* handle, int64_t plan_id) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->plan_reset_feedback(plan_id);
  });
}

// Per-bucket phase timings of the plan's last execute, as JSON.
int tft_plan_stats_json(void* handle, int64_t plan_id, char** out) {
  return guarded([&] {
    *out = dup_string(
        static_cast<HostCollectives*>(handle)->plan_stats_json(plan_id));
  });
}

int tft_hc_allgather(void* handle, const void* in, void* out, size_t nbytes,
                     int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->allgather(in, out, nbytes, timeout_ms);
  });
}

int tft_hc_broadcast(void* handle, void* data, size_t nbytes, int64_t root,
                     int64_t timeout_ms) {
  return guarded([&] {
    static_cast<HostCollectives*>(handle)->broadcast(data, nbytes, root,
                                                     timeout_ms);
  });
}

int tft_hc_barrier(void* handle, int64_t timeout_ms) {
  return guarded(
      [&] { static_cast<HostCollectives*>(handle)->barrier(timeout_ms); });
}

void tft_hc_abort(void* handle) { static_cast<HostCollectives*>(handle)->abort(); }

// Requests per-frame CRC32C on the ring wire for the NEXT configure
// (default: TORCHFT_WIRE_CRC). All members must agree — the hello magic
// carries the frame format, and the Python layer negotiates the knob
// through the store like stripes.
void tft_hc_set_wire_crc(void* handle, int on) {
  static_cast<HostCollectives*>(handle)->set_wire_crc(on != 0);
}

// Whether the ACTIVE ring (last configure) runs the CRC-guarded frames.
int tft_hc_wire_crc(void* handle) {
  return static_cast<HostCollectives*>(handle)->wire_crc() ? 1 : 0;
}

// ---- chaos plane (deterministic fault injection) ----
// The seeded fault schedule is process-global: rules match on (seam,
// member, op_index) so one armed plan drives every member hosted by the
// process (thread fleets included). See native/src/fault.h.

// Arms (replaces) the fault plan: {"seed": u64, "rules": [{"seam":
// "ring_send"|"net_send"|..., "kind": "drop"|"delay"|"truncate"|
// "duplicate"|"bit_flip"|"partition", "member": -1|rank, "min_op",
// "max_op", "permille", "max_fires", "param"}]}. Stats persist across
// re-arms (the harness re-arms per step); tft_fault_disarm resets them.
int tft_fault_arm(const char* plan_json) {
  return guarded([&] { fault::arm_from_json(plan_json ? plan_json : "{}"); });
}

void tft_fault_disarm(void) { fault::disarm(); }

int tft_fault_armed(void) { return fault::armed() ? 1 : 0; }

// Injection stats: {"armed", "fired_total", "fired": {"seam:kind": n}}.
int tft_fault_stats_json(char** out) {
  return guarded([&] { *out = dup_string(fault::stats_json()); });
}

// CRC32C (Castagnoli) over a buffer — the same polynomial the ring
// frames ride; exposed so the Python heal stream and tests share one
// implementation.
uint32_t tft_crc32c(const void* data, uint64_t len) {
  return fault::crc32c(data, static_cast<size_t>(len));
}

// Incremental form for non-contiguous payloads (the heal staging's
// per-leaf segments): seed with 0xFFFFFFFF, chain updates, invert at the
// end — exactly what tft_crc32c does for one buffer.
uint32_t tft_crc32c_update(uint32_t state, const void* data, uint64_t len) {
  return fault::crc32c_update(state, data, static_cast<size_t>(len));
}

int64_t tft_hc_world_size(void* handle) {
  return static_cast<HostCollectives*>(handle)->world_size();
}

int64_t tft_hc_stripes(void* handle) {
  return static_cast<HostCollectives*>(handle)->stripes();
}

// Copies up to `cap` per-stripe wall times (ns) of the last bulk op into
// `out`; returns how many stripes the op actually ran. Must be called from
// the thread that issued the op (the Python single-op executor), which is
// the only thread that reads these between ops.
int64_t tft_hc_last_stripe_ns(void* handle, int64_t* out, int64_t cap) {
  const auto& ns = static_cast<HostCollectives*>(handle)->last_stripe_ns();
  int64_t n = static_cast<int64_t>(ns.size());
  for (int64_t i = 0; i < n && i < cap; i++) out[i] = ns[i];
  return n;
}

// ---- shared-memory segments (isolated accelerator data plane) ----
// Lifecycle for the POSIX shm staging buffers the isolated XLA backend
// feeds its disposable child through (see native/src/shm.h for the
// ownership contract: the creator unlinks, attachments never do, and a
// SIGKILLed child's mapping vanishes with it while the parent's survives).

void* tft_shm_create(const char* name, int64_t bytes) {
  try {
    return ShmSegment::Create(name, static_cast<size_t>(bytes));
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return nullptr;
  }
}

void* tft_shm_attach(const char* name, int64_t bytes) {
  try {
    return ShmSegment::Attach(name, static_cast<size_t>(bytes));
  } catch (const std::exception& e) {
    g_last_error = e.what();
    return nullptr;
  }
}

void* tft_shm_data(void* handle) {
  return static_cast<ShmSegment*>(handle)->data();
}

int64_t tft_shm_size(void* handle) {
  return static_cast<int64_t>(static_cast<ShmSegment*>(handle)->size());
}

void tft_shm_close(void* handle) { delete static_cast<ShmSegment*>(handle); }

int tft_shm_unlink(const char* name) {
  return guarded([&] { ShmSegment::Unlink(name); });
}

int64_t tft_shm_live_count() { return ShmSegment::live_count(); }

// The CommPlan leaf->offset layout both sides of the shm boundary lay
// payloads out with (the authority the Python mirror is pinned against).
// wire: 0 native dtypes, 1 bf16, 2 q8, 3 q8+EF — plan_build's codes.
int tft_shm_layout_json(const int64_t* counts, const int32_t* dtypes,
                        int64_t n_leaves, int wire, char** out) {
  return guarded([&] {
    *out = dup_string(shm_layout_json(counts, dtypes, n_leaves, wire));
  });
}

// ---- pure functions (test entry points) ----

// state_json: {participants: {id: {joined_ms, member: {...}}}, heartbeats:
// {id: ms}, prev_quorum: {...}|null, quorum_id: int}; opt_json: LighthouseOpt
// fields. Returns {"quorum": [members]|null, "reason": str}.
int tft_quorum_compute(int64_t now, const char* state_json, const char* opt_json,
                       char** result_json) {
  return guarded([&] {
    LighthouseState state = lighthouse_state_from_json(Json::parse(state_json));
    LighthouseOpt opt = lighthouse_opt_from_json(Json::parse(opt_json));
    auto [quorum, reason] = quorum_compute(now, state, opt);
    JsonObject out;
    if (quorum.has_value()) {
      JsonArray arr;
      for (const auto& m : *quorum) arr.push_back(member_to_json(m));
      out["quorum"] = Json(std::move(arr));
    } else {
      out["quorum"] = Json();
    }
    out["reason"] = reason;
    *result_json = dup_string(Json(std::move(out)).dump());
  });
}

int tft_compute_quorum_results(const char* replica_id, int64_t rank,
                               const char* quorum_json, char** result_json) {
  return guarded([&] {
    torchft_tpu::Quorum quorum = quorum_from_json(Json::parse(quorum_json));
    auto resp = compute_quorum_results(replica_id, rank, quorum);
    *result_json = dup_string(quorum_response_to_json(resp).dump());
  });
}

// One full quorum tick as a pure state transition (the exact function both
// the flat lighthouse and the hierarchical root run per tick). Returns
// {"state": ..., "quorum": {...}|null, "changed": bool, "reason": str} —
// the entry point of the flat-vs-hierarchical equivalence property suite.
int tft_quorum_step(int64_t now, int64_t unix_now, const char* state_json,
                    const char* opt_json, char** result_json) {
  return guarded([&] {
    LighthouseState state = lighthouse_state_from_json(Json::parse(state_json));
    LighthouseOpt opt = lighthouse_opt_from_json(Json::parse(opt_json));
    QuorumStepResult res = quorum_step(now, unix_now, state, opt);
    JsonObject out;
    out["state"] = lighthouse_state_to_json(state);
    out["quorum"] = res.quorum.has_value() ? quorum_to_json(*res.quorum) : Json();
    out["changed"] = res.changed;
    out["reason"] = res.reason;
    *result_json = dup_string(Json(std::move(out)).dump());
  });
}

// Applies a batched lease renewal to a state; returns the new state JSON.
int tft_lease_apply(const char* state_json, const char* entries_json, int64_t now,
                    char** result_json) {
  return guarded([&] {
    LighthouseState state = lighthouse_state_from_json(Json::parse(state_json));
    apply_lease_batch(state, lease_entries_from_json(Json::parse(entries_json)),
                      now);
    *result_json = dup_string(lighthouse_state_to_json(state).dump());
  });
}

// Explicit depart; returns the new state JSON.
int tft_depart_apply(const char* state_json, const char* replica_id,
                     char** result_json) {
  return guarded([&] {
    LighthouseState state = lighthouse_state_from_json(Json::parse(state_json));
    apply_depart(state, replica_id);
    *result_json = dup_string(lighthouse_state_to_json(state).dump());
  });
}

// Region side of the digest protocol: compresses a region state to
// age-relative entries at `now` on the region clock.
int tft_digest_make(const char* state_json, int64_t now, const char* opt_json,
                    char** result_json) {
  return guarded([&] {
    LighthouseState state = lighthouse_state_from_json(Json::parse(state_json));
    LighthouseOpt opt = lighthouse_opt_from_json(Json::parse(opt_json));
    *result_json = dup_string(digest_to_json(make_digest(state, now, opt)).dump());
  });
}

// Root side: merges a digest into a state at `now` on the root clock;
// returns the new state JSON.
int tft_digest_apply(const char* state_json, const char* digest_json, int64_t now,
                     char** result_json) {
  return guarded([&] {
    LighthouseState state = lighthouse_state_from_json(Json::parse(state_json));
    apply_digest(state, digest_from_json(Json::parse(digest_json)), now);
    *result_json = dup_string(lighthouse_state_to_json(state).dump());
  });
}

// ---- write-ahead quorum log (pure entry points) ----
// The scripted kill-at-every-record property suites drive the EXACT
// DurableLog encoder/decoder the live root runs, with caller-supplied
// clocks (mono == unix == scripted t makes the rebase an identity).

void* tft_wal_open(const char* dir, int64_t snapshot_every) {
  DurableLog* wal = nullptr;
  int rc = guarded([&] { wal = new DurableLog(dir, snapshot_every); });
  return rc == kOk ? wal : nullptr;
}

void tft_wal_close(void* handle) { delete static_cast<DurableLog*>(handle); }

// entries_json: [{replica_id, age_ms, ttl_ms, participating,
// joined_age_ms, member}] — the POST-APPLY state slices (ages relative
// to unix_ms).
int tft_wal_log_lease(void* handle, const char* entries_json, int64_t unix_ms) {
  return guarded([&] {
    static_cast<DurableLog*>(handle)->log_lease(
        wal_lease_entries_from_json(Json::parse(entries_json)), unix_ms);
  });
}

int tft_wal_log_depart(void* handle, const char* replica_id) {
  return guarded(
      [&] { static_cast<DurableLog*>(handle)->log_depart(replica_id); });
}

int tft_wal_log_quorum(void* handle, const char* quorum_json,
                       int64_t quorum_gen, int64_t root_epoch) {
  return guarded([&] {
    static_cast<DurableLog*>(handle)->log_quorum(
        quorum_from_json(Json::parse(quorum_json)), quorum_gen, root_epoch);
  });
}

int tft_wal_log_epoch(void* handle, int64_t epoch) {
  return guarded([&] { static_cast<DurableLog*>(handle)->log_epoch(epoch); });
}

// state_json uses the lighthouse_state_to_json schema with MONOTONIC
// times at mono_now (the scripted suites pass mono_now == unix_now == t).
int tft_wal_snapshot(void* handle, const char* state_json, int64_t quorum_gen,
                     int64_t root_epoch, int64_t mono_now, int64_t unix_now) {
  return guarded([&] {
    static_cast<DurableLog*>(handle)->snapshot(
        lighthouse_state_from_json(Json::parse(state_json)), quorum_gen,
        root_epoch, mono_now, unix_now);
  });
}

// Replays snapshot + log; returns {"state": <lighthouse state JSON>,
// "quorum_gen", "root_epoch", "replayed", "records_replayed",
// "dropped_tail_bytes"} with times re-based onto mono_now.
int tft_wal_recover(const char* dir, int64_t mono_now, int64_t unix_now,
                    char** result_json) {
  return guarded([&] {
    WalRecovery rec = DurableLog::recover(dir, mono_now, unix_now);
    JsonObject out;
    out["state"] = lighthouse_state_to_json(rec.state);
    out["quorum_gen"] = rec.quorum_gen;
    out["root_epoch"] = rec.root_epoch;
    out["replayed"] = rec.replayed;
    out["records_replayed"] = rec.records_replayed;
    out["dropped_tail_bytes"] = rec.dropped_tail_bytes;
    *result_json = dup_string(Json(std::move(out)).dump());
  });
}

// Deterministic jittered exponential backoff schedule (the manager renewal
// loop's retry delays), exposed for the backoff-schedule unit tests.
int64_t tft_backoff_ms(int failures, int64_t base_ms, int64_t max_ms,
                       uint64_t seed) {
  return backoff_ms(failures, base_ms, max_ms, seed);
}

// Deterministic jittered renewal interval (the healthy-path herd spread).
int64_t tft_jittered_interval_ms(int64_t interval_ms, uint64_t seed,
                                 uint64_t tick) {
  return jittered_interval_ms(interval_ms, seed, tick);
}

} // extern "C"

// Per-replica-group coordinator, hosted by group rank 0. Aggregates the
// group's local ranks (quorum barrier, should_commit AND-vote, checkpoint
// metadata exchange) and forwards one quorum request to the lighthouse on
// their behalf. Reference: src/manager.rs.
#pragma once

#include <atomic>
#include <map>
#include <memory>
#include <optional>
#include <set>
#include <string>
#include <thread>
#include <vector>

#include "conn_pool.h"
#include "conn_tracker.h"
#include "net.h"
#include "quorum.h"
#include "thread_annotations.h"

namespace tft {

// Client for the lighthouse protocol (used by ManagerServer, the region
// tier's upstream side, host-level renewal batchers, and tests).
class LighthouseClient {
 public:
  LighthouseClient(const std::string& addr, int64_t connect_timeout_ms);

  // connect_timeout_ms <= 0 uses the client's constructor value; the
  // manager's failover walk passes a SHORT bound so one dead endpoint
  // cannot eat the whole quorum deadline connecting.
  torchft_tpu::Quorum quorum(const torchft_tpu::QuorumMember& requester,
                             int64_t timeout_ms,
                             int64_t connect_timeout_ms = -1);
  void heartbeat(const std::string& replica_id, int64_t timeout_ms);
  // Batched lease renewal; returns the lighthouse's current quorum_id.
  int64_t lease_renew(const std::vector<LeaseEntry>& entries, int64_t timeout_ms);
  // Explicit immediate departure (vs waiting out the lease TTL).
  void depart(const std::string& replica_id, int64_t timeout_ms);

  const std::string& addr() const { return addr_; }

 private:
  // One request/response over the persistent connection, re-established on
  // error (heartbeats, renewals and departs all ride the same socket).
  // uint8_t carries MsgType so this header stays free of wire.h.
  template <typename Req, typename Resp>
  Resp roundtrip(uint8_t req_type, const Req& req, uint8_t resp_type,
                 int64_t timeout_ms);

  std::string addr_;
  int64_t connect_timeout_ms_;
  // Persistent heartbeat connection (re-established on error).
  Mutex hb_mu_;
  Socket hb_sock_ TFT_GUARDED_BY(hb_mu_);
};

class ManagerServer {
 public:
  // `lighthouse_addr` is the group's assigned lighthouse: the flat/root
  // service, or a REGION lighthouse when a hierarchical tier is deployed.
  // Both it and `root_addr` may be COMMA-SEPARATED endpoint lists (the
  // durable-control-plane failover set: an active root plus its warm
  // standbys); a failed renewal/quorum rotates to the next endpoint on
  // the existing jittered-backoff schedule, and a standby's UNAVAILABLE
  // rejection rotates the same way.
  // `root_addr` (optional, "" = none) is the root fallback: when the region
  // stops answering, the manager demotes itself to direct-root registration
  // and probes the region periodically until it returns (bounded by
  // `region_probe_max` consecutive failures — a long root-fallback tenure
  // must not leak a connect attempt per TTL forever; 0 = probe forever).
  // `lease_ttl_ms`
  // <= 0 leaves liveness on the lighthouse's heartbeat_timeout_ms default.
  // `region` (optional, "" = unlabeled) is the group's topology label
  // (TORCHFT_REGION): it rides the quorum requester into every member's
  // QuorumMember, and the quorum result's region map is what the data
  // plane compiles into the two-tier collective schedule. `host`
  // (optional, "" = unlabeled; TORCHFT_HOST, default hostname at the
  // Python layer) rides the same way — the quorum's host map is what
  // groups co-hosted members into the shared-memory intra-host tier.
  ManagerServer(const std::string& replica_id, const std::string& lighthouse_addr,
                const std::string& hostname, const std::string& bind,
                const std::string& store_addr, uint64_t world_size,
                int64_t heartbeat_interval_ms, int64_t connect_timeout_ms,
                const std::string& root_addr = "", int64_t lease_ttl_ms = 0,
                const std::string& region = "", const std::string& host = "",
                int64_t region_probe_max = 0);
  ~ManagerServer();

  std::string address() const; // "http://host:port"
  void shutdown();
  // Whether the manager is currently registered directly at the root
  // (region failover active). Always false without a root_addr.
  bool using_root_fallback();
  // Whether the bounded region re-probe gave up (region_probe_max
  // consecutive failed probes while demoted): the manager stays on the
  // root for the rest of its life instead of leaking a connect attempt
  // per TTL at a region that is gone from the topology.
  bool region_probe_given_up();
  // Publishes a member-health digest (JSON string) that rides every
  // subsequent lease renewal to the lighthouse, where it appears in the
  // per-member /status.json view. Display-only. Empty stops PUBLISHING
  // (renewals then carry no digest — the wire form of a pre-status
  // client); the lighthouse keeps the last non-empty digest until the
  // member departs or its lease is pruned, because an empty entry is
  // indistinguishable from a renewer that simply doesn't speak status.
  void set_status_json(const std::string& status_json);

 private:
  void accept_loop();
  void heartbeat_loop();
  void handle_conn(Socket& sock);
  void handle_quorum(Socket& sock, const std::string& payload);
  void handle_should_commit(Socket& sock, const std::string& payload);
  // The endpoint client quorum/renewal traffic should currently flow
  // through, with the (list, index) it was picked from — the token
  // rotate_if_current() needs.
  struct EndpointPick {
    bool on_root = false;
    size_t idx = 0;
    LighthouseClient* client = nullptr;
  };
  EndpointPick pick_endpoint();
  // Advance to the next endpoint of the picked list after a failure —
  // but only if nobody rotated it since the failing call picked it
  // (compare-and-rotate): a slow failing quorum forward must not undo
  // the renewal loop's rotation onto a live endpoint.
  void rotate_if_current(const EndpointPick& pick);

  std::string replica_id_;
  std::string lighthouse_addr_;
  std::string root_addr_;
  std::string hostname_;
  std::string store_addr_;
  std::string region_;
  std::string host_label_;
  uint64_t world_size_;
  int64_t heartbeat_interval_ms_;
  int64_t connect_timeout_ms_;
  int64_t lease_ttl_ms_;
  int64_t region_probe_max_;

  std::unique_ptr<Listener> listener_;
  // One persistent client per endpoint of each (comma-separated) list;
  // the failover sets of the durable control plane. Vectors are built in
  // the constructor and never resized after — readers copy the active
  // pointer under lh_mu_ and call through it lock-free (every client
  // outlives every reader: destroyed only after the threads join).
  std::vector<std::unique_ptr<LighthouseClient>> lighthouse_clients_;
  std::vector<std::unique_ptr<LighthouseClient>> root_clients_; // empty without root_addr

  // Region-failover + endpoint-rotation state.
  Mutex lh_mu_;
  bool using_root_ TFT_GUARDED_BY(lh_mu_) = false;
  size_t lh_idx_ TFT_GUARDED_BY(lh_mu_) = 0;
  size_t root_idx_ TFT_GUARDED_BY(lh_mu_) = 0;
  bool probe_given_up_ TFT_GUARDED_BY(lh_mu_) = false;

  Mutex mu_;
  std::string status_json_ TFT_GUARDED_BY(mu_);
  // Reference: src/manager.rs:40-48 (ManagerState).
  std::map<int64_t, std::string> checkpoint_metadata_ TFT_GUARDED_BY(mu_);
  std::set<int64_t> participants_ TFT_GUARDED_BY(mu_);
  // OR of local ranks' force_reconfigure since the last lighthouse forward.
  bool force_reconfigure_pending_ TFT_GUARDED_BY(mu_) = false;
  CondVar quorum_cv_;
  int64_t quorum_gen_ TFT_GUARDED_BY(mu_) = 0;
  torchft_tpu::Quorum latest_quorum_ TFT_GUARDED_BY(mu_);
  // set when the lighthouse call failed
  std::string quorum_error_ TFT_GUARDED_BY(mu_);
  torchft_tpu::ErrorResponse::Code quorum_error_code_ TFT_GUARDED_BY(mu_) =
      torchft_tpu::ErrorResponse::UNAVAILABLE;

  std::set<int64_t> should_commit_count_ TFT_GUARDED_BY(mu_);
  std::set<int64_t> should_commit_failures_ TFT_GUARDED_BY(mu_);
  CondVar commit_cv_;
  int64_t commit_gen_ TFT_GUARDED_BY(mu_) = 0;
  bool latest_decision_ TFT_GUARDED_BY(mu_) = false;

  // Interruptible sleep for the renewal loop (backoff waits can reach
  // seconds; shutdown must not stall behind them). Notified in shutdown().
  CondVar hb_cv_;

  std::atomic<bool> shutting_down_{false};
  std::thread accept_thread_;
  std::thread heartbeat_thread_;
  ConnTracker conns_;
};

// Blocking client for a manager server, mirrored into Python.
// Reference: src/lib.rs:88-197 (ManagerClient pyclass). Uses a connection
// pool: persistent connections (should_commit runs every training step) that
// still allow concurrent barrier RPCs from multiple threads.
class ManagerClient {
 public:
  ManagerClient(const std::string& addr, int64_t connect_timeout_ms);

  torchft_tpu::ManagerQuorumResponse quorum(int64_t rank, int64_t step,
                                            const std::string& checkpoint_metadata,
                                            bool shrink_only,
                                            bool force_reconfigure,
                                            int64_t timeout_ms);
  std::string checkpoint_metadata(int64_t rank, int64_t timeout_ms);
  bool should_commit(int64_t rank, int64_t step, bool should_commit,
                     int64_t timeout_ms);
  // Best-effort: the target exits before replying.
  void kill(const std::string& msg);

 private:
  template <typename Req, typename Resp>
  Resp roundtrip(uint8_t req_type, const Req& req, uint8_t resp_type,
                 int64_t timeout_ms);

  ConnPool pool_;
};

} // namespace tft

// Global quorum service. One per job; replica-group managers heartbeat (or
// batch-renew leases) into it and long-poll Quorum requests against it. Also
// the ROOT of the hierarchical tier: region lighthouses push membership
// digests into it and long-poll the global quorum back out. Serves an HTML
// dashboard plus a JSON status view on the same port (HTTP requests are
// sniffed apart from protocol frames). Reference: src/lighthouse.rs.
//
// DURABLE CONTROL PLANE (LighthouseOpt.wal_dir / peers / standby):
//
// - Write-ahead quorum log: with `wal_dir` set, every externally visible
//   promise (quorum commit, lease grant, explicit depart, root-epoch
//   claim) is appended to a CRC-framed WAL (see wal.h) BEFORE it is
//   published; restart replays snapshot+log to the exact pre-crash
//   quorum_id/quorum_gen watermark. A torn append kills the log and the
//   service stops forming NEW quorums (frozen promises beat regressed
//   ones) — reads, renewals and status keep serving.
//
// - Root epochs + warm standby: every ACTIVE claim (startup or standby
//   takeover) bumps a monotonic root epoch, fenced through the WAL. A
//   root started with `standby=true` (or fenced at startup by an active
//   peer holding a >= epoch) stays PASSIVE: it rejects the serving
//   protocol with UNAVAILABLE ("standby root ...", so clients rotate to
//   the next endpoint of their root list), tails the active peer's
//   membership through RootSync digests (the same age-relative entries
//   the region tier pushes), and takes over — epoch = max(seen)+1 —
//   when the active peer's lease lapses (`takeover_ms` without a
//   successful sync). An active root probes its peers and DEMOTES itself
//   when one reports active with a strictly higher epoch (the deposed
//   primary returning from a crash or stall fences instead of forking
//   the quorum history); a tick-loop stall longer than takeover_ms
//   forces that probe before any further promise is made.
#pragma once

#include <atomic>
#include <map>
#include <memory>
#include <string>
#include <thread>
#include <vector>

#include "conn_tracker.h"
#include "net.h"
#include "quorum.h"
#include "thread_annotations.h"
#include "wal.h"

namespace tft {

class Lighthouse {
 public:
  Lighthouse(const std::string& bind_addr, const LighthouseOpt& opt);
  ~Lighthouse();

  // "http://host:port" (dashboard is literally served over HTTP here).
  std::string address() const;
  uint16_t port() const;
  void shutdown();

  // Machine-readable status (the /status.json payload): members + lease
  // deadlines, last quorum, tier role, tick cost counters, region digests,
  // root epoch + WAL replay stamps, active/standby role.
  std::string status_json();

  // Whether this root is ACTIVE (serving quorums) vs a passive standby.
  bool active();
  // Monotonic root epoch (0 = never claimed active; epochs are bumped at
  // every active claim and fenced through the WAL when one is configured).
  int64_t root_epoch();

 private:
  void accept_loop();
  void tick_loop();
  void peer_loop();
  void handle_conn(Socket& sock);
  void handle_http(Socket& sock, const std::string& head);
  void handle_quorum_req(Socket& sock, const std::string& payload);
  void handle_lease_renew(Socket& sock, const std::string& payload);
  void handle_depart(Socket& sock, const std::string& payload);
  void handle_region_digest(Socket& sock, const std::string& payload);
  void handle_region_poll(Socket& sock, const std::string& payload);
  void handle_root_sync(Socket& sock, const std::string& payload);

  // Sends the standby rejection (UNAVAILABLE) when passive; returns true
  // when the caller must bail out.
  bool reject_if_standby(Socket& sock);

  // Runs one quorum check; called with mu_ held. On success publishes the new
  // quorum (bumping quorum_id only when membership changed) and wakes waiters.
  void quorum_tick_locked() TFT_REQUIRES(mu_);

  // WAL glue (no-ops without a wal_dir). wal_commit_quorum_locked returns
  // false when the promise could NOT be made durable (torn log) — the
  // caller must not publish it.
  bool wal_commit_quorum_locked(const torchft_tpu::Quorum& q)
      TFT_REQUIRES(mu_);
  void wal_log_members_locked(const std::vector<std::string>& ids)
      TFT_REQUIRES(mu_);
  // Synchronous best-effort replication of a freshly committed quorum to
  // the standby peers, BEFORE publication: the standby WAL-logs it and
  // acks, so a primary kill at any later instant finds the watermark
  // already replicated (the pull loop alone lags one sync interval).
  // Short-deadline and best-effort — a dead peer must not stall commits.
  void push_quorum_to_peers_locked(const torchft_tpu::Quorum& q)
      TFT_REQUIRES(mu_);

  // Peer-set plumbing (the root failover set).
  bool sync_from_peers();   // standby: pull state from the active peer
  void probe_peers_fence(); // active: demote behind a higher-epoch active
  void do_takeover();       // standby -> active (epoch bump, WAL-fenced)

  std::string render_status_locked() TFT_REQUIRES(mu_);
  Json status_json_locked() TFT_REQUIRES(mu_);

  LighthouseOpt opt_;
  std::unique_ptr<Listener> listener_;
  std::string hostname_;

  // Failover-set peers (parsed from opt_.peers; empty = classic single
  // root) and takeover bound. Immutable after construction.
  std::vector<std::string> peers_;
  int64_t takeover_ms_ = 3000;

  std::unique_ptr<DurableLog> wal_;  // null without wal_dir
  bool wal_replayed_ = false;        // restart restored pre-crash state
  int64_t wal_records_replayed_ = 0;
  int64_t wal_dropped_tail_bytes_ = 0;
  int64_t wal_replay_ms_ = 0;        // wall time of the recovery replay

  Mutex mu_;
  CondVar quorum_cv_;
  LighthouseState state_ TFT_GUARDED_BY(mu_);
  // Broadcast channel equivalent: monotone generation + latest value.
  int64_t quorum_gen_ TFT_GUARDED_BY(mu_) = 0;
  torchft_tpu::Quorum latest_quorum_ TFT_GUARDED_BY(mu_);

  // Role + fencing state. claim_nonce_ is the per-activation tie-break:
  // regenerated at every active claim, carried in RootSync responses —
  // two roots that end up at the SAME epoch (a restarted primary whose
  // startup probe missed the standby, or two simultaneously starving
  // standbys) fence on nonce order instead of both staying active.
  bool active_ TFT_GUARDED_BY(mu_) = true;
  int64_t root_epoch_ TFT_GUARDED_BY(mu_) = 0;
  uint64_t claim_nonce_ TFT_GUARDED_BY(mu_) = 0;
  int64_t seen_peer_epoch_ TFT_GUARDED_BY(mu_) = 0;
  int64_t last_sync_ok_ms_ TFT_GUARDED_BY(mu_) = 0;  // standby sync health
  int64_t wal_quorum_logged_ TFT_GUARDED_BY(mu_) = 0;  // standby qid ledger
  bool wal_dead_logged_ TFT_GUARDED_BY(mu_) = false;   // log-once flag

  // Region tier bookkeeping (status only; liveness rides the groups' own
  // forwarded leases, so a region's death needs no root-side timeout).
  struct RegionInfo {
    int64_t last_digest_ms = 0;
    int64_t entries = 0;
  };
  std::map<std::string, RegionInfo> regions_ TFT_GUARDED_BY(mu_);

  // Tick cost counters (/status.json `tick.computed` vs `tick.total`). Idle
  // ticks — no registered participant, so no quorum can possibly form —
  // skip the O(groups) membership scan entirely; that is the lease-based
  // replacement for the unconditional per-tick recompute.
  int64_t ticks_total_ TFT_GUARDED_BY(mu_) = 0;
  int64_t ticks_computed_ TFT_GUARDED_BY(mu_) = 0;
  int64_t last_compute_us_ TFT_GUARDED_BY(mu_) = 0;
  int64_t total_compute_us_ TFT_GUARDED_BY(mu_) = 0;
  int64_t last_tick_ms_ TFT_GUARDED_BY(mu_) = 0;  // stall-self-fence probe

  std::atomic<bool> shutting_down_{false};
  std::thread accept_thread_;
  std::thread tick_thread_;
  std::thread peer_thread_;
  ConnTracker conns_;
};

} // namespace tft

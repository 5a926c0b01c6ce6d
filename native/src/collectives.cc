#include "collectives.h"

#include <linux/futex.h>
#include <netdb.h>
#include <poll.h>
#include <signal.h>
#include <string.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <time.h>
#include <unistd.h>

#include <algorithm>
#include <cstdlib>
#include <chrono>
#include <cmath>
#include <exception>
#include <limits>
#include <set>
#include <thread>
#include <vector>

#include "fault.h"
#include "json.h"
#include "log.h"
#include "shm.h"
#include "store.h"

namespace tft {

size_t dtype_size(Dtype d) {
  switch (d) {
    case Dtype::kF32:
    case Dtype::kI32:
      return 4;
    case Dtype::kF64:
    case Dtype::kI64:
      return 8;
    case Dtype::kBF16:
      return 2;
  }
  throw SocketError("bad dtype");
}

namespace {

// Hello magic, versioned: the low byte is the ring wire-protocol revision.
// History: the original "tftc" magic (0x74667463) spanned BOTH the
// pre-op-header wire and the build that added check_op_header, so the
// magic alone could not distinguish them; a ring mixing those desyncs
// mid-op (the old side consumes the 24-byte op header as payload). This
// versioned magic makes any mix of revisions — including byte-compatible
// "tftc" builds that already spoke op headers — fail AT CONNECT with a
// clear error; that over-rejection is the price of screening out the
// truly incompatible older builds sharing the old magic. Bump the low
// byte on any future wire change.
// rev 3: hello grew from {magic, rank} to {magic, rank, stripe, nstripes}
// for the striped multi-connection ring.
// rev 4: hello grew a TIER word ({magic, rank, stripe, nstripes, tier})
// for the two-tier topology — one listener serves the flat, intra-region
// and inter-region (leader) rings, and the hello names which ring a
// connection belongs to.
constexpr uint32_t kHelloMagic = 0x74667404; // "tft" + proto rev 4
// rev 5: the CRC-guarded frame format — every ring/stripe payload frame
// carries a 4-byte CRC32C trailer (TORCHFT_WIRE_CRC, store-negotiated
// like stripes). The rev-5 magic is used ONLY when CRC is on, so a
// CRC-off fleet keeps speaking the byte-identical rev-4 format and
// interops with un-upgraded peers; a mixed on/off pair fails AT CONNECT
// with a CRC-specific error instead of a frame desync.
constexpr uint32_t kHelloMagicCrc = 0x74667405;
// "tftp": per-op header magic (part of the wire protocol).
constexpr uint32_t kOpMagic = 0x74667470;

// Connection tiers named in the hello (and indexing RingTier members).
constexpr uint32_t kTierFlat = 0;
constexpr uint32_t kTierIntra = 1;
constexpr uint32_t kTierInter = 2;
// Host (intra-host) tier: shared-memory rings by default, so the hello
// tier word only appears on the wire under the TORCHFT_HC_SHM=0
// loopback-TCP fallback.
constexpr uint32_t kTierHost = 3;

// ---- shared-memory ring buffers (the host tier's transport) ----
//
// One SPSC byte ring per directed edge per stripe, living in a POSIX shm
// segment (ShmSegment, creator = the producing member). Layout: a
// 64-byte header, then `capacity` data bytes. head/tail are free-running
// byte counters (the ring is full when head - tail == capacity); db_w /
// db_r are futex doorbells bumped after every publish/consume. SHARED
// futexes (no PRIVATE flag): producer and consumer are different
// processes mapping the same page. The magic doubles as the liveness
// word — abort/teardown/torn-segment faults poison it, and both sides
// treat a poisoned ring exactly like a socket FIN.

struct ShmRingHdr {
  std::atomic<uint32_t> magic;
  uint32_t capacity;
  std::atomic<uint64_t> head;   // bytes produced (free-running)
  std::atomic<uint64_t> tail;   // bytes consumed
  std::atomic<uint32_t> db_w;   // producer doorbell
  std::atomic<uint32_t> db_r;   // consumer doorbell
  // Liveness: the producer (creator) and consumer (attacher) publish
  // their pids. A SIGKILLed co-hosted process closes no socket and
  // poisons no magic — the kernel tells us nothing — so a blocked
  // waiter probes the counterpart's pid (kill(pid, 0), ESRCH = gone)
  // once per futex slice and surfaces the death in ~100 ms instead of
  // waiting out the whole op deadline.
  std::atomic<uint32_t> owner_pid;  // producer, set at create
  std::atomic<uint32_t> peer_pid;   // consumer, set at attach
};
static_assert(sizeof(ShmRingHdr) <= 64, "shm ring header outgrew its slot");
static_assert(std::atomic<uint64_t>::is_always_lock_free,
              "shm ring counters must be lock-free (they cross processes)");

constexpr uint32_t kShmRingMagic = 0x74667368;   // "tfsh"
constexpr uint32_t kShmRingPoison = 0xDEADD00Du;
constexpr size_t kShmHdrBytes = 64;

// Every shm_duplex call moves exactly one frame per direction: a 16-byte
// in-stream header (monotonic per-edge sequence + payload length), then
// the payload. The sequence is the stale-payload oracle (a replayed
// frame mismatches), the length the desync oracle (a mismatched op would
// otherwise reduce the wrong bytes).
struct ShmFrame {
  uint64_t fseq;
  uint32_t len;
  uint32_t pad;
};
static_assert(sizeof(ShmFrame) == 16, "shm frame header must be 16 bytes");

inline ShmRingHdr* shm_ring_hdr(void* seg) {
  return static_cast<ShmRingHdr*>(seg);
}
inline char* shm_ring_data(void* seg) {
  return static_cast<char*>(seg) + kShmHdrBytes;
}

// True when `pid` names a process that can never feed its ring again:
// gone entirely (ESRCH), or a ZOMBIE — a SIGKILLed bench/training child
// whose parent has not reaped it yet still *exists* for kill(pid, 0),
// but will never produce another byte (the /proc state disambiguates,
// exactly like the isolated plane's stall monitor). pid 0 = not yet
// published — indeterminate, not dead. Co-hosted by construction, so
// the pid is always probeable.
bool shm_pid_gone(uint32_t pid) {
  if (pid == 0) return false;
  if (kill(static_cast<pid_t>(pid), 0) != 0) return errno == ESRCH;
  char path[64];
  snprintf(path, sizeof(path), "/proc/%u/stat", pid);
  FILE* f = fopen(path, "r");
  if (f == nullptr) return false;  // no /proc: fall back to the deadline
  char buf[256];
  size_t n = fread(buf, 1, sizeof(buf) - 1, f);
  fclose(f);
  buf[n] = '\0';
  // State is the field after the parenthesized comm (which may itself
  // contain spaces and parens — scan from the LAST ')').
  const char* rp = strrchr(buf, ')');
  if (rp == nullptr) return false;
  for (rp++; *rp == ' '; rp++) {
  }
  return *rp == 'Z' || *rp == 'X';
}

// Deadline-sliced futex wait on a doorbell: `expect` must be the value
// read BEFORE the caller re-checked its condition (the lost-wakeup
// protocol); the slice cap bounds the worst case even if a wake is
// missed entirely.
void shm_futex_wait(std::atomic<uint32_t>* addr, uint32_t expect,
                    int64_t max_ms) {
  if (max_ms <= 0) return;
  if (max_ms > 100) max_ms = 100;
  struct timespec ts;
  ts.tv_sec = max_ms / 1000;
  ts.tv_nsec = (max_ms % 1000) * 1000000;
  syscall(SYS_futex, reinterpret_cast<uint32_t*>(addr), FUTEX_WAIT, expect,
          &ts, nullptr, 0);
}

void shm_futex_wake(std::atomic<uint32_t>* addr) {
  syscall(SYS_futex, reinterpret_cast<uint32_t*>(addr), FUTEX_WAKE,
          std::numeric_limits<int>::max(), nullptr, nullptr, 0);
}

// Wrap-aware copy of `n` bytes into/out of a ring at free-running
// position `pos`.
void shm_ring_write(char* data, uint32_t cap, uint64_t pos, const char* src,
                    size_t n) {
  size_t off = static_cast<size_t>(pos % cap);
  size_t first = std::min<size_t>(n, cap - off);
  memcpy(data + off, src, first);
  if (n > first) memcpy(data, src + first, n - first);
}

void shm_ring_read(const char* data, uint32_t cap, uint64_t pos, char* dst,
                   size_t n) {
  size_t off = static_cast<size_t>(pos % cap);
  size_t first = std::min<size_t>(n, cap - off);
  memcpy(dst, data + off, first);
  if (n > first) memcpy(dst + first, data, n - first);
}

// FNV-1a over a string — the shm segment namespace and the topology-map
// hash mixed into hier plan signatures.
uint64_t fnv64(const std::string& s) {
  uint64_t h = 1469598103934665603ull;
  for (unsigned char c : s) {
    h ^= c;
    h *= 1099511628211ull;
  }
  return h;
}

// Floor on bytes a stripe must carry before an extra connection/thread is
// worth waking: below this, per-op thread dispatch costs more than the
// wire. The effective stripe count derived from it depends only on
// (payload, configured stripes) — identical on every member, preserving
// the schedule agreement.
constexpr size_t kMinStripeBytes = 64 << 10;

int64_t effective_stripes(size_t payload_bytes, int64_t configured) {
  int64_t by_size = static_cast<int64_t>(payload_bytes / kMinStripeBytes);
  return std::max<int64_t>(1, std::min(configured, std::max<int64_t>(by_size, 1)));
}

template <typename T>
void reduce_typed(T* dst, const T* src, size_t n, ReduceOp op) {
  switch (op) {
    case ReduceOp::kSum:
      for (size_t i = 0; i < n; i++) dst[i] += src[i];
      return;
    case ReduceOp::kProduct:
      for (size_t i = 0; i < n; i++) dst[i] *= src[i];
      return;
    case ReduceOp::kMin:
      for (size_t i = 0; i < n; i++) dst[i] = std::min(dst[i], src[i]);
      return;
    case ReduceOp::kMax:
      for (size_t i = 0; i < n; i++) dst[i] = std::max(dst[i], src[i]);
      return;
  }
  throw SocketError("bad reduce op");
}

inline float bf16_to_f32(uint16_t h) {
  uint32_t bits = static_cast<uint32_t>(h) << 16;
  float f;
  memcpy(&f, &bits, sizeof(f));
  return f;
}

inline uint16_t f32_to_bf16(float f) {
  uint32_t bits;
  memcpy(&bits, &f, sizeof(bits));
  // Round to nearest even (NaN payloads preserved by the +0x7FFF carry-free
  // path since NaN mantissas survive truncation of the low half).
  uint32_t lsb = (bits >> 16) & 1;
  bits += 0x7FFF + lsb;
  return static_cast<uint16_t>(bits >> 16);
}

void reduce_bf16(uint16_t* dst, const uint16_t* src, size_t n, ReduceOp op) {
  for (size_t i = 0; i < n; i++) {
    float a = bf16_to_f32(dst[i]);
    float b = bf16_to_f32(src[i]);
    float r;
    switch (op) {
      case ReduceOp::kSum: r = a + b; break;
      case ReduceOp::kProduct: r = a * b; break;
      case ReduceOp::kMin: r = std::min(a, b); break;
      case ReduceOp::kMax: r = std::max(a, b); break;
      default: throw SocketError("bad reduce op");
    }
    dst[i] = f32_to_bf16(r);
  }
}

void reduce_into(void* dst, const void* src, size_t n, Dtype dtype, ReduceOp op) {
  switch (dtype) {
    case Dtype::kF32:
      reduce_typed(static_cast<float*>(dst), static_cast<const float*>(src), n, op);
      return;
    case Dtype::kF64:
      reduce_typed(static_cast<double*>(dst), static_cast<const double*>(src), n,
                   op);
      return;
    case Dtype::kI32:
      reduce_typed(static_cast<int32_t*>(dst), static_cast<const int32_t*>(src), n,
                   op);
      return;
    case Dtype::kI64:
      reduce_typed(static_cast<int64_t*>(dst), static_cast<const int64_t*>(src), n,
                   op);
      return;
    case Dtype::kBF16:
      reduce_bf16(static_cast<uint16_t*>(dst), static_cast<const uint16_t*>(src),
                  n, op);
      return;
  }
  throw SocketError("bad dtype");
}

// Element range of ring chunk `c` when `count` elements are split into `ws`
// near-equal chunks (first `count % ws` chunks get one extra element).
std::pair<size_t, size_t> chunk_range(size_t count, int64_t ws, int64_t c) {
  size_t q = count / ws;
  size_t r = count % ws;
  size_t start = c * q + std::min<size_t>(c, r);
  size_t len = q + (static_cast<size_t>(c) < r ? 1 : 0);
  return {start, len};
}

int64_t ns_between(std::chrono::steady_clock::time_point a,
                   std::chrono::steady_clock::time_point b) {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(b - a).count();
}

int64_t cap_to_bps(const char* cap) {
  return cap ? static_cast<int64_t>(std::atof(cap) * (1 << 20)) : 0;
}

}  // namespace

std::pair<size_t, size_t> HostCollectives::stripe_range(size_t count,
                                                        int64_t n, int64_t s) {
  return chunk_range(count, n, s);
}

namespace {

bool env_wire_crc() {
  const char* e = std::getenv("TORCHFT_WIRE_CRC");
  if (e == nullptr) return false;
  std::string v(e);
  return v == "1" || v == "on" || v == "true";
}

// "host:port" of a connected socket's peer, for edge diagnostics.
std::string peer_addr_str(int fd) {
  struct sockaddr_storage ss;
  socklen_t slen = sizeof(ss);
  if (getpeername(fd, reinterpret_cast<struct sockaddr*>(&ss), &slen) != 0)
    return "?";
  char host[NI_MAXHOST];
  char port[NI_MAXSERV];
  if (getnameinfo(reinterpret_cast<struct sockaddr*>(&ss), slen, host,
                  sizeof(host), port, sizeof(port),
                  NI_NUMERICHOST | NI_NUMERICSERV) != 0)
    return "?";
  return std::string(host) + ":" + port;
}

}  // namespace

HostCollectives::HostCollectives() : crc_req_(env_wire_crc()) {}

HostCollectives::~HostCollectives() {
  abort();
  std::vector<std::thread> workers;
  {
    MutexLock lock(pool_mu_);
    pool_stop_ = true;
    workers.swap(pool_);
  }
  pool_cv_.notify_all();
  for (auto& w : workers) w.join();
}

void HostCollectives::abort() {
  MutexLock lock(cfg_mu_);
  aborted_ = true;
  abort_epoch_++;
  if (listener_) listener_->close();
  shutdown_sockets_locked();
}

void HostCollectives::shutdown_sockets_locked() {
  for (RingTier* T : {&flat_, &intra_, &inter_, &host_}) {
    for (auto& s : T->next) s.shutdown_rdwr();
    for (auto& s : T->prev) s.shutdown_rdwr();
  }
  shm_poison_wake_locked();
}

void HostCollectives::shm_poison_wake_locked() {
  // The shm analog of the socket FIN sweep: poison every ring magic this
  // member produces into (its TX rings) so the consumer errors instead
  // of waiting out its deadline, and wake every doorbell — local waiters
  // re-check aborted_/magic, the peer's waiter sees the poison.
  for (auto& e : host_.shm) {
    if (e.tx) {
      ShmRingHdr* h = shm_ring_hdr(e.tx->data());
      h->magic.store(kShmRingPoison, std::memory_order_release);
      shm_futex_wake(&h->db_w);
      shm_futex_wake(&h->db_r);
    }
    if (e.rx) {
      ShmRingHdr* h = shm_ring_hdr(e.rx->data());
      shm_futex_wake(&h->db_w);
      shm_futex_wake(&h->db_r);
    }
  }
}

void HostCollectives::shutdown_sockets() {
  MutexLock lock(cfg_mu_);
  shutdown_sockets_locked();
}

void HostCollectives::release_rings() {
  abort();                    // poison + wake every waiter
  MutexLock op_lock(op_mu_);  // wait for in-flight ops to drain
  MutexLock lock(cfg_mu_);
  flat_.clear();
  intra_.clear();
  inter_.clear();
  host_.clear();  // unlinks this member's shm segments (creator-owned)
  listener_.reset();
}

int64_t HostCollectives::tier_tx(const RingTier& T) {
  int64_t t = 0;
  for (const auto& sc : T.scratch) t += sc.tx_bytes;
  return t;
}

int64_t HostCollectives::tier_shm(const RingTier& T) {
  int64_t t = 0;
  for (const auto& sc : T.scratch) t += sc.shm_bytes;
  return t;
}

void HostCollectives::reset_tier_tx(RingTier& T) {
  for (auto& sc : T.scratch) {
    sc.tx_bytes = 0;
    sc.shm_bytes = 0;
  }
}

namespace {

// Remaining budget before `deadline`; throws once it is exhausted (a
// non-positive timeout must never leak into a blocking call, where some
// callees read <0 as "wait forever").
int64_t remain_or_throw(int64_t deadline) {
  int64_t r = deadline - now_ms();
  if (r <= 0) throw TimeoutError("configure timed out");
  return r;
}

} // namespace

namespace {

// TORCHFT_HC_SHM: the host tier's transport. Default on — the whole
// point of the tier is replacing loopback TCP; 0/off/false falls back to
// a TCP host ring with identical geometry (the bench's honest control).
bool env_shm_on() {
  const char* e = std::getenv("TORCHFT_HC_SHM");
  if (e == nullptr) return true;
  std::string v(e);
  // Case-insensitive, matching the Python layer's parse exactly: the
  // negotiated fingerprint is computed from Python's reading, so any
  // divergence here would pass the mismatch guard and then wedge
  // configure (one member wiring shm, the other TCP).
  for (auto& c : v) c = static_cast<char>(tolower(c));
  return !(v == "0" || v == "off" || v == "false");
}

size_t env_shm_ring_bytes() {
  const char* e = std::getenv("TORCHFT_HC_SHM_RING_BYTES");
  size_t v = e ? static_cast<size_t>(std::atoll(e)) : (1u << 20);
  // Floor keeps the frame pump making progress at sane chunk sizes; the
  // ring handles frames larger than itself, but a degenerate capacity
  // would turn every hop into a futex ping-pong.
  return std::max<size_t>(v, 4096);
}

}  // namespace

void HostCollectives::configure(const std::string& store_addr, int64_t rank,
                                int64_t world_size, int64_t timeout_ms,
                                int64_t stripes,
                                const std::vector<std::string>& regions,
                                int64_t stripes_inter,
                                const std::vector<std::string>& hosts) {
  if (rank < 0 || world_size <= 0 || rank >= world_size)
    throw SocketError("bad rank/world_size");
  if (stripes < 1 || stripes > kMaxStripes)
    throw SocketError("bad stripe count (want 1.." +
                      std::to_string(kMaxStripes) + ")");
  if (stripes_inter <= 0) stripes_inter = stripes;
  if (stripes_inter > kMaxStripes)
    throw SocketError("bad inter stripe count (want 1.." +
                      std::to_string(kMaxStripes) + ")");
  if (!regions.empty() &&
      static_cast<int64_t>(regions.size()) != world_size)
    throw SocketError("region map must carry one label per rank");
  if (!hosts.empty() && static_cast<int64_t>(hosts.size()) != world_size)
    throw SocketError("host map must carry one label per rank");
  abort(); // unblock any op stuck on the old ring
  MutexLock op_lock(op_mu_); // wait for it to drain

  {
    // Comm plans bake in (world_size, stripes) layout arithmetic and
    // persistent staging sized for the old ring: every one of them is
    // stale the moment membership changes. Dropping them here (no
    // execute can be in flight — op_mu_ is held) turns a stale plan id
    // into a descriptive error instead of a desynced wire schedule.
    MutexLock plan_lock(plan_mu_);
    plans_.clear();
  }

  // Hierarchical topology from the (region, host) maps: pure arithmetic
  // on (labels, rank order), identical on every member. The region
  // LEADER is the lowest rank of the region (ranks sort by replica-id,
  // so this is the lowest replica-id); the inter ring orders regions by
  // their leader's rank. HOST groups are keyed by the (region, host)
  // PAIR — a host label that leaks across region boundaries can never
  // stitch two regions together — and the host leader is the lowest
  // rank of the group, so the region leader is always a host leader.
  // The intra ring spans the HOST LEADERS of a region (with no host
  // grouping every member is its own host leader, which is exactly the
  // two-tier topology).
  const bool regions_labeled = [&] {
    if (regions.empty() || world_size <= 1) return false;
    for (const auto& r : regions)
      if (r.empty()) return false;
    return true;
  }();
  const bool hosts_labeled = [&] {
    if (hosts.empty() || world_size <= 1) return false;
    for (const auto& h : hosts)
      if (h.empty()) return false;
    return true;
  }();
  auto region_of = [&](int64_t r) {
    return regions_labeled ? regions[r] : std::string();
  };
  auto hkey = [&](int64_t r) {
    return region_of(r) + '\x1f' + hosts[r];
  };

  bool multi_region = false;
  if (regions_labeled) {
    std::set<std::string> distinct(regions.begin(), regions.end());
    multi_region = distinct.size() >= 2;
  }
  bool host_grouped = false;
  if (hosts_labeled) {
    std::map<std::string, int64_t> sizes;
    for (int64_t r = 0; r < world_size; r++)
      if (++sizes[hkey(r)] >= 2) host_grouped = true;
  }
  const bool hier = multi_region || host_grouped;

  std::vector<int64_t> host_members;   // my (region, host) group
  int64_t host_rank = -1;
  std::vector<int64_t> intra_members;  // host leaders of my region
  int64_t intra_rank = -1;
  std::vector<int64_t> leaders;        // region leaders
  int64_t inter_rank = -1;
  bool is_host_leader = true;
  if (hier) {
    if (hosts_labeled) {
      for (int64_t r = 0; r < world_size; r++) {
        if (hkey(r) == hkey(rank)) {
          if (r == rank)
            host_rank = static_cast<int64_t>(host_members.size());
          host_members.push_back(r);
        }
      }
    } else {
      host_members = {rank};
      host_rank = 0;
    }
    is_host_leader = host_members[0] == rank;
    // Host leaders of my region, rank order — the intra tier's members.
    std::set<std::string> seen_hosts;
    for (int64_t r = 0; r < world_size; r++) {
      if (region_of(r) != region_of(rank)) continue;
      std::string k = hosts_labeled ? hkey(r) : std::to_string(r);
      if (!seen_hosts.insert(k).second) continue;  // not the host leader
      if (r == rank) intra_rank = static_cast<int64_t>(intra_members.size());
      intra_members.push_back(r);
    }
    std::map<std::string, int64_t> leader_of;
    for (int64_t r = 0; r < world_size; r++)
      if (!leader_of.count(region_of(r))) leader_of[region_of(r)] = r;
    for (const auto& [_, l] : leader_of) leaders.push_back(l);
    std::sort(leaders.begin(), leaders.end());
    for (size_t i = 0; i < leaders.size(); i++)
      if (leaders[i] == rank) inter_rank = static_cast<int64_t>(i);
  }
  const int64_t host_world =
      hier ? static_cast<int64_t>(host_members.size()) : 0;
  const int64_t intra_world = hier ? static_cast<int64_t>(intra_members.size()) : 0;
  const int64_t inter_world = hier ? static_cast<int64_t>(leaders.size()) : 0;
  const bool is_leader = hier && inter_rank >= 0;
  const bool shm_on = env_shm_on();
  // Canonical topology hash (mixed into hier plan signatures): identical
  // maps hash identically on every member.
  uint64_t topo = 1469598103934665603ull;
  {
    std::string all;
    for (int64_t r = 0; r < world_size; r++) {
      all += region_of(r);
      all += '\x1f';
      all += hosts_labeled ? hosts[r] : std::string();
      all += '\x1e';
    }
    topo = fnv64(all);
  }

  // Phase 1 (under cfg_mu_, non-blocking): retire the old ring, stand up the
  // new listener so a concurrent abort() can close it and wake phase 2.
  int64_t epoch;
  {
    MutexLock lock(cfg_mu_);
    flat_.clear();
    intra_.clear();
    inter_.clear();
    // Dropping the host tier's edges unlinks every segment this member
    // created — shm segments are owned by the configure generation.
    host_.clear();
    listener_.reset();
    rank_ = rank;
    world_size_ = world_size;
    stripes_ = stripes;
    stripes_inter_ = stripes_inter;
    hier_ = hier;
    topo_hash_ = topo;
    shm_ring_bytes_ = env_shm_ring_bytes();
    // Per-connection send cap: TORCHFT_HC_WIRE_CAP_MBPS paces the
    // slow/wide-area links (the flat ring's edges, the inter hop); the
    // fast in-region links (intra, host) are never paced. Snapshotted
    // here so the knob is stable for the lifetime of a ring.
    const int64_t cap_main =
        cap_to_bps(std::getenv("TORCHFT_HC_WIRE_CAP_MBPS"));
    auto init_tier = [](RingTier& T, const char* name, int64_t trank,
                        int64_t tworld, int64_t conns, int64_t cap) {
      T.rank = trank;
      T.world = tworld;
      T.conns = conns;
      T.cap_bps = cap;
      T.name = name;
      T.peer_next_addr.clear();
      T.peer_prev_addr.clear();
      T.scratch.assign(conns, StripeScratch{});
      for (auto& sc : T.scratch) sc.cap_bps = cap;
    };
    init_tier(flat_, "flat", rank, world_size, stripes, cap_main);
    if (hier) {
      // Only HOST LEADERS participate in the intra (and inter) rings;
      // world stays 0 for everyone else so op bodies branch uniformly.
      init_tier(intra_, "intra", intra_rank,
                is_host_leader ? intra_world : 0, stripes, /*cap=*/0);
      init_tier(inter_, "inter", inter_rank, is_leader ? inter_world : 0,
                stripes_inter, cap_main);
      // The host ring is intra-host by construction: never paced (there
      // is no NIC to protect), shm-backed unless TORCHFT_HC_SHM=0.
      init_tier(host_, "host", host_rank, host_world > 1 ? host_world : 0,
                stripes, /*cap=*/0);
    }
    // The frame format is fixed for the life of the ring: snapshot the
    // CRC request here, under the same publication protocol as the
    // geometry.
    crc_ = crc_req_;
    aborted_ = true;
    epoch = abort_epoch_;
    if (world_size == 1) {
      aborted_ = false;
      return;
    }
    listener_ = std::make_unique<Listener>("[::]:0");
  }

  // Phase 2 (no locks held, every step deadline-bounded): rendezvous through
  // the store and wire the rings. All neighbors dial concurrently; connect()
  // lands in the peer's listen backlog, so no accept ordering is needed.
  int64_t deadline = now_ms() + timeout_ms;
  auto [kv_addr, prefix] = split_store_addr(store_addr);
  StoreClient store(kv_addr, remain_or_throw(deadline));

  std::string my_addr =
      local_hostname() + ":" + std::to_string(listener_->port());
  store.set(prefix + "/hc_addr_" + std::to_string(rank), my_addr,
            remain_or_throw(deadline));

  // (tier, next global rank, prev global rank, connection count) of every
  // ring this member participates in.
  struct TierPlanEntry {
    uint32_t tier;
    int64_t next_rank;
    int64_t prev_rank;
    int64_t conns;
    std::vector<Socket> next;
    std::vector<Socket> prev;
    std::string next_addr;  // diagnostics: where this tier's edges lead
    std::string prev_addr;
  };
  std::vector<TierPlanEntry> tiers;
  tiers.push_back({kTierFlat, (rank + 1) % world_size,
                   (rank - 1 + world_size) % world_size, stripes, {}, {},
                   {}, {}});
  if (hier && is_host_leader && intra_world > 1) {
    tiers.push_back(
        {kTierIntra, intra_members[(intra_rank + 1) % intra_world],
         intra_members[(intra_rank - 1 + intra_world) % intra_world],
         stripes, {}, {}, {}, {}});
  }
  if (is_leader && inter_world > 1) {
    tiers.push_back({kTierInter, leaders[(inter_rank + 1) % inter_world],
                     leaders[(inter_rank - 1 + inter_world) % inter_world],
                     stripes_inter, {}, {}, {}, {}});
  }
  const int64_t host_next =
      host_world > 1 ? host_members[(host_rank + 1) % host_world] : -1;
  const int64_t host_prev =
      host_world > 1 ? host_members[(host_rank - 1 + host_world) % host_world]
                     : -1;
  if (host_world > 1 && !shm_on) {
    // TORCHFT_HC_SHM=0: the host ring rides loopback TCP with identical
    // geometry — the honest control the shm bench row is measured
    // against, and the fallback where /dev/shm is unavailable.
    tiers.push_back({kTierHost, host_next, host_prev, stripes, {}, {}, {},
                     {}});
  }

  // Dial every tier's next member once per stripe; the hello names the
  // (tier, stripe) slot so the peer can place accepted connections
  // regardless of arrival order, and carries the stripe COUNT so a config
  // mismatch that slipped past the store-level negotiation still fails at
  // connect, not mid-op.
  // The hello magic names the FRAME FORMAT (rev 4 raw, rev 5 CRC-guarded):
  // a pair that disagrees on TORCHFT_WIRE_CRC fails right here instead of
  // desyncing 4 bytes into the first payload frame.
  const uint32_t hello_magic = crc_ ? kHelloMagicCrc : kHelloMagic;
  for (auto& tp : tiers) {
    tp.next_addr =
        store.get(prefix + "/hc_addr_" + std::to_string(tp.next_rank),
                  remain_or_throw(deadline));
    tp.next.resize(tp.conns);
    for (int64_t s = 0; s < tp.conns; s++) {
      tp.next[s] = connect_with_retry(tp.next_addr, remain_or_throw(deadline));
      uint32_t hello[5] = {hello_magic, static_cast<uint32_t>(rank),
                           static_cast<uint32_t>(s),
                           static_cast<uint32_t>(tp.conns), tp.tier};
      tp.next[s].send_all(hello, sizeof(hello), deadline);
    }
    tp.prev.resize(tp.conns);
  }

  int64_t expected = 0;
  for (auto& tp : tiers) expected += tp.conns;
  for (int64_t i = 0; i < expected; i++) {
    Socket sock = listener_->accept(deadline);
    if (!sock.valid()) throw SocketError("listener closed during configure");
    uint32_t peer_hello[5];
    sock.recv_all(peer_hello, sizeof(peer_hello), deadline);
    if (peer_hello[0] != hello_magic) {
      if (peer_hello[0] == kHelloMagic || peer_hello[0] == kHelloMagicCrc)
        throw SocketError(
            "ring handshake: wire-CRC mismatch (this rank has "
            "TORCHFT_WIRE_CRC " + std::string(crc_ ? "on" : "off") +
            ", peer has the opposite — all members must agree; the store "
            "negotiation should have caught this first)");
      throw SocketError(
          "ring handshake: wire-protocol mismatch (peer binary speaks a "
          "different ring protocol revision)");
    }
    TierPlanEntry* tp = nullptr;
    for (auto& cand : tiers)
      if (cand.tier == peer_hello[4]) { tp = &cand; break; }
    if (tp == nullptr)
      throw SocketError(
          "ring handshake: connection for a tier this rank does not "
          "participate in (mismatched region maps?)");
    if (peer_hello[1] != static_cast<uint32_t>(tp->prev_rank))
      throw SocketError("ring handshake: unexpected peer rank");
    if (peer_hello[3] != static_cast<uint32_t>(tp->conns))
      throw SocketError(
          "ring handshake: stripe-count mismatch (this rank " +
          std::to_string(tp->conns) + ", prev rank " +
          std::to_string(peer_hello[3]) +
          " — all members must configure the same stripes)");
    uint32_t slot = peer_hello[2];
    if (slot >= static_cast<uint32_t>(tp->conns) || tp->prev[slot].valid())
      throw SocketError("ring handshake: bad or duplicate stripe index");
    if (tp->prev_addr.empty()) tp->prev_addr = peer_addr_str(sock.fd());
    tp->prev[slot] = std::move(sock);
  }

  // Shared-memory host edges: created/attached AFTER the TCP rendezvous
  // (the store round already ordered everyone into this generation), one
  // edge pair per stripe. Deadline-bounded like every phase-2 step.
  std::vector<ShmEdge> shm_edges;
  if (host_world > 1 && shm_on) {
    // Segment namespace: the store prefix is unique per quorum, so its
    // hash scopes the names to this generation; ranks scope the edge.
    std::string base = "tft_hc_" + [&] {
      char buf[20];
      snprintf(buf, sizeof(buf), "%016llx",
               static_cast<unsigned long long>(fnv64(store_addr)));
      return std::string(buf);
    }();
    wire_shm_edges(shm_edges, stripes, base, host_next, host_prev, deadline);
  }

  // Phase 3: publish the new rings unless an abort raced in.
  MutexLock lock(cfg_mu_);
  if (abort_epoch_ != epoch) throw SocketError("aborted during configure");
  for (auto& tp : tiers) {
    RingTier& T = tp.tier == kTierFlat ? flat_
                  : tp.tier == kTierIntra ? intra_
                  : tp.tier == kTierInter ? inter_
                                          : host_;
    T.next = std::move(tp.next);
    T.prev = std::move(tp.prev);
    T.peer_next_addr = tp.next_addr;
    T.peer_prev_addr = tp.prev_addr;
    for (size_t s = 0; s < T.scratch.size(); s++)
      T.scratch[s].tag = "tier=" + T.name + " stripe=" + std::to_string(s) +
                         " prev_peer=" + T.peer_prev_addr;
  }
  if (!shm_edges.empty()) {
    host_.use_shm = true;
    host_.shm = std::move(shm_edges);
    host_.peer_next_addr = "shm:rank" + std::to_string(host_next);
    host_.peer_prev_addr = "shm:rank" + std::to_string(host_prev);
    for (size_t s = 0; s < host_.scratch.size(); s++)
      host_.scratch[s].tag = "tier=host stripe=" + std::to_string(s) +
                             " prev_peer=" + host_.peer_prev_addr;
  }
  aborted_ = false;
}

void HostCollectives::wire_shm_edges(std::vector<ShmEdge>& edges,
                                     int64_t conns, const std::string& base,
                                     int64_t next_rank, int64_t prev_rank,
                                     int64_t deadline) {
  const size_t seg_bytes = kShmHdrBytes + shm_ring_bytes_;
  for (int64_t s = 0; s < conns; s++) {
    ShmEdge e;
    std::string txname = base + "_" + std::to_string(rank_) + "_" +
                         std::to_string(next_rank) + "_s" + std::to_string(s);
    // Defensive unlink: a SIGKILLed predecessor of a crashed run may have
    // leaked the name (same idempotent discipline as the iso plane).
    ShmSegment::Unlink(txname);
    e.tx.reset(ShmSegment::Create(txname, seg_bytes));
    // Fresh segments are zero-filled (ftruncate): head/tail/doorbells
    // start at 0; publish capacity, then the magic with release so an
    // attacher that sees the magic sees the capacity too.
    ShmRingHdr* h = shm_ring_hdr(e.tx->data());
    h->capacity = static_cast<uint32_t>(shm_ring_bytes_);
    h->owner_pid.store(static_cast<uint32_t>(getpid()),
                       std::memory_order_relaxed);
    h->magic.store(kShmRingMagic, std::memory_order_release);

    std::string rxname = base + "_" + std::to_string(prev_rank) + "_" +
                         std::to_string(rank_) + "_s" + std::to_string(s);
    for (;;) {
      remain_or_throw(deadline);
      try {
        e.rx.reset(ShmSegment::Attach(rxname, seg_bytes));
        break;
      } catch (const SocketError&) {
        // Not created yet (or still the wrong generation's size): the
        // peer is inside its own configure. Retry until the deadline.
        struct timespec ts{0, 5 * 1000000};
        nanosleep(&ts, nullptr);
      }
    }
    ShmRingHdr* rh = shm_ring_hdr(e.rx->data());
    while (rh->magic.load(std::memory_order_acquire) != kShmRingMagic) {
      remain_or_throw(deadline);
      struct timespec ts{0, 1 * 1000000};
      nanosleep(&ts, nullptr);
    }
    if (rh->capacity != shm_ring_bytes_)
      throw SocketError(
          "shm ring capacity mismatch (TORCHFT_HC_SHM_RING_BYTES drifted "
          "across co-hosted members: mine " +
          std::to_string(shm_ring_bytes_) + ", peer " +
          std::to_string(rh->capacity) + ")");
    rh->peer_pid.store(static_cast<uint32_t>(getpid()),
                       std::memory_order_relaxed);
    edges.push_back(std::move(e));
  }
}

void HostCollectives::duplex(Socket& next, Socket& prev, const char* send_buf,
                             size_t send_len, char* recv_buf, size_t recv_len,
                             int64_t deadline_ms, StripeScratch* sc,
                             bool header_frame) {
  const double bps = sc ? static_cast<double>(sc->cap_bps) : 0.0;
  PaceState* pace = sc ? &sc->pace : nullptr;
  // Burst = 20 ms of credit (floor 64 KB): small enough that the realized
  // rate tracks the cap within any measurement window, large enough that a
  // chunk-sized write needs one send call.
  const double burst = std::max(65536.0, bps / 50.0);

  // Chaos seam: the ring frame send path. Disarmed, this is one relaxed
  // atomic load; armed, the seeded schedule decides per (member,
  // op_index) — and at most one frame of the op is hit (the harness arms
  // one-shot rules), on whichever stripe claims the firing first.
  bool flip_pending = false;
  bool partitioned = false;
  fault::Decision fd =
      send_len > 0
          ? TFT_FAULT_CHECK(header_frame ? fault::kSeamRingHdr
                                         : fault::kSeamRingSend,
                            rank_, op_seq_)
          : fault::Decision{};
  if (fd.kind != fault::kNone) {
    // Deadline-bounded raw send of a fault's own bytes (the sockets are
    // non-blocking).
    auto raw_send = [&](const char* buf, size_t n) {
      size_t done = 0;
      while (done < n) {
        ssize_t w =
            ::send(next.fd(), buf + done, n - done, MSG_NOSIGNAL | MSG_DONTWAIT);
        if (w > 0) {
          done += static_cast<size_t>(w);
          if (sc) sc->tx_bytes += w;
          continue;
        }
        if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
          struct pollfd pfd{next.fd(), POLLOUT, 0};
          int timeout =
              poll_timeout_or_throw(deadline_ms, "collective timed out");
          if (::poll(&pfd, 1, timeout) < 0 && errno != EINTR)
            throw SocketError(std::string("poll: ") + strerror(errno));
          continue;
        }
        if (w < 0 && errno == EINTR) continue;
        throw SocketError(std::string("ring send: ") + strerror(errno));
      }
    };
    switch (fd.kind) {
      case fault::kDrop:
        next.shutdown_rdwr();
        prev.shutdown_rdwr();
        throw SocketError("chaos injected: ring send dropped (" +
                          (sc ? sc->tag : std::string("?")) + ")");
      case fault::kDelay: {
        // Bounded by the op deadline (the fault.h contract): a delay
        // fault stalls the op, it must never stall PAST the op.
        int64_t ms = fd.param;
        if (deadline_ms >= 0) {
          int64_t remain = deadline_ms - now_ms();
          if (remain < 0) remain = 0;
          if (ms > remain) ms = remain;
        }
        struct timespec ts;
        ts.tv_sec = ms / 1000;
        ts.tv_nsec = (ms % 1000) * 1000000;
        nanosleep(&ts, nullptr);
        break;
      }
      case fault::kTruncate:
        // A torn write then death: the peer sees a partial frame + EOF.
        raw_send(send_buf, send_len / 2);
        next.shutdown_rdwr();
        prev.shutdown_rdwr();
        throw SocketError("chaos injected: ring send truncated (" +
                          (sc ? sc->tag : std::string("?")) + ")");
      case fault::kDuplicate:
        // Repeat a prefix: every later byte of the stream lands at the
        // wrong offset. With CRC on, THIS frame's trailer check catches
        // it; off, the desync surfaces at the next op header.
        raw_send(send_buf, send_len < 16 ? send_len : 16);
        break;
      case fault::kBitFlip:
        // Applied to the first chunk actually sent below: the caller's
        // buffer (and the CRC, computed over the ORIGINAL bytes) stay
        // clean — only the wire is poisoned.
        flip_pending = true;
        break;
      case fault::kPartition:
        // Asymmetric partition: our sends silently vanish while our
        // receives keep draining — the peer stalls until ITS op
        // deadline (a stall, not an error, is the injected failure).
        partitioned = true;
        break;
      default:
        break;
    }
  }

  // CRC-guarded framing (negotiated at configure): each direction with a
  // payload carries a 4-byte CRC32C trailer after its last payload byte.
  // The CRC state updates incrementally per kernel chunk, so the payload
  // is walked exactly once either way; with crc_ off the totals collapse
  // to the raw lengths and no CRC code runs — the single-branch contract.
  const bool crc = crc_;
  const size_t send_total = send_len + ((crc && send_len > 0) ? 4 : 0);
  const size_t recv_total = recv_len + ((crc && recv_len > 0) ? 4 : 0);
  uint32_t scrc = 0xFFFFFFFFu;
  uint32_t rcrc = 0xFFFFFFFFu;
  char strail[4];
  char rtrail[4];
  size_t sent = partitioned ? send_total : 0;
  size_t got = 0;
  while (sent < send_total || got < recv_total) {
    // Refill the token bucket and decide whether this pass may send; when
    // token-dry, the send fd leaves the poll set and the poll timeout
    // shrinks to the refill time, so receives still drain at full speed.
    // Pacing covers payload bytes only (the 4-byte trailer is noise).
    int64_t pace_wait_ms = -1;
    bool may_send = sent < send_total;
    if (may_send && sent < send_len && pace && bps > 0) {
      auto now = std::chrono::steady_clock::now();
      if (!pace->init) {
        pace->init = true;
        pace->tokens = burst;
      } else {
        pace->tokens +=
            std::chrono::duration<double>(now - pace->last).count() * bps;
        if (pace->tokens > burst) pace->tokens = burst;
      }
      pace->last = now;
      if (pace->tokens < 1.0) {
        may_send = false;
        pace_wait_ms =
            static_cast<int64_t>((1.0 - pace->tokens) / bps * 1000.0) + 1;
      }
    }
    struct pollfd pfds[2];
    int n = 0;
    int send_idx = -1, recv_idx = -1;
    if (may_send) {
      send_idx = n;
      pfds[n].fd = next.fd();
      pfds[n].events = POLLOUT;
      n++;
    }
    if (got < recv_total) {
      recv_idx = n;
      pfds[n].fd = prev.fd();
      pfds[n].events = POLLIN;
      n++;
    }
    int timeout = poll_timeout_or_throw(deadline_ms, "collective timed out");
    if (pace_wait_ms >= 0 && (timeout < 0 || pace_wait_ms < timeout))
      timeout = static_cast<int>(pace_wait_ms);
    int prc = ::poll(pfds, n, timeout);
    if (prc == 0) {
      if (pace_wait_ms >= 0) continue;  // token refill elapsed, not a stall
      throw TimeoutError("collective timed out");
    }
    if (prc < 0) {
      if (errno == EINTR) continue;
      throw SocketError(std::string("poll: ") + strerror(errno));
    }
    if (send_idx >= 0 && (pfds[send_idx].revents & (POLLOUT | POLLERR | POLLHUP))) {
      if (sent < send_len) {
        size_t allow = send_len - sent;
        if (pace && bps > 0 && static_cast<double>(allow) > pace->tokens)
          allow = static_cast<size_t>(pace->tokens);
        const char* src = send_buf + sent;
        char flipbuf[4096];
        if (flip_pending && allow > 0) {
          // Poison exactly one bit of the first byte of this chunk on
          // its way to the wire; the sender's CRC (below) covers the
          // ORIGINAL bytes, so the receiver's trailer check must fire.
          size_t n = allow < sizeof(flipbuf) ? allow : sizeof(flipbuf);
          memcpy(flipbuf, src, n);
          flipbuf[0] ^= static_cast<char>(1u << ((fd.h >> 8) % 8));
          src = flipbuf;
          allow = n;
        }
        ssize_t w = ::send(next.fd(), src, allow,
                           MSG_NOSIGNAL | MSG_DONTWAIT);
        if (w > 0) {
          if (flip_pending) flip_pending = false;  // byte 0 is out
          if (crc) scrc = fault::crc32c_update(scrc, send_buf + sent, w);
          sent += static_cast<size_t>(w);
          if (pace && bps > 0) pace->tokens -= static_cast<double>(w);
          // Per-connection tx accounting (the hierarchical per-tier byte
          // bill sums these): bytes actually handed to the kernel.
          if (sc) sc->tx_bytes += w;
          if (crc && sent == send_len) {
            uint32_t fin = ~scrc;
            memcpy(strail, &fin, sizeof(fin));
          }
        } else if (w < 0 && errno != EAGAIN && errno != EWOULDBLOCK &&
                   errno != EINTR) {
          throw SocketError(std::string("ring send: ") + strerror(errno));
        }
      } else {
        // CRC trailer (4 bytes, unpaced).
        ssize_t w = ::send(next.fd(), strail + (sent - send_len),
                           send_total - sent, MSG_NOSIGNAL | MSG_DONTWAIT);
        if (w > 0) {
          sent += static_cast<size_t>(w);
          if (sc) sc->tx_bytes += w;
        } else if (w < 0 && errno != EAGAIN && errno != EWOULDBLOCK &&
                   errno != EINTR) {
          throw SocketError(std::string("ring send: ") + strerror(errno));
        }
      }
    }
    if (recv_idx >= 0 &&
        (pfds[recv_idx].revents & (POLLIN | POLLERR | POLLHUP))) {
      if (got < recv_len) {
        ssize_t r =
            ::recv(prev.fd(), recv_buf + got, recv_len - got, MSG_DONTWAIT);
        if (r > 0) {
          if (crc) rcrc = fault::crc32c_update(rcrc, recv_buf + got, r);
          got += static_cast<size_t>(r);
        } else if (r == 0) {
          throw SocketError("ring peer closed connection");
        } else if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR) {
          throw SocketError(std::string("ring recv: ") + strerror(errno));
        }
      } else {
        ssize_t r = ::recv(prev.fd(), rtrail + (got - recv_len),
                           recv_total - got, MSG_DONTWAIT);
        if (r > 0) {
          got += static_cast<size_t>(r);
          if (got == recv_total) {
            uint32_t want;
            memcpy(&want, rtrail, sizeof(want));
            if (want != ~rcrc)
              // The typed integrity error: rides the caller's latch ->
              // vote-discard -> reconfigure machinery instead of
              // committing poisoned bytes.
              throw WireCorruptionError(
                  "ring frame CRC32C mismatch (" +
                  (sc ? sc->tag : std::string("?")) + ", rank " +
                  std::to_string(rank_) + ", op_index " +
                  std::to_string(op_seq_) + ", frame " +
                  std::to_string(recv_len) + " bytes)");
          }
        } else if (r == 0) {
          throw SocketError("ring peer closed connection");
        } else if (errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR) {
          throw SocketError(std::string("ring recv: ") + strerror(errno));
        }
      }
    }
  }
}

void HostCollectives::edge_duplex(RingTier& T, int64_t s, const char* send_buf,
                                  size_t send_len, char* recv_buf,
                                  size_t recv_len, int64_t deadline_ms,
                                  bool header_frame) {
  if (T.use_shm)
    shm_duplex(T, s, send_buf, send_len, recv_buf, recv_len, deadline_ms,
               header_frame);
  else
    duplex(T.next[s], T.prev[s], send_buf, send_len, recv_buf, recv_len,
           deadline_ms, &T.scratch[s], header_frame);
}

void HostCollectives::shm_duplex(RingTier& T, int64_t s, const char* send_buf,
                                 size_t send_len, char* recv_buf,
                                 size_t recv_len, int64_t deadline_ms,
                                 bool header_frame) {
  ShmEdge& e = T.shm[s];
  StripeScratch& sc = T.scratch[s];
  ShmRingHdr* txh = shm_ring_hdr(e.tx->data());
  ShmRingHdr* rxh = shm_ring_hdr(e.rx->data());
  char* txd = shm_ring_data(e.tx->data());
  char* rxd = shm_ring_data(e.rx->data());
  const uint32_t tx_cap = txh->capacity;
  const uint32_t rx_cap = rxh->capacity;

  // Chaos seam: the shm ring frame path (payload frames only — like
  // ring_hdr/ring_send, a "mid-ring corruption" plan must not be
  // satisfiable by the op header). Disarmed: one relaxed atomic load.
  bool swallow = false;  // drop-doorbell: the publish silently vanishes
  bool stale = false;    // stale-payload: replay the previous frame seq
  bool torn = false;     // torn-segment: half a frame, then poison + die
  fault::Decision fd =
      (send_len > 0 && !header_frame)
          ? TFT_FAULT_CHECK(fault::kSeamShmRing, rank_, op_seq_)
          : fault::Decision{};
  switch (fd.kind) {
    case fault::kDrop:
    case fault::kPartition:
      // The doorbell (and the bytes behind it) never land: the consumer
      // stalls until ITS op deadline — the stall, not an error, is the
      // injected failure (the co-hosted analog of an asymmetric
      // partition / SIGKILLed producer).
      swallow = true;
      break;
    case fault::kBitFlip:
      stale = true;
      break;
    case fault::kTruncate:
      torn = true;
      break;
    case fault::kDelay: {
      int64_t ms = fd.param;
      if (deadline_ms >= 0) {
        int64_t remain = deadline_ms - now_ms();
        if (remain < 0) remain = 0;
        if (ms > remain) ms = remain;
      }
      struct timespec ts;
      ts.tv_sec = ms / 1000;
      ts.tv_nsec = (ms % 1000) * 1000000;
      nanosleep(&ts, nullptr);
      break;
    }
    default:
      break;
  }

  ShmFrame shdr{};
  // A swallowed (dropped/partitioned) frame never ships: its sequence
  // must not advance either, or a later frame would read as a skip.
  if (send_len > 0 && !swallow) e.fseq_tx++;
  shdr.fseq = stale ? e.fseq_tx - 1 : e.fseq_tx;
  shdr.len = static_cast<uint32_t>(send_len);
  const char* shdr_bytes = reinterpret_cast<const char*>(&shdr);
  const size_t send_total = send_len > 0 ? sizeof(ShmFrame) + send_len : 0;
  // Torn-segment fault: stop mid-frame, poison, die (the consumer's
  // magic check is the detection).
  const size_t send_stop =
      torn ? sizeof(ShmFrame) + send_len / 2 : send_total;
  const size_t recv_total = recv_len > 0 ? sizeof(ShmFrame) + recv_len : 0;

  size_t sent = swallow ? send_total : 0;
  size_t got = 0;
  char rhdr_buf[sizeof(ShmFrame)];
  bool rhdr_checked = recv_total == 0;

  while (sent < send_total || got < recv_total) {
    if (aborted_.load(std::memory_order_relaxed))
      throw SocketError("collective aborted (" + sc.tag + ")");
    // Doorbell values read BEFORE the condition re-check: the standard
    // futex lost-wakeup protocol (a publish between our check and the
    // wait makes the wait return immediately).
    uint32_t v_w = rxh->db_w.load(std::memory_order_acquire);
    uint32_t v_r = txh->db_r.load(std::memory_order_acquire);
    bool progress = false;

    if (sent < send_stop) {
      if (txh->magic.load(std::memory_order_relaxed) != kShmRingMagic)
        throw SocketError("shm ring torn (aborted or reconfigured): " +
                          sc.tag);
      uint64_t head = txh->head.load(std::memory_order_relaxed);
      uint64_t tail = txh->tail.load(std::memory_order_acquire);
      size_t space = tx_cap - static_cast<size_t>(head - tail);
      if (space > 0) {
        size_t n = std::min(space, send_stop - sent);
        // The logical stream: 16 header bytes, then the payload.
        size_t done = 0;
        while (done < n) {
          size_t off = sent + done;
          const char* src;
          size_t avail;
          if (off < sizeof(ShmFrame)) {
            src = shdr_bytes + off;
            avail = sizeof(ShmFrame) - off;
          } else {
            src = send_buf + (off - sizeof(ShmFrame));
            avail = send_total - off;
          }
          size_t chunk = std::min(n - done, avail);
          shm_ring_write(txd, tx_cap, head + done, src, chunk);
          done += chunk;
        }
        txh->head.store(head + n, std::memory_order_release);
        txh->db_w.fetch_add(1, std::memory_order_release);
        shm_futex_wake(&txh->db_w);
        sc.shm_bytes += static_cast<int64_t>(n);
        sent += n;
        progress = true;
      }
      if (torn && sent >= send_stop) {
        {
          MutexLock lock(cfg_mu_);
          shm_poison_wake_locked();
        }
        throw SocketError("chaos injected: shm segment torn (" + sc.tag +
                          ")");
      }
    }

    if (got < recv_total) {
      uint64_t head = rxh->head.load(std::memory_order_acquire);
      uint64_t tail = rxh->tail.load(std::memory_order_relaxed);
      size_t avail = static_cast<size_t>(head - tail);
      if (avail == 0 &&
          rxh->magic.load(std::memory_order_acquire) != kShmRingMagic)
        throw SocketError("shm ring torn by peer (abort or death): " +
                          sc.tag);
      if (avail > 0) {
        size_t n = std::min(avail, recv_total - got);
        size_t done = 0;
        while (done < n) {
          size_t off = got + done;
          char* dst;
          size_t room;
          if (off < sizeof(ShmFrame)) {
            dst = rhdr_buf + off;
            room = sizeof(ShmFrame) - off;
          } else {
            dst = recv_buf + (off - sizeof(ShmFrame));
            room = recv_total - off;
          }
          size_t chunk = std::min(n - done, room);
          shm_ring_read(rxd, rx_cap, tail + done, dst, chunk);
          done += chunk;
        }
        rxh->tail.store(tail + n, std::memory_order_release);
        rxh->db_r.fetch_add(1, std::memory_order_release);
        shm_futex_wake(&rxh->db_r);
        got += n;
        progress = true;
        if (!rhdr_checked && got >= sizeof(ShmFrame)) {
          ShmFrame rhdr;
          memcpy(&rhdr, rhdr_buf, sizeof(rhdr));
          e.fseq_rx++;
          if (rhdr.fseq != e.fseq_rx)
            // The typed integrity verdict: a replayed (stale) frame must
            // ride the latch -> vote-discard -> reconfigure machinery,
            // not silently reduce yesterday's bytes.
            throw WireCorruptionError(
                "shm ring stale frame (" + sc.tag + ", rank " +
                std::to_string(rank_) + ", op_index " +
                std::to_string(op_seq_) + ": expected frame " +
                std::to_string(e.fseq_rx) + ", got " +
                std::to_string(rhdr.fseq) + ")");
          if (rhdr.len != recv_len)
            throw SocketError(
                "shm ring frame desync (" + sc.tag + "): expected " +
                std::to_string(recv_len) + " bytes, peer framed " +
                std::to_string(rhdr.len) +
                " (members must run identical ops)");
          rhdr_checked = true;
        }
      }
    }

    if (!progress) {
      int64_t remain = deadline_ms < 0 ? 100 : deadline_ms - now_ms();
      if (remain <= 0) throw TimeoutError("collective timed out");
      // Liveness probe before sleeping: a SIGKILLed co-hosted peer
      // leaves no FIN and no poison — its pid vanishing is the only
      // signal, checked once per slice (~100 ms surfacing).
      if (got < recv_total &&
          shm_pid_gone(rxh->owner_pid.load(std::memory_order_relaxed)))
        throw SocketError("shm ring peer died (producer pid gone): " +
                          sc.tag);
      if (sent < send_stop &&
          shm_pid_gone(txh->peer_pid.load(std::memory_order_relaxed)))
        throw SocketError("shm ring peer died (consumer pid gone): " +
                          sc.tag);
      // Wait on whichever side is blocking us; receives take priority
      // (they are what unblocks a full TX ring on the far side).
      if (got < recv_total)
        shm_futex_wait(&rxh->db_w, v_w, remain);
      else
        shm_futex_wait(&txh->db_r, v_r, remain);
    }
  }
}

void HostCollectives::check_op_header(RingTier& T, uint32_t kind,
                                      uint64_t count, uint32_t dtype,
                                      uint32_t op, int64_t deadline_ms) {
  // One tiny duplex exchange describing the op each neighbor is about to
  // run. A mismatched op (different tree sizes, dtypes, or op kinds on
  // different members) otherwise DEADLOCKS silently: the small member
  // finishes, stops reading, and the large member blocks forever once
  // kernel buffers fill. ~20 bytes per collective — noise next to any
  // payload — converts that into an immediate, descriptive error. Runs on
  // stripe 0 of the tier (the stripe COUNT is already pinned at connect
  // time by the hello, so one stripe's agreement covers the schedule);
  // hierarchical ops run it once per tier they touch.
  struct Header {
    uint32_t magic, kind;
    uint64_t count;
    uint32_t dtype, op;
  } mine{kOpMagic, kind, count, dtype, op}, theirs{};
  edge_duplex(T, 0, reinterpret_cast<const char*>(&mine), sizeof(mine),
              reinterpret_cast<char*>(&theirs), sizeof(theirs), deadline_ms,
              /*header_frame=*/true);
  if (theirs.magic != kOpMagic)
    // Keep the historic prefix (operators and tests grep for it); the
    // context after it is what makes the error actionable in a W=8
    // fleet log — which edge, which tier, which op.
    throw SocketError(
        "ring op header corrupt (protocol desync): tier=" + T.name +
        " prev_peer=" + T.peer_prev_addr + " op_kind=" +
        std::to_string(kind) + " op_index=" + std::to_string(op_seq_) +
        " rank=" + std::to_string(rank_) + " got_magic=0x" + [&] {
          char buf[16];
          snprintf(buf, sizeof(buf), "%08x", theirs.magic);
          return std::string(buf);
        }());
  if (theirs.kind != mine.kind || theirs.count != mine.count ||
      theirs.dtype != mine.dtype || theirs.op != mine.op)
    throw SocketError(
        "ring op mismatch: this rank kind=" + std::to_string(kind) +
        " count=" + std::to_string(count) + " dtype=" +
        std::to_string(dtype) + " op=" + std::to_string(op) +
        ", prev rank kind=" + std::to_string(theirs.kind) + " count=" +
        std::to_string(theirs.count) + " dtype=" +
        std::to_string(theirs.dtype) + " op=" + std::to_string(theirs.op) +
        " (members must reduce identical trees)");
}

void HostCollectives::run_striped(const std::function<void(int64_t)>& fn) {
  int64_t n = static_cast<int64_t>(last_stripe_ns_.size());
  std::vector<std::exception_ptr> errs(n);

  auto body = [&](int64_t s) {
    auto t0 = std::chrono::steady_clock::now();
    try {
      fn(s);
    } catch (...) {
      errs[s] = std::current_exception();
      // Wake every sibling stripe immediately: they share the op's fate,
      // and letting them block until their timeout would stall the abort
      // path the whole design exists to keep fast.
      shutdown_sockets();
    }
    last_stripe_ns_[s] =
        std::chrono::duration_cast<std::chrono::nanoseconds>(
            std::chrono::steady_clock::now() - t0)
            .count();
  };

  if (n <= 1) {
    body(0);
  } else {
    // Publish the job to the persistent workers (a thread per stripe per
    // native op would cost more than the stripe's transport at pipelined
    // chunk sizes), run stripe 0 here, then wait for the drain. The drain
    // wait is unconditional-bounded: failing stripes shut down every
    // socket, so no sibling can block past its IO wakeup.
    std::function<void(int64_t)> body_fn = body;
    ensure_pool(n - 1);
    {
      MutexLock lock(pool_mu_);
      pool_body_ = &body_fn;
      pool_n_ = n;
      pool_pending_ = n - 1;
      pool_gen_++;
    }
    pool_cv_.notify_all();
    body(0);
    {
      UniqueMutexLock lock(pool_mu_);
      while (pool_pending_ != 0) pool_done_cv_.wait(lock);
      pool_body_ = nullptr;
    }
  }
  // ONE error is rethrown. A typed WireCorruptionError beats its
  // siblings regardless of stripe index: the failing stripe's shutdown
  // makes every other stripe die with a GENERIC socket error, and
  // rethrowing one of those would erase the integrity verdict the
  // cross-language "wire corruption:" contract (and the chaos harness's
  // detection ledger) depends on. Otherwise: lowest stripe wins.
  std::exception_ptr chosen;
  for (auto& e : errs) {
    if (!e) continue;
    if (!chosen) chosen = e;
    try {
      std::rethrow_exception(e);
    } catch (const WireCorruptionError&) {
      chosen = e;
      break;
    } catch (...) {
    }
  }
  if (chosen) std::rethrow_exception(chosen);
}

void HostCollectives::ensure_pool(int64_t workers) {
  MutexLock lock(pool_mu_);
  while (static_cast<int64_t>(pool_.size()) < workers) {
    // Seed each worker with the CURRENT generation (stable under pool_mu_):
    // a fresh thread must not mistake an already-running or past job for
    // its first wakeup.
    pool_.emplace_back(&HostCollectives::pool_main, this,
                       static_cast<int64_t>(pool_.size()), pool_gen_);
  }
}

void HostCollectives::pool_main(int64_t idx, int64_t start_gen) {
  int64_t seen_gen = start_gen;
  for (;;) {
    const std::function<void(int64_t)>* body;
    int64_t n;
    {
      UniqueMutexLock lock(pool_mu_);
      while (!pool_stop_ && pool_gen_ == seen_gen) pool_cv_.wait(lock);
      if (pool_stop_) return;
      seen_gen = pool_gen_;
      body = pool_body_;
      n = pool_n_;
    }
    // Worker idx owns stripe idx+1; jobs narrower than the pool (fewer
    // effective stripes) don't count the spare workers in pool_pending_.
    if (idx + 1 < n) {
      (*body)(idx + 1);
      MutexLock lock(pool_mu_);
      if (--pool_pending_ == 0) pool_done_cv_.notify_all();
    }
  }
}

void HostCollectives::rs_phase_stripe(RingTier& T, int64_t s, char* bytes,
                                      size_t count, size_t esize, Dtype dtype,
                                      ReduceOp op, int64_t deadline) {
  size_t max_chunk = count / T.world + 1;
  std::vector<char>& recv_tmp = T.scratch[s].recv;
  if (recv_tmp.size() < max_chunk * esize) recv_tmp.resize(max_chunk * esize);

  // Reduce-scatter: after step t, chunk (rank - t) has accumulated the
  // values of ranks rank-t..rank. After ws-1 steps chunk (rank+1) holds the
  // full reduction at this rank — computed in the identical rank order
  // everywhere.
  for (int64_t t = 0; t < T.world - 1; t++) {
    int64_t send_c = ((T.rank - t) % T.world + T.world) % T.world;
    int64_t recv_c = ((T.rank - t - 1) % T.world + T.world) % T.world;
    auto [s_start, s_len] = chunk_range(count, T.world, send_c);
    auto [r_start, r_len] = chunk_range(count, T.world, recv_c);
    edge_duplex(T, s, bytes + s_start * esize, s_len * esize,
                recv_tmp.data(), r_len * esize, deadline);
    reduce_into(bytes + r_start * esize, recv_tmp.data(), r_len, dtype, op);
  }
}

void HostCollectives::ag_phase_stripe(RingTier& T, int64_t s, char* bytes,
                                      size_t count, size_t esize,
                                      int64_t deadline) {
  // Allgather: circulate the owned chunks, starting from (rank + 1) —
  // the chunk the reduce-scatter phase leaves fully reduced here.
  for (int64_t t = 0; t < T.world - 1; t++) {
    int64_t send_c = ((T.rank + 1 - t) % T.world + T.world) % T.world;
    int64_t recv_c = ((T.rank - t) % T.world + T.world) % T.world;
    auto [s_start, s_len] = chunk_range(count, T.world, send_c);
    auto [r_start, r_len] = chunk_range(count, T.world, recv_c);
    edge_duplex(T, s, bytes + s_start * esize, s_len * esize,
                bytes + r_start * esize, r_len * esize, deadline);
  }
}

void HostCollectives::allreduce_stripe(RingTier& T, int64_t s, char* bytes,
                                       size_t count, size_t esize, Dtype dtype,
                                       ReduceOp op, int64_t deadline) {
  rs_phase_stripe(T, s, bytes, count, esize, dtype, op, deadline);
  ag_phase_stripe(T, s, bytes, count, esize, deadline);
}

void HostCollectives::allreduce(void* data, size_t count, Dtype dtype,
                                ReduceOp op, int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  if (world_size_ == 1) return;
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // header exchanged even for count==0: an empty-vs-nonempty mismatch
    // must error, not hang the nonempty member
    check_op_header(flat_, 0, count, static_cast<uint32_t>(dtype),
                    static_cast<uint32_t>(op), deadline);
    if (count == 0) return;
    char* bytes = static_cast<char*>(data);
    size_t esize = dtype_size(dtype);
    int64_t eff = effective_stripes(count * esize, stripes_);
    last_stripe_ns_.assign(eff, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff, s);
      if (len == 0) return;
      allreduce_stripe(flat_, s, bytes + start * esize, len, esize, dtype, op,
                       deadline);
    });
  });
}

namespace {

// One chunk on the q8 wire: 4-byte f32 scale, then `len` int8 codes.
void q8_encode(const float* src, size_t len, char* wire) {
  float absmax = 0.f;
  bool finite = true;
  for (size_t i = 0; i < len; i++) {
    float a = std::fabs(src[i]);
    if (!std::isfinite(a)) finite = false;
    absmax = std::max(absmax, a);
  }
  if (!finite) {
    // Non-finite gradients must poison the result the way the f32/bf16
    // wires do: std::max/min drop NaN (they return the other operand),
    // so a diverged model would otherwise be encoded as clamped finite
    // codes and the blow-up silently hidden. A NaN scale makes every
    // decoded element NaN on all ranks.
    float nan = std::numeric_limits<float>::quiet_NaN();
    memcpy(wire, &nan, sizeof(float));
    memset(wire + sizeof(float), 0, len);
    return;
  }
  float scale = absmax > 0.f ? absmax / 127.f : 1.f;
  memcpy(wire, &scale, sizeof(float));
  int8_t* q = reinterpret_cast<int8_t*>(wire + sizeof(float));
  for (size_t i = 0; i < len; i++) {
    float v = std::nearbyint(src[i] / scale);
    q[i] = static_cast<int8_t>(std::max(-127.f, std::min(127.f, v)));
  }
}

// dst[i] (+)= scale * q[i]
void q8_decode(const char* wire, size_t len, float* dst, bool accumulate) {
  float scale;
  memcpy(&scale, wire, sizeof(float));
  const int8_t* q = reinterpret_cast<const int8_t*>(wire + sizeof(float));
  if (accumulate) {
    for (size_t i = 0; i < len; i++) dst[i] += scale * static_cast<float>(q[i]);
  } else {
    for (size_t i = 0; i < len; i++) dst[i] = scale * static_cast<float>(q[i]);
  }
}

}  // namespace

void HostCollectives::rs_q8_phase_stripe(RingTier& T, int64_t s, float* data,
                                         size_t count, int64_t deadline) {
  size_t max_chunk = count / T.world + 1;
  size_t max_wire = sizeof(float) + max_chunk;
  std::vector<char>& send_wire = T.scratch[s].send;
  std::vector<char>& recv_wire = T.scratch[s].recv;
  if (send_wire.size() < max_wire) send_wire.resize(max_wire);
  if (recv_wire.size() < max_wire) recv_wire.resize(max_wire);

  // Reduce-scatter: each hop quantizes its CURRENT partial sum of the
  // outgoing chunk and dequant-accumulates the incoming one in f32.
  for (int64_t t = 0; t < T.world - 1; t++) {
    int64_t send_c = ((T.rank - t) % T.world + T.world) % T.world;
    int64_t recv_c = ((T.rank - t - 1) % T.world + T.world) % T.world;
    auto [s_start, s_len] = chunk_range(count, T.world, send_c);
    auto [r_start, r_len] = chunk_range(count, T.world, recv_c);
    q8_encode(data + s_start, s_len, send_wire.data());
    edge_duplex(T, s, send_wire.data(), sizeof(float) + s_len,
                recv_wire.data(), sizeof(float) + r_len, deadline);
    q8_decode(recv_wire.data(), r_len, data + r_start, /*accumulate=*/true);
  }
}

void HostCollectives::ag_q8_phase_stripe(RingTier& T, int64_t s, float* data,
                                         size_t count, int64_t deadline) {
  // Allgather: the OWNER quantizes its fully-reduced chunk exactly once
  // (first send); every later hop forwards the received wire bytes
  // verbatim, so all members decode identical codes — the reduced
  // values stay bit-identical across ranks (the determinism oracle).
  std::vector<std::vector<char>>& stored = T.scratch[s].stored;
  stored.resize(T.world);
  {
    int64_t own_c = (T.rank + 1) % T.world;
    auto [o_start, o_len] = chunk_range(count, T.world, own_c);
    stored[own_c].resize(sizeof(float) + o_len);
    q8_encode(data + o_start, o_len, stored[own_c].data());
    // decode own chunk too: every member must hold the DECODED codes,
    // not its higher-precision f32 partial (bit-identity across ranks)
    q8_decode(stored[own_c].data(), o_len, data + o_start, false);
  }
  for (int64_t t = 0; t < T.world - 1; t++) {
    int64_t send_c = ((T.rank + 1 - t) % T.world + T.world) % T.world;
    int64_t recv_c = ((T.rank - t) % T.world + T.world) % T.world;
    auto [r_start, r_len] = chunk_range(count, T.world, recv_c);
    stored[recv_c].resize(sizeof(float) + r_len);
    edge_duplex(T, s, stored[send_c].data(), stored[send_c].size(),
                stored[recv_c].data(), stored[recv_c].size(), deadline);
    q8_decode(stored[recv_c].data(), r_len, data + r_start, false);
  }
}

void HostCollectives::allreduce_q8_stripe(RingTier& T, int64_t s, float* data,
                                          size_t count, int64_t deadline) {
  rs_q8_phase_stripe(T, s, data, count, deadline);
  ag_q8_phase_stripe(T, s, data, count, deadline);
}

void HostCollectives::allreduce_q8(float* data, size_t count,
                                   int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  if (world_size_ == 1) return;
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // distinct kind: a q8 op meeting a plain allreduce must error, not
    // desync (their wire framings differ even at equal counts)
    check_op_header(flat_, 4, count, /*dtype=*/100, /*op=*/0, deadline);
    if (count == 0) return;
    // ~1 wire byte per f32 element (int8 codes + per-chunk scales)
    int64_t eff = effective_stripes(count, stripes_);
    last_stripe_ns_.assign(eff, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff, s);
      if (len == 0) return;
      allreduce_q8_stripe(flat_, s, data + start, len, deadline);
    });
  });
}

void HostCollectives::allgather(const void* in, void* out, size_t nbytes,
                                int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  char* slots = static_cast<char*>(out);
  memcpy(slots + rank_ * nbytes, in, nbytes);
  if (world_size_ == 1) return;
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    check_op_header(flat_, 1, nbytes, 0, 0, deadline);
    if (nbytes == 0) return;
    int64_t eff = effective_stripes(nbytes, stripes_);
    last_stripe_ns_.assign(eff, 0);
    run_striped([&](int64_t st) {
      auto [off, len] = stripe_range(nbytes, eff, st);
      if (len == 0) return;
      for (int64_t t = 0; t < world_size_ - 1; t++) {
        int64_t send_c = ((rank_ - t) % world_size_ + world_size_) % world_size_;
        int64_t recv_c =
            ((rank_ - t - 1) % world_size_ + world_size_) % world_size_;
        duplex(flat_.next[st], flat_.prev[st], slots + send_c * nbytes + off,
               len, slots + recv_c * nbytes + off, len, deadline,
               &flat_.scratch[st]);
      }
    });
  });
}

std::vector<std::pair<size_t, size_t>> HostCollectives::shard_ranges(
    size_t count, size_t esize, int64_t r, int64_t layout_stripes) const {
  if (r < 0 || r >= world_size_) throw SocketError("bad shard rank");
  int64_t eff = layout_stripes > 0
                    ? std::min(layout_stripes, stripes_)
                    : effective_stripes(count * esize, stripes_);
  int64_t own_c = (r + 1) % world_size_;
  std::vector<std::pair<size_t, size_t>> out;
  for (int64_t s = 0; s < eff; s++) {
    auto [st, sl] = stripe_range(count, eff, s);
    if (sl == 0) continue;
    auto [cs, cl] = chunk_range(sl, world_size_, own_c);
    if (cl) out.emplace_back(st + cs, cl);
  }
  return out;
}

void HostCollectives::copy_shard(char* data, char* shard, size_t count,
                                 size_t esize, int64_t eff,
                                 bool to_shard) const {
  // One source of truth for the layout: walk the same ranges Python gets
  // from shard_ranges, so compaction can never disagree with them.
  size_t off = 0;
  for (auto [start, len] : shard_ranges(count, esize, rank_, eff)) {
    if (to_shard)
      memcpy(shard + off * esize, data + start * esize, len * esize);
    else
      memcpy(data + start * esize, shard + off * esize, len * esize);
    off += len;
  }
}

void HostCollectives::reduce_scatter(void* data, size_t count, Dtype dtype,
                                     ReduceOp op, void* shard_out,
                                     int64_t layout_stripes,
                                     int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  size_t esize = dtype_size(dtype);
  if (world_size_ == 1) {
    memcpy(shard_out, data, count * esize);
    return;
  }
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    int64_t eff = layout_stripes > 0
                      ? std::min(layout_stripes, stripes_)
                      : effective_stripes(count * esize, stripes_);
    // The layout rides the header's op slot: a reduce_scatter meeting a
    // differently-partitioned one must error, not scatter to the wrong
    // shard boundaries (ReduceOp fits in the low byte).
    check_op_header(flat_, 5, count, static_cast<uint32_t>(dtype),
                    static_cast<uint32_t>(op) |
                        (static_cast<uint32_t>(eff) << 8),
                    deadline);
    if (count == 0) return;
    char* bytes = static_cast<char*>(data);
    last_stripe_ns_.assign(eff, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff, s);
      if (len == 0) return;
      rs_phase_stripe(flat_, s, bytes + start * esize, len, esize, dtype, op,
                      deadline);
    });
    copy_shard(bytes, static_cast<char*>(shard_out), count, esize, eff,
               /*to_shard=*/true);
  });
}

void HostCollectives::reduce_scatter_q8(float* data, size_t count,
                                        float* shard_out, bool grid_shard,
                                        int64_t layout_stripes,
                                        int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  if (world_size_ == 1) {
    memcpy(shard_out, data, count * sizeof(float));
    return;
  }
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // ~1 wire byte per f32 element, like the fused q8 op
    int64_t eff = layout_stripes > 0
                      ? std::min(layout_stripes, stripes_)
                      : effective_stripes(count, stripes_);
    check_op_header(flat_, 7, count, /*dtype=*/100,
                    static_cast<uint32_t>(eff) << 8, deadline);
    if (count == 0) return;
    last_stripe_ns_.assign(eff, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff, s);
      if (len == 0) return;
      rs_q8_phase_stripe(flat_, s, data + start, len, deadline);
      if (grid_shard) {
        // Reproduce the fused op's phase-2 owner quantize+decode so the
        // shard sits on the same int8 grid the fused allreduce returns.
        int64_t own_c = (rank_ + 1) % world_size_;
        auto [cs, cl] = chunk_range(len, world_size_, own_c);
        if (cl) {
          std::vector<char>& wire = flat_.scratch[s].send;
          if (wire.size() < sizeof(float) + cl)
            wire.resize(sizeof(float) + cl);
          q8_encode(data + start + cs, cl, wire.data());
          q8_decode(wire.data(), cl, data + start + cs, /*accumulate=*/false);
        }
      }
    });
    copy_shard(reinterpret_cast<char*>(data),
               reinterpret_cast<char*>(shard_out), count, sizeof(float), eff,
               /*to_shard=*/true);
  });
}

void HostCollectives::allgather_into(const void* shard, void* data,
                                     size_t count, Dtype dtype,
                                     int64_t layout_stripes,
                                     int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  size_t esize = dtype_size(dtype);
  if (world_size_ == 1) {
    memcpy(data, shard, count * esize);
    return;
  }
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    int64_t eff = layout_stripes > 0
                      ? std::min(layout_stripes, stripes_)
                      : effective_stripes(count * esize, stripes_);
    check_op_header(flat_, 6, count, static_cast<uint32_t>(dtype),
                    static_cast<uint32_t>(eff) << 8, deadline);
    if (count == 0) return;
    char* bytes = static_cast<char*>(data);
    copy_shard(bytes, const_cast<char*>(static_cast<const char*>(shard)),
               count, esize, eff, /*to_shard=*/false);
    last_stripe_ns_.assign(eff, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff, s);
      if (len == 0) return;
      ag_phase_stripe(flat_, s, bytes + start * esize, len, esize, deadline);
    });
  });
}

// ---- hierarchical (two-tier) schedule ----

void HostCollectives::bcast_pipe_stripe(RingTier& T, int64_t s, char* bytes,
                                        size_t nbytes, int64_t root,
                                        int64_t deadline) {
  if (T.world <= 1 || nbytes == 0) return;
  int64_t d = ((T.rank - root) % T.world + T.world) % T.world;
  // Chunk-pipelined store-and-forward: member d forwards chunk c-1 while
  // receiving chunk c (duplex pumps both directions), so the wall is
  // ~bytes/bw + (world-1) chunk fills instead of (world-1) * bytes/bw.
  // The chunk count is a pure function of nbytes — identical everywhere.
  int64_t k = std::min<int64_t>(16, std::max<int64_t>(
                                        1, static_cast<int64_t>(
                                               nbytes / (256 << 10))));
  const bool fwd = d + 1 < T.world;  // the last member's next IS the root
  for (int64_t c = 0; c < k; c++) {
    auto [cs, cl] = chunk_range(nbytes, k, c);
    if (d == 0) {
      edge_duplex(T, s, bytes + cs, cl, nullptr, 0, deadline);
    } else {
      const char* sbuf = nullptr;
      size_t slen = 0;
      if (fwd && c > 0) {
        auto [ps, pl] = chunk_range(nbytes, k, c - 1);
        sbuf = bytes + ps;
        slen = pl;
      }
      edge_duplex(T, s, sbuf, slen, bytes + cs, cl, deadline);
    }
  }
  if (d > 0 && fwd) {
    auto [ps, pl] = chunk_range(nbytes, k, k - 1);
    edge_duplex(T, s, bytes + ps, pl, nullptr, 0, deadline);
  }
}

void HostCollectives::inter_ring_phase(HierWire wire, char* buf, size_t count,
                                       size_t esize, Dtype dtype, ReduceOp op,
                                       int64_t eff_inter, int64_t deadline,
                                       int64_t* rs_tx) {
  // Two explicit ring phases (the same rs/ag bodies the flat ring uses)
  // so the per-phase slow-link bill — (L-1)/L of the wire payload each
  // way — is measured separately.
  const int64_t tx0 = tier_tx(inter_);
  if (wire == HierWire::kQ8) {
    float* f = reinterpret_cast<float*>(buf);
    last_stripe_ns_.assign(eff_inter, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_inter, s);
      if (len == 0) return;
      rs_q8_phase_stripe(inter_, s, f + start, len, deadline);
    });
    *rs_tx = tier_tx(inter_) - tx0;
    last_stripe_ns_.assign(eff_inter, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_inter, s);
      if (len == 0) return;
      ag_q8_phase_stripe(inter_, s, f + start, len, deadline);
    });
  } else if (wire == HierWire::kBF16) {
    // Leaders round the f32 payload to bf16 ONCE, ride the slow hop at
    // half width (per-hop f32 math, RNE back — the native bf16 ring
    // body), and decode; quantization noise is paid exactly once, on
    // the link that needs it, and all leaders decode identical words.
    if (hier_wire_buf_.size() < count * 2) hier_wire_buf_.resize(count * 2);
    uint16_t* w = reinterpret_cast<uint16_t*>(hier_wire_buf_.data());
    const float* f = reinterpret_cast<const float*>(buf);
    for (size_t i = 0; i < count; i++) w[i] = f32_to_bf16(f[i]);
    char* wb = hier_wire_buf_.data();
    last_stripe_ns_.assign(eff_inter, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_inter, s);
      if (len == 0) return;
      rs_phase_stripe(inter_, s, wb + start * 2, len, 2, Dtype::kBF16,
                      ReduceOp::kSum, deadline);
    });
    *rs_tx = tier_tx(inter_) - tx0;
    last_stripe_ns_.assign(eff_inter, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_inter, s);
      if (len == 0) return;
      ag_phase_stripe(inter_, s, wb + start * 2, len, 2, deadline);
    });
    float* out = reinterpret_cast<float*>(buf);
    for (size_t i = 0; i < count; i++) out[i] = bf16_to_f32(w[i]);
  } else {
    last_stripe_ns_.assign(eff_inter, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_inter, s);
      if (len == 0) return;
      rs_phase_stripe(inter_, s, buf + start * esize, len, esize, dtype, op,
                      deadline);
    });
    *rs_tx = tier_tx(inter_) - tx0;
    last_stripe_ns_.assign(eff_inter, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_inter, s);
      if (len == 0) return;
      ag_phase_stripe(inter_, s, buf + start * esize, len, esize, deadline);
    });
  }
}

void HostCollectives::hier_schedule(char* bytes, size_t count, size_t esize,
                                    Dtype dtype, ReduceOp op, HierWire wire,
                                    int64_t eff_intra, int64_t eff_inter,
                                    int64_t deadline) {
  using clock = std::chrono::steady_clock;
  const bool host_leader = host_.world <= 1 || host_.rank == 0;
  const bool leader =
      host_leader && (intra_.world <= 1 || intra_.rank == 0);
  // The host tier partitions exactly like the intra one (full-width
  // bytes over the main stripe knob) — the two tiers hand the same
  // buckets to the same phase bodies.
  const int64_t eff_host = eff_intra;

  // Phase 0a/0b — host reduce-scatter + allgather over the shm rings
  // (or the loopback-TCP fallback): the HOST leader ends with the host
  // sum, at memcpy speed, before any socket is touched. Non-leaders
  // rejoin at the host broadcast.
  auto h0 = clock::now();
  if (host_.world > 1) {
    last_stripe_ns_.assign(eff_host, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_host, s);
      if (len == 0) return;
      rs_phase_stripe(host_, s, bytes + start * esize, len, esize, dtype,
                      op, deadline);
    });
  }
  auto h1 = clock::now();
  if (host_.world > 1) {
    last_stripe_ns_.assign(eff_host, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_host, s);
      if (len == 0) return;
      ag_phase_stripe(host_, s, bytes + start * esize, len, esize, deadline);
    });
  }
  auto h2 = clock::now();
  last_hier_.shm_rs_ns += ns_between(h0, h1);
  last_hier_.shm_ag_ns += ns_between(h1, h2);

  // Phase 1 — intra reduce-scatter: HOST-LEADER shards of the REGION
  // sum, on the fast links, spreading reduction bandwidth and compute.
  // (intra_.world is 0 on non-host-leaders — they skip straight to the
  // host broadcast below.)
  auto t0 = clock::now();
  if (intra_.world > 1) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_intra, s);
      if (len == 0) return;
      rs_phase_stripe(intra_, s, bytes + start * esize, len, esize, dtype,
                      op, deadline);
    });
  }
  // Phase 2 — intra allgather: delivers the full region sum to the LEADER
  // (on a ring, gather-to-one costs the same edges as gather-to-all).
  auto t1 = clock::now();
  if (intra_.world > 1) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_intra, s);
      if (len == 0) return;
      ag_phase_stripe(intra_, s, bytes + start * esize, len, esize, deadline);
    });
  }
  // Phase 3 — inter ring among leaders: the ONLY bytes on the slow links
  // ((L-1)/L of the wire payload per phase, measured into rs_tx/the
  // counter delta by the shared inter_ring_phase body).
  auto t2 = clock::now();
  const int64_t inter_tx0 = tier_tx(inter_);
  int64_t inter_rs_tx = 0;
  if (leader && inter_.world > 1)
    inter_ring_phase(wire, bytes, count, esize, dtype, op, eff_inter,
                     deadline, &inter_rs_tx);
  // Phase 4 — chunk-pipelined intra broadcast of the leader's result:
  // every member adopts the leader's bytes VERBATIM, and leaders are
  // bit-identical across regions (ring determinism), so the global
  // result is bit-identical on every member.
  auto t3 = clock::now();
  if (intra_.world > 1) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_intra, s);
      if (len == 0) return;
      bcast_pipe_stripe(intra_, s, bytes + start * esize, len * esize, 0,
                        deadline);
    });
  }
  auto t4 = clock::now();
  // Phase 5 — host broadcast of the host leader's (now-global) bytes:
  // every co-hosted member adopts them verbatim, completing the
  // bit-identity chain host member -> host leader -> region leader.
  if (host_.world > 1) {
    last_stripe_ns_.assign(eff_host, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(count, eff_host, s);
      if (len == 0) return;
      bcast_pipe_stripe(host_, s, bytes + start * esize, len * esize, 0,
                        deadline);
    });
  }
  auto h3 = clock::now();
  last_hier_.intra_rs_ns += ns_between(t0, t1);
  last_hier_.intra_ag_ns += ns_between(t1, t2);
  last_hier_.inter_ring_ns += ns_between(t2, t3);
  last_hier_.intra_bcast_ns += ns_between(t3, t4);
  last_hier_.shm_bcast_ns += ns_between(t4, h3);
  last_hier_.inter_rs_tx_bytes += inter_rs_tx;
  last_hier_.inter_ag_tx_bytes += tier_tx(inter_) - inter_tx0 - inter_rs_tx;
}

void HostCollectives::allreduce_hier(void* data, size_t count, Dtype dtype,
                                     ReduceOp op, HierWire wire,
                                     int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  last_hier_ = HierStats{};
  last_hier_.wire = static_cast<int>(wire);
  if (world_size_ == 1) return;
  if (!hier_)
    throw SocketError(
        "hierarchical schedule unavailable: configure() saw neither a "
        "region map with >= 2 distinct labels nor a host map grouping "
        ">= 2 co-hosted ranks (the cohort rides the flat ring)");
  if (wire != HierWire::kNone &&
      (dtype != Dtype::kF32 || op != ReduceOp::kSum))
    throw SocketError("hier wire bf16/q8 takes f32 payloads and SUM only");
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    size_t esize = dtype_size(dtype);
    size_t inter_esize = wire == HierWire::kQ8 ? 1
                         : wire == HierWire::kBF16 ? 2
                                                   : esize;
    int64_t eff_intra = effective_stripes(count * esize, stripes_);
    int64_t eff_inter = effective_stripes(count * inter_esize, stripes_inter_);
    reset_tier_tx(intra_);
    reset_tier_tx(inter_);
    reset_tier_tx(host_);
    // Both effective stripe counts and the wire ride the header's op slot:
    // every member derives them from negotiated inputs, but a drifted knob
    // must error, not desync two tiers' schedules. The host tier shares
    // eff_intra by construction.
    uint32_t opword = static_cast<uint32_t>(op) |
                      (static_cast<uint32_t>(wire) << 4) |
                      (static_cast<uint32_t>(eff_intra) << 8) |
                      (static_cast<uint32_t>(eff_inter) << 16);
    if (host_.world > 1)
      check_op_header(host_, 9, count, static_cast<uint32_t>(dtype), opword,
                      deadline);
    if (intra_.world > 1)
      check_op_header(intra_, 9, count, static_cast<uint32_t>(dtype), opword,
                      deadline);
    const bool host_leader = host_.world <= 1 || host_.rank == 0;
    const bool leader =
        host_leader && (intra_.world <= 1 || intra_.rank == 0);
    if (leader && inter_.world > 1)
      check_op_header(inter_, 9, count, static_cast<uint32_t>(dtype), opword,
                      deadline);
    if (count == 0) return;
    last_hier_.payload_bytes = static_cast<int64_t>(count * esize);
    last_hier_.eff_intra = eff_intra;
    last_hier_.eff_inter = eff_inter;
    last_hier_.eff_host = host_.world > 1 ? eff_intra : 0;
    last_hier_.intra_world = intra_.world;
    last_hier_.inter_world = leader ? inter_.world : 0;
    last_hier_.host_world = host_.world;
    last_hier_.leader = leader;
    last_hier_.host_leader = host_leader;
    last_hier_.host_shm = host_.use_shm;
    hier_schedule(static_cast<char*>(data), count, esize, dtype, op, wire,
                  eff_intra, eff_inter, deadline);
    last_hier_.intra_tx_bytes = tier_tx(intra_);
    last_hier_.inter_tx_bytes = tier_tx(inter_);
    last_hier_.host_tx_bytes = tier_tx(host_);
    last_hier_.shm_bytes = tier_shm(host_);
  });
}

std::string HostCollectives::last_hier_json() const {
  JsonObject o;
  o["intra_rs_s"] = Json(last_hier_.intra_rs_ns / 1e9);
  o["intra_ag_s"] = Json(last_hier_.intra_ag_ns / 1e9);
  o["inter_ring_s"] = Json(last_hier_.inter_ring_ns / 1e9);
  o["intra_bcast_s"] = Json(last_hier_.intra_bcast_ns / 1e9);
  o["intra_tx_bytes"] = Json(last_hier_.intra_tx_bytes);
  o["inter_tx_bytes"] = Json(last_hier_.inter_tx_bytes);
  o["inter_rs_tx_bytes"] = Json(last_hier_.inter_rs_tx_bytes);
  o["inter_ag_tx_bytes"] = Json(last_hier_.inter_ag_tx_bytes);
  o["shm_rs_s"] = Json(last_hier_.shm_rs_ns / 1e9);
  o["shm_ag_s"] = Json(last_hier_.shm_ag_ns / 1e9);
  o["shm_bcast_s"] = Json(last_hier_.shm_bcast_ns / 1e9);
  o["host_tx_bytes"] = Json(last_hier_.host_tx_bytes);
  o["shm_bytes"] = Json(last_hier_.shm_bytes);
  o["payload_bytes"] = Json(last_hier_.payload_bytes);
  o["eff_intra"] = Json(last_hier_.eff_intra);
  o["eff_inter"] = Json(last_hier_.eff_inter);
  o["eff_host"] = Json(last_hier_.eff_host);
  o["intra_world"] = Json(last_hier_.intra_world);
  o["inter_world"] = Json(last_hier_.inter_world);
  o["host_world"] = Json(last_hier_.host_world);
  o["leader"] = Json(last_hier_.leader);
  o["host_leader"] = Json(last_hier_.host_leader);
  o["host_shm"] = Json(last_hier_.host_shm);
  o["wire"] = Json(static_cast<int64_t>(last_hier_.wire));
  return Json(std::move(o)).dump();
}

// ---- persistent comm plans ----

namespace {

// Python-floor integer division (numpy's // semantics): C++ / truncates
// toward zero, which would disagree with the legacy host path on
// negative sums.
template <typename T>
T floor_div(T a, T d) {
  T q = a / d;
  if ((a % d != 0) && ((a < 0) != (d < 0))) q--;
  return q;
}

}  // namespace

int64_t HostCollectives::plan_build(const int64_t* counts,
                                    const int32_t* dtypes, int64_t n_leaves,
                                    PlanWire wire, bool prepacked, bool hier) {
  if (world_size_ <= 0)
    throw SocketError("plan_build before configure (layout needs the ring)");
  if (n_leaves <= 0) throw SocketError("plan_build of an empty signature");
  if (hier && prepacked)
    throw SocketError(
        "hier plans take no pre-packed leaves (the wire encoding happens at "
        "the leader's inter hop, not at pack)");
  auto p = std::make_unique<CommPlan>();
  p->wire = wire;
  p->prepacked = prepacked;
  p->hier = hier;
  p->leaves.resize(n_leaves);
  // FNV-1a over (wire, geometry, signature): exchanged in the execute
  // header so mismatched plans error instead of desyncing the ring.
  uint64_t h = 1469598103934665603ull;
  auto mix = [&h](uint64_t v) {
    for (int i = 0; i < 8; i++) {
      h ^= (v >> (8 * i)) & 0xff;
      h *= 1099511628211ull;
    }
  };
  mix(static_cast<uint64_t>(wire));
  mix(static_cast<uint64_t>(world_size_));
  mix(static_cast<uint64_t>(stripes_));
  if (hier) {
    // Hier plans bake in the hierarchical geometry as well: a hier plan
    // meeting a flat plan — or one built against a different inter
    // stripe knob or a drifted (region, host) topology map — must error
    // at the header, not desync mid-payload.
    mix(0x48494552ull /*"HIER"*/);
    mix(static_cast<uint64_t>(stripes_inter_));
    mix(topo_hash_);
  }
  const bool q8 = wire == PlanWire::kQ8 || wire == PlanWire::kQ8EF;
  for (int64_t i = 0; i < n_leaves; i++) {
    if (counts[i] < 0) throw SocketError("plan_build: negative leaf count");
    Dtype dt = static_cast<Dtype>(dtypes[i]);
    dtype_size(dt);  // validates the code
    p->leaves[i] = {static_cast<size_t>(counts[i]), dt};
    mix(static_cast<uint64_t>(counts[i]));
    mix(static_cast<uint64_t>(dtypes[i]));
    Dtype gdt;
    if (q8) {
      if (dt != Dtype::kF32 && dt != Dtype::kBF16)
        throw SocketError(
            "comm plan: q8 wires take f32/bf16 leaves only (callers fall "
            "back to the legacy path for other dtypes)");
      gdt = Dtype::kF32;
    } else if (wire == PlanWire::kBF16) {
      // Hier: the wire applies at the INTER hop only — staging (and the
      // intra ring) stays full-width native, the leader casts for the
      // slow link. Flat: the whole ring rides the bf16 group.
      gdt = (!hier && dt == Dtype::kF32) ? Dtype::kBF16 : dt;
    } else {
      gdt = dt;
    }
    // First-appearance group order — the legacy host path's dict order.
    CommPlan::Group* g = nullptr;
    for (auto& cand : p->groups)
      if (cand.dtype == gdt) { g = &cand; break; }
    if (g == nullptr) {
      p->groups.emplace_back();
      g = &p->groups.back();
      g->dtype = gdt;
    }
    g->leaf_idx.push_back(i);
    g->leaf_off.push_back(g->count);
    g->count += static_cast<size_t>(counts[i]);
  }
  size_t total_f32 = 0;
  for (auto& g : p->groups) {
    size_t esize = dtype_size(g.dtype);
    // The stripe partition IS the plan's bucket list, derived exactly
    // like the fused op derives it (q8 wires: ~1 byte/element) so the
    // ring arithmetic — chunk boundaries, q8 scales — matches the
    // legacy single-op path bit for bit. Hier plans partition by the
    // INTRA tier's full-width bytes (the intra ring is what streams per
    // bucket; the inter hop re-stripes per phase at execute).
    g.eff = effective_stripes(
        g.count * (q8 && !hier ? 1 : esize), stripes_);
    g.staging.resize(g.count * esize);
    total_f32 += g.count;
  }
  // Prepacked kQ8EF: the error-feedback carry lives device-side in the
  // packer (that is the point — the full-f32 residual never crosses the
  // device link), so the plan allocates none.
  if (wire == PlanWire::kQ8EF && !prepacked) p->residual.assign(total_f32, 0.f);
  // NOTE: `prepacked` is NOT mixed into the hash — pack placement is a
  // local choice, and a device-packing member must interoperate with a
  // host-packing one (the device kernels mirror the native arithmetic
  // bit for bit; tests/test_device_pack.py pins the mixed-ring case).
  p->sig = h;
  MutexLock lock(plan_mu_);
  plans_[next_plan_id_] = std::move(p);
  return next_plan_id_++;
}

int64_t HostCollectives::plan_build_sharded(const int64_t* counts,
                                            const int32_t* dtypes,
                                            int64_t n_leaves, PlanWire rs_wire,
                                            PlanWire ag_wire) {
  if (world_size_ <= 0)
    throw SocketError("plan_build before configure (layout needs the ring)");
  if (n_leaves <= 0) throw SocketError("plan_build of an empty signature");
  if (rs_wire == PlanWire::kQ8EF)
    throw SocketError(
        "sharded plans take no q8ef grad wire (error feedback corrects a "
        "FUSED lossy result; the shard owner keeps full f32 here, so there "
        "is no owner-side loss to feed back)");
  if (ag_wire != PlanWire::kNative && ag_wire != PlanWire::kBF16)
    throw SocketError(
        "sharded plans allgather params at native or bf16 wires only (a "
        "quantized param broadcast would drift the cohort's weights)");
  auto p = std::make_unique<CommPlan>();
  p->wire = rs_wire;
  p->ag_wire = ag_wire;
  p->sharded = true;
  p->leaves.resize(n_leaves);
  uint64_t h = 1469598103934665603ull;
  auto mix = [&h](uint64_t v) {
    for (int i = 0; i < 8; i++) {
      h ^= (v >> (8 * i)) & 0xff;
      h *= 1099511628211ull;
    }
  };
  mix(static_cast<uint64_t>(rs_wire));
  mix(static_cast<uint64_t>(world_size_));
  mix(static_cast<uint64_t>(stripes_));
  // The sharded schedule (and its second wire) is part of the contract a
  // peer must share: a sharded plan meeting a fused plan of the same
  // signature — or one gathering at a different param wire — must error
  // at the header, not desync.
  mix(0x53485244ull /*"SHRD"*/);
  mix(static_cast<uint64_t>(ag_wire));
  p->groups.emplace_back();
  CommPlan::Group& g = p->groups.back();
  g.dtype = Dtype::kF32;
  for (int64_t i = 0; i < n_leaves; i++) {
    if (counts[i] < 0) throw SocketError("plan_build: negative leaf count");
    if (static_cast<Dtype>(dtypes[i]) != Dtype::kF32)
      throw SocketError(
          "sharded plans take f32 leaves only (the shard layout is one flat "
          "f32 group; callers keep f32 master weights or use a fused plan)");
    p->leaves[i] = {static_cast<size_t>(counts[i]), Dtype::kF32};
    mix(static_cast<uint64_t>(counts[i]));
    mix(static_cast<uint64_t>(dtypes[i]));
    g.leaf_idx.push_back(i);
    g.leaf_off.push_back(g.count);
    g.count += static_cast<size_t>(counts[i]);
  }
  // The stripe partition derives from the GRAD leg's wire bytes (the
  // fused op's own rule: q8 ~1 byte, bf16 2, f32 4 per element) and is
  // shared by both legs — shard boundaries must be one arithmetic fact.
  const size_t rs_esize = rs_wire == PlanWire::kQ8     ? 1
                          : rs_wire == PlanWire::kBF16 ? 2
                                                       : 4;
  g.eff = effective_stripes(g.count * rs_esize, stripes_);
  g.staging.resize(g.count * sizeof(float));
  if (rs_wire == PlanWire::kBF16 || ag_wire == PlanWire::kBF16)
    p->wirebuf.resize(g.count * 2);
  p->sig = h;
  MutexLock lock(plan_mu_);
  plans_[next_plan_id_] = std::move(p);
  return next_plan_id_++;
}

void HostCollectives::plan_sharded_meta(int64_t plan_id, int64_t* out) {
  MutexLock op_lock(op_mu_);
  CommPlan& p = plan_get(plan_id);
  if (!p.sharded)
    throw SocketError("plan_sharded_meta on a non-sharded plan");
  const CommPlan::Group& g = p.groups[0];
  size_t shard_count = 0;
  for (auto [start, len] :
       shard_ranges(g.count, sizeof(float), rank_, g.eff))
    shard_count += len;
  out[0] = static_cast<int64_t>(shard_count);
  out[1] = g.eff;
  out[2] = static_cast<int64_t>(g.count);
}

CommPlan& HostCollectives::plan_get(int64_t plan_id) {
  MutexLock lock(plan_mu_);
  auto it = plans_.find(plan_id);
  if (it == plans_.end())
    throw SocketError(
        "unknown or invalidated comm plan (plans do not survive "
        "reconfigure; rebuild after every quorum change)");
  return *it->second;
}

void HostCollectives::plan_free(int64_t plan_id) {
  MutexLock op_lock(op_mu_);  // no execute in flight
  MutexLock lock(plan_mu_);
  plans_.erase(plan_id);
}

void HostCollectives::plan_reset_feedback(int64_t plan_id) {
  MutexLock op_lock(op_mu_);
  CommPlan& p = plan_get(plan_id);
  std::fill(p.residual.begin(), p.residual.end(), 0.f);
}

std::string HostCollectives::plan_stats_json(int64_t plan_id) {
  MutexLock op_lock(op_mu_);
  CommPlan& p = plan_get(plan_id);
  JsonObject out;
  out["execs"] = Json(p.execs);
  out["wire"] = Json(static_cast<int64_t>(p.wire));
  out["prepacked"] = Json(static_cast<int64_t>(p.prepacked ? 1 : 0));
  out["hier"] = Json(static_cast<int64_t>(p.hier ? 1 : 0));
  JsonArray buckets;
  for (const auto& st : p.stats) {
    JsonObject b;
    b["group"] = Json(st.group);
    b["stripe"] = Json(st.stripe);
    b["leg"] = Json(st.leg);
    b["bytes"] = Json(st.bytes);
    b["pack_s"] = Json(st.pack_ns / 1e9);
    b["ring_s"] = Json(st.ring_ns / 1e9);
    b["unpack_s"] = Json(st.unpack_ns / 1e9);
    buckets.push_back(Json(std::move(b)));
  }
  out["buckets"] = Json(std::move(buckets));
  return Json(std::move(out)).dump();
}

void HostCollectives::plan_pack_range(CommPlan& p, CommPlan::Group& g,
                                      const void* const* leaf_in,
                                      size_t start, size_t len) const {
  size_t end = start + len;
  size_t gesize = dtype_size(g.dtype);
  for (size_t k = 0; k < g.leaf_idx.size(); k++) {
    int64_t li = g.leaf_idx[k];
    const CommPlan::Leaf& leaf = p.leaves[li];
    size_t off = g.leaf_off[k];
    size_t lend = off + leaf.count;
    if (lend <= start || off >= end) continue;
    size_t a = std::max(off, start);
    size_t b = std::min(lend, end);
    size_t n = b - a;
    const char* src = static_cast<const char*>(leaf_in[li]) +
                      (a - off) * dtype_size(leaf.dtype);
    char* dst = g.staging.data() + a * gesize;
    if (leaf.dtype == g.dtype) {
      memcpy(dst, src, n * gesize);
    } else if (leaf.dtype == Dtype::kF32 && g.dtype == Dtype::kBF16) {
      const float* s = reinterpret_cast<const float*>(src);
      uint16_t* d = reinterpret_cast<uint16_t*>(dst);
      for (size_t i = 0; i < n; i++) d[i] = f32_to_bf16(s[i]);
    } else if (leaf.dtype == Dtype::kBF16 && g.dtype == Dtype::kF32) {
      const uint16_t* s = reinterpret_cast<const uint16_t*>(src);
      float* d = reinterpret_cast<float*>(dst);
      for (size_t i = 0; i < n; i++) d[i] = bf16_to_f32(s[i]);
    } else {
      throw SocketError("comm plan: unsupported pack cast");
    }
  }
}

void HostCollectives::plan_unpack_range(const CommPlan& p,
                                        const CommPlan::Group& g,
                                        void* const* leaf_out, size_t start,
                                        size_t len, double divisor,
                                        bool has_divisor) const {
  size_t end = start + len;
  size_t gesize = dtype_size(g.dtype);
  // Divisor semantics mirror the legacy host path exactly: f32 groups
  // divide in f32 (numpy 2's in-place weak-scalar rule), f64 in f64,
  // bf16 via f32 with round-to-nearest-even back (_apply_divisor), ints
  // floor-divide.
  const float div32 = static_cast<float>(divisor);
  for (size_t k = 0; k < g.leaf_idx.size(); k++) {
    int64_t li = g.leaf_idx[k];
    const CommPlan::Leaf& leaf = p.leaves[li];
    size_t off = g.leaf_off[k];
    size_t lend = off + leaf.count;
    if (lend <= start || off >= end) continue;
    size_t a = std::max(off, start);
    size_t b = std::min(lend, end);
    size_t n = b - a;
    const char* src = g.staging.data() + a * gesize;
    char* dst = static_cast<char*>(leaf_out[li]) +
                (a - off) * dtype_size(leaf.dtype);
    switch (g.dtype) {
      case Dtype::kF32: {
        const float* s = reinterpret_cast<const float*>(src);
        if (leaf.dtype == Dtype::kF32) {
          float* d = reinterpret_cast<float*>(dst);
          for (size_t i = 0; i < n; i++)
            d[i] = has_divisor ? s[i] / div32 : s[i];
        } else if (leaf.dtype == Dtype::kBF16) {
          uint16_t* d = reinterpret_cast<uint16_t*>(dst);
          for (size_t i = 0; i < n; i++)
            d[i] = f32_to_bf16(has_divisor ? s[i] / div32 : s[i]);
        } else {
          throw SocketError("comm plan: unsupported unpack cast");
        }
        break;
      }
      case Dtype::kBF16: {
        const uint16_t* s = reinterpret_cast<const uint16_t*>(src);
        if (leaf.dtype == Dtype::kBF16 || leaf.dtype == Dtype::kF32) {
          for (size_t i = 0; i < n; i++) {
            uint16_t w = s[i];
            if (has_divisor) w = f32_to_bf16(bf16_to_f32(w) / div32);
            if (leaf.dtype == Dtype::kBF16)
              reinterpret_cast<uint16_t*>(dst)[i] = w;
            else
              reinterpret_cast<float*>(dst)[i] = bf16_to_f32(w);
          }
        } else {
          throw SocketError("comm plan: unsupported unpack cast");
        }
        break;
      }
      case Dtype::kF64: {
        const double* s = reinterpret_cast<const double*>(src);
        double* d = reinterpret_cast<double*>(dst);
        for (size_t i = 0; i < n; i++)
          d[i] = has_divisor ? s[i] / divisor : s[i];
        break;
      }
      case Dtype::kI32: {
        const int32_t* s = reinterpret_cast<const int32_t*>(src);
        int32_t* d = reinterpret_cast<int32_t*>(dst);
        int32_t dv = static_cast<int32_t>(divisor);
        for (size_t i = 0; i < n; i++)
          d[i] = has_divisor ? floor_div(s[i], dv) : s[i];
        break;
      }
      case Dtype::kI64: {
        const int64_t* s = reinterpret_cast<const int64_t*>(src);
        int64_t* d = reinterpret_cast<int64_t*>(dst);
        int64_t dv = static_cast<int64_t>(divisor);
        for (size_t i = 0; i < n; i++)
          d[i] = has_divisor ? floor_div(s[i], dv) : s[i];
        break;
      }
    }
  }
}

void HostCollectives::plan_pack_ef(CommPlan& p, CommPlan::Group& g,
                                   const void* const* leaf_in) const {
  // The native mirror of quantize.quantize_with_feedback, leaf by leaf:
  // the per-leaf absmax spans stripe boundaries, so EF packs the whole
  // group before the striped ring starts (the only plan phase that
  // cannot stream per bucket). Arithmetic matches the jitted original
  // op for op: f32 adds, absmax/127 in f32 floored at 1e-12,
  // round-to-nearest-even, clip to [-127, 127], dq = q * scale,
  // residual = d - dq.
  float* stg = reinterpret_cast<float*>(g.staging.data());
  for (size_t k = 0; k < g.leaf_idx.size(); k++) {
    int64_t li = g.leaf_idx[k];
    const CommPlan::Leaf& leaf = p.leaves[li];
    size_t off = g.leaf_off[k];
    size_t n = leaf.count;
    float* d = stg + off;
    float* res = p.residual.data() + off;
    if (leaf.dtype == Dtype::kF32) {
      const float* s = static_cast<const float*>(leaf_in[li]);
      for (size_t i = 0; i < n; i++) d[i] = s[i] + res[i];
    } else {  // kBF16, enforced at build
      const uint16_t* s = static_cast<const uint16_t*>(leaf_in[li]);
      for (size_t i = 0; i < n; i++) d[i] = bf16_to_f32(s[i]) + res[i];
    }
    float absmax = 0.f;
    bool finite = true;
    for (size_t i = 0; i < n; i++) {
      float a = std::fabs(d[i]);
      if (!std::isfinite(a)) finite = false;
      absmax = std::max(absmax, a);
    }
    if (!finite) {
      // A diverged leaf poisons its own payload AND its carry — the
      // same NaN propagation the jitted path produces — and the q8
      // wire's NaN-scale encode then poisons every member.
      float nan = std::numeric_limits<float>::quiet_NaN();
      for (size_t i = 0; i < n; i++) {
        res[i] = nan;
        d[i] = nan;
      }
      continue;
    }
    float scale = std::max(absmax / 127.0f, 1e-12f);
    for (size_t i = 0; i < n; i++) {
      float q = std::nearbyint(d[i] / scale);
      q = std::max(-127.f, std::min(127.f, q));
      float dq = q * scale;
      res[i] = d[i] - dq;
      d[i] = dq;
    }
  }
}

void HostCollectives::plan_ef_inplace(CommPlan& p, CommPlan::Group& g) const {
  // The hier kQ8EF step: identical arithmetic to plan_pack_ef, applied to
  // the REGION SUM already sitting in staging (d = staging + residual).
  // Runs at the LEADER only, just before the quantized inter hop — the
  // carry refines this region's contribution window over window, and the
  // expensive residual never rides the fast intra links at all.
  float* stg = reinterpret_cast<float*>(g.staging.data());
  for (size_t k = 0; k < g.leaf_idx.size(); k++) {
    size_t off = g.leaf_off[k];
    size_t n = p.leaves[g.leaf_idx[k]].count;
    float* d = stg + off;
    float* res = p.residual.data() + off;
    for (size_t i = 0; i < n; i++) d[i] = d[i] + res[i];
    float absmax = 0.f;
    bool finite = true;
    for (size_t i = 0; i < n; i++) {
      float a = std::fabs(d[i]);
      if (!std::isfinite(a)) finite = false;
      absmax = std::max(absmax, a);
    }
    if (!finite) {
      float nan = std::numeric_limits<float>::quiet_NaN();
      for (size_t i = 0; i < n; i++) {
        res[i] = nan;
        d[i] = nan;
      }
      continue;
    }
    float scale = std::max(absmax / 127.0f, 1e-12f);
    for (size_t i = 0; i < n; i++) {
      float q = std::nearbyint(d[i] / scale);
      q = std::max(-127.f, std::min(127.f, q));
      float dq = q * scale;
      res[i] = d[i] - dq;
      d[i] = dq;
    }
  }
}

void HostCollectives::plan_pack_pre_range(const CommPlan& p,
                                          CommPlan::Group& g,
                                          const void* group_in,
                                          const void* group_aux, size_t start,
                                          size_t len) const {
  size_t gesize = dtype_size(g.dtype);
  const bool q8 = p.wire == PlanWire::kQ8 || p.wire == PlanWire::kQ8EF;
  if (!q8) {
    // The payload already IS the staging encoding (bf16/native words,
    // cast on device): a straight copy into the ring's in-place buffer.
    memcpy(g.staging.data() + start * gesize,
           static_cast<const char*>(group_in) + start * gesize, len * gesize);
    return;
  }
  // q8 wires: int8 codes + one f32 scale per leaf. dq = q * scale is the
  // exact product the host EF writes into staging (same q, same scale —
  // the device kernel's tested contract), so the ring sees identical
  // bits. A NaN scale (the device kernel's non-finite signal) poisons
  // every element of its leaf: 0 * NaN = NaN, the host EF's whole-leaf
  // propagation.
  if (group_aux == nullptr)
    throw SocketError("prepacked q8 plan: missing per-leaf scale sidecar");
  const int8_t* q = static_cast<const int8_t*>(group_in);
  const float* scales = static_cast<const float*>(group_aux);
  float* stg = reinterpret_cast<float*>(g.staging.data());
  size_t end = start + len;
  for (size_t k = 0; k < g.leaf_idx.size(); k++) {
    const CommPlan::Leaf& leaf = p.leaves[g.leaf_idx[k]];
    size_t off = g.leaf_off[k];
    size_t lend = off + leaf.count;
    if (lend <= start || off >= end) continue;
    size_t a = std::max(off, start);
    size_t b = std::min(lend, end);
    float scale = scales[k];
    for (size_t i = a; i < b; i++)
      stg[i] = static_cast<float>(q[i]) * scale;
  }
}

void HostCollectives::plan_execute_pre(int64_t plan_id,
                                       const void* const* group_in,
                                       const void* const* group_aux,
                                       void* const* leaf_out, double divisor,
                                       bool has_divisor, int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  CommPlan& p = plan_get(plan_id);
  if (!p.prepacked)
    throw SocketError(
        "plan_execute_pre on a plan built without prepacked leaves");
  p.stats.clear();
  const bool q8 = p.wire == PlanWire::kQ8 || p.wire == PlanWire::kQ8EF;
  if (world_size_ == 1) {
    for (size_t gi = 0; gi < p.groups.size(); gi++) {
      CommPlan::Group& g = p.groups[gi];
      plan_pack_pre_range(p, g, group_in[gi], group_aux[gi], 0, g.count);
      plan_unpack_range(p, g, leaf_out, 0, g.count, divisor, has_divisor);
    }
    p.execs++;
    return;
  }
  if (aborted_) throw SocketError("collectives not configured");
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // Same header as the host-pack execute (the hash excludes
    // `prepacked`): a device-packing member and a host-packing member of
    // one ring agree here and produce identical staging.
    check_op_header(flat_, 8, p.sig, static_cast<uint32_t>(p.wire), 0,
                    deadline);
    for (size_t gi = 0; gi < p.groups.size(); gi++) {
      CommPlan::Group& g = p.groups[gi];
      if (g.count == 0) continue;
      size_t esize = dtype_size(g.dtype);
      size_t stat_base = p.stats.size();
      p.stats.resize(stat_base + g.eff);
      last_stripe_ns_.assign(g.eff, 0);
      // Unlike the host EF (whole-group absmax before any stripe may
      // start), the prepacked decode is per-element and streams per
      // bucket — the triple pipeline covers the q8 wires too.
      run_striped([&](int64_t s) {
        auto [start, len] = stripe_range(g.count, g.eff, s);
        CommPlan::BucketStat& st = p.stats[stat_base + s];
        st.group = static_cast<int64_t>(gi);
        st.stripe = s;
        st.bytes = static_cast<int64_t>(len * esize);
        if (len == 0) return;
        auto t0 = std::chrono::steady_clock::now();
        plan_pack_pre_range(p, g, group_in[gi], group_aux[gi], start, len);
        auto t1 = std::chrono::steady_clock::now();
        if (q8) {
          allreduce_q8_stripe(
              flat_, s, reinterpret_cast<float*>(g.staging.data()) + start,
              len, deadline);
        } else {
          allreduce_stripe(flat_, s, g.staging.data() + start * esize, len,
                           esize, g.dtype, ReduceOp::kSum, deadline);
        }
        auto t2 = std::chrono::steady_clock::now();
        plan_unpack_range(p, g, leaf_out, start, len, divisor, has_divisor);
        auto t3 = std::chrono::steady_clock::now();
        st.pack_ns = ns_between(t0, t1);
        st.ring_ns = ns_between(t1, t2);
        st.unpack_ns = ns_between(t2, t3);
      });
    }
  });
  p.execs++;
}

void HostCollectives::plan_execute_hier_group(CommPlan& p, size_t gi,
                                              const void* const* leaf_in,
                                              void* const* leaf_out,
                                              double divisor, bool has_divisor,
                                              int64_t deadline) {
  CommPlan::Group& g = p.groups[gi];
  if (g.count == 0) return;
  size_t esize = dtype_size(g.dtype);
  const bool q8 = p.wire == PlanWire::kQ8 || p.wire == PlanWire::kQ8EF;
  // The plan wire applies at the inter hop, and only where it means
  // something: q8 plans have a single f32 group; a bf16 plan's non-f32
  // groups (ints, f64, native bf16) ride the inter ring at native width.
  HierWire wire = HierWire::kNone;
  if (g.dtype == Dtype::kF32) {
    if (q8) wire = HierWire::kQ8;
    else if (p.wire == PlanWire::kBF16) wire = HierWire::kBF16;
  }
  const int64_t eff_intra = g.eff;
  const size_t inter_esize = wire == HierWire::kQ8 ? 1
                             : wire == HierWire::kBF16 ? 2
                                                       : esize;
  const int64_t eff_inter =
      effective_stripes(g.count * inter_esize, stripes_inter_);
  const bool host_leader = host_.world <= 1 || host_.rank == 0;
  const bool leader =
      host_leader && (intra_.world <= 1 || intra_.rank == 0);
  char* stg = g.staging.data();

  size_t stat_base = p.stats.size();
  p.stats.resize(stat_base + eff_intra);
  for (int64_t s = 0; s < eff_intra; s++) {
    auto [start, len] = stripe_range(g.count, eff_intra, s);
    p.stats[stat_base + s].group = static_cast<int64_t>(gi);
    p.stats[stat_base + s].stripe = s;
    p.stats[stat_base + s].bytes = static_cast<int64_t>(len * esize);
  }

  using clock = std::chrono::steady_clock;
  auto h0 = clock::now();
  // Phase 0 — pack fused into the HOST reduce-scatter when the host tier
  // exists (bucket i+1 packs while bucket i rides its shm ring), then
  // the host allgather: the host leader ends with the host sum without
  // a socket in sight. With no host tier the pack fuses into the intra
  // reduce-scatter exactly as before.
  const bool host_active = host_.world > 1;
  if (host_active) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, eff_intra, s);
      if (len == 0) return;
      auto p0 = clock::now();
      plan_pack_range(p, g, leaf_in, start, len);
      auto p1 = clock::now();
      rs_phase_stripe(host_, s, stg + start * esize, len, esize, g.dtype,
                      ReduceOp::kSum, deadline);
      auto p2 = clock::now();
      CommPlan::BucketStat& st = p.stats[stat_base + s];
      st.pack_ns = ns_between(p0, p1);
      st.ring_ns += ns_between(p1, p2);
    });
  }
  auto h1 = clock::now();
  if (host_active) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, eff_intra, s);
      if (len == 0) return;
      auto p0 = clock::now();
      ag_phase_stripe(host_, s, stg + start * esize, len, esize, deadline);
      p.stats[stat_base + s].ring_ns += ns_between(p0, clock::now());
    });
  }
  auto h2 = clock::now();
  last_hier_.shm_rs_ns += ns_between(h0, h1);
  last_hier_.shm_ag_ns += ns_between(h1, h2);

  auto t0 = clock::now();
  // Phase 1 — pack fused into the intra reduce-scatter, per stripe bucket
  // (bucket i+1 packs while bucket i rides its intra connection: the
  // triple pipeline survives the extra tier). Under an active host tier
  // the payload is already packed and host-summed; intra_.world is 0 on
  // non-host-leaders, so only host leaders run these phases.
  if (intra_.world > 1) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, eff_intra, s);
      if (len == 0) return;
      auto p0 = clock::now();
      if (!host_active) plan_pack_range(p, g, leaf_in, start, len);
      auto p1 = clock::now();
      rs_phase_stripe(intra_, s, stg + start * esize, len, esize, g.dtype,
                      ReduceOp::kSum, deadline);
      auto p2 = clock::now();
      CommPlan::BucketStat& st = p.stats[stat_base + s];
      st.pack_ns += ns_between(p0, p1);
      st.ring_ns += ns_between(p1, p2);
    });
  } else if (!host_active) {
    plan_pack_range(p, g, leaf_in, 0, g.count);
  }
  auto t1 = clock::now();
  // Phase 2 — intra allgather: the leader ends with the full region sum.
  if (intra_.world > 1) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, eff_intra, s);
      if (len == 0) return;
      auto p0 = clock::now();
      ag_phase_stripe(intra_, s, stg + start * esize, len, esize, deadline);
      p.stats[stat_base + s].ring_ns += ns_between(p0, clock::now());
    });
  }
  auto t2 = clock::now();
  const int64_t inter_tx0 = tier_tx(inter_);
  int64_t inter_rs_tx = 0;
  // Phase 3 — the leader's inter hop at the plan wire. kQ8EF first runs
  // the per-leaf error-feedback quantization against the plan's residual
  // — on the REGION SUM, at the leader, so the carry refines this
  // region's contribution and quantization noise is paid exactly once.
  if (leader && inter_.world > 1) {
    if (p.wire == PlanWire::kQ8EF && wire == HierWire::kQ8)
      plan_ef_inplace(p, g);
    // The SAME inter-ring body the bulk op runs — a wire or accounting
    // change can never desync the plan path from allreduce_hier.
    inter_ring_phase(wire, stg, g.count, esize, g.dtype, ReduceOp::kSum,
                     eff_inter, deadline, &inter_rs_tx);
  }
  auto t3 = clock::now();
  // Phase 4 — broadcast the leader's result down the tiers. With a host
  // tier the unpack fuses into the HOST broadcast (the last phase every
  // member runs); otherwise into the intra broadcast as before.
  if (intra_.world > 1) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, eff_intra, s);
      if (len == 0) return;
      auto p0 = clock::now();
      bcast_pipe_stripe(intra_, s, stg + start * esize, len * esize, 0,
                        deadline);
      auto p1 = clock::now();
      if (!host_active)
        plan_unpack_range(p, g, leaf_out, start, len, divisor, has_divisor);
      auto p2 = clock::now();
      CommPlan::BucketStat& st = p.stats[stat_base + s];
      st.ring_ns += ns_between(p0, p1);
      st.unpack_ns += ns_between(p1, p2);
    });
  } else if (!host_active) {
    plan_unpack_range(p, g, leaf_out, 0, g.count, divisor, has_divisor);
  }
  auto t4 = clock::now();
  if (host_active) {
    last_stripe_ns_.assign(eff_intra, 0);
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, eff_intra, s);
      if (len == 0) return;
      auto p0 = clock::now();
      bcast_pipe_stripe(host_, s, stg + start * esize, len * esize, 0,
                        deadline);
      auto p1 = clock::now();
      plan_unpack_range(p, g, leaf_out, start, len, divisor, has_divisor);
      auto p2 = clock::now();
      CommPlan::BucketStat& st = p.stats[stat_base + s];
      st.ring_ns += ns_between(p0, p1);
      st.unpack_ns += ns_between(p1, p2);
    });
  }
  auto h3 = clock::now();
  last_hier_.intra_rs_ns += ns_between(t0, t1);
  last_hier_.intra_ag_ns += ns_between(t1, t2);
  last_hier_.inter_ring_ns += ns_between(t2, t3);
  last_hier_.intra_bcast_ns += ns_between(t3, t4);
  last_hier_.shm_bcast_ns += ns_between(t4, h3);
  last_hier_.inter_rs_tx_bytes += inter_rs_tx;
  last_hier_.inter_ag_tx_bytes += tier_tx(inter_) - inter_tx0 - inter_rs_tx;
  last_hier_.payload_bytes += static_cast<int64_t>(g.count * esize);
  last_hier_.eff_intra = eff_intra;
  last_hier_.eff_inter = eff_inter;
  last_hier_.eff_host = host_active ? eff_intra : 0;
}

void HostCollectives::plan_execute(int64_t plan_id,
                                   const void* const* leaf_in,
                                   void* const* leaf_out, double divisor,
                                   bool has_divisor, int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  CommPlan& p = plan_get(plan_id);
  if (p.prepacked)
    throw SocketError(
        "plan_execute on a prepacked plan (use plan_execute_pre)");
  p.stats.clear();
  const bool q8 = p.wire == PlanWire::kQ8 || p.wire == PlanWire::kQ8EF;
  if (world_size_ == 1) {
    // Solo: pack -> identity -> unpack. Flat kQ8EF advances the
    // error-feedback state exactly as it would in a ring (a member that
    // later joins a cohort carries coherent state); a HIER plan's EF
    // belongs to the inter hop, which does not exist solo, so the carry
    // stays untouched (the wire only ever applies on the slow link).
    for (auto& g : p.groups) {
      if (p.wire == PlanWire::kQ8EF && !p.hier)
        plan_pack_ef(p, g, leaf_in);
      else
        plan_pack_range(p, g, leaf_in, 0, g.count);
      plan_unpack_range(p, g, leaf_out, 0, g.count, divisor, has_divisor);
    }
    p.execs++;
    return;
  }
  if (aborted_) throw SocketError("collectives not configured");
  if (p.hier) {
    if (!hier_)
      throw SocketError(
          "hier plan on a flat ring: configure() was not given a region map "
          "with >= 2 distinct labels");
    run_op([&] {
      int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
      last_hier_ = HierStats{};
      last_hier_.wire = static_cast<int>(
          p.wire == PlanWire::kBF16 ? HierWire::kBF16
          : q8 ? HierWire::kQ8
               : HierWire::kNone);
      reset_tier_tx(intra_);
      reset_tier_tx(inter_);
      reset_tier_tx(host_);
      const bool host_leader = host_.world <= 1 || host_.rank == 0;
      const bool leader =
          host_leader && (intra_.world <= 1 || intra_.rank == 0);
      // kind 10 = hier plan: a hier plan meeting a flat plan (kind 8) or
      // a bulk hier op (kind 9) must error at the header.
      if (host_.world > 1)
        check_op_header(host_, 10, p.sig, static_cast<uint32_t>(p.wire), 0,
                        deadline);
      if (intra_.world > 1)
        check_op_header(intra_, 10, p.sig, static_cast<uint32_t>(p.wire), 0,
                        deadline);
      if (leader && inter_.world > 1)
        check_op_header(inter_, 10, p.sig, static_cast<uint32_t>(p.wire), 0,
                        deadline);
      last_hier_.intra_world = intra_.world;
      last_hier_.inter_world = leader ? inter_.world : 0;
      last_hier_.host_world = host_.world;
      last_hier_.leader = leader;
      last_hier_.host_leader = host_leader;
      last_hier_.host_shm = host_.use_shm;
      for (size_t gi = 0; gi < p.groups.size(); gi++)
        plan_execute_hier_group(p, gi, leaf_in, leaf_out, divisor,
                                has_divisor, deadline);
      last_hier_.intra_tx_bytes = tier_tx(intra_);
      last_hier_.inter_tx_bytes = tier_tx(inter_);
      last_hier_.host_tx_bytes = tier_tx(host_);
      last_hier_.shm_bytes = tier_shm(host_);
    });
    p.execs++;
    return;
  }
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // The signature hash covers (wire, geometry, leaf counts, dtypes):
    // two members executing different plans error here instead of
    // deadlocking mid-payload.
    check_op_header(flat_, 8, p.sig, static_cast<uint32_t>(p.wire), 0,
                    deadline);
    for (size_t gi = 0; gi < p.groups.size(); gi++) {
      CommPlan::Group& g = p.groups[gi];
      if (g.count == 0) continue;
      if (p.wire == PlanWire::kQ8EF) plan_pack_ef(p, g, leaf_in);
      size_t esize = dtype_size(g.dtype);
      size_t stat_base = p.stats.size();
      p.stats.resize(stat_base + g.eff);
      last_stripe_ns_.assign(g.eff, 0);
      // The triple pipeline: every stripe sub-range is one bucket whose
      // pack -> ring -> unpack runs end-to-end on its own pool worker,
      // so bucket i+1 packs/casts while bucket i rides its connection
      // and bucket i-1 unpacks — with NO cross-bucket barrier and no
      // Python between phases. The ring body and stripe partition are
      // the fused op's own, so results are bit-identical to the legacy
      // path by construction.
      run_striped([&](int64_t s) {
        auto [start, len] = stripe_range(g.count, g.eff, s);
        CommPlan::BucketStat& st = p.stats[stat_base + s];
        st.group = static_cast<int64_t>(gi);
        st.stripe = s;
        st.bytes = static_cast<int64_t>(len * esize);
        if (len == 0) return;
        auto t0 = std::chrono::steady_clock::now();
        if (p.wire != PlanWire::kQ8EF)
          plan_pack_range(p, g, leaf_in, start, len);
        auto t1 = std::chrono::steady_clock::now();
        if (q8) {
          allreduce_q8_stripe(
              flat_, s, reinterpret_cast<float*>(g.staging.data()) + start,
              len, deadline);
        } else {
          allreduce_stripe(flat_, s, g.staging.data() + start * esize, len,
                           esize, g.dtype, ReduceOp::kSum, deadline);
        }
        auto t2 = std::chrono::steady_clock::now();
        plan_unpack_range(p, g, leaf_out, start, len, divisor, has_divisor);
        auto t3 = std::chrono::steady_clock::now();
        st.pack_ns = ns_between(t0, t1);
        st.ring_ns = ns_between(t1, t2);
        st.unpack_ns = ns_between(t2, t3);
      });
    }
  });
  p.execs++;
}

void HostCollectives::plan_execute_rs(int64_t plan_id,
                                      const void* const* leaf_in,
                                      float* shard_out, double divisor,
                                      bool has_divisor, int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  CommPlan& p = plan_get(plan_id);
  if (!p.sharded)
    throw SocketError("plan_execute_rs on a non-sharded plan");
  p.stats.clear();
  CommPlan::Group& g = p.groups[0];
  float* stg = reinterpret_cast<float*>(g.staging.data());
  const float div32 = static_cast<float>(divisor);
  if (world_size_ == 1) {
    // Solo: the shard IS the whole payload — pack, divide, done.
    plan_pack_range(p, g, leaf_in, 0, g.count);
    for (size_t i = 0; i < g.count; i++)
      shard_out[i] = has_divisor ? stg[i] / div32 : stg[i];
    p.execs++;
    return;
  }
  if (aborted_) throw SocketError("collectives not configured");
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // kind 11 = sharded grad leg: a sharded rs meeting a fused plan
    // execute (kind 8) or the param leg (kind 12) errors at the header.
    check_op_header(flat_, 11, p.sig, static_cast<uint32_t>(p.wire), 0,
                    deadline);
    const size_t wesize = p.wire == PlanWire::kQ8     ? 1
                          : p.wire == PlanWire::kBF16 ? 2
                                                      : 4;
    p.stats.resize(g.eff);
    last_stripe_ns_.assign(g.eff, 0);
    const int64_t own_c = (rank_ + 1) % world_size_;
    // Each stripe bucket runs pack -> rs phase end-to-end on its own
    // pool worker — the fused plan's triple pipeline, minus the phase
    // the schedule exists to drop.
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, g.eff, s);
      CommPlan::BucketStat& st = p.stats[s];
      st.group = 0;
      st.stripe = s;
      st.leg = 1;
      st.bytes = static_cast<int64_t>(len * wesize);
      if (len == 0) return;
      auto t0 = std::chrono::steady_clock::now();
      plan_pack_range(p, g, leaf_in, start, len);
      auto t1 = std::chrono::steady_clock::now();
      if (p.wire == PlanWire::kQ8) {
        // Per-hop dequant-accumulate in f32: the owner's chunk ends as
        // the FULL f32 running sum — the fused op's phase-2 owner
        // quantization only existed to ship the chunk, and here it
        // never ships (the PR-2 reduce_scatter_q8 discipline).
        rs_q8_phase_stripe(flat_, s, stg + start, len, deadline);
      } else if (p.wire == PlanWire::kBF16) {
        // Cast the stripe to bf16 wire words, ride the rs phase at half
        // width (per-hop f32 math, RNE back — the native bf16 body),
        // then decode only the OWNER chunk back into f32 staging: the
        // non-owned chunks' partial sums never leave the wire buffer.
        uint16_t* w = reinterpret_cast<uint16_t*>(p.wirebuf.data()) + start;
        for (size_t i = 0; i < len; i++) w[i] = f32_to_bf16(stg[start + i]);
        rs_phase_stripe(flat_, s, reinterpret_cast<char*>(w), len, 2,
                        Dtype::kBF16, ReduceOp::kSum, deadline);
        auto [cs, cl] = chunk_range(len, world_size_, own_c);
        for (size_t i = 0; i < cl; i++)
          stg[start + cs + i] = bf16_to_f32(w[cs + i]);
      } else {
        rs_phase_stripe(flat_, s, reinterpret_cast<char*>(stg + start), len,
                        sizeof(float), Dtype::kF32, ReduceOp::kSum, deadline);
      }
      auto t2 = std::chrono::steady_clock::now();
      st.pack_ns = ns_between(t0, t1);
      st.ring_ns = ns_between(t1, t2);
    });
    auto u0 = std::chrono::steady_clock::now();
    copy_shard(reinterpret_cast<char*>(stg),
               reinterpret_cast<char*>(shard_out), g.count, sizeof(float),
               g.eff, /*to_shard=*/true);
    if (has_divisor) {
      size_t sn = 0;
      for (auto [start, len] :
           shard_ranges(g.count, sizeof(float), rank_, g.eff))
        sn += len;
      // The owner's slice of the fused unpack arithmetic: f32 / f32.
      for (size_t i = 0; i < sn; i++) shard_out[i] /= div32;
    }
    if (!p.stats.empty())
      p.stats[0].unpack_ns = ns_between(u0, std::chrono::steady_clock::now());
  });
  p.execs++;
}

void HostCollectives::plan_execute_ag(int64_t plan_id, const float* shard_in,
                                      void* const* leaf_out,
                                      int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  CommPlan& p = plan_get(plan_id);
  if (!p.sharded)
    throw SocketError("plan_execute_ag on a non-sharded plan");
  CommPlan::Group& g = p.groups[0];
  float* stg = reinterpret_cast<float*>(g.staging.data());
  if (world_size_ == 1) {
    memcpy(stg, shard_in, g.count * sizeof(float));
    plan_unpack_range(p, g, leaf_out, 0, g.count, 1.0, /*has_divisor=*/false);
    p.execs++;
    return;
  }
  if (aborted_) throw SocketError("collectives not configured");
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    // kind 12 = sharded param leg; the header carries the AG wire so a
    // native-gathering member and a bf16-gathering one error apart.
    check_op_header(flat_, 12, p.sig, static_cast<uint32_t>(p.ag_wire), 0,
                    deadline);
    copy_shard(reinterpret_cast<char*>(stg),
               const_cast<char*>(reinterpret_cast<const char*>(shard_in)),
               g.count, sizeof(float), g.eff, /*to_shard=*/false);
    const size_t wesize = p.ag_wire == PlanWire::kBF16 ? 2 : 4;
    const size_t stat_base = p.stats.size();  // append after the rs leg
    p.stats.resize(stat_base + g.eff);
    last_stripe_ns_.assign(g.eff, 0);
    const int64_t own_c = (rank_ + 1) % world_size_;
    run_striped([&](int64_t s) {
      auto [start, len] = stripe_range(g.count, g.eff, s);
      CommPlan::BucketStat& st = p.stats[stat_base + s];
      st.group = 0;
      st.stripe = s;
      st.leg = 2;
      st.bytes = static_cast<int64_t>(len * wesize);
      if (len == 0) return;
      auto t0 = std::chrono::steady_clock::now();
      auto t1 = t0;
      if (p.ag_wire == PlanWire::kBF16) {
        // Encode only the OWNED chunk (the rest arrives over the ring),
        // circulate the bf16 words, then decode the WHOLE stripe: every
        // member adopts the identical decoded words, so the gathered
        // params are bit-identical across the cohort — the property the
        // commit vote's determinism oracle rests on.
        uint16_t* w = reinterpret_cast<uint16_t*>(p.wirebuf.data()) + start;
        auto [cs, cl] = chunk_range(len, world_size_, own_c);
        for (size_t i = 0; i < cl; i++)
          w[cs + i] = f32_to_bf16(stg[start + cs + i]);
        t1 = std::chrono::steady_clock::now();
        ag_phase_stripe(flat_, s, reinterpret_cast<char*>(w), len, 2,
                        deadline);
        for (size_t i = 0; i < len; i++) stg[start + i] = bf16_to_f32(w[i]);
      } else {
        ag_phase_stripe(flat_, s, reinterpret_cast<char*>(stg + start), len,
                        sizeof(float), deadline);
      }
      auto t2 = std::chrono::steady_clock::now();
      plan_unpack_range(p, g, leaf_out, start, len, 1.0,
                        /*has_divisor=*/false);
      auto t3 = std::chrono::steady_clock::now();
      st.pack_ns = ns_between(t0, t1);
      st.ring_ns = ns_between(t1, t2);
      st.unpack_ns = ns_between(t2, t3);
    });
  });
  p.execs++;
}

void HostCollectives::broadcast(void* data, size_t nbytes, int64_t root,
                                int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  if (world_size_ == 1) return;
  if (root < 0 || root >= world_size_) throw SocketError("bad broadcast root");
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    check_op_header(flat_, 2, nbytes, static_cast<uint32_t>(root), 0,
                    deadline);
    if (nbytes == 0) return;
    char* bytes = static_cast<char*>(data);
    int64_t eff = effective_stripes(nbytes, stripes_);
    last_stripe_ns_.assign(eff, 0);
    // Forward around the ring, root first; the last hop before root does not
    // send. recv-then-send per hop (latency is fine at control-plane sizes;
    // bulk weight transfer goes through the checkpoint transport instead).
    run_striped([&](int64_t st) {
      auto [off, len] = stripe_range(nbytes, eff, st);
      if (len == 0) return;
      if (rank_ == root) {
        duplex(flat_.next[st], flat_.prev[st], bytes + off, len, nullptr, 0,
               deadline, &flat_.scratch[st]);
      } else {
        duplex(flat_.next[st], flat_.prev[st], nullptr, 0, bytes + off, len,
               deadline, &flat_.scratch[st]);
        if ((rank_ + 1) % world_size_ != root)
          duplex(flat_.next[st], flat_.prev[st], bytes + off, len, nullptr, 0,
                 deadline, &flat_.scratch[st]);
      }
    });
  });
}

void HostCollectives::barrier(int64_t timeout_ms) {
  MutexLock lock(op_mu_);
  op_seq_++;
  if (aborted_) throw SocketError("collectives not configured");
  if (world_size_ == 1) return;
  run_op([&] {
    int64_t deadline = timeout_ms < 0 ? -1 : now_ms() + timeout_ms;
    check_op_header(flat_, 3, 0, 0, 0, deadline);
    // Two full ring passes on stripe 0: after the first, rank 0 knows
    // everyone arrived; the second releases everyone.
    char token = 1;
    for (int round = 0; round < 2; round++) {
      if (rank_ == 0) {
        duplex(flat_.next[0], flat_.prev[0], &token, 1, nullptr, 0, deadline,
               &flat_.scratch[0]);
        duplex(flat_.next[0], flat_.prev[0], nullptr, 0, &token, 1, deadline,
               &flat_.scratch[0]);
      } else {
        duplex(flat_.next[0], flat_.prev[0], nullptr, 0, &token, 1, deadline,
               &flat_.scratch[0]);
        duplex(flat_.next[0], flat_.prev[0], &token, 1, nullptr, 0, deadline,
               &flat_.scratch[0]);
      }
    }
  });
}

} // namespace tft

// Host-side collective communication over TCP: the role Gloo plays in the
// reference (reference torchft/process_group.py:282-296 ProcessGroupGloo and
// the reconfigure discipline of process_group.py:238-254).
//
// Design for the TPU build: cross-replica-group traffic stays OUTSIDE XLA
// (host-side sockets), so a dead peer surfaces as a socket error on an
// abortable fd instead of a wedged ICI collective — the property the
// reference gets from subprocess-isolated NCCL ("Baby" PGs,
// process_group.py:551-1064). Intra-group collectives are XLA's job (pjit
// over the slice mesh); this class only ever spans replica groups.
//
// Topology: a ring, STRIPED over N parallel TCP connections per neighbor
// edge. configure() rendezvouses through the Store (the caller passes
// "host:port/prefix" where prefix is unique per quorum, mirroring
// manager.py:470-477), each rank listens on an ephemeral port, dials rank+1
// `stripes` times and accepts `stripes` connections from rank-1 (the hello
// carries the stripe index, so accept order never matters). Every bulk op
// splits its payload into `stripes` contiguous sub-ranges; stripe s runs the
// full ring schedule over its own sub-range on its own connection pair, on
// its own thread. A single TCP connection is window-limited on
// high-bandwidth-delay paths (the DCN links these collectives actually
// cross), so striping multiplies achievable throughput the way
// NCCL channels or multi-stream object fetches do.
//
// HIERARCHICAL TOPOLOGY (configure with a region and/or host map): on a
// fleet spanning regions, the flat ring makes every member push
// 2*(W-1)/W*N bytes across whatever link its neighbor happens to sit
// behind — on a topology-oblivious placement that is the slow inter-region
// (DCN) path for every edge. With a region label per rank, configure()
// additionally builds
//   - an INTRA ring per region (the member's region peers, rank order), and
//   - an INTER ring among one deterministic LEADER per region (the lowest
//     rank — i.e. lowest replica-id, since ranks sort by replica-id — with
//     regions ordered by their leader's rank),
// and allreduce_hier() runs the hierarchical schedule
//   intra reduce-scatter -> intra allgather (delivers the full region sum to
//   the leader; on a ring, gather-to-one costs the same edges as
//   gather-to-all) -> inter ring allreduce among leaders (the only bytes on
//   the slow links: (L-1)/L*N sent per leader per phase, L = region count)
//   -> chunk-pipelined intra broadcast of the leader's result.
// Every phase reuses the SAME rs/ag stripe bodies as the flat ring, so the
// schedule is composed from proven pieces; all members of a region adopt the
// leader's bytes verbatim and leaders are bit-identical by ring determinism,
// so results are bit-identical across ALL members and across runs. The sum
// ORDER differs from the flat ring (documented; tolerance-class equal).
//
// THIRD TIER — the HOST ring (configure with a host map): members sharing
// a (region, host) label pair are co-resident processes; pushing their
// ring bytes through loopback TCP costs two kernel copies plus syscalls
// per chunk. configure() groups them into a HOST ring below the intra
// tier, carried over POSIX shared-memory ring buffers (one SPSC ring per
// directed edge per stripe, tft_shm_* segments, futex doorbells) — a
// single memcpy per hop instead of a socket round trip. The schedule
// grows to
//   host reduce-scatter -> host allgather (the HOST leader — lowest rank
//   on the host — holds the host sum) -> intra rs/ag among HOST leaders
//   of a region -> inter ring among region leaders (wire applied there,
//   unchanged) -> intra broadcast to host leaders -> host broadcast.
// The intra tier therefore spans host LEADERS only; the region leader is
// the lowest rank of its region, which is by construction also a host
// leader. Segments are owned by the configure generation (created by the
// producing member, torn down — unlinked — on reconfigure/destruction);
// abort() poisons the ring magic and futex-wakes every waiter, so a
// failure propagates across the shm tier the way a socket FIN does on
// TCP. TORCHFT_HC_SHM=0 falls the host tier back to loopback TCP (same
// geometry, kTierHost hello) — the honest control the shm bench row is
// measured against; with no host map (or no (region,host) group of >= 2)
// the host tier is absent and the schedule is exactly the two-tier one.
// Shared-memory hops hand NOTHING to the kernel: their tx_bytes stay 0
// (wire accounting is honest) and the bytes moved are reported
// separately as shm_bytes.
//
// Ring allreduce = reduce-scatter + allgather; within each stripe every
// chunk is reduced in the same rank order on every participant, and stripe
// boundaries depend only on (count, stripes, world_size) — all negotiated —
// so results are bit-identical across ranks and across runs: the
// determinism oracle the reference tests demand
// (manager_integ_test.py:279-282).
#pragma once

#include <atomic>
#include <chrono>
#include <cstdint>
#include <functional>
#include <map>
#include <memory>
#include <string>
#include <thread>
#include <vector>

#include "net.h"
#include "thread_annotations.h"

namespace tft {

enum class ReduceOp : int {
  kSum = 0,
  kProduct = 1,
  kMin = 2,
  kMax = 3,
};

enum class Dtype : int {
  kF32 = 0,
  kF64 = 1,
  kI32 = 2,
  kI64 = 3,
  // bfloat16 ships natively (2 bytes on the wire — half the DCN traffic of
  // an f32 upcast); reduction arithmetic is f32 per hop with
  // round-to-nearest-even back to bf16.
  kBF16 = 4,
};

size_t dtype_size(Dtype d);

// Upper bound on ring stripes (sockets + threads per neighbor edge); far
// above the knee of any measured sweep, low enough that a bad config can't
// fork-bomb the host.
constexpr int64_t kMaxStripes = 64;

// Wire format of a CommPlan (see CommPlan below). Mirrored by the Python
// layer's `wire=` strings: None -> kNative, "bf16" -> kBF16, "q8" -> kQ8,
// "q8ef" -> kQ8EF.
enum class PlanWire : int {
  // Each leaf rides the ring in its own native dtype (f32/f64/i32/i64/
  // bf16 groups) — the legacy managed path's accumulation-dtype grouping.
  kNative = 0,
  // f32 leaves are rounded (nearest-even) to bf16 at pack and ride a
  // bf16 group; other dtypes group natively. Halves the f32 wire bytes,
  // matching ddp's compress="bf16" (jax downcast + bf16 ring) exactly.
  kBF16 = 1,
  // Whole tree packs into ONE f32 group and rides the quantized ring
  // (int8 chunks + per-chunk scales) — the legacy wire="q8" schedule.
  kQ8 = 2,
  // kQ8 plus per-leaf symmetric int8 quantization with ERROR FEEDBACK
  // executed natively at pack time: d = leaf + residual; scale =
  // max(|d|)/127 (floored 1e-12); dq = round(d/scale)*scale ships;
  // residual = d - dq persists in the plan. The native mirror of
  // quantize.quantize_with_feedback so the q8 DDP mode needs no jitted
  // quantize program on the per-step hot path.
  kQ8EF = 3,
};

// Wire of the hierarchical op's INTER hop (allreduce_hier / hier plans).
// The intra tier always rides native dtypes — quantization noise is paid
// exactly once, on the slow link that needs it.
enum class HierWire : int {
  kNone = 0,   // native dtype across regions too
  kBF16 = 1,   // leaders ring in bf16 (f32 payloads, SUM only)
  kQ8 = 2,     // leaders ride the quantized ring (f32 payloads, SUM only)
};

// Token bucket for per-connection send pacing (TORCHFT_HC_WIRE_CAP_MBPS).
// Two uses: QoS — cap the gradient ring's per-connection rate so it cannot
// starve heal/checkpoint traffic on a shared NIC — and emulating a
// per-connection-limited path on loopback (scripts/chaos_run.py measures
// the wire CRC's cost under one). Pure pacing: no wire-format or schedule
// effect, so members need NOT agree on it.
struct PaceState {
  double tokens = 0;  // bytes available to send now
  std::chrono::steady_clock::time_point last{};
  bool init = false;
};

// Per-stripe persistent staging (grow-only, reused across ops): per-op
// allocation of a world-size chunk — up to payload/world_size bytes —
// costs an mmap + demand-zero page faults EVERY op at gradient scale.
// Also carries the connection's pacing state and the per-op tx counter
// (bytes actually handed to the kernel by duplex) the hierarchical
// accounting sums per tier — measured traffic, not a model.
struct StripeScratch {
  std::vector<char> recv;           // allreduce recv / q8 recv wire
  std::vector<char> send;           // q8 send wire
  std::vector<std::vector<char>> stored;  // q8 phase-2 circulating codes
  PaceState pace;                   // this connection's send pacing
  int64_t cap_bps = 0;              // tier's per-connection send cap
  int64_t tx_bytes = 0;             // bytes sent since the op reset it
  // Bytes moved through this stripe's SHARED-MEMORY rings since the op
  // reset it (frame headers included). Kept apart from tx_bytes on
  // purpose: shm hops hand nothing to the kernel, so the wire bill
  // stays honest while the movement is still measurable.
  int64_t shm_bytes = 0;
  // Diagnostic tag ("tier=... stripe=... prev=host:port") baked at
  // configure: wire-integrity and desync errors carry it so a W=8 fleet
  // log names the guilty edge instead of an anonymous socket.
  std::string tag;
};

class ShmSegment;

// One directed shared-memory edge pair of the host ring, per stripe: the
// TX ring this member CREATES and produces into (toward its next host
// neighbor) and the RX ring it ATTACHES and consumes from (fed by its
// prev neighbor). Creator-owned segments: dropping the handle unlinks
// the name — the configure-generation ownership contract.
struct ShmEdge {
  std::unique_ptr<ShmSegment> tx;
  std::unique_ptr<ShmSegment> rx;
  uint64_t fseq_tx = 0;  // frames produced (stale-payload detection)
  uint64_t fseq_rx = 0;  // frames consumed
  // Chaos: op index whose sends this edge swallows (drop-doorbell /
  // partition faults persist for the whole op — the injected failure is
  // the peer's stall, not a detectable frame skip).
  int64_t drop_op = -1;
};

// One ring a member participates in: the FLAT ring over all W members, the
// INTRA ring over its region peers, or the INTER ring over region leaders.
// `rank`/`world` are tier-local (flat: the global rank/world). `conns` is
// the tier's parallel-connection count per neighbor edge, `cap_bps` the
// tier's per-connection send pacing (0 = unpaced) — a hierarchical fleet
// paces its slow inter links without throttling the fast intra ones.
struct RingTier {
  int64_t rank = -1;
  int64_t world = 0;
  int64_t conns = 0;
  int64_t cap_bps = 0;
  // Diagnostics: tier name ("flat"/"intra"/"inter"/"host") and the
  // neighbor addresses wired at configure — protocol-desync and CRC
  // errors name the edge they fired on.
  std::string name;
  std::string peer_next_addr;
  std::string peer_prev_addr;
  std::vector<Socket> next;   // one per stripe
  std::vector<Socket> prev;   // one per stripe
  // Shared-memory transport (host tier only, TORCHFT_HC_SHM on): one
  // edge pair per stripe instead of sockets. When non-empty, every ring
  // body routes its duplex through the shm rings.
  bool use_shm = false;
  std::vector<ShmEdge> shm;
  // Persistent per-stripe staging + pacing + per-op tx accounting
  // (grow-only, reused across ops).
  std::vector<StripeScratch> scratch;
  void clear() {
    rank = -1;
    world = 0;
    next.clear();
    prev.clear();
    use_shm = false;
    shm.clear();
  }
};

// Per-op phase/byte breakdown of the last hierarchical op (allreduce_hier
// or one hier plan execute): wall seconds per schedule phase and MEASURED
// bytes sent on each tier's connections (summed from the per-connection tx
// counters duplex maintains — what actually hit the kernel, headers
// included). inter_rs/inter_ag split the leader's slow-link bill per ring
// phase: each is (L-1)/L of the payload, the number the topology buys.
struct HierStats {
  int64_t intra_rs_ns = 0;
  int64_t intra_ag_ns = 0;
  int64_t inter_ring_ns = 0;
  int64_t intra_bcast_ns = 0;
  int64_t intra_tx_bytes = 0;
  int64_t inter_tx_bytes = 0;
  int64_t inter_rs_tx_bytes = 0;
  int64_t inter_ag_tx_bytes = 0;
  // Host (third) tier: phase walls of the shm (or loopback-TCP
  // fallback) ring, its MEASURED socket tx (0 under shm — the honest
  // zero-tx contract) and the bytes moved through the shm rings.
  int64_t shm_rs_ns = 0;
  int64_t shm_ag_ns = 0;
  int64_t shm_bcast_ns = 0;
  int64_t host_tx_bytes = 0;
  int64_t shm_bytes = 0;
  int64_t payload_bytes = 0;
  int64_t eff_intra = 0;
  int64_t eff_inter = 0;
  int64_t eff_host = 0;
  int64_t intra_world = 0;
  int64_t inter_world = 0;
  int64_t host_world = 0;
  bool leader = false;       // region leader
  bool host_leader = false;
  bool host_shm = false;     // host tier transport: shm (else TCP)
  int wire = 0;  // HierWire of the inter hop
};

// A persistent, precompiled description of one pytree's gradient sync:
// leaf -> dtype-group assignment with per-leaf element offsets, the wire
// format, the stripe partition (the plan's "buckets" — each stripe
// sub-range is packed, ridden, and unpacked as one pipeline unit), and
// persistent staging buffers sized once at build. Built once per
// (signature, wire) by HostCollectives::plan_build and executed each step
// as a single native call; Python's only per-step work is collecting leaf
// pointers. Executing the ring over the IDENTICAL per-group stripe
// partition the legacy single-op path uses (and through the same
// *_stripe bodies) makes plan-vs-legacy bit-identity structural, not
// coincidental. Plans are invalidated by configure(): the layout bakes in
// (world_size, stripes) and a new ring means new geometry.
struct CommPlan {
  struct Leaf {
    size_t count;   // flat elements
    Dtype dtype;    // source (and result) dtype
  };
  // One contiguous staging buffer per ring dtype; leaves are packed at
  // fixed offsets in signature order (the legacy concatenation layout).
  struct Group {
    Dtype dtype;                     // ring/staging dtype
    std::vector<int64_t> leaf_idx;   // leaves packed into this group
    std::vector<size_t> leaf_off;    // element offset of each leaf
    size_t count = 0;                // total flat elements
    int64_t eff = 1;                 // stripe partition (fixed at build)
    std::vector<char> staging;       // persistent, count * esize bytes
  };
  // Per-bucket (= per stripe sub-range) phase timings of the last
  // execute; the plan-path analog of the bulk path's bucket stats.
  // `leg` distinguishes a sharded plan's two halves (1 = reduce-scatter
  // grad leg, 2 = allgather param leg; 0 = fused execute) so the
  // accounting layer can bill each leg's wire separately.
  struct BucketStat {
    int64_t group = 0;
    int64_t stripe = 0;
    int64_t leg = 0;
    int64_t bytes = 0;
    int64_t pack_ns = 0, ring_ns = 0, unpack_ns = 0;
  };

  PlanWire wire = PlanWire::kNative;
  // Pre-packed leaves: the caller (a device-side Pallas pack) already
  // emitted the WIRE encoding — one contiguous payload per group in the
  // group's staging dtype (int8 codes for q8 wires, with a per-leaf f32
  // scale sidecar), so execute's pack stage collapses to a straight
  // decode/memcpy into staging. The ring and unpack phases are the
  // host-pack plan's own, and `prepacked` is deliberately EXCLUDED from
  // the signature hash: a device-packing member and a host-packing member
  // produce bit-identical staging (the device kernels mirror the native
  // EF/cast arithmetic), so mixed rings interoperate — pack placement is
  // a local choice, not a wire-contract change.
  bool prepacked = false;
  // Hierarchical plan: execute runs the two-tier schedule (intra rs/ag,
  // inter ring at `wire` among leaders, intra bcast) instead of the flat
  // ring. Groups keep their NATIVE dtypes — the plan wire applies at the
  // inter hop only (kBF16: leaders cast f32 staging to bf16 for the slow
  // link; kQ8/kQ8EF: leaders ride the quantized ring, kQ8EF with the
  // per-leaf error-feedback carry applied to the REGION sum at the
  // leader, so the residual refines each region's own contribution).
  // Baked into the signature hash: a hier plan meeting a flat plan must
  // error, not desync.
  bool hier = false;
  // Sharded plan (per-step ZeRO): the fused schedule split at the
  // reduce-scatter boundary into two first-class executes. `wire` is the
  // GRAD reduce-scatter leg's encoding; `ag_wire` the PARAM allgather
  // leg's (native or bf16). One flat f32 group; the rank-owned shard —
  // shard_ranges over the group's eff — always lands in FULL f32
  // precision (a lossy wire only ever paid to ship bytes the owner never
  // ships). Both legs share the group's eff, so the two partitions can
  // never disagree. Runs on the FLAT ring regardless of topology (the
  // flat ring always exists; the shard layout is its layout).
  bool sharded = false;
  PlanWire ag_wire = PlanWire::kNative;
  // Persistent bf16 wire staging for a sharded plan's bf16 leg(s)
  // (grow-only, the hier_wire_buf_ discipline, but per plan: sized once
  // at build).
  std::vector<char> wirebuf;
  std::vector<Leaf> leaves;
  std::vector<Group> groups;
  // kQ8EF: persistent error-feedback carry, laid out exactly like the
  // single f32 group's staging (per-leaf offsets shared). Prepacked q8
  // plans leave it empty — the carry lives device-side in the packer.
  // Hier kQ8EF plans allocate it everywhere but only the region LEADER
  // advances it (the EF quantize happens at the inter hop); a leader
  // change rebuilds plans (configure invalidates), so a new leader
  // starts from a zero carry — the standard reset discipline.
  std::vector<float> residual;
  uint64_t sig = 0;      // structure hash, exchanged in the op header
  int64_t execs = 0;     // executes since build (0 = cold)
  std::vector<BucketStat> stats;  // last execute, one entry per bucket
};

class HostCollectives {
 public:
  HostCollectives();  // wire-CRC default snapshotted from TORCHFT_WIRE_CRC
  ~HostCollectives();

  // Rebuilds the ring(s) for a (possibly new) membership. store_addr is
  // "host:port/prefix"; the prefix must be unique per quorum — stale members
  // of an old quorum never see the new keys, so they cannot cross-talk
  // (reference manager.py:470-477 store-prefix discipline). Aborts any
  // in-flight op first. `stripes` is the parallel-connection count per
  // neighbor edge; every member must pass the same value (the hello
  // handshake rejects mismatches, and the Python layer additionally
  // negotiates it through the store so mismatched ranks fail fast with a
  // descriptive error before any socket work).
  //
  // `regions` (optional): one region label per rank, identical on every
  // member (it comes from the quorum, which already agrees). When given
  // with >= 2 distinct labels, the TWO-TIER topology is built alongside
  // the flat ring (see the file comment) and allreduce_hier()/hier plans
  // become available; `stripes_inter` (0 = `stripes`) is the inter
  // (leader) ring's connection count — the slow wide-area hop is where
  // striping pays, so it gets its own knob.
  //
  // `hosts` (optional): one host label per rank (quorum-agreed, like
  // regions). Whenever a (region, host) pair groups >= 2 ranks, the
  // HOST tier is built below the intra one (see the file comment) —
  // shared-memory rings by default, loopback TCP under TORCHFT_HC_SHM=0
  // — and the hierarchical schedule becomes available even on a
  // single-region cohort (host rings + a leader ring are two real
  // tiers). Ring-buffer bytes per edge per stripe:
  // TORCHFT_HC_SHM_RING_BYTES (default 1 MiB).
  void configure(const std::string& store_addr, int64_t rank, int64_t world_size,
                 int64_t timeout_ms, int64_t stripes = 1,
                 const std::vector<std::string>& regions = {},
                 int64_t stripes_inter = 0,
                 const std::vector<std::string>& hosts = {});

  // Whether the last configure() built a hierarchical topology: a region
  // map with >= 2 distinct labels, a host map grouping >= 2 co-hosted
  // ranks, or both.
  bool hier_capable() const { return hier_; }

  // Host-tier transport of the last configure: 0 = no host tier,
  // 1 = loopback TCP (TORCHFT_HC_SHM off), 2 = shared-memory rings.
  int host_tier_transport() const {
    if (!hier_ || host_.world <= 1) return 0;
    return host_.use_shm ? 2 : 1;
  }

  // Requests per-frame CRC32C on every ring/stripe payload frame of the
  // NEXT configure() (and thereafter, until changed). Every member must
  // agree — the hello magic carries the frame format, so a mismatch
  // fails at connect with a descriptive error, and the Python layer
  // additionally negotiates the knob through the store. Default comes
  // from TORCHFT_WIRE_CRC at construction. A CRC mismatch on a frame
  // raises WireCorruptionError ("wire corruption: ..."), which rides
  // the normal latch -> vote-discard -> reconfigure machinery. Disabled
  // (the default), the wire format is byte-identical to the pre-CRC
  // protocol and duplex pays a single branch.
  void set_wire_crc(bool on) { crc_req_ = on; }
  bool wire_crc() const { return crc_; }

  // In-place ring allreduce over `count` elements of `data`.
  void allreduce(void* data, size_t count, Dtype dtype, ReduceOp op,
                 int64_t timeout_ms);

  // In-place TWO-TIER allreduce (requires a hier configure):
  //   intra reduce-scatter -> intra allgather -> inter ring among leaders
  //   -> chunk-pipelined intra broadcast.
  // `wire` selects the INTER hop's encoding (HierWire; bf16/q8 take f32
  // payloads and kSum only — intra stays native/full precision either
  // way). Results are bit-identical across members and runs; the sum
  // order differs from the flat ring (two-tier reduction tree).
  // Phase/byte breakdown of the last call: last_hier_json().
  void allreduce_hier(void* data, size_t count, Dtype dtype, ReduceOp op,
                      HierWire wire, int64_t timeout_ms);

  // In-place QUANTIZED ring SUM over `count` f32 elements: every hop
  // ships each chunk as [f32 absmax/127 scale][int8 payload] and the
  // receiver dequantize-accumulates into its f32 buffer (the same
  // f32-accumulator discipline the bf16 path uses). Phase 2 circulates
  // the owner-quantized reduced chunks verbatim, so wire bytes per
  // member are ~2x the int8 payload REGARDLESS of world size — unlike a
  // quantized allgather, whose traffic grows O(world). Per-hop
  // requantization of partial sums keeps relative error at the int8
  // quantization class (~1/127 of each chunk's absmax).
  void allreduce_q8(float* data, size_t count, int64_t timeout_ms);

  // ---- sharded (split) collectives ----
  //
  // Ring allreduce is reduce-scatter + allgather; these expose the two
  // phases as first-class ops so a caller can stop at the reduce-scatter
  // boundary, update only the shard it owns, and allgather the *updated*
  // values — the weight-update sharding of "Automatic Cross-Replica
  // Sharding of Weight Update in Data-Parallel Training" (Xu et al.).
  //
  // Shard layout: payload striping partitions `count` elements into
  // `layout_stripes` contiguous sub-ranges (stripe_range); within each
  // sub-range the ring schedule leaves chunk (rank+1) % world_size fully
  // reduced at this rank (the same chunk the fused op starts phase 2
  // from). Rank r's SHARD is the union of those per-stripe owned chunks,
  // compacted in stripe order. `layout_stripes` <= 0 means "derive from
  // the payload size like the fused op" (effective_stripes over
  // count * esize bytes — esize 1 for the q8 wire); a caller composing a
  // reduce-scatter with a later allgather_into of a DIFFERENT element
  // size (e.g. q8 reduce, bf16 gather) must pin the same explicit value
  // on both ops or the two partitions disagree. The layout is pure
  // arithmetic on (count, layout_stripes, world_size) — identical on
  // every member — and the per-op header carries it, so a mismatch
  // errors instead of desyncing.

  // Element (start, len) ranges of rank r's shard for a `count`-element
  // payload of `esize`-byte elements. Valid after configure().
  std::vector<std::pair<size_t, size_t>> shard_ranges(
      size_t count, size_t esize, int64_t r, int64_t layout_stripes = 0) const;

  // Ring reduce-scatter: phase 1 of the fused allreduce (bit-identical
  // arithmetic order), stopping at the reduce-scatter boundary. `data`
  // (count elements, clobbered: non-owned regions hold partial sums on
  // return) is reduced in place; the rank-owned shard is compacted into
  // `shard_out` (shard_ranges-many elements).
  void reduce_scatter(void* data, size_t count, Dtype dtype, ReduceOp op,
                      void* shard_out, int64_t layout_stripes,
                      int64_t timeout_ms);

  // Quantized-wire reduce-scatter: phase 1 of allreduce_q8 (int8 chunks,
  // per-hop dequant-accumulate in f32). The owned shard lands in FULL
  // f32 precision — the fused op's lossy phase-2 owner quantization only
  // existed to ship the chunk, and here it never ships. `grid_shard`
  // true applies that owner quantize+decode anyway, reproducing the
  // fused allreduce_q8's bits exactly (the determinism oracle for
  // decomposed-vs-fused tests).
  void reduce_scatter_q8(float* data, size_t count, float* shard_out,
                         bool grid_shard, int64_t layout_stripes,
                         int64_t timeout_ms);

  // Ring allgather of per-rank shards into the full buffer: phase 2 of
  // the fused allreduce. `shard` is this rank's shard (shard_ranges
  // layout); `data` (count elements) is filled with every rank's shard
  // at its owned positions. Composing reduce_scatter + allgather_into at
  // the same (dtype, layout_stripes) is bit-identical to the fused
  // allreduce on every rank.
  void allgather_into(const void* shard, void* data, size_t count,
                      Dtype dtype, int64_t layout_stripes,
                      int64_t timeout_ms);

  // ---- persistent comm plans ----
  //
  // plan_build compiles a CommPlan for a leaf signature (counts[i],
  // dtypes[i]) and wire format; returns a plan id valid until the next
  // configure() (which invalidates every plan — the layout bakes in the
  // ring geometry) or plan_free. Build is pure layout arithmetic — no
  // sockets touched — so ranks may build at different times; the id is
  // local. All members of a ring must build plans from identical
  // signatures (the execute header hashes the signature and errors on
  // mismatch, like every other op). `prepacked` builds a plan whose
  // execute takes pre-packed per-GROUP wire buffers (plan_execute_pre)
  // instead of per-leaf source pointers; it does not change the wire
  // contract (see CommPlan::prepacked), so prepacked and plain plans of
  // the same signature interoperate in one ring. `hier` builds a
  // HIERARCHICAL plan (see CommPlan::hier; requires a hier configure at
  // execute time): groups stay native-dtype and `wire` applies at the
  // inter hop only.
  int64_t plan_build(const int64_t* counts, const int32_t* dtypes,
                     int64_t n_leaves, PlanWire wire, bool prepacked = false,
                     bool hier = false);

  // Executes one gradient sync over the plan: packs/casts leaf_in[i]
  // into the persistent staging (kQ8EF additionally runs the native
  // error-feedback quantization against the plan's residual), rides the
  // ring, and unpacks (divisor applied, AVG-style) into leaf_out[i].
  // Each stripe sub-range is one pipeline bucket running
  // pack -> ring -> unpack on its own pool worker, so bucket i+1
  // packs/casts while bucket i rides the ring and bucket i-1 unpacks.
  // The ring arithmetic per group is bit-identical to the legacy
  // single-op path (same stripe partition, same *_stripe bodies).
  // Aborts/peer death wake every stripe exactly like the bulk ops.
  // Hier plans run the two-tier schedule instead: pack streams into the
  // intra reduce-scatter phase and unpack out of the broadcast phase, so
  // the per-bucket triple pipeline survives the extra tiers.
  void plan_execute(int64_t plan_id, const void* const* leaf_in,
                    void* const* leaf_out, double divisor, bool has_divisor,
                    int64_t timeout_ms);

  // Executes a PREPACKED plan: group_in[g] points at group g's wire
  // payload (g.count elements of the group's staging dtype — int8 codes
  // for q8 wires, bf16/native words otherwise) and group_aux[g] at its
  // per-leaf f32 scale sidecar (q8 wires only; ignored — may be null —
  // for other groups). The pack stage per stripe bucket is a straight
  // decode (q8: staging[i] = q[i] * scale; else memcpy) streamed
  // per bucket like any other phase; ring and unpack are plan_execute's
  // own, so device-packed results are bit-identical to host-packed ones
  // whenever the device pack mirrors the native pack arithmetic (the
  // Pallas kernels' tested contract). A NaN scale poisons its whole leaf
  // (0 * NaN), reproducing the host EF's non-finite propagation.
  void plan_execute_pre(int64_t plan_id, const void* const* group_in,
                        const void* const* group_aux, void* const* leaf_out,
                        double divisor, bool has_divisor, int64_t timeout_ms);

  // ---- sharded comm plans (per-step ZeRO weight-update sharding) ----
  //
  // plan_build_sharded compiles a SHARDED CommPlan: the fused allreduce
  // schedule split at the reduce-scatter boundary so a caller can update
  // only the 1/W shard it owns (optimizer state sharded with it) and
  // allgather the *updated* params — "Automatic Cross-Replica Sharding
  // of Weight Update in Data-Parallel Training" (Xu et al.) on the
  // per-step path. f32 leaves only (they pack one flat f32 group whose
  // shard_ranges over the group eff IS the shard layout); `rs_wire`
  // encodes the grad leg (native/bf16/q8 — the owner's shard stays full
  // f32 either way), `ag_wire` the param leg (native/bf16). Like every
  // plan: valid until the next configure(), signature exchanged in the
  // op headers (kinds 11/12) so mismatched plans error, not desync.
  int64_t plan_build_sharded(const int64_t* counts, const int32_t* dtypes,
                             int64_t n_leaves, PlanWire rs_wire,
                             PlanWire ag_wire);

  // Grad leg: packs leaf_in into the f32 staging, runs the rs phase per
  // stripe bucket (the fused op's own body at the plan's partition),
  // compacts the rank-owned chunks into `shard_out` (plan_sharded_meta's
  // shard_count f32 elements) and applies the divisor to the SHARD only
  // — the owner's slice of the fused unpack arithmetic (f32 / f32).
  void plan_execute_rs(int64_t plan_id, const void* const* leaf_in,
                       float* shard_out, double divisor, bool has_divisor,
                       int64_t timeout_ms);

  // Param leg: scatters `shard_in` (the UPDATED shard, same layout) back
  // into staging, rides the ag phase at `ag_wire` (bf16: every member
  // decodes the identical wire words, so gathered params are
  // bit-identical across the cohort) and unpacks into leaf_out, no
  // divisor.
  void plan_execute_ag(int64_t plan_id, const float* shard_in,
                       void* const* leaf_out, int64_t timeout_ms);

  // out[0] = this rank's shard element count, out[1] = the plan's stripe
  // partition (the layout_stripes to pass shard_ranges), out[2] = total
  // flat element count.
  void plan_sharded_meta(int64_t plan_id, int64_t* out);

  void plan_free(int64_t plan_id);
  // Zeroes a kQ8EF plan's error-feedback carry (no-op otherwise): the
  // caller's heal/abort discipline — a recovered member must not carry a
  // residual from its abandoned trajectory.
  void plan_reset_feedback(int64_t plan_id);
  // Per-bucket phase stats of the plan's last execute, as JSON:
  // {"execs": n, "buckets": [{"group", "stripe", "bytes", "pack_s",
  // "ring_s", "unpack_s"}, ...]}.
  std::string plan_stats_json(int64_t plan_id);

  // Phase/byte breakdown of the LAST hierarchical op (allreduce_hier or
  // hier plan execute; hier plans accumulate across their groups), as
  // JSON: {"intra_rs_s", "intra_ag_s", "inter_ring_s", "intra_bcast_s",
  // "intra_tx_bytes", "inter_tx_bytes", "inter_rs_tx_bytes",
  // "inter_ag_tx_bytes", "payload_bytes", "eff_intra", "eff_inter",
  // "intra_world", "inter_world", "leader", "wire"}. tx bytes are
  // MEASURED (summed from the per-connection counters duplex maintains),
  // not modeled. Same read discipline as last_stripe_ns: call from the
  // thread that issued the op.
  std::string last_hier_json() const;

  // Gathers `nbytes` from every rank into `out` (world_size * nbytes), in
  // rank order.
  void allgather(const void* in, void* out, size_t nbytes, int64_t timeout_ms);
  // Broadcasts `nbytes` of `data` from `root` to all ranks, in place.
  void broadcast(void* data, size_t nbytes, int64_t root, int64_t timeout_ms);
  void barrier(int64_t timeout_ms);

  int64_t rank() const { return rank_; }
  int64_t world_size() const { return world_size_; }
  int64_t stripes() const { return stripes_; }

  // Wall-clock nanoseconds each stripe spent inside the last bulk op
  // (index = stripe). Written under op_mu_; callers read it from the same
  // thread that issued the op (the Python executor), so no extra locking.
  const std::vector<int64_t>& last_stripe_ns() const { return last_stripe_ns_; }

  // Wakes any thread blocked inside an op with a SocketError; the instance
  // stays usable via a subsequent configure(). Safe to call from any thread.
  void abort();

  // abort() plus deterministic release of every ring resource — sockets,
  // listener and the host tier's shm segments (creator unlink) — without
  // destroying the instance. The shutdown() counterpart of configure's
  // generation ownership: callers that keep the object alive (pending
  // GC, caches) must not keep kernel-named segments alive with it. A
  // later configure() rebuilds everything.
  void release_rings();

 private:
  // Sends send_len bytes to next while concurrently receiving recv_len
  // bytes from prev (full-duplex pump; one-directional blocking would
  // deadlock once kernel buffers fill on a large ring step). `sc`
  // (nullable) carries the connection's send pacing (cap_bps token
  // bucket) and accumulates sent bytes into its tx counter; receives are
  // never paced, and a token-dry sender keeps draining its receive side.
  void duplex(Socket& next, Socket& prev, const char* send_buf,
              size_t send_len, char* recv_buf, size_t recv_len,
              int64_t deadline_ms, StripeScratch* sc = nullptr,
              bool header_frame = false);

  // The shared-memory analog of duplex for one host-tier edge pair:
  // produces one frame ([len, fseq] header + payload) into the stripe's
  // TX ring while consuming one from its RX ring, futex-blocking (with
  // the op deadline) when a ring is full/empty. Frame sequence numbers
  // and lengths are checked on consume — a stale or desynced frame
  // errors instead of reducing wrong bytes; a poisoned ring magic (peer
  // abort/death, torn segment) errors like a socket FIN. Accounts moved
  // bytes into scratch.shm_bytes, never tx_bytes.
  void shm_duplex(RingTier& T, int64_t s, const char* send_buf,
                  size_t send_len, char* recv_buf, size_t recv_len,
                  int64_t deadline_ms, bool header_frame);

  // Routes one edge exchange of tier T / stripe s through the tier's
  // transport: shm rings when T.use_shm, else the TCP duplex. Every ring
  // body goes through here, so the host tier reuses the proven phase
  // bodies unchanged.
  void edge_duplex(RingTier& T, int64_t s, const char* send_buf,
                   size_t send_len, char* recv_buf, size_t recv_len,
                   int64_t deadline_ms, bool header_frame = false);

  // Exchanges a tiny (kind, count, dtype, op) header with both neighbors
  // of tier `T` on stripe 0 before a collective and throws on mismatch — a
  // size/dtype-mismatched op would otherwise deadlock silently once kernel
  // buffers fill.
  void check_op_header(RingTier& T, uint32_t kind, uint64_t count,
                       uint32_t dtype, uint32_t op, int64_t deadline_ms);

  // Runs fn(stripe) for every stripe concurrently: stripe 0 on the calling
  // thread, the rest on PERSISTENT pool workers. The FIRST failing stripe
  // shuts down every stripe's sockets (waking its siblings within
  // milliseconds — the same abort-propagation discipline run_op applies
  // ring-wide), the job is fully drained, and the lowest-stripe error is
  // rethrown. Also records per-stripe wall time into last_stripe_ns_.
  void run_striped(const std::function<void(int64_t)>& fn);

  // Grows the stripe worker pool to at least `workers` threads (grow-only;
  // workers outlive reconfigures and die with the instance). Spawning a
  // thread per stripe per native op costs ~0.1 ms each under sandboxed
  // runtimes, and one chunk-pipelined gradient allreduce issues hundreds
  // of native ring ops — the pool turns each op's fan-out into a condvar
  // wake. Between jobs workers block on pool_cv_, never inside socket IO,
  // so abort() needs no extra wakeup path for an idle pool.
  void ensure_pool(int64_t workers);
  void pool_main(int64_t idx, int64_t start_gen);

  // Per-stripe ring bodies over an element/byte sub-range of tier `T`'s
  // ring. Parameterized by tier so the flat, intra and inter rings all
  // run the SAME proven bodies — the two-tier schedule is composed from
  // them, never reimplemented.
  void allreduce_stripe(RingTier& T, int64_t s, char* bytes, size_t count,
                        size_t esize, Dtype dtype, ReduceOp op,
                        int64_t deadline);
  void allreduce_q8_stripe(RingTier& T, int64_t s, float* data, size_t count,
                           int64_t deadline);
  // The two phases of the ring schedule, shared verbatim by the fused
  // allreduce, the first-class reduce_scatter / allgather_into, and the
  // two-tier schedule's intra/inter hops (the sharing is what makes
  // decomposed-vs-fused and hier-vs-oracle bit-identity structural
  // rather than coincidental).
  void rs_phase_stripe(RingTier& T, int64_t s, char* bytes, size_t count,
                       size_t esize, Dtype dtype, ReduceOp op,
                       int64_t deadline);
  void ag_phase_stripe(RingTier& T, int64_t s, char* bytes, size_t count,
                       size_t esize, int64_t deadline);
  void rs_q8_phase_stripe(RingTier& T, int64_t s, float* data, size_t count,
                          int64_t deadline);
  // The allgather phase of the quantized ring (owner-quantize + circulate
  // codes verbatim); allreduce_q8_stripe = rs_q8_phase + this.
  void ag_q8_phase_stripe(RingTier& T, int64_t s, float* data, size_t count,
                          int64_t deadline);
  // Chunk-pipelined store-and-forward broadcast of a byte sub-range from
  // tier rank `root` around tier T's ring: member d forwards chunk k-1
  // while receiving chunk k (duplex), so the wall is ~bytes/bw + a chunk
  // of fill per hop instead of hops * bytes/bw. The two-tier schedule's
  // distribution phase.
  void bcast_pipe_stripe(RingTier& T, int64_t s, char* bytes, size_t nbytes,
                         int64_t root, int64_t deadline);
  // One hierarchical schedule over `count` elements of `data` (already
  // under op_mu_/run_op): the shared body of allreduce_hier and the hier
  // plan execute. Runs the host (shm) phases when the host tier exists,
  // the intra/inter phases on host leaders, and accumulates phase/byte
  // stats into last_hier_.
  void hier_schedule(char* bytes, size_t count, size_t esize, Dtype dtype,
                     ReduceOp op, HierWire wire, int64_t eff_intra,
                     int64_t eff_inter, int64_t deadline);
  // The leader's inter hop — rs then ag among region leaders over `buf`,
  // re-striped at eff_inter, with the wire encoding applied (bf16: cast
  // through hier_wire_buf_; q8: the quantized ring bodies). ONE
  // implementation serves the bulk op and the hier plan, so a wire or
  // accounting change can never desync the two. `*rs_tx` receives the
  // rs phase's measured slow-link tx (delta of the tier counter).
  void inter_ring_phase(HierWire wire, char* buf, size_t count, size_t esize,
                        Dtype dtype, ReduceOp op, int64_t eff_inter,
                        int64_t deadline, int64_t* rs_tx);
  // Copies the rank-owned chunk of every stripe between the full buffer
  // and the compacted shard (to_shard=true: gather out of `data` into
  // `shard`; false: scatter back).
  void copy_shard(char* data, char* shard, size_t count, size_t esize,
                  int64_t eff, bool to_shard) const;
  // Sum of the per-connection tx counters of a tier's scratch; resetting
  // them is the per-op accounting boundary. tier_shm sums the bytes
  // moved through the tier's shared-memory rings (0 on TCP tiers).
  static int64_t tier_tx(const RingTier& T);
  static int64_t tier_shm(const RingTier& T);
  static void reset_tier_tx(RingTier& T);

  // Builds the host tier's shared-memory edges (create TX, attach RX
  // with retry until `deadline`) for the freshly computed geometry;
  // called from configure's phase 2 with no locks held.
  void wire_shm_edges(std::vector<ShmEdge>& edges, int64_t conns,
                      const std::string& base, int64_t next_rank,
                      int64_t prev_rank, int64_t deadline);
  // Poisons every shm ring magic and futex-wakes all waiters (local and
  // peer) — the shm analog of a socket shutdown; part of the abort/
  // failure-propagation path.
  void shm_poison_wake_locked() TFT_REQUIRES(cfg_mu_);

  // Plan internals: pack/unpack one element range of a group (casts per
  // the plan wire; unpack applies the divisor), and the kQ8EF per-leaf
  // error-feedback quantization (whole group — the per-leaf absmax spans
  // stripe boundaries, so it cannot run per stripe).
  void plan_pack_range(CommPlan& p, CommPlan::Group& g,
                       const void* const* leaf_in, size_t start,
                       size_t len) const;
  void plan_unpack_range(const CommPlan& p, const CommPlan::Group& g,
                         void* const* leaf_out, size_t start, size_t len,
                         double divisor, bool has_divisor) const;
  void plan_pack_ef(CommPlan& p, CommPlan::Group& g,
                    const void* const* leaf_in) const;
  // Hier kQ8EF: the same per-leaf EF quantization applied IN PLACE to the
  // group's staging (which holds the REGION sum at the leader before the
  // inter hop): d = staging + residual; quantize; staging = dq;
  // residual = d - dq. Leader-only by construction.
  void plan_ef_inplace(CommPlan& p, CommPlan::Group& g) const;
  // Prepacked decode of one element range: q8 groups dequantize the int8
  // codes against the per-leaf scale sidecar, everything else memcpys the
  // already-wire-encoded words into staging.
  void plan_pack_pre_range(const CommPlan& p, CommPlan::Group& g,
                           const void* group_in, const void* group_aux,
                           size_t start, size_t len) const;
  // The hier plan execute body for one group (under run_op): pack fused
  // into the intra_rs phase, unpack fused into the bcast phase.
  void plan_execute_hier_group(CommPlan& p, size_t gi,
                               const void* const* leaf_in,
                               void* const* leaf_out, double divisor,
                               bool has_divisor, int64_t deadline);
  CommPlan& plan_get(int64_t plan_id);

  // Shuts down every ring socket (all tiers, all stripes); cfg_mu_ must
  // NOT be held.
  void shutdown_sockets();
  void shutdown_sockets_locked() TFT_REQUIRES(cfg_mu_);

  // Runs an op body; on ANY failure shuts down all ring sockets before
  // rethrowing. The FIN propagates the failure around the ring — and, for
  // hierarchical ops, ACROSS TIERS: a dead region leader kills its inter
  // peers' op, whose intra members then fail on their own tier's sockets,
  // so every member of every region errors within one op deadline instead
  // of blocking while a majority of survivors can't reach the next quorum
  // — the distributed analog of NCCL's abort-on-error. The dead ring stays
  // dead (ops throw immediately) until the next configure().
  template <typename Fn>
  void run_op(Fn&& fn) {
    try {
      fn();
    } catch (...) {
      {
        MutexLock lock(cfg_mu_);
        shutdown_sockets_locked();
        aborted_ = true;
      }
      throw;
    }
  }

  // Element range [start, len) of stripe `s` when `count` elements are
  // split into `n` near-equal contiguous stripes.
  static std::pair<size_t, size_t> stripe_range(size_t count, int64_t n,
                                                int64_t s);

  // Guards socket object identity (swap/close) against concurrent abort.
  // Never held across blocking IO, so abort() always runs promptly.
  Mutex cfg_mu_;
  // Serializes collective ops (they share the ring sockets and must issue in
  // the same order on every rank anyway).
  Mutex op_mu_;

  // Ring geometry and per-stripe/tier state below ride a DUAL protocol no
  // single capability can express (so no GUARDED_BY): identity writers
  // (configure) hold op_mu_ AND cfg_mu_; the op thread reads under op_mu_;
  // pool workers read with NO lock, synchronized by the pool_mu_ job
  // handoff (the op thread publishes the job under pool_mu_ while itself
  // holding op_mu_, so no write can overlap a worker's read). abort()/
  // run_op touch only the sockets' fds, under cfg_mu_.
  int64_t rank_ = -1;
  int64_t world_size_ = 0;
  int64_t stripes_ = 1;
  int64_t stripes_inter_ = 1;
  bool hier_ = false;
  // Canonical hash of the (region, host) topology map of the last
  // configure — mixed into hier plan signatures so plans built against
  // different topologies error at the header instead of desyncing.
  uint64_t topo_hash_ = 0;
  // Shared-memory ring-buffer bytes per edge per stripe, snapshotted at
  // configure (TORCHFT_HC_SHM_RING_BYTES).
  size_t shm_ring_bytes_ = 1 << 20;
  // Wire CRC: crc_req_ is the caller's request (env default at
  // construction, settable until configure); crc_ is the ACTIVE frame
  // format, snapshotted by configure so it is stable for the life of a
  // ring (same dual protocol as rank_/stripes_).
  bool crc_req_ = false;
  bool crc_ = false;
  // Monotonic per-member collective-op counter (bumped under op_mu_ at
  // every public op): the op_index axis of the seeded fault schedule and
  // the index desync/corruption errors report.
  int64_t op_seq_ = 0;
  std::unique_ptr<Listener> listener_;
  // The four rings a member can participate in. flat_ always exists
  // after a multi-member configure; intra_/inter_/host_ only under a
  // hier configure (intra_.world == 1 for a one-member region,
  // inter_.world only meaningful on the region leader, host_.world <= 1
  // when this member is alone on its host).
  RingTier flat_;
  RingTier intra_;
  RingTier inter_;
  RingTier host_;
  HierStats last_hier_;
  // Leader-side inter-hop wire staging for allreduce_hier's bf16 wire
  // (grow-only, reused across ops).
  std::vector<char> hier_wire_buf_;
  std::vector<int64_t> last_stripe_ns_;    // per-stripe time of the last op
  std::atomic<bool> aborted_{true}; // not configured yet
  // Bumped by every abort(); configure() uses it to detect an abort that
  // raced with its (lock-free) rendezvous phase.
  std::atomic<int64_t> abort_epoch_{0};

  // Stripe worker pool state (all under pool_mu_). Worker `idx` runs stripe
  // `idx + 1` of the current job when that stripe exists (ops can use fewer
  // effective stripes than configured); stripe 0 always runs on the op
  // thread. op_mu_ guarantees at most one job is in flight. The job BODY is
  // invoked by workers after dropping pool_mu_ (it blocks in socket IO);
  // its lifetime is the run_striped stack frame, pinned until the
  // pool_pending_ drain completes.
  Mutex pool_mu_;
  CondVar pool_cv_;       // workers: wait for a new job
  CondVar pool_done_cv_;  // run_striped: wait for drain
  const std::function<void(int64_t)>* pool_body_ TFT_GUARDED_BY(pool_mu_) =
      nullptr;
  int64_t pool_gen_ TFT_GUARDED_BY(pool_mu_) = 0;  // bumped once per job
  int64_t pool_n_ TFT_GUARDED_BY(pool_mu_) = 0;  // stripe count of the job
  int64_t pool_pending_ TFT_GUARDED_BY(pool_mu_) = 0;  // workers not yet done
  bool pool_stop_ TFT_GUARDED_BY(pool_mu_) = false;
  std::vector<std::thread> pool_ TFT_GUARDED_BY(pool_mu_);

  // Comm plans (guarded by plan_mu_ for map identity; a plan's buffers
  // are only ever touched under op_mu_ during execute). Cleared by
  // configure() — ids from an old ring error instead of running with a
  // stale layout.
  Mutex plan_mu_;
  std::map<int64_t, std::unique_ptr<CommPlan>> plans_ TFT_GUARDED_BY(plan_mu_);
  int64_t next_plan_id_ TFT_GUARDED_BY(plan_mu_) = 1;
};

} // namespace tft

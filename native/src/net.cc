#include "net.h"

#include "fault.h"

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <chrono>
#include <random>

namespace tft {

int64_t now_ms() {
  auto t = std::chrono::steady_clock::now().time_since_epoch();
  return std::chrono::duration_cast<std::chrono::milliseconds>(t).count();
}

int64_t unix_ms() {
  auto t = std::chrono::system_clock::now().time_since_epoch();
  return std::chrono::duration_cast<std::chrono::milliseconds>(t).count();
}

std::string format_unix_ms(int64_t ms) {
  time_t secs = static_cast<time_t>(ms / 1000);
  struct tm tm_utc;
  gmtime_r(&secs, &tm_utc);
  char buf[16];
  snprintf(buf, sizeof(buf), "%02d:%02d:%02d", tm_utc.tm_hour, tm_utc.tm_min,
           tm_utc.tm_sec);
  return buf;
}

int poll_timeout_or_throw(int64_t deadline_ms, const char* what) {
  if (deadline_ms < 0) return -1;
  int64_t remain = deadline_ms - now_ms();
  if (remain <= 0) throw TimeoutError(what);
  return static_cast<int>(std::min<int64_t>(remain, 1 << 30));
}

std::string local_hostname() {
  char buf[256];
  if (gethostname(buf, sizeof(buf)) != 0) return "localhost";
  buf[sizeof(buf) - 1] = '\0';
  return buf;
}

namespace {

uint16_t parse_port(const std::string& raw, const std::string& port_str) {
  if (port_str.empty() ||
      port_str.find_first_not_of("0123456789") != std::string::npos)
    throw SocketError("bad port in address: " + raw);
  long port = std::strtol(port_str.c_str(), nullptr, 10);
  if (port < 0 || port > 65535)
    throw SocketError("port out of range in address: " + raw);
  return static_cast<uint16_t>(port);
}

} // namespace

Addr parse_addr(const std::string& raw) {
  std::string s = raw;
  for (const char* scheme : {"http://", "tft://", "grpc://"}) {
    if (s.rfind(scheme, 0) == 0) {
      s = s.substr(strlen(scheme));
      break;
    }
  }
  // strip trailing slash but reject a real path
  while (!s.empty() && s.back() == '/') s.pop_back();
  if (s.find('/') != std::string::npos)
    throw SocketError("address contains a path component: " + raw);

  size_t colon;
  if (!s.empty() && s[0] == '[') {
    // [v6]:port
    size_t close = s.find(']');
    if (close == std::string::npos || close + 1 >= s.size() || s[close + 1] != ':')
      throw SocketError("bad address: " + raw);
    Addr a;
    a.host = s.substr(1, close - 1);
    a.port = parse_port(raw, s.substr(close + 2));
    return a;
  }
  colon = s.rfind(':');
  if (colon == std::string::npos) throw SocketError("address missing port: " + raw);
  Addr a;
  a.host = s.substr(0, colon);
  a.port = parse_port(raw, s.substr(colon + 1));
  if (a.host.empty()) a.host = "::";
  return a;
}

std::pair<std::string, std::string> split_store_addr(const std::string& addr) {
  std::string s = addr;
  size_t slash = s.find('/');
  if (slash == std::string::npos) return {s, ""};
  return {s.substr(0, slash), s.substr(slash + 1)};
}

Socket& Socket::operator=(Socket&& other) noexcept {
  if (this != &other) {
    close();
    fd_ = other.fd_;
    other.fd_ = -1;
  }
  return *this;
}

Socket::~Socket() { close(); }

void Socket::close() {
  if (fd_ >= 0) {
    ::close(fd_);
    fd_ = -1;
  }
}

void Socket::shutdown_rdwr() {
  if (fd_ >= 0) ::shutdown(fd_, SHUT_RDWR);
}

void Socket::wait_ready(bool for_read, int64_t deadline_ms) {
  struct pollfd pfd;
  pfd.fd = fd_;
  pfd.events = for_read ? POLLIN : POLLOUT;
  while (true) {
    int timeout = poll_timeout_or_throw(deadline_ms, "socket io timed out");
    int rc = ::poll(&pfd, 1, timeout);
    if (rc > 0) return;
    if (rc == 0) throw TimeoutError("socket io timed out");
    if (errno == EINTR) continue;
    throw SocketError(std::string("poll: ") + strerror(errno));
  }
}

void Socket::send_all(const void* buf, size_t len, int64_t deadline_ms) {
  const char* p = static_cast<const char*>(buf);
  size_t sent = 0;
  // Chaos seam: the control plane's send path (store ops, manager/
  // lighthouse RPC frames, ring hellos). Disarmed this is one relaxed
  // load; armed, the seeded schedule decides per frame. `corrupt` keeps
  // a mutated copy alive for the send loop — the caller's buffer is
  // never touched, and nothing recurses back through the fault check.
  std::string corrupt;
  bool truncate_after = false;
  fault::Decision fd =
      TFT_FAULT_CHECK(fault::kSeamNetSend, /*member=*/-1, /*op_index=*/-1);
  if (fd.kind != fault::kNone && len > 0) {
    switch (fd.kind) {
      case fault::kDrop:
        shutdown_rdwr();
        throw SocketError("chaos injected: control-plane send dropped");
      case fault::kDelay: {
        // Bounded by the caller's deadline (the fault.h contract).
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
        // Ship a torn prefix, then die — the peer sees a partial frame
        // followed by EOF (a mid-write crash).
        corrupt.assign(p, len / 2);
        p = corrupt.data();
        len = corrupt.size();
        truncate_after = true;
        break;
      case fault::kPartition:
        // Asymmetric partition: the frame silently vanishes; the peer
        // keeps waiting until ITS deadline while our receives still
        // flow. Nothing to throw here — the stall IS the fault.
        return;
      case fault::kBitFlip:
        // Corrupt one bit of the frame on the wire: protocol framing on
        // the far side must reject it, never act on it.
        corrupt.assign(p, len);
        corrupt[fd.h % len] ^= static_cast<char>(1u << ((fd.h >> 8) % 8));
        p = corrupt.data();
        break;
      case fault::kDuplicate:
        // Repeat a prefix of the frame: every byte after it lands at
        // the wrong stream offset (the classic torn-retry desync).
        corrupt.assign(p, len < 16 ? len : 16);
        corrupt.append(p, len);
        p = corrupt.data();
        len = corrupt.size();
        break;
      default:
        break;
    }
  }
  while (sent < len) {
    ssize_t n = ::send(fd_, p + sent, len - sent, MSG_NOSIGNAL);
    if (n > 0) {
      sent += static_cast<size_t>(n);
      continue;
    }
    if (n < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
      wait_ready(/*for_read=*/false, deadline_ms);
      continue;
    }
    if (n < 0 && errno == EINTR) continue;
    throw SocketError(std::string("send: ") + strerror(errno));
  }
  if (truncate_after) {
    shutdown_rdwr();
    throw SocketError("chaos injected: control-plane send truncated");
  }
}

void Socket::recv_all(void* buf, size_t len, int64_t deadline_ms) {
  char* p = static_cast<char*>(buf);
  size_t got = 0;
  while (got < len) {
    ssize_t n = ::recv(fd_, p + got, len - got, 0);
    if (n > 0) {
      got += static_cast<size_t>(n);
      continue;
    }
    if (n == 0) throw SocketError("connection closed by peer");
    if (errno == EAGAIN || errno == EWOULDBLOCK) {
      wait_ready(/*for_read=*/true, deadline_ms);
      continue;
    }
    if (errno == EINTR) continue;
    throw SocketError(std::string("recv: ") + strerror(errno));
  }
}

size_t Socket::peek(void* buf, size_t len, int64_t deadline_ms) {
  while (true) {
    ssize_t n = ::recv(fd_, buf, len, MSG_PEEK);
    if (n > 0) return static_cast<size_t>(n);
    if (n == 0) throw SocketError("connection closed by peer");
    if (errno == EAGAIN || errno == EWOULDBLOCK) {
      wait_ready(/*for_read=*/true, deadline_ms);
      continue;
    }
    if (errno == EINTR) continue;
    throw SocketError(std::string("peek: ") + strerror(errno));
  }
}

namespace {

void set_nonblocking(int fd) {
  int flags = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

// Large kernel buffers: the bulk ring crosses high-bandwidth-delay paths
// (DCN, wide-area links) where a default-window TCP connection caps
// throughput at window/RTT, and on any path a deeper buffer halves the
// poll/send wakeup count per MB. Must run BEFORE the handshake (before
// ::connect on the client, on the listening fd for accepted sockets) —
// the window-scale factor is fixed at SYN from the buffer size then in
// effect. Best-effort — the kernel clamps to net.core.{r,w}mem_max.
void set_bulk_buffers(int fd) {
  int buf = 4 << 20;
  setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &buf, sizeof(buf));
  setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &buf, sizeof(buf));
}

void set_common_opts(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  // TCP keepalive plays the role of reference src/net.rs HTTP2 keep-alive.
  setsockopt(fd, SOL_SOCKET, SO_KEEPALIVE, &one, sizeof(one));
  int idle = 60, intvl = 20, cnt = 3;
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPIDLE, &idle, sizeof(idle));
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPINTVL, &intvl, sizeof(intvl));
  setsockopt(fd, IPPROTO_TCP, TCP_KEEPCNT, &cnt, sizeof(cnt));
}

} // namespace

// The listener fd is non-blocking: accept() waits in poll, so a deadline is
// always enforceable and a peer that vanishes from the backlog between poll
// and ::accept surfaces as EAGAIN (retried) instead of a blocking accept.
Listener::Listener(const std::string& bind_addr) {
  Addr a = parse_addr(bind_addr);
  struct addrinfo hints, *res = nullptr;
  memset(&hints, 0, sizeof(hints));
  hints.ai_family = AF_UNSPEC;
  hints.ai_socktype = SOCK_STREAM;
  hints.ai_flags = AI_PASSIVE;
  std::string port_str = std::to_string(a.port);
  const char* host = a.host == "::" || a.host.empty() ? nullptr : a.host.c_str();
  int rc = getaddrinfo(host, port_str.c_str(), &hints, &res);
  if (rc != 0) throw SocketError(std::string("getaddrinfo: ") + gai_strerror(rc));

  int last_errno = 0;
  for (struct addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
    int fd = ::socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
    if (fd < 0) {
      last_errno = errno;
      continue;
    }
    int one = 1;
    setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    set_bulk_buffers(fd); // accepted sockets inherit; scale is fixed at SYN
    if (ai->ai_family == AF_INET6) {
      int zero = 0; // dual-stack
      setsockopt(fd, IPPROTO_IPV6, IPV6_V6ONLY, &zero, sizeof(zero));
    }
    if (::bind(fd, ai->ai_addr, ai->ai_addrlen) == 0 && ::listen(fd, 1024) == 0) {
      set_nonblocking(fd);
      fd_ = fd;
      struct sockaddr_storage ss;
      socklen_t slen = sizeof(ss);
      getsockname(fd, reinterpret_cast<struct sockaddr*>(&ss), &slen);
      if (ss.ss_family == AF_INET)
        port_ = ntohs(reinterpret_cast<struct sockaddr_in*>(&ss)->sin_port);
      else
        port_ = ntohs(reinterpret_cast<struct sockaddr_in6*>(&ss)->sin6_port);
      break;
    }
    last_errno = errno;
    ::close(fd);
  }
  freeaddrinfo(res);
  if (fd_ < 0)
    throw SocketError("bind " + bind_addr + ": " + strerror(last_errno));
  int wake[2];
  if (::pipe(wake) == 0) {
    for (int wfd : wake) {
      int flags = fcntl(wfd, F_GETFL, 0);
      fcntl(wfd, F_SETFL, flags | O_NONBLOCK);
      fcntl(wfd, F_SETFD, FD_CLOEXEC);
    }
    wake_rd_ = wake[0];
    wake_wr_ = wake[1];
  }
}

Listener::~Listener() {
  close();
  // The fd NUMBERS (the /dev/null placeholder close() left in the listen
  // slot, and the pipe) are released only here: a racing accept() may
  // still hold them for its poll/::accept pair for an instant after
  // close() returns. Every caller joins/serializes its accept threads
  // before destroying the Listener, so releasing the numbers here is
  // race-free.
  int fd = fd_.exchange(-1);
  if (fd >= 0) ::close(fd);
  if (wake_rd_ >= 0) ::close(wake_rd_);
  if (wake_wr_ >= 0) ::close(wake_wr_);
}

void Listener::close() {
  if (closed_.exchange(true)) return;
  // Order matters: signal the pipe BEFORE touching the listen fd, so a
  // thread blocked in poll() wakes via the pipe even though closing the
  // fd under it would not (Linux<4.5 / gVisor never wake such a poller).
  if (wake_wr_ >= 0) {
    char b = 1;
    [[maybe_unused]] ssize_t rc = ::write(wake_wr_, &b, 1);
  }
  // The listening SOCKET must die now — peers must get ECONNREFUSED and
  // the port must free immediately (shutdown() alone is a no-op for a
  // LISTENING fd on gVisor/Linux<4.5, which would leave dials landing in
  // a backlog nobody drains). But plainly ::close()ing would let the
  // kernel recycle the fd NUMBER into an unrelated socket that a racing
  // accept() — which already loaded the number for its poll/::accept
  // pair — could steal a connection from. dup2()ing /dev/null over the
  // slot does both atomically: the socket closes (port freed, dials
  // refused) while the number stays reserved until ~Listener, and the
  // racing accept() gets ENOTSOCK and exits.
  int fd = fd_.load();
  if (fd >= 0) {
    ::shutdown(fd, SHUT_RDWR);  // wakes pollers on kernels that honor it
    int nul = ::open("/dev/null", O_RDONLY | O_CLOEXEC);
    if (nul >= 0) {
      ::dup2(nul, fd);
      ::close(nul);
    } else {
      // No placeholder available: fall back to a plain close (the
      // fd-reuse window returns, but a dead /dev/null is not an option).
      fd_.store(-1);
      ::close(fd);
    }
  }
}

Socket Listener::accept() { return accept(-1); }

Socket Listener::accept(int64_t deadline_ms) {
  while (true) {
    // closed_ is the close() signal (the fd slot then holds a /dev/null
    // placeholder, not the socket; fd_ goes -1 only in the destructor or
    // the close() fallback path). Bail out before polling: poll() would
    // silently skip a negative fd and sleep the whole timeout. One load
    // per iteration: poll and ::accept below must see the same fd.
    int lfd = fd_.load();
    if (closed_ || lfd < 0) return Socket();
    struct pollfd pfds[2];
    pfds[0].fd = lfd;
    pfds[0].events = POLLIN;
    pfds[1].fd = wake_rd_; // -1 (pipe creation failed) is skipped by poll
    pfds[1].events = POLLIN;
    int timeout = poll_timeout_or_throw(deadline_ms, "accept timed out");
    int prc = ::poll(pfds, 2, timeout);
    if (prc == 0) throw TimeoutError("accept timed out");
    if (prc < 0) {
      if (errno == EINTR) continue;
      throw SocketError(std::string("poll: ") + strerror(errno));
    }
    if (pfds[1].revents & (POLLIN | POLLHUP | POLLERR)) return Socket();
    if (pfds[0].revents & POLLNVAL) return Socket(); // fd closed under us
    if (!(pfds[0].revents & (POLLIN | POLLHUP | POLLERR))) continue;
    int fd = ::accept(lfd, nullptr, nullptr);
    if (fd >= 0) {
      set_common_opts(fd);
      set_nonblocking(fd);
      return Socket(fd);
    }
    // Transient failures (peer vanished from the backlog between poll and
    // accept, fd pressure) must not stop the loop — only a closed listener
    // should. The fd is non-blocking, so the retry waits in poll above.
    if (errno == EINTR || errno == ECONNABORTED || errno == EAGAIN ||
        errno == EWOULDBLOCK)
      continue;
    if (errno == EMFILE || errno == ENFILE || errno == ENOBUFS ||
        errno == ENOMEM) {
      struct timespec ts{0, 10 * 1000 * 1000}; // 10ms breather
      nanosleep(&ts, nullptr);
      continue;
    }
    return Socket(); // listener closed (EBADF/EINVAL/ENOTSOCK)
  }
}

Socket connect_once(const Addr& addr, int64_t deadline_ms) {
  struct addrinfo hints, *res = nullptr;
  memset(&hints, 0, sizeof(hints));
  hints.ai_family = AF_UNSPEC;
  hints.ai_socktype = SOCK_STREAM;
  std::string host = addr.host;
  if (host == "::" || host.empty() || host == "0.0.0.0") host = "localhost";
  std::string port_str = std::to_string(addr.port);
  int rc = getaddrinfo(host.c_str(), port_str.c_str(), &hints, &res);
  if (rc != 0) throw SocketError(std::string("getaddrinfo: ") + gai_strerror(rc));

  std::string last_err = "no addresses";
  for (struct addrinfo* ai = res; ai != nullptr; ai = ai->ai_next) {
    int fd = ::socket(ai->ai_family, ai->ai_socktype, ai->ai_protocol);
    if (fd < 0) {
      last_err = strerror(errno);
      continue;
    }
    set_bulk_buffers(fd); // before ::connect: window scale is fixed at SYN
    set_nonblocking(fd);
    int crc = ::connect(fd, ai->ai_addr, ai->ai_addrlen);
    if (crc != 0 && errno != EINPROGRESS) {
      last_err = strerror(errno);
      ::close(fd);
      continue;
    }
    if (crc != 0) {
      // wait for connect completion
      struct pollfd pfd;
      pfd.fd = fd;
      pfd.events = POLLOUT;
      int64_t remain = deadline_ms < 0 ? -1 : deadline_ms - now_ms();
      if (deadline_ms >= 0 && remain <= 0) {
        ::close(fd);
        freeaddrinfo(res);
        throw TimeoutError("connect timed out");
      }
      int prc = ::poll(&pfd, 1, deadline_ms < 0 ? -1 : static_cast<int>(remain));
      if (prc <= 0) {
        ::close(fd);
        freeaddrinfo(res);
        throw TimeoutError("connect timed out");
      }
      int err = 0;
      socklen_t elen = sizeof(err);
      getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &elen);
      if (err != 0) {
        last_err = strerror(err);
        ::close(fd);
        continue;
      }
    }
    set_common_opts(fd);
    freeaddrinfo(res);
    return Socket(fd);
  }
  freeaddrinfo(res);
  throw SocketError("connect " + host + ":" + port_str + ": " + last_err);
}

Socket connect_with_retry(const std::string& addr_str, int64_t timeout_ms) {
  Addr addr = parse_addr(addr_str);
  int64_t deadline = now_ms() + timeout_ms;
  // Reference src/retry.rs: initial 100ms, multiplier 1.5, max 10s, jitter 100ms.
  double backoff = 100.0;
  std::mt19937 rng(static_cast<uint32_t>(now_ms()));
  std::uniform_real_distribution<double> jitter(0.0, 100.0);
  std::string last_err;
  while (true) {
    try {
      return connect_once(addr, deadline);
    } catch (const TimeoutError&) {
      throw TimeoutError("connect to " + addr_str + " timed out after " +
                         std::to_string(timeout_ms) + "ms" +
                         (last_err.empty() ? "" : " (last error: " + last_err + ")"));
    } catch (const SocketError& e) {
      last_err = e.what();
    }
    int64_t remain = deadline - now_ms();
    if (remain <= 0)
      throw TimeoutError("connect to " + addr_str + " timed out after " +
                         std::to_string(timeout_ms) + "ms (last error: " + last_err +
                         ")");
    int64_t sleep_ms =
        std::min<int64_t>(static_cast<int64_t>(backoff + jitter(rng)), remain);
    struct timespec ts;
    ts.tv_sec = sleep_ms / 1000;
    ts.tv_nsec = (sleep_ms % 1000) * 1000000;
    nanosleep(&ts, nullptr);
    backoff = std::min(backoff * 1.5, 10000.0);
  }
}

namespace {

// splitmix64: tiny, well-mixed, and stable across platforms — exactly what a
// deterministic (testable) jitter needs.
uint64_t splitmix64(uint64_t x) {
  x += 0x9e3779b97f4a7c15ULL;
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

// Uniform double in [0, 1) from the top 53 bits.
double unit_double(uint64_t h) {
  return static_cast<double>(h >> 11) * (1.0 / 9007199254740992.0);
}

} // namespace

int64_t backoff_ms(int failures, int64_t base_ms, int64_t max_ms, uint64_t seed) {
  if (failures <= 0 || base_ms <= 0) return 0;
  // Cap the exponent before shifting so 63+ consecutive failures cannot
  // overflow into a negative delay.
  int exp = failures - 1 > 40 ? 40 : failures - 1;
  int64_t raw = base_ms << exp;
  if (raw > max_ms || raw <= 0) raw = max_ms;
  double jitter = 0.5 + unit_double(splitmix64(seed ^ static_cast<uint64_t>(failures)));
  int64_t out = static_cast<int64_t>(static_cast<double>(raw) * jitter);
  return out > max_ms ? max_ms : out;
}

int64_t jittered_interval_ms(int64_t interval_ms, uint64_t seed, uint64_t tick) {
  if (interval_ms <= 0) return 0;
  double f = 0.75 + 0.5 * unit_double(splitmix64(seed ^ (tick * 0x9e3779b97f4a7c15ULL)));
  return static_cast<int64_t>(static_cast<double>(interval_ms) * f);
}

std::vector<std::string> split_addr_list(const std::string& s) {
  std::vector<std::string> out;
  std::string cur;
  for (char c : s) {
    if (c == ',') {
      if (!cur.empty()) out.push_back(cur);
      cur.clear();
    } else if (!std::isspace(static_cast<unsigned char>(c))) {
      cur.push_back(c);
    }
  }
  if (!cur.empty()) out.push_back(cur);
  return out;
}

} // namespace tft

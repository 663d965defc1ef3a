// TCP key-value store for rank rendezvous.
//
// Reference parity: paddle/phi/core/distributed/store/tcp_store.{h,cc}
// (MasterDaemon + TCPStore client: SET/GET/ADD/WAIT/CHECK commands over a
// length-prefixed socket protocol) — re-designed, not translated: one
// poll()-driven daemon thread, a blocking-with-timeout client, and a C ABI
// consumed from Python via ctypes (the reference binds through pybind).
//
// Wire format (little-endian):
//   request : u8 op | u32 klen | key | (SET: u32 vlen | val) (ADD: i64)
//   reply   : GET/WAIT -> u8 found [| u32 vlen | val]
//             SET      -> u8 ok
//             ADD      -> i64 new_value
#include <arpa/inet.h>
#include <errno.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <string.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

enum Op : uint8_t { OP_SET = 1, OP_GET = 2, OP_ADD = 3, OP_DEL = 4 };

bool read_full(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n > 0) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r <= 0) {
      if (r < 0 && (errno == EINTR)) continue;
      // EAGAIN/EWOULDBLOCK = SO_RCVTIMEO expired: treat as failure so a
      // stalled peer can't block the caller forever
      return false;
    }
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

void set_op_timeout(int fd, double seconds) {
  timeval tv{};
  tv.tv_sec = static_cast<long>(seconds);
  tv.tv_usec = static_cast<long>((seconds - tv.tv_sec) * 1e6);
  ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
  ::setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
}

bool write_full(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n > 0) {
    ssize_t r = ::send(fd, p, n, MSG_NOSIGNAL);
    if (r <= 0) {
      if (r < 0 && errno == EINTR) continue;
      return false;
    }
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

class Server {
 public:
  explicit Server(int port) : port_(port) {}

  bool start() {
    listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    if (listen_fd_ < 0) return false;
    int one = 1;
    ::setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
    addr.sin_port = htons(static_cast<uint16_t>(port_));
    if (::bind(listen_fd_, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) < 0)
      return false;
    if (::listen(listen_fd_, 128) < 0) return false;
    if (port_ == 0) {  // ephemeral: report the real port
      socklen_t len = sizeof(addr);
      ::getsockname(listen_fd_, reinterpret_cast<sockaddr*>(&addr), &len);
      port_ = ntohs(addr.sin_port);
    }
    running_.store(true);
    thread_ = std::thread([this] { loop(); });
    return true;
  }

  void stop() {
    running_.store(false);
    if (listen_fd_ >= 0) ::shutdown(listen_fd_, SHUT_RDWR);
    if (thread_.joinable()) thread_.join();
    if (listen_fd_ >= 0) ::close(listen_fd_);
    for (int fd : conns_) ::close(fd);
    conns_.clear();
  }

  int port() const { return port_; }

  ~Server() { stop(); }

 private:
  void loop() {
    while (running_.load()) {
      std::vector<pollfd> fds;
      fds.push_back({listen_fd_, POLLIN, 0});
      for (int fd : conns_) fds.push_back({fd, POLLIN, 0});
      int rc = ::poll(fds.data(), fds.size(), 100 /*ms*/);
      if (rc <= 0) continue;
      std::vector<int> alive;
      for (size_t i = 1; i < fds.size(); i++) {
        int fd = fds[i].fd;
        if (fds[i].revents & (POLLERR | POLLHUP)) {
          ::close(fd);
          continue;
        }
        if (fds[i].revents & POLLIN) {
          if (!handle(fd)) {
            ::close(fd);
            continue;
          }
        }
        alive.push_back(fd);
      }
      if (fds[0].revents & POLLIN) {
        int conn = ::accept(listen_fd_, nullptr, nullptr);
        if (conn >= 0) {
          int one = 1;
          ::setsockopt(conn, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
          // bound per-request reads so one stalled/partial-writing peer
          // cannot wedge the single daemon thread
          set_op_timeout(conn, 30.0);
          alive.push_back(conn);
        }
      }
      conns_ = std::move(alive);
    }
  }

  bool handle(int fd) {
    uint8_t op;
    uint32_t klen;
    if (!read_full(fd, &op, 1) || !read_full(fd, &klen, 4)) return false;
    if (klen > (1u << 20)) return false;
    std::string key(klen, '\0');
    if (!read_full(fd, key.data(), klen)) return false;
    switch (op) {
      case OP_SET: {
        uint32_t vlen;
        if (!read_full(fd, &vlen, 4)) return false;
        if (vlen > (1u << 30)) return false;
        std::string val(vlen, '\0');
        if (!read_full(fd, val.data(), vlen)) return false;
        {
          std::lock_guard<std::mutex> g(mu_);
          kv_[key] = std::move(val);
        }
        uint8_t ok = 1;
        return write_full(fd, &ok, 1);
      }
      case OP_GET: {
        std::string val;
        uint8_t found = 0;
        {
          std::lock_guard<std::mutex> g(mu_);
          auto it = kv_.find(key);
          if (it != kv_.end()) {
            found = 1;
            val = it->second;
          }
        }
        if (!write_full(fd, &found, 1)) return false;
        if (found) {
          uint32_t vlen = static_cast<uint32_t>(val.size());
          if (!write_full(fd, &vlen, 4)) return false;
          if (!write_full(fd, val.data(), vlen)) return false;
        }
        return true;
      }
      case OP_ADD: {
        int64_t delta;
        if (!read_full(fd, &delta, 8)) return false;
        int64_t nv;
        {
          std::lock_guard<std::mutex> g(mu_);
          int64_t cur = 0;
          auto it = kv_.find(key);
          if (it != kv_.end() && it->second.size() == 8)
            memcpy(&cur, it->second.data(), 8);
          nv = cur + delta;
          std::string val(8, '\0');
          memcpy(val.data(), &nv, 8);
          kv_[key] = std::move(val);
        }
        return write_full(fd, &nv, 8);
      }
      case OP_DEL: {
        {
          std::lock_guard<std::mutex> g(mu_);
          kv_.erase(key);
        }
        uint8_t ok = 1;
        return write_full(fd, &ok, 1);
      }
      default:
        return false;
    }
  }

  int port_;
  int listen_fd_ = -1;
  std::thread thread_;
  std::atomic<bool> running_{false};
  std::vector<int> conns_;
  std::mutex mu_;
  std::map<std::string, std::string> kv_;
};

class Client {
 public:
  bool connect_to(const char* host, int port, double timeout_s) {
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::duration<double>(timeout_s);
    addrinfo hints{}, *res = nullptr;
    hints.ai_family = AF_INET;
    hints.ai_socktype = SOCK_STREAM;
    char portstr[16];
    snprintf(portstr, sizeof(portstr), "%d", port);
    if (::getaddrinfo(host, portstr, &hints, &res) != 0 || !res) return false;
    // retry until the daemon is up (reference tcp_utils retry loop)
    while (std::chrono::steady_clock::now() < deadline) {
      fd_ = ::socket(res->ai_family, res->ai_socktype, res->ai_protocol);
      if (fd_ >= 0 &&
          ::connect(fd_, res->ai_addr, res->ai_addrlen) == 0) {
        int one = 1;
        ::setsockopt(fd_, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        // honor the Python-level timeout on every socket op, not just
        // connect: a dead daemon must surface as an error, not a hang
        default_timeout_ = timeout_s > 0 ? timeout_s : 30.0;
        set_op_timeout(fd_, default_timeout_);
        ::freeaddrinfo(res);
        return true;
      }
      if (fd_ >= 0) ::close(fd_);
      fd_ = -1;
      std::this_thread::sleep_for(std::chrono::milliseconds(50));
    }
    ::freeaddrinfo(res);
    return false;
  }

  bool set(const char* key, uint32_t klen, const char* val, uint32_t vlen) {
    std::lock_guard<std::mutex> g(mu_);
    uint8_t op = OP_SET;
    if (!write_full(fd_, &op, 1) || !write_full(fd_, &klen, 4) ||
        !write_full(fd_, key, klen) || !write_full(fd_, &vlen, 4) ||
        !write_full(fd_, val, vlen))
      return false;
    uint8_t ok;
    return read_full(fd_, &ok, 1) && ok == 1;
  }

  // polls until the key exists or timeout; *out is malloc'd
  int get(const char* key, uint32_t klen, char** out, uint32_t* out_len,
          double timeout_s) {
    auto deadline = std::chrono::steady_clock::now() +
                    std::chrono::duration<double>(timeout_s);
    // honor the per-call timeout even against a STALLED (not dead) daemon:
    // bound each blocking recv by the call deadline, not the ctor default
    if (timeout_s > 0 && timeout_s < default_timeout_)
      set_op_timeout(fd_, timeout_s);
    struct Restore {
      Client* c;
      ~Restore() { set_op_timeout(c->fd_, c->default_timeout_); }
    } restore{this};
    while (true) {
      {
        std::lock_guard<std::mutex> g(mu_);
        uint8_t op = OP_GET;
        if (!write_full(fd_, &op, 1) || !write_full(fd_, &klen, 4) ||
            !write_full(fd_, key, klen))
          return -1;
        uint8_t found;
        if (!read_full(fd_, &found, 1)) return -1;
        if (found) {
          uint32_t vlen;
          if (!read_full(fd_, &vlen, 4)) return -1;
          char* buf = static_cast<char*>(malloc(vlen ? vlen : 1));
          if (!read_full(fd_, buf, vlen)) {
            free(buf);
            return -1;
          }
          *out = buf;
          *out_len = vlen;
          return 0;
        }
      }
      if (std::chrono::steady_clock::now() >= deadline) return 1;  // timeout
      std::this_thread::sleep_for(std::chrono::milliseconds(10));
    }
  }

  int64_t add(const char* key, uint32_t klen, int64_t delta) {
    std::lock_guard<std::mutex> g(mu_);
    uint8_t op = OP_ADD;
    if (!write_full(fd_, &op, 1) || !write_full(fd_, &klen, 4) ||
        !write_full(fd_, key, klen) || !write_full(fd_, &delta, 8))
      return INT64_MIN;
    int64_t nv;
    if (!read_full(fd_, &nv, 8)) return INT64_MIN;
    return nv;
  }

  bool del(const char* key, uint32_t klen) {
    std::lock_guard<std::mutex> g(mu_);
    uint8_t op = OP_DEL;
    if (!write_full(fd_, &op, 1) || !write_full(fd_, &klen, 4) ||
        !write_full(fd_, key, klen))
      return false;
    uint8_t ok;
    return read_full(fd_, &ok, 1) && ok == 1;
  }

  ~Client() {
    if (fd_ >= 0) ::close(fd_);
  }

 private:
  int fd_ = -1;
  double default_timeout_ = 30.0;
  std::mutex mu_;  // one request in flight per client
};

}  // namespace

extern "C" {

void* pd_store_server_start(int port) {
  auto* s = new Server(port);
  if (!s->start()) {
    delete s;
    return nullptr;
  }
  return s;
}

int pd_store_server_port(void* h) { return static_cast<Server*>(h)->port(); }

void pd_store_server_stop(void* h) { delete static_cast<Server*>(h); }

void* pd_store_client_connect(const char* host, int port, double timeout_s) {
  auto* c = new Client();
  if (!c->connect_to(host, port, timeout_s)) {
    delete c;
    return nullptr;
  }
  return c;
}

int pd_store_client_set(void* h, const char* key, uint32_t klen,
                        const char* val, uint32_t vlen) {
  return static_cast<Client*>(h)->set(key, klen, val, vlen) ? 0 : -1;
}

int pd_store_client_get(void* h, const char* key, uint32_t klen, char** out,
                        uint32_t* out_len, double timeout_s) {
  return static_cast<Client*>(h)->get(key, klen, out, out_len, timeout_s);
}

long long pd_store_client_add(void* h, const char* key, uint32_t klen,
                              long long delta) {
  return static_cast<Client*>(h)->add(key, klen, delta);
}

int pd_store_client_del(void* h, const char* key, uint32_t klen) {
  return static_cast<Client*>(h)->del(key, klen) ? 0 : -1;
}

void pd_store_client_close(void* h) { delete static_cast<Client*>(h); }

void pd_store_free(char* p) { free(p); }

}  // extern "C"

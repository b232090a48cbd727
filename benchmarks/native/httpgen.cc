// httpgen: the benchmark's HTTP/1.1 load generator, open loop or closed.
//
// Started from the program's native/loadgen_http.cc (closed loop, fixed
// mix); this one sends a SCHEDULE. It reads a pool of request templates
// and a list of (due time, template), opens its connections before the
// clock starts, then sends each request when it is due on a free
// keep-alive connection: one request in flight per connection, never
// pipelined. A request due while every connection is busy waits in
// order for the next free one, so at most <connections> are in flight.
// `closed` is the closed loop: the schedule's due times are not read,
// its templates are a SEQUENCE, and every connection sends the next one
// of the sequence as soon as its last answer is whole (after a 403 or a
// HEAD, as soon as it has reconnected from the same address). A
// request's due time is then the time it was sent.
// A request takes the free connection that has idled longest, so every
// connection carries its share in turn, as the keep-alive connections
// of a site's many clients do, and none idles into the server's idle
// timeout (30 s: taken the shortest-idle first, the idle ones are
// closed all at once, a 700 ms stall in PR 26's chip runs).
// Every request is timed from when it was DUE. Nothing here aborts the
// run: a reset, a refused connect, a malformed or missing response is
// an outcome in that request's record.
//
// Usage: httpgen <port> <connections> <templates.bin>
//            <schedule.bin> <records.bin> <window_ns> <drain_ns>
//            [<addresses.bin> [closed]]
//   templates.bin: u32 count, then per template u32 length, u8 is_head,
//                  the request bytes
//   schedule.bin:  per request i64 due_ns (ascending), u32 template
//   records.bin:   per request, in schedule order, a Record (below)
//   addresses.bin: per connection slot a u32, the IPv4 source address
//                  (host byte order) its socket is bound to before it
//                  connects, 0 for the kernel's choice: the server
//                  sees clients across 127.0.0.0/8, and a slot keeps
//                  its address over reconnects
// Prints two JSON lines: when the clock starts (CLOCK_MONOTONIC and
// CLOCK_REALTIME ns, connections open then), and counts at the end
// (with the longest the loop took over one round of events and the
// longest epoll_wait overslept: a generator that was not run shows
// there, and in every request's sent - due).
//
// A request still unsent at window_ns is never sent (outcome 3); the
// generator then waits up to drain_ns for answers still in flight.

#include <arpa/inet.h>
#include <errno.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <signal.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <time.h>
#include <unistd.h>

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <string>
#include <vector>

namespace {

enum Outcome : uint8_t {
  kAnswered = 0,   // a whole response arrived; see status
  kConnLost = 1,   // reset / closed / refused before a whole response
  kNoAnswer = 2,   // sent, nothing whole came back before the drain ended
  kUnsent = 3,     // still waiting for a connection when the window closed
};

#pragma pack(push, 1)
struct Record {
  int64_t due_ns;
  int64_t sent_ns;   // -1: never sent
  int64_t done_ns;   // -1: no whole response
  uint32_t tmpl;
  uint16_t status;
  uint8_t outcome;
  uint8_t fresh;     // 1: the first request on a newly opened connection
  uint32_t conn;     // the connection slot it went out on
};
#pragma pack(pop)

struct Template {
  std::string bytes;
  bool is_head;
};

struct Conn {
  int fd = -1;
  bool connected = false;
  bool busy = false;
  int64_t req = -1;        // schedule index in flight
  size_t out_off = 0;      // bytes of the request already written
  std::string inbuf;
  long long content_left = -1;  // -1: head not parsed yet
  bool close_after = false;
  int64_t retry_at = 0;    // reconnect not before (ns on the run's clock)
  bool used = false;       // a request has gone out on this socket
};

int64_t mono_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
}

int64_t real_ns() {
  timespec ts;
  clock_gettime(CLOCK_REALTIME, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
}

// Close with a reset: the generator's side then leaves no TIME_WAIT
// socket behind, so runs made back to back do not eat the machine's
// ephemeral ports (4096 connections a run, twice with the rehearsal).
void close_now(int fd) {
  linger lg{1, 0};
  setsockopt(fd, SOL_SOCKET, SO_LINGER, &lg, sizeof(lg));
  close(fd);
}

bool read_file(const char* path, std::string* out) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return false;
  char buf[1 << 16];
  size_t n;
  while ((n = std::fread(buf, 1, sizeof(buf), f)) > 0) out->append(buf, n);
  std::fclose(f);
  return true;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 8) {
    std::fprintf(stderr,
                 "usage: %s <port> <connections> <templates.bin> "
                 "<schedule.bin> <records.bin> <window_ns> <drain_ns>\n",
                 argv[0]);
    return 2;
  }
  signal(SIGPIPE, SIG_IGN);
  const uint16_t port = static_cast<uint16_t>(std::atoi(argv[1]));
  const int n_conns = std::atoi(argv[2]);
  const int64_t window_ns = std::atoll(argv[6]);
  const int64_t drain_ns = std::atoll(argv[7]);

  std::string raw;
  if (!read_file(argv[3], &raw) || raw.size() < 4) {
    std::fprintf(stderr, "cannot read templates %s\n", argv[3]);
    return 2;
  }
  std::vector<Template> templates;
  {
    uint32_t count;
    std::memcpy(&count, raw.data(), 4);
    size_t off = 4;
    for (uint32_t i = 0; i < count; ++i) {
      uint32_t len;
      std::memcpy(&len, raw.data() + off, 4);
      bool is_head = raw[off + 4] != 0;
      templates.push_back({raw.substr(off + 5, len), is_head});
      off += 5 + len;
    }
  }
  std::string sched;
  if (!read_file(argv[4], &sched)) {
    std::fprintf(stderr, "cannot read schedule %s\n", argv[4]);
    return 2;
  }
  const size_t n_req = sched.size() / 12;
  std::vector<Record> rec(n_req);
  for (size_t i = 0; i < n_req; ++i) {
    std::memcpy(&rec[i].due_ns, sched.data() + i * 12, 8);
    std::memcpy(&rec[i].tmpl, sched.data() + i * 12 + 8, 4);
    if (rec[i].tmpl >= templates.size()) {
      std::fprintf(stderr, "schedule names template %u of %zu\n",
                   rec[i].tmpl, templates.size());
      return 2;
    }
    rec[i].sent_ns = -1;
    rec[i].done_ns = -1;
    rec[i].status = 0;
    rec[i].outcome = kUnsent;
    rec[i].fresh = 0;
    rec[i].conn = 0;
  }
  std::vector<uint32_t> sources(static_cast<size_t>(n_conns > 0 ? n_conns : 0), 0);
  if (argc > 8) {
    std::string raw_addr;
    if (!read_file(argv[8], &raw_addr) ||
        raw_addr.size() < sources.size() * 4) {
      std::fprintf(stderr, "cannot read %d addresses from %s\n", n_conns,
                   argv[8]);
      return 2;
    }
    std::memcpy(sources.data(), raw_addr.data(), sources.size() * 4);
  }

  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  addr.sin_port = htons(port);

  int ep = epoll_create1(0);
  std::vector<Conn> conns(n_conns);
  std::deque<int> free_conns;   // connected and idle, the longest idle first
  const bool closed = argc > 9 && std::strcmp(argv[9], "closed") == 0;
  long long reconnects = 0, connect_failures = 0;

  auto start_connect = [&](int slot) {
    Conn& c = conns[slot];
    c = Conn();
    c.fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (c.fd < 0) return false;
    int one = 1;
    setsockopt(c.fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    if (sources[slot] != 0) {
      // the port is chosen at connect(), so that one address can hold
      // more connections than it has ephemeral ports to bind
      setsockopt(c.fd, IPPROTO_IP, IP_BIND_ADDRESS_NO_PORT, &one, sizeof(one));
      sockaddr_in from{};
      from.sin_family = AF_INET;
      from.sin_addr.s_addr = htonl(sources[slot]);
      if (bind(c.fd, reinterpret_cast<sockaddr*>(&from), sizeof(from)) != 0) {
        close(c.fd);
        c.fd = -1;
        return false;
      }
    }
    if (connect(c.fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 &&
        errno != EINPROGRESS) {
      close(c.fd);
      c.fd = -1;
      return false;
    }
    epoll_event e{};
    e.events = EPOLLOUT | EPOLLIN;
    e.data.u32 = static_cast<uint32_t>(slot);
    epoll_ctl(ep, EPOLL_CTL_ADD, c.fd, &e);
    return true;
  };

  auto arm = [&](int slot, bool want_out) {
    epoll_event e{};
    e.events = want_out ? (EPOLLIN | EPOLLOUT) : EPOLLIN;
    e.data.u32 = static_cast<uint32_t>(slot);
    epoll_ctl(ep, EPOLL_CTL_MOD, conns[slot].fd, &e);
  };

  // Open every connection before the clock starts (at most 10 s).
  for (int i = 0; i < n_conns; ++i) start_connect(i);
  {
    int64_t give_up = mono_ns() + 10000000000LL;
    size_t open = 0;
    while (open < conns.size() && mono_ns() < give_up) {
      epoll_event events[256];
      int n = epoll_wait(ep, events, 256, 100);
      for (int i = 0; i < n; ++i) {
        int slot = static_cast<int>(events[i].data.u32);
        Conn& c = conns[slot];
        if (c.fd < 0 || c.connected) continue;
        int err = 0;
        socklen_t len = sizeof(err);
        getsockopt(c.fd, SOL_SOCKET, SO_ERROR, &err, &len);
        if ((events[i].events & (EPOLLHUP | EPOLLERR)) || err != 0) {
          epoll_ctl(ep, EPOLL_CTL_DEL, c.fd, nullptr);
          close(c.fd);
          c.fd = -1;
          ++connect_failures;
          continue;
        }
        if (events[i].events & EPOLLOUT) {
          c.connected = true;
          arm(slot, false);
          free_conns.push_back(slot);
          ++open;
        }
      }
      for (int i = 0; i < n_conns; ++i)
        if (conns[i].fd < 0) start_connect(i);
    }
  }
  const size_t open_at_start = free_conns.size();

  const int64_t t0 = mono_ns();
  const int64_t t0_real = real_ns();
  std::printf("{\"t0_mono_ns\": %lld, \"t0_real_ns\": %lld, "
              "\"connections_at_start\": %zu}\n",
              static_cast<long long>(t0), static_cast<long long>(t0_real),
              open_at_start);
  std::fflush(stdout);
  size_t next_due = 0;          // first request not yet handed out
  std::deque<int64_t> waiting;  // due, no free connection yet
  long long in_flight = 0, answered = 0, lost = 0;
  std::vector<int> reconnect_q;  // slots with no socket, to be reopened
  for (int i = 0; i < n_conns; ++i)
    if (conns[i].fd < 0) reconnect_q.push_back(i);

  auto drop_conn = [&](int slot, int64_t now) {
    Conn& c = conns[slot];
    if (c.busy) {
      rec[c.req].outcome = kConnLost;
      ++lost;
      --in_flight;
    }
    if (c.fd >= 0) {
      epoll_ctl(ep, EPOLL_CTL_DEL, c.fd, nullptr);
      close_now(c.fd);
    }
    c = Conn();
    c.retry_at = now;   // the main loop reconnects
    reconnect_q.push_back(slot);
    ++reconnects;
  };

  auto try_write = [&](int slot, int64_t now) {
    Conn& c = conns[slot];
    const std::string& bytes = templates[rec[c.req].tmpl].bytes;
    while (c.out_off < bytes.size()) {
      ssize_t w = send(c.fd, bytes.data() + c.out_off,
                       bytes.size() - c.out_off, MSG_NOSIGNAL);
      if (w > 0) {
        c.out_off += static_cast<size_t>(w);
      } else if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        arm(slot, true);
        return;
      } else {
        drop_conn(slot, now);
        return;
      }
    }
    arm(slot, false);
  };

  auto assign = [&](int slot, int64_t req, int64_t now) {
    Conn& c = conns[slot];
    c.busy = true;
    c.req = req;
    c.out_off = 0;
    c.inbuf.clear();
    c.content_left = -1;
    c.close_after = false;
    rec[req].sent_ns = now;
    if (closed) rec[req].due_ns = now;
    rec[req].conn = static_cast<uint32_t>(slot);
    rec[req].fresh = c.used ? 0 : 1;
    c.used = true;
    rec[req].outcome = kNoAnswer;
    ++in_flight;
    try_write(slot, now);
  };

  int64_t round_max_ns = 0, overslept_max_ns = 0, woke = 0;
  while (true) {
    int64_t now = mono_ns() - t0;
    if (woke && now - woke > round_max_ns) round_max_ns = now - woke;
    bool window_open = now < window_ns;
    if (!window_open && (in_flight == 0 || now >= window_ns + drain_ns)) break;

    if (window_open) {
      while (next_due < n_req && (closed || rec[next_due].due_ns <= now))
        waiting.push_back(static_cast<int64_t>(next_due++));
      while (!waiting.empty() && !free_conns.empty()) {
        int slot = free_conns.front();
        free_conns.pop_front();
        Conn& c = conns[slot];
        if (c.fd < 0 || !c.connected || c.busy) continue;  // stale entry
        int64_t req = waiting.front();
        waiting.pop_front();
        assign(slot, req, mono_ns() - t0);
      }
    }
    if (!reconnect_q.empty()) {
      std::vector<int> later;
      for (int slot : reconnect_q) {
        if (conns[slot].fd >= 0) continue;
        if (conns[slot].retry_at > now) {
          later.push_back(slot);
        } else if (!start_connect(slot)) {
          ++connect_failures;
          conns[slot].retry_at = now + 100000000LL;
          later.push_back(slot);
        }
      }
      reconnect_q.swap(later);
    }

    int timeout_ms = 50;
    if (window_open) {
      int64_t until = window_ns - now;
      if (next_due < n_req && rec[next_due].due_ns - now < until)
        until = rec[next_due].due_ns - now;
      timeout_ms = until <= 1000000 ? 0 : static_cast<int>(until / 1000000);
      if (timeout_ms > 50) timeout_ms = 50;
    }
    epoll_event events[512];
    const int64_t slept_from = mono_ns() - t0;
    int n = epoll_wait(ep, events, 512, timeout_ms);
    now = mono_ns() - t0;
    woke = now;
    if (n == 0 && now - slept_from - timeout_ms * 1000000LL > overslept_max_ns)
      overslept_max_ns = now - slept_from - timeout_ms * 1000000LL;
    for (int i = 0; i < n; ++i) {
      int slot = static_cast<int>(events[i].data.u32);
      Conn& c = conns[slot];
      if (c.fd < 0) continue;
      if (!c.connected) {
        int err = 0;
        socklen_t len = sizeof(err);
        getsockopt(c.fd, SOL_SOCKET, SO_ERROR, &err, &len);
        if ((events[i].events & (EPOLLHUP | EPOLLERR)) || err != 0) {
          epoll_ctl(ep, EPOLL_CTL_DEL, c.fd, nullptr);
          close(c.fd);
          c = Conn();
          c.retry_at = now + 100000000LL;
          reconnect_q.push_back(slot);
          ++connect_failures;
        } else if (events[i].events & EPOLLOUT) {
          c.connected = true;
          arm(slot, false);
          free_conns.push_back(slot);
        }
        continue;
      }
      if (events[i].events & (EPOLLHUP | EPOLLERR)) {
        drop_conn(slot, now);
        continue;
      }
      if ((events[i].events & EPOLLOUT) && c.busy) {
        try_write(slot, now);
        if (c.fd < 0) continue;
      }
      if (!(events[i].events & EPOLLIN)) continue;
      char buf[16384];
      ssize_t r;
      bool eof = false;
      while ((r = read(c.fd, buf, sizeof(buf))) > 0)
        c.inbuf.append(buf, static_cast<size_t>(r));
      if (r == 0) eof = true;
      if (r < 0 && errno != EAGAIN && errno != EWOULDBLOCK) eof = true;
      bool done = false;
      if (c.busy && c.content_left == -1) {
        size_t he = c.inbuf.find("\r\n\r\n");
        if (he != std::string::npos) {
          std::string head = c.inbuf.substr(0, he + 4);
          c.inbuf.erase(0, he + 4);
          for (char& ch : head)
            if (ch >= 'A' && ch <= 'Z') ch = static_cast<char>(ch + 32);
          int status = head.size() > 12 ? std::atoi(head.c_str() + 9) : 0;
          rec[c.req].status = static_cast<uint16_t>(status);
          c.content_left = 0;
          size_t p = head.find("content-length:");
          if (p != std::string::npos)
            c.content_left = std::atoll(head.c_str() + p + 15);
          c.close_after = head.find("connection: close") != std::string::npos;
          if (templates[rec[c.req].tmpl].is_head) {
            // no body follows a HEAD's response; the connection is not
            // reused, so whatever the server does next cannot desync it
            c.content_left = 0;
            c.close_after = true;
          }
          if (status == 0) {  // not HTTP: the request is lost
            drop_conn(slot, now);
            continue;
          }
        }
      }
      if (c.busy && c.content_left >= 0) {
        long long take = c.content_left;
        if (take > static_cast<long long>(c.inbuf.size()))
          take = static_cast<long long>(c.inbuf.size());
        c.inbuf.erase(0, static_cast<size_t>(take));
        c.content_left -= take;
        if (c.content_left == 0) done = true;
      }
      if (done) {
        rec[c.req].done_ns = mono_ns() - t0;
        rec[c.req].outcome = kAnswered;
        ++answered;
        --in_flight;
        c.busy = false;
        c.req = -1;
        if (c.close_after || eof) {
          drop_conn(slot, now);
        } else {
          free_conns.push_back(slot);
        }
        continue;
      }
      if (eof) drop_conn(slot, now);
    }
  }
  const int64_t t_end = mono_ns() - t0;

  for (Conn& c : conns)
    if (c.fd >= 0) close_now(c.fd);
  FILE* out = std::fopen(argv[5], "wb");
  if (out) {
    std::fwrite(rec.data(), sizeof(Record), rec.size(), out);
    std::fclose(out);
  } else {
    std::fprintf(stderr, "cannot write %s\n", argv[5]);
  }
  std::printf(
      "{\"t0_mono_ns\": %lld, \"t0_real_ns\": %lld, \"elapsed_ns\": %lld, "
      "\"connections_at_start\": %zu, \"scheduled\": %zu, \"answered\": %lld, "
      "\"lost\": %lld, \"reconnects\": %lld, \"connect_failures\": %lld, "
      "\"round_max_ns\": %lld, \"overslept_max_ns\": %lld, "
      "\"records_written\": %s}\n",
      static_cast<long long>(t0), static_cast<long long>(t0_real),
      static_cast<long long>(t_end), open_at_start, n_req, answered, lost,
      reconnects, connect_failures, static_cast<long long>(round_max_ns),
      static_cast<long long>(overslept_max_ns), out ? "true" : "false");
  return 0;
}

// tpurx native KV store server.
//
// Drop-in replacement for the Python asyncio server
// (tpu_resiliency/store/server.py) speaking the same wire protocol
// (tpu_resiliency/store/protocol.py):
//
//   request:  u8 opcode | u32 nargs | { u32 len | bytes }*
//   response: u8 status | u32 nargs | { u32 len | bytes }*
//
// Architecture: single-threaded epoll event loop — every mutation is atomic
// with respect to every other request (the same serializability argument the
// asyncio server makes), no locks, no GIL.  Blocking ops (GET/WAIT) park a
// waiter on the key; SET-like ops notify waiters; expiry runs off a deadline
// heap driving the epoll timeout.
//
// Reference analog: the role torch's C++ TCPStore daemon plays under NVRx's
// control plane (rendezvous CAS/counters, barriers, heartbeats) — the hot
// spot where Python-loop latency costs pod-scale restart time.
//
// Build: g++ -O2 -std=c++17 -o tpurx-store-server store_server.cpp

#include <arpa/inet.h>
#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <signal.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <strings.h>
#include <sys/epoll.h>
#include <sys/file.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <deque>
#include <queue>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>

namespace {

// BEGIN GENERATED OP TABLE (source: tpu_resiliency/store/protocol.py;
// regenerate: python -m tpu_resiliency.store.protocol --cpp)
enum Op : uint8_t {
  OP_SET = 1,
  OP_GET = 2,
  OP_TRY_GET = 3,
  OP_ADD = 4,
  OP_APPEND = 5,
  OP_COMPARE_SET = 6,
  OP_WAIT = 7,
  OP_CHECK = 8,
  OP_DELETE = 9,
  OP_NUM_KEYS = 10,
  OP_PING = 11,
  OP_LIST_KEYS = 12,
  OP_MULTI_SET = 13,
  OP_MULTI_GET = 14,
  OP_MULTI_TRY_GET = 15,
  OP_APPEND_CHECK = 16,
  OP_ADD_SET = 17,
  OP_WAIT_GE = 18,
  OP__LAST = 18,
};
// END GENERATED OP TABLE

// protocol.ADD_SLOT: spliced into ADD_SET's set_value (first occurrence)
constexpr char kAddSlot[] = "%TPURX_N%";

enum Status : uint8_t {
  ST_OK = 0, ST_KEY_MISS = 1, ST_TIMEOUT = 2, ST_ERROR = 3, ST_CAS_FAIL = 4,
};

using Clock = std::chrono::steady_clock;
using Ms = std::chrono::milliseconds;

struct Conn;

struct Waiter {
  Conn* conn;                       // null once cancelled
  std::vector<std::string> keys;    // keys still missing
  Clock::time_point deadline;
  uint8_t op;                       // OP_GET, OP_WAIT, or OP_WAIT_GE
  std::string get_key;              // for OP_GET / OP_WAIT_GE
  long long threshold = 0;          // for OP_WAIT_GE
  uint64_t id;
};

struct Conn {
  int fd = -1;
  std::string in;                   // read buffer
  std::string out;                  // pending writes
  std::unordered_set<uint64_t> waiting_ids;
  bool closed = false;
};

struct Store {
  std::unordered_map<std::string, std::string> data;
  // key -> waiter ids parked on it
  std::unordered_map<std::string, std::vector<uint64_t>> key_waiters;
  std::unordered_map<uint64_t, Waiter> waiters;
  std::priority_queue<
      std::pair<Clock::time_point, uint64_t>,
      std::vector<std::pair<Clock::time_point, uint64_t>>,
      std::greater<>>
      deadlines;
  uint64_t next_waiter_id = 1;
};

Store g_store;
int g_epfd = -1;
// TPURX_STORE_TEST_BROWNOUT: accept connections and read requests but never
// answer — the fault class where a shard looks alive at the TCP layer while
// its serving loop is wedged.  Clients must escape via per-op deadlines.
bool g_brownout = false;

// ---- journal ---------------------------------------------------------------
// Same on-disk format as the Python server (store/server.py: final-state
// records, replay order reconstructs the map), so a control plane can switch
// between the asyncio and native servers over one journal file:
//   'S' u32(klen) key u32(vlen) value     -- key set to value
//   'D' u32(klen) key                     -- key deleted
// Appends are fwrite+fflush per mutation; fsync runs on a 1s cadence driven
// by the epoll loop (matching the Python server's fsync interval).
// Compaction rewrites the journal as a snapshot of live data when appends
// exceed the cap, re-arming at max(cap, 2x snapshot) so a snapshot larger
// than the cap doesn't trigger an O(state) rewrite per mutation.  The
// snapshot write is inline (single-threaded loop): unlike the Python
// server's executor offload this briefly parks traffic, but the native
// write path makes the pause milliseconds at control-plane state sizes.

struct Journal {
  FILE* f = nullptr;
  std::string path;
  int lock_fd = -1;
  size_t bytes = 0;
  size_t max_bytes = 64ull << 20;
  size_t compact_at = 64ull << 20;
  bool dirty = false;
  Clock::time_point last_sync = Clock::now();
  size_t replayed = 0;
};
Journal g_journal;

void append_u32_j(std::string* s, uint32_t v) {
  char b[4];
  memcpy(b, &v, 4);
  s->append(b, 4);
}

std::string journal_record(const std::string& key, const std::string* value) {
  std::string rec;
  rec.push_back(value ? 'S' : 'D');
  append_u32_j(&rec, static_cast<uint32_t>(key.size()));
  rec.append(key);
  if (value) {
    append_u32_j(&rec, static_cast<uint32_t>(value->size()));
    rec.append(*value);
  }
  return rec;
}

void journal_disable() {
  if (g_journal.f) {
    fclose(g_journal.f);
    g_journal.f = nullptr;
    fprintf(stderr, "journal write failed; journal disabled\n");
  }
}

size_t journal_replay(const std::string& buf) {
  size_t i = 0, n = buf.size(), good = 0;
  while (i < n) {
    char tag = buf[i];
    if (tag == 'S') {
      if (i + 5 > n) break;
      uint32_t kl;
      memcpy(&kl, buf.data() + i + 1, 4);
      if (i + 5 + kl + 4 > n) break;
      std::string key = buf.substr(i + 5, kl);
      uint32_t vl;
      memcpy(&vl, buf.data() + i + 5 + kl, 4);
      size_t end = i + 9 + kl + vl;
      if (end > n) break;
      g_store.data[key] = buf.substr(i + 9 + kl, vl);
      i = end;
    } else if (tag == 'D') {
      if (i + 5 > n) break;
      uint32_t kl;
      memcpy(&kl, buf.data() + i + 1, 4);
      size_t end = i + 5 + kl;
      if (end > n) break;
      g_store.data.erase(buf.substr(i + 5, kl));
      i = end;
    } else {
      break;
    }
    good = i;
  }
  return good;
}

void journal_append(const std::string& key, const std::string* value);

bool journal_open(const std::string& path,
                  const std::vector<std::string>& strip_prefixes) {
  // exclusive sidecar lockfile: two servers interleaving appends on one
  // journal would corrupt exactly the state it exists to preserve; the
  // sidecar (not the journal fd) stays valid across compaction's rename
  std::string lock_path = path + ".lock";
  g_journal.lock_fd = open(lock_path.c_str(), O_CREAT | O_RDWR, 0644);
  if (g_journal.lock_fd < 0 || flock(g_journal.lock_fd, LOCK_EX | LOCK_NB) != 0) {
    fprintf(stderr, "journal %s is locked by another store instance\n",
            path.c_str());
    return false;
  }
  std::string buf;
  FILE* rf = fopen(path.c_str(), "rb");
  if (rf) {
    char chunk[1 << 16];
    size_t got;
    while ((got = fread(chunk, 1, sizeof(chunk), rf)) > 0) buf.append(chunk, got);
    fclose(rf);
  }
  size_t good = journal_replay(buf);
  if (good < buf.size())
    fprintf(stderr,
            "journal %s: truncated tail at byte %zu of %zu; discarding\n",
            path.c_str(), good, buf.size());
  g_journal.replayed = g_store.data.size();
  g_journal.path = path;
  g_journal.f = fopen(path.c_str(), good < buf.size() ? "rb+" : "ab");
  if (!g_journal.f) {
    fprintf(stderr, "journal %s: cannot open for append\n", path.c_str());
    return false;
  }
  if (good < buf.size()) {
    if (ftruncate(fileno(g_journal.f), static_cast<off_t>(good)) != 0)
      fprintf(stderr, "journal %s: truncate failed\n", path.c_str());
    fseek(g_journal.f, 0, SEEK_END);
  }
  g_journal.bytes = good;
  g_journal.compact_at = g_journal.max_bytes;
  // job-terminal keys must not replay into the next job
  for (const auto& prefix : strip_prefixes) {
    std::vector<std::string> doomed;
    for (const auto& [k, _] : g_store.data)
      if (k.rfind(prefix, 0) == 0) doomed.push_back(k);
    for (const auto& k : doomed) {
      g_store.data.erase(k);
      journal_append(k, nullptr);
      if (g_journal.replayed) g_journal.replayed--;
    }
  }
  if (g_journal.replayed)
    fprintf(stderr, "journal restored %zu key(s)\n", g_journal.replayed);
  return true;
}

void journal_compact() {
  std::string tmp = g_journal.path + ".tmp";
  FILE* tf = fopen(tmp.c_str(), "wb");
  if (!tf) return journal_disable();
  size_t snapshot_bytes = 0;
  for (const auto& [k, v] : g_store.data) {
    std::string rec = journal_record(k, &v);
    if (fwrite(rec.data(), 1, rec.size(), tf) != rec.size()) {
      fclose(tf);
      unlink(tmp.c_str());
      return journal_disable();
    }
    snapshot_bytes += rec.size();
  }
  fflush(tf);
  fsync(fileno(tf));
  fclose(tf);
  fclose(g_journal.f);
  g_journal.f = nullptr;
  if (rename(tmp.c_str(), g_journal.path.c_str()) != 0) {
    unlink(tmp.c_str());
    return journal_disable();
  }
  g_journal.f = fopen(g_journal.path.c_str(), "ab");
  if (!g_journal.f) return journal_disable();
  g_journal.bytes = snapshot_bytes;
  g_journal.compact_at = std::max(g_journal.max_bytes, 2 * snapshot_bytes);
  g_journal.dirty = false;
  fprintf(stderr, "journal compacted to %zu bytes (%zu keys)\n",
          snapshot_bytes, g_store.data.size());
}

void journal_append(const std::string& key, const std::string* value) {
  if (!g_journal.f) return;
  std::string rec = journal_record(key, value);
  if (fwrite(rec.data(), 1, rec.size(), g_journal.f) != rec.size() ||
      fflush(g_journal.f) != 0)
    return journal_disable();
  g_journal.bytes += rec.size();
  g_journal.dirty = true;
  if (g_journal.bytes > g_journal.compact_at) journal_compact();
}

void journal_maybe_fsync() {
  if (!g_journal.f || !g_journal.dirty) return;
  auto now = Clock::now();
  if (now - g_journal.last_sync < Ms(1000)) return;
  if (fsync(fileno(g_journal.f)) != 0) return journal_disable();
  g_journal.dirty = false;
  g_journal.last_sync = now;
}

void append_u32(std::string* s, uint32_t v) {
  char b[4];
  memcpy(b, &v, 4);  // little-endian hosts only (x86/arm64 LE)
  s->append(b, 4);
}

void encode_response(std::string* out, uint8_t status,
                     const std::vector<std::string>& args) {
  out->push_back(static_cast<char>(status));
  append_u32(out, static_cast<uint32_t>(args.size()));
  for (const auto& a : args) {
    append_u32(out, static_cast<uint32_t>(a.size()));
    out->append(a);
  }
}

void arm_write(Conn* c) {
  epoll_event ev{};
  ev.events = EPOLLIN | (c->out.empty() ? 0 : EPOLLOUT);
  ev.data.ptr = c;
  epoll_ctl(g_epfd, EPOLL_CTL_MOD, c->fd, &ev);
}

void reply(Conn* c, uint8_t status, const std::vector<std::string>& args) {
  if (g_brownout) return;  // test mode: read everything, answer nothing
  encode_response(&c->out, status, args);
  arm_write(c);
}

void notify_key(const std::string& key);

void journal_append(const std::string& key, const std::string* value);

void do_set(const std::string& key, const std::string& value) {
  g_store.data[key] = value;
  journal_append(key, &value);
  notify_key(key);
}

// ---- waiters ---------------------------------------------------------------

bool parse_int(const std::string& s, long long* out);

long long int_value_of(const std::string& key) {
  // WAIT_GE semantics: a missing or non-integer key counts as 0
  long long cur = 0;
  auto it = g_store.data.find(key);
  if (it != g_store.data.end()) parse_int(it->second, &cur);
  return cur;
}

void complete_waiter(uint64_t id, bool timed_out) {
  auto it = g_store.waiters.find(id);
  if (it == g_store.waiters.end()) return;
  Waiter w = std::move(it->second);
  g_store.waiters.erase(it);
  // drop this waiter's id from any key list it is still parked on: sliced
  // clients re-park every ~2s, and on never-set keys the stale ids would
  // otherwise accumulate until the key is finally SET (or forever)
  for (const auto& k : w.keys) {
    auto kit = g_store.key_waiters.find(k);
    if (kit == g_store.key_waiters.end()) continue;
    auto& vec = kit->second;
    vec.erase(std::remove(vec.begin(), vec.end(), id), vec.end());
    if (vec.empty()) g_store.key_waiters.erase(kit);
  }
  if (!w.conn || w.conn->closed) return;
  w.conn->waiting_ids.erase(id);
  if (timed_out) {
    reply(w.conn, ST_TIMEOUT, {});
  } else if (w.op == OP_GET) {
    auto d = g_store.data.find(w.get_key);
    if (d == g_store.data.end())
      reply(w.conn, ST_ERROR, {"key vanished"});
    else
      reply(w.conn, ST_OK, {d->second});
  } else if (w.op == OP_WAIT_GE) {
    reply(w.conn, ST_OK, {std::to_string(int_value_of(w.get_key))});
  } else {
    reply(w.conn, ST_OK, {});
  }
}

void notify_key(const std::string& key) {
  auto kit = g_store.key_waiters.find(key);
  if (kit == g_store.key_waiters.end()) return;
  std::vector<uint64_t> ids = std::move(kit->second);
  g_store.key_waiters.erase(kit);
  for (uint64_t id : ids) {
    auto wit = g_store.waiters.find(id);
    if (wit == g_store.waiters.end()) continue;
    Waiter& w = wit->second;
    if (w.op == OP_WAIT_GE) {
      // threshold waiter: the key existing is not enough — the value must
      // have reached the threshold, else re-park for the next bump
      if (int_value_of(w.get_key) >= w.threshold)
        complete_waiter(id, /*timed_out=*/false);
      else
        g_store.key_waiters[w.get_key].push_back(id);
      continue;
    }
    // drop this key; if all satisfied, complete
    auto& ks = w.keys;
    for (size_t i = 0; i < ks.size();) {
      if (g_store.data.count(ks[i]))
        ks.erase(ks.begin() + i);
      else
        ++i;
    }
    if (ks.empty()) complete_waiter(id, /*timed_out=*/false);
    else {
      // re-park on a remaining missing key
      g_store.key_waiters[ks.front()].push_back(id);
    }
  }
}

void park_waiter(Conn* c, uint8_t op, std::vector<std::string> missing,
                 const std::string& get_key, int64_t timeout_ms,
                 long long threshold = 0) {
  uint64_t id = g_store.next_waiter_id++;
  Waiter w;
  w.conn = c;
  w.keys = std::move(missing);
  w.deadline = Clock::now() + Ms(timeout_ms);
  w.op = op;
  w.get_key = get_key;
  w.threshold = threshold;
  w.id = id;
  g_store.key_waiters[w.keys.front()].push_back(id);
  g_store.deadlines.emplace(w.deadline, id);
  c->waiting_ids.insert(id);
  g_store.waiters.emplace(id, std::move(w));
}

int next_timeout_ms() {
  while (!g_store.deadlines.empty()) {
    auto [dl, id] = g_store.deadlines.top();
    if (!g_store.waiters.count(id)) {
      g_store.deadlines.pop();
      continue;
    }
    auto now = Clock::now();
    if (dl <= now) return 0;
    return static_cast<int>(
        std::chrono::duration_cast<Ms>(dl - now).count() + 1);
  }
  return 1000;
}

void expire_waiters() {
  auto now = Clock::now();
  while (!g_store.deadlines.empty()) {
    auto [dl, id] = g_store.deadlines.top();
    if (dl > now) break;
    g_store.deadlines.pop();
    if (g_store.waiters.count(id)) complete_waiter(id, /*timed_out=*/true);
  }
}

// ---- request handling ------------------------------------------------------

bool parse_int(const std::string& s, long long* out) {
  if (s.empty()) return false;
  errno = 0;
  char* end = nullptr;
  long long v = strtoll(s.c_str(), &end, 10);
  if (errno || end != s.c_str() + s.size()) return false;
  *out = v;
  return true;
}

void handle_request(Conn* c, uint8_t op, std::vector<std::string> args) {
  auto& data = g_store.data;
  switch (op) {
    case OP_SET: {
      if (args.size() != 2) return reply(c, ST_ERROR, {"SET wants 2 args"});
      do_set(args[0], args[1]);
      return reply(c, ST_OK, {});
    }
    case OP_TRY_GET: {
      if (args.size() != 1) return reply(c, ST_ERROR, {"TRY_GET wants 1 arg"});
      auto it = data.find(args[0]);
      if (it == data.end()) return reply(c, ST_KEY_MISS, {});
      return reply(c, ST_OK, {it->second});
    }
    case OP_GET: {
      long long timeout_ms;
      if (args.size() != 2 || !parse_int(args[1], &timeout_ms))
        return reply(c, ST_ERROR, {"GET wants key,timeout_ms"});
      auto it = data.find(args[0]);
      if (it != data.end()) return reply(c, ST_OK, {it->second});
      park_waiter(c, OP_GET, {args[0]}, args[0], timeout_ms);
      return;
    }
    case OP_ADD: {
      long long amount, cur = 0;
      if (args.size() != 2 || !parse_int(args[1], &amount))
        return reply(c, ST_ERROR, {"ADD wants key,amount"});
      auto it = data.find(args[0]);
      if (it != data.end() && !parse_int(it->second, &cur))
        return reply(c, ST_ERROR, {"value not an integer"});
      long long nv = cur + amount;
      do_set(args[0], std::to_string(nv));
      return reply(c, ST_OK, {std::to_string(nv)});
    }
    case OP_APPEND: {
      if (args.size() != 2) return reply(c, ST_ERROR, {"APPEND wants 2 args"});
      std::string& v = data[args[0]];
      v.append(args[1]);
      journal_append(args[0], &v);  // final-state record
      std::string nlen = std::to_string(v.size());
      notify_key(args[0]);
      return reply(c, ST_OK, {nlen});
    }
    case OP_COMPARE_SET: {
      if (args.size() != 3) return reply(c, ST_ERROR, {"CAS wants 3 args"});
      auto it = data.find(args[0]);
      bool absent_ok = (it == data.end() && args[1].empty());
      if (absent_ok || (it != data.end() && it->second == args[1])) {
        do_set(args[0], args[2]);
        return reply(c, ST_OK, {args[2]});
      }
      return reply(c, ST_CAS_FAIL, {it == data.end() ? "" : it->second});
    }
    case OP_WAIT: {
      long long timeout_ms;
      if (args.empty() || !parse_int(args[0], &timeout_ms))
        return reply(c, ST_ERROR, {"WAIT wants timeout_ms,keys..."});
      std::vector<std::string> missing;
      for (size_t i = 1; i < args.size(); ++i)
        if (!data.count(args[i])) missing.push_back(args[i]);
      if (missing.empty()) return reply(c, ST_OK, {});
      park_waiter(c, OP_WAIT, std::move(missing), "", timeout_ms);
      return;
    }
    case OP_CHECK: {
      for (const auto& k : args)
        if (!data.count(k)) return reply(c, ST_OK, {"0"});
      return reply(c, ST_OK, {"1"});
    }
    case OP_DELETE: {
      if (args.size() != 1) return reply(c, ST_ERROR, {"DELETE wants 1 arg"});
      bool existed = data.erase(args[0]) > 0;
      if (existed) journal_append(args[0], nullptr);
      return reply(c, ST_OK, {existed ? "1" : "0"});
    }
    case OP_NUM_KEYS:
      return reply(c, ST_OK, {std::to_string(data.size())});
    case OP_PING:
      return reply(c, ST_OK, {"pong"});
    case OP_LIST_KEYS: {
      std::string prefix = args.empty() ? "" : args[0];
      std::vector<std::string> keys;
      for (const auto& [k, _] : data)
        if (k.rfind(prefix, 0) == 0) keys.push_back(k);
      return reply(c, ST_OK, keys);
    }
    case OP_MULTI_SET: {
      if (args.size() % 2) return reply(c, ST_ERROR, {"MULTI_SET wants pairs"});
      for (size_t i = 0; i + 1 < args.size(); i += 2) do_set(args[i], args[i + 1]);
      return reply(c, ST_OK, {});
    }
    case OP_MULTI_GET: {
      std::vector<std::string> vals;
      for (const auto& k : args) {
        auto it = data.find(k);
        if (it == data.end()) return reply(c, ST_KEY_MISS, {k});
        vals.push_back(it->second);
      }
      return reply(c, ST_OK, vals);
    }
    case OP_MULTI_TRY_GET: {
      // per-key misses: (flag, value) pairs, flag "0" + empty when absent
      std::vector<std::string> pairs;
      pairs.reserve(args.size() * 2);
      for (const auto& k : args) {
        auto it = data.find(k);
        if (it == data.end()) {
          pairs.push_back("0");
          pairs.push_back("");
        } else {
          pairs.push_back("1");
          pairs.push_back(it->second);
        }
      }
      return reply(c, ST_OK, pairs);
    }
    case OP_APPEND_CHECK: {
      // one-RTT barrier arrival: append + population check + done-key set
      // as one atomic step (see store/server.py for the reference semantics)
      if (args.size() < 5)
        return reply(c, ST_ERROR, {"APPEND_CHECK wants >=5 args"});
      long long required;
      if (!parse_int(args[4], &required))
        return reply(c, ST_ERROR, {"required not an integer"});
      std::string& v = data[args[0]];
      v.append(args[1]);
      journal_append(args[0], &v);
      size_t new_len = v.size();
      std::unordered_set<std::string> seen;
      size_t start = 0;
      while (start < v.size()) {
        size_t comma = v.find(',', start);
        if (comma == std::string::npos) comma = v.size();
        if (comma > start) seen.insert(v.substr(start, comma - start));
        start = comma + 1;
      }
      bool done;
      if (args.size() > 5) {  // narrowed participant set: exact membership
        done = true;
        for (size_t i = 5; i < args.size(); ++i)
          if (!seen.count(args[i])) {
            done = false;
            break;
          }
      } else {  // full population: distinct tokens (dedup re-entries)
        done = static_cast<long long>(seen.size()) >= required;
      }
      notify_key(args[0]);
      // do_set may rehash `data` — the reference v is dead past this point
      if (done) do_set(args[2], args[3]);
      return reply(c, ST_OK, {std::to_string(new_len), done ? "1" : "0"});
    }
    case OP_ADD_SET: {
      // one-RTT rendezvous join: counter bump + record write, splicing the
      // post-add value into the record at the first kAddSlot marker
      if (args.size() != 4)
        return reply(c, ST_ERROR, {"ADD_SET wants 4 args"});
      long long amount, cur = 0;
      if (!parse_int(args[1], &amount))
        return reply(c, ST_ERROR, {"ADD_SET amount not an integer"});
      auto it = data.find(args[0]);
      if (it != data.end() && !parse_int(it->second, &cur))
        return reply(c, ST_ERROR, {"value not an integer"});
      long long nv = cur + amount;
      do_set(args[0], std::to_string(nv));
      std::string sv = args[3];
      size_t slot = sv.find(kAddSlot);
      if (slot != std::string::npos)
        sv.replace(slot, sizeof(kAddSlot) - 1, std::to_string(nv));
      do_set(args[2], sv);
      return reply(c, ST_OK, {std::to_string(nv)});
    }
    case OP_WAIT_GE: {
      long long threshold, timeout_ms;
      if (args.size() != 3 || !parse_int(args[1], &threshold) ||
          !parse_int(args[2], &timeout_ms))
        return reply(c, ST_ERROR, {"WAIT_GE wants key,threshold,timeout_ms"});
      long long cur = int_value_of(args[0]);
      if (cur >= threshold) return reply(c, ST_OK, {std::to_string(cur)});
      park_waiter(c, OP_WAIT_GE, {args[0]}, args[0], timeout_ms, threshold);
      return;
    }
    default:
      return reply(c, ST_ERROR, {"unknown op"});
  }
}

// Try to parse one complete frame from c->in; returns false if incomplete.
bool try_parse_frame(Conn* c) {
  const std::string& b = c->in;
  if (b.size() < 5) return false;
  uint8_t op = static_cast<uint8_t>(b[0]);
  uint32_t nargs;
  memcpy(&nargs, b.data() + 1, 4);
  if (nargs > 1u << 20) {  // sanity cap
    c->closed = true;
    return false;
  }
  size_t off = 5;
  std::vector<std::string> args;
  args.reserve(nargs);
  for (uint32_t i = 0; i < nargs; ++i) {
    if (b.size() < off + 4) return false;
    uint32_t len;
    memcpy(&len, b.data() + off, 4);
    if (len > 1u << 30) {
      c->closed = true;
      return false;
    }
    off += 4;
    if (b.size() < off + len) return false;
    args.emplace_back(b.data() + off, len);
    off += len;
  }
  c->in.erase(0, off);
  if (op < OP_SET || op > OP__LAST) {
    // a well-framed request with an opcode this server does not serve (a
    // retired one, a newer client's): refused, connection kept, as the
    // Python server does.  Garbage trips the caps above.
    reply(c, ST_ERROR, {"unknown op"});
    return true;
  }
  handle_request(c, op, std::move(args));
  return true;
}

void close_conn(Conn* c) {
  for (uint64_t id : c->waiting_ids) {
    auto it = g_store.waiters.find(id);
    if (it != g_store.waiters.end()) it->second.conn = nullptr;
  }
  epoll_ctl(g_epfd, EPOLL_CTL_DEL, c->fd, nullptr);
  close(c->fd);
  delete c;
}

}  // namespace

int main(int argc, char** argv) {
  const char* host = "0.0.0.0";
  int port = 29500;
  const char* journal_path = nullptr;
  std::vector<std::string> strip_prefixes;
  for (int i = 1; i < argc - 1; ++i) {
    if (!strcmp(argv[i], "--host")) host = argv[++i];
    else if (!strcmp(argv[i], "--port")) port = atoi(argv[++i]);
    else if (!strcmp(argv[i], "--journal")) journal_path = argv[++i];
    else if (!strcmp(argv[i], "--journal-max-bytes"))
      g_journal.max_bytes = strtoull(argv[++i], nullptr, 10);
    else if (!strcmp(argv[i], "--strip-prefix"))
      strip_prefixes.push_back(argv[++i]);
  }
  signal(SIGPIPE, SIG_IGN);
  const char* bo = getenv("TPURX_STORE_TEST_BROWNOUT");
  if (bo && *bo && strcmp(bo, "0") != 0 && strcasecmp(bo, "false") != 0) {
    g_brownout = true;
    fprintf(stderr, "TEST MODE: brownout — accepting but never replying\n");
  }
  if (journal_path && !journal_open(journal_path, strip_prefixes)) return 1;

  int lfd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
  int one = 1;
  setsockopt(lfd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  inet_pton(AF_INET, host, &addr.sin_addr);
  if (bind(lfd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0) {
    perror("bind");
    return 1;
  }
  if (listen(lfd, 1024) != 0) {
    perror("listen");
    return 1;
  }
  socklen_t alen = sizeof(addr);
  getsockname(lfd, reinterpret_cast<sockaddr*>(&addr), &alen);
  fprintf(stderr, "tpurx-store-server (native) listening on %s:%d\n", host,
          ntohs(addr.sin_port));
  fflush(stderr);

  g_epfd = epoll_create1(0);
  epoll_event lev{};
  lev.events = EPOLLIN;
  lev.data.ptr = nullptr;  // marks the listener
  epoll_ctl(g_epfd, EPOLL_CTL_ADD, lfd, &lev);

  std::vector<epoll_event> events(256);
  while (true) {
    int tmo = next_timeout_ms();
    if (g_journal.dirty) tmo = std::min(tmo, 250);
    int n = epoll_wait(g_epfd, events.data(), static_cast<int>(events.size()),
                       tmo);
    expire_waiters();
    journal_maybe_fsync();
    for (int i = 0; i < n; ++i) {
      if (events[i].data.ptr == nullptr) {
        while (true) {
          int cfd = accept4(lfd, nullptr, nullptr, SOCK_NONBLOCK);
          if (cfd < 0) break;
          setsockopt(cfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
          Conn* c = new Conn();
          c->fd = cfd;
          epoll_event ev{};
          ev.events = EPOLLIN;
          ev.data.ptr = c;
          epoll_ctl(g_epfd, EPOLL_CTL_ADD, cfd, &ev);
        }
        continue;
      }
      Conn* c = static_cast<Conn*>(events[i].data.ptr);
      if (events[i].events & (EPOLLHUP | EPOLLERR)) {
        close_conn(c);
        continue;
      }
      if (events[i].events & EPOLLIN) {
        char buf[1 << 16];
        while (true) {
          ssize_t r = read(c->fd, buf, sizeof(buf));
          if (r > 0) {
            c->in.append(buf, static_cast<size_t>(r));
          } else if (r == 0) {
            c->closed = true;
            break;
          } else {
            if (errno == EAGAIN || errno == EWOULDBLOCK) break;
            c->closed = true;
            break;
          }
        }
        while (!c->closed && try_parse_frame(c)) {
        }
      }
      if (!c->closed && (events[i].events & EPOLLOUT)) arm_write(c);
      // flush pending output
      if (!c->closed && !c->out.empty()) {
        ssize_t wr = write(c->fd, c->out.data(), c->out.size());
        if (wr > 0) c->out.erase(0, static_cast<size_t>(wr));
        else if (wr < 0 && errno != EAGAIN && errno != EWOULDBLOCK)
          c->closed = true;
        arm_write(c);
      }
      if (c->closed) close_conn(c);
    }
  }
  return 0;
}

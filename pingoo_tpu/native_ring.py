"""Python binding for the native shared-memory verdict ring.

The C++ side (pingoo_tpu/native/pingoo_ring.{h,cc}) owns the queue
algebra; this module maps the ring file, exposes enqueue/dequeue via
ctypes, and — the part that matters for throughput — decodes a whole
dequeued batch into engine arrays with one numpy structured view (the
slot layout mirrors engine/batch.py field specs by construction).

`RingSidecar` is the TPU-side drain loop: dequeue a batch, run the
jitted verdict, post (ticket, action, bot_score) back. Together with
native/loadgen.cc this is the host<->device transport of SURVEY.md §7
item 4 running end-to-end.
"""

from __future__ import annotations

import contextlib
import ctypes
import functools
import logging
import mmap
import os
import subprocess
import time
from typing import Optional

import numpy as np

_log = logging.getLogger(__name__)

NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
LIB_PATH = os.path.join(NATIVE_DIR, "libpingoo_ring.so")

FIELD_CAPS = {"method": 16, "host": 256, "path": 2048, "url": 2048,
              "user_agent": 256}

RING_MAGIC = 0x50474F52  # PINGOO_RING_MAGIC ("PGOR")
SLOT_FLAG_TRUNCATED = 0x1  # PINGOO_SLOT_FLAG_TRUNCATED
SPILL_SLOTS = 64  # PINGOO_SPILL_SLOTS
SPILL_DATA_CAP = 65536  # PINGOO_SPILL_DATA_CAP
SPILL_NONE = 0xFF  # PINGOO_SPILL_NONE

# -- ABI mirror of pingoo_ring.h -----------------------------------------
# These constants and structured dtypes are the Python half of the
# cross-plane ABI contract. They are NOT free-hand: `make analyze-abi`
# (tools/analyze/abi.py) diffs every size/offset below against a C++
# emitter compiled from pingoo_ring.h and against the committed golden
# table (tools/analyze/abi_golden.json). Change the header, the dtypes,
# and the golden together or the check fails.

RING_FORMAT_VERSION = 6  # PINGOO_RING_VERSION
REQUEST_SLOT_SIZE = 4688  # sizeof(PingooRequestSlot)
VERDICT_SLOT_SIZE = 24  # sizeof(PingooVerdictSlot)
RING_HEADER_SIZE = 640  # sizeof(PingooRingHeader)
TELEMETRY_BLOCK_SIZE = 128  # sizeof(PingooRingTelemetry)
SPILL_SLOT_SIZE = 65552  # sizeof(PingooSpillSlot)
WAIT_BUCKETS = 8  # PINGOO_WAIT_BUCKETS
BODY_SLOTS = 256  # PINGOO_BODY_SLOTS (v6 body-window ring)
BODY_WINDOW_CAP = 4096  # PINGOO_BODY_WINDOW_CAP
BODY_SLOT_SIZE = 4136  # sizeof(PingooBodySlot)
BODY_FLAG_FINAL = 0x1  # PINGOO_BODY_FLAG_FINAL
BODY_FLAG_ABORT = 0x2  # PINGOO_BODY_FLAG_ABORT
# Body verdicts ride the shared verdict ring with this bit set in the
# ticket (PINGOO_BODY_VERDICT_BIT) so the data plane demuxes them.
BODY_VERDICT_BIT = 1 << 63

# numpy mirror of PingooRequestSlot. The explicit itemsize carries the
# C struct's 8-byte tail padding (4684 -> 4688) so a whole dequeued
# batch decodes with one structured view.
REQUEST_SLOT_DTYPE = np.dtype({
    "names": [
        "seq", "ticket", "enq_ms",
        "method_len", "host_len", "path_len", "url_len", "ua_len",
        "remote_port", "ip", "asn", "country", "flags", "spill_idx",
        "method", "host", "path", "url", "user_agent",
    ],
    "formats": [
        "<u8", "<u8", "<u8",
        "<u2", "<u2", "<u2", "<u2", "<u2",
        "<u2", ("u1", 16), "<u4", "S2", "u1", "u1",
        ("u1", 16), ("u1", 256), ("u1", 2048), ("u1", 2048), ("u1", 256),
    ],
    "offsets": [
        0, 8, 16,
        24, 26, 28, 30, 32,
        34, 36, 52, 56, 58, 59,
        60, 76, 332, 2380, 4428,
    ],
    "itemsize": REQUEST_SLOT_SIZE,
})

# numpy mirror of PingooVerdictSlot.
VERDICT_SLOT_DTYPE = np.dtype({
    "names": ["seq", "ticket", "action", "_pad", "bot_score"],
    "formats": ["<u8", "<u8", "u1", ("u1", 3), "<f4"],
    "offsets": [0, 8, 16, 17, 20],
    "itemsize": VERDICT_SLOT_SIZE,
})

# numpy mirror of PingooRingTelemetry (the v4 atomic header block;
# alignas(64) pads the struct to 128 bytes).
TELEMETRY_DTYPE = np.dtype({
    "names": ["enqueued", "enqueue_full", "dequeued", "depth_hwm",
              "verdicts_posted", "verdict_post_full", "wait_sum_ms",
              "wait_hist"],
    "formats": ["<u8", "<u8", "<u8", "<u8", "<u8", "<u8", "<u8",
                ("<u8", WAIT_BUCKETS)],
    "offsets": [0, 8, 16, 24, 32, 40, 48, 56],
    "itemsize": TELEMETRY_BLOCK_SIZE,
})

# numpy mirror of PingooRingHeader (cache-line-aligned counters; the
# v5 liveness block — sidecar_epoch / sidecar_heartbeat_ms /
# posted_floor — rides its own cache line after the telemetry block;
# the v6 body-window ring adds body_slot_size/body_capacity up front
# and a body_head/body_tail cache-line pair at the end).
RING_HEADER_DTYPE = np.dtype({
    "names": ["magic", "version", "capacity", "request_slot_size",
              "verdict_slot_size", "body_slot_size", "body_capacity",
              "req_head", "req_tail", "ver_head", "ver_tail",
              "telemetry", "sidecar_epoch", "sidecar_heartbeat_ms",
              "posted_floor", "body_head", "body_tail"],
    "formats": ["<u4", "<u4", "<u4", "<u4", "<u4", "<u4", "<u4", "<u8",
                "<u8", "<u8", "<u8", TELEMETRY_DTYPE, "<u8", "<u8",
                "<u8", "<u8", "<u8"],
    "offsets": [0, 4, 8, 12, 16, 20, 24, 64, 128, 192, 256, 320, 448,
                456, 464, 512, 576],
    "itemsize": RING_HEADER_SIZE,
})

# numpy mirror of PingooSpillSlot (overflow url/path strings).
SPILL_SLOT_DTYPE = np.dtype({
    "names": ["state", "url_len", "path_len", "data"],
    "formats": ["<u8", "<u4", "<u4", ("u1", 65536)],
    "offsets": [0, 8, 12, 16],
    "itemsize": SPILL_SLOT_SIZE,
})

# numpy mirror of PingooBodySlot (v6 body-window ring): a whole
# dequeued window batch decodes with one structured view, same as the
# request slots.
BODY_SLOT_DTYPE = np.dtype({
    "names": ["seq", "flow", "win_seq", "win_len", "total_len", "flags",
              "_pad", "data"],
    "formats": ["<u8", "<u8", "<u4", "<u4", "<u8", "u1", ("u1", 7),
                ("u1", BODY_WINDOW_CAP)],
    "offsets": [0, 8, 16, 20, 24, 32, 33, 40],
    "itemsize": BODY_SLOT_SIZE,
})

for _dt, _size in ((REQUEST_SLOT_DTYPE, REQUEST_SLOT_SIZE),
                   (VERDICT_SLOT_DTYPE, VERDICT_SLOT_SIZE),
                   (TELEMETRY_DTYPE, TELEMETRY_BLOCK_SIZE),
                   (RING_HEADER_DTYPE, RING_HEADER_SIZE),
                   (SPILL_SLOT_DTYPE, SPILL_SLOT_SIZE),
                   (BODY_SLOT_DTYPE, BODY_SLOT_SIZE)):
    assert _dt.itemsize == _size, (_dt, _dt.itemsize, _size)
del _dt, _size

# Flat order of pingoo_ring_telemetry_snapshot (pingoo_ring.h
# PINGOO_TELEMETRY_WORDS); the 8 wait_hist buckets follow.
TELEMETRY_FIELDS = ("enqueued", "enqueue_full", "dequeued", "depth",
                    "depth_hwm", "verdicts_posted", "verdict_post_full",
                    "wait_sum_ms")
TELEMETRY_WORDS = len(TELEMETRY_FIELDS) + 8
WAIT_BUCKET_BOUNDS_MS = (1, 2, 5, 10, 50, 100, 1000)  # last bucket +inf


@functools.cache
def ensure_built() -> bool:
    """Bring every native target up to date with its sources (once per
    process); False if there is no toolchain or the build fails. `make`
    runs even when the binaries exist — it is a no-op when they are
    current, and a stale `.so`/`httpd` left over from another checkout
    of the sources must never be what serves."""
    try:
        subprocess.run(["make", "-C", NATIVE_DIR], check=True,
                       capture_output=True)
        return True
    except (subprocess.CalledProcessError, FileNotFoundError):
        return False


def _load_lib():
    lib = ctypes.CDLL(LIB_PATH)
    lib.pingoo_ring_bytes.restype = ctypes.c_size_t
    lib.pingoo_ring_bytes.argtypes = [ctypes.c_uint32]
    lib.pingoo_ring_init.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.pingoo_ring_attach.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint32)]
    lib.pingoo_ring_attach.restype = ctypes.c_int
    lib.pingoo_ring_enqueue_request.restype = ctypes.c_uint64
    lib.pingoo_ring_enqueue_request.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p, ctypes.c_uint32,  # method
        ctypes.c_char_p, ctypes.c_uint32,  # host
        ctypes.c_char_p, ctypes.c_uint32,  # path
        ctypes.c_char_p, ctypes.c_uint32,  # url
        ctypes.c_char_p, ctypes.c_uint32,  # ua
        ctypes.c_char_p,                   # ip[16]
        ctypes.c_uint16, ctypes.c_uint32, ctypes.c_char_p,
    ]
    lib.pingoo_ring_dequeue_requests.restype = ctypes.c_uint32
    lib.pingoo_ring_dequeue_requests.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32]
    lib.pingoo_ring_post_verdict.restype = ctypes.c_int
    lib.pingoo_ring_post_verdict.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint8, ctypes.c_float]
    lib.pingoo_ring_post_verdicts.restype = ctypes.c_uint32
    lib.pingoo_ring_post_verdicts.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32]
    lib.pingoo_ring_poll_verdict.restype = ctypes.c_int
    lib.pingoo_ring_poll_verdict.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float)]
    # Body-window ring (v6, ISSUE 13).
    lib.pingoo_ring_enqueue_body.restype = ctypes.c_int
    lib.pingoo_ring_enqueue_body.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32,
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint32,
        ctypes.c_uint8]
    lib.pingoo_ring_dequeue_bodies.restype = ctypes.c_uint32
    lib.pingoo_ring_dequeue_bodies.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32]
    lib.pingoo_ring_spill_read.restype = ctypes.c_int
    lib.pingoo_ring_spill_read.argtypes = [
        ctypes.c_void_p, ctypes.c_uint8,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint32)]
    lib.pingoo_ring_spill_release.argtypes = [ctypes.c_void_p,
                                              ctypes.c_uint8]
    lib.pingoo_ring_telemetry_snapshot.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
    lib.pingoo_ring_record_waits.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint32]
    lib.pingoo_ring_now_ms.restype = ctypes.c_uint64
    lib.pingoo_ring_now_ms.argtypes = []
    # Liveness / supervision protocol (v5, ISSUE 10).
    lib.pingoo_ring_sidecar_attach.restype = ctypes.c_uint64
    lib.pingoo_ring_sidecar_attach.argtypes = [ctypes.c_void_p]
    lib.pingoo_ring_heartbeat.argtypes = [ctypes.c_void_p]
    lib.pingoo_ring_liveness.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
    lib.pingoo_ring_set_posted_floor.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64]
    lib.pingoo_ring_reclaim_request.restype = ctypes.c_int
    lib.pingoo_ring_reclaim_request.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p]
    return lib


class Ring:
    """A mapped ring file."""

    def __init__(self, path: str, capacity: int = 4096, create: bool = False):
        if not ensure_built():
            raise RuntimeError("native ring library unavailable (no g++?)")
        if capacity & (capacity - 1) or capacity <= 0:
            # The C ring masks with `pos & (cap - 1)`; a non-pow2
            # capacity would silently alias slots and corrupt the queue.
            raise ValueError(f"ring capacity must be a power of two, got {capacity}")
        self.lib = _load_lib()
        self.path = path
        self.capacity = capacity
        nbytes = self.lib.pingoo_ring_bytes(capacity)
        flags = os.O_RDWR | (os.O_CREAT if create else 0)
        self.fd = os.open(path, flags, 0o600)
        if create:
            os.ftruncate(self.fd, nbytes)
        self.map = mmap.mmap(self.fd, nbytes)
        self.addr = ctypes.addressof(
            (ctypes.c_char * nbytes).from_buffer(self.map))
        if create:
            self.lib.pingoo_ring_init(self.addr, capacity)
        cap_out = ctypes.c_uint32()
        if self.lib.pingoo_ring_attach(self.addr, ctypes.byref(cap_out)) != 0:
            raise RuntimeError("ring attach failed (layout mismatch?)")
        self.capacity = int(cap_out.value)
        self._scratch = np.zeros(self.capacity, dtype=REQUEST_SLOT_DTYPE)
        self._body_scratch = None  # allocated on first dequeue_bodies

    def close(self) -> None:
        self._scratch = None
        self._body_scratch = None
        self.map.close()
        os.close(self.fd)

    # -- producer side (tests / python data plane) ---------------------------

    def enqueue(self, method=b"GET", host=b"", path=b"/", url=b"/",
                user_agent=b"", ip: bytes = b"\x00" * 16, port: int = 0,
                asn: int = 0, country: bytes = b"XX") -> Optional[int]:
        ticket = self.lib.pingoo_ring_enqueue_request(
            self.addr, method, len(method), host, len(host), path, len(path),
            url, len(url), user_agent, len(user_agent), ip, port, asn,
            country)
        return None if ticket == 2**64 - 1 else int(ticket)

    # -- consumer side (sidecar) ---------------------------------------------

    def dequeue_batch(self, max_batch: int = 1024) -> np.ndarray:
        """-> structured array view of up to max_batch request slots."""
        n = self.lib.pingoo_ring_dequeue_requests(
            self.addr, self._scratch.ctypes.data_as(ctypes.c_void_p),
            min(max_batch, self.capacity))
        return self._scratch[:n].copy()

    def dequeue_batch_into(self, out: np.ndarray) -> int:
        """Zero-copy bulk dequeue (ISSUE 9, docs/EXECUTOR.md): the FFI
        slot copy lands directly in the caller's REQUEST_SLOT_DTYPE
        buffer — typically a row offset into the sidecar's pooled
        accumulation buffer, so multi-ring parts merge WITHOUT the
        scratch round trip, the per-part `.copy()`, or the launch-time
        `np.concatenate`. Returns the slot count written; the caller
        owns `out` for the batch's whole lifetime."""
        assert out.dtype == REQUEST_SLOT_DTYPE and out.flags.c_contiguous
        if not len(out):
            return 0
        n = self.lib.pingoo_ring_dequeue_requests(
            self.addr, out.ctypes.data_as(ctypes.c_void_p),
            min(len(out), self.capacity))
        return int(n)

    def post_verdict(self, ticket: int, action: int, score: float = 0.0) -> bool:
        return self.lib.pingoo_ring_post_verdict(
            self.addr, ticket, action, score) == 0

    def post_verdicts(self, tickets: np.ndarray, actions: np.ndarray) -> int:
        """Batched post (one FFI hop); returns count posted — fewer than
        len(tickets) only when the verdict ring is full."""
        tickets = np.ascontiguousarray(tickets, dtype=np.uint64)
        actions = np.ascontiguousarray(actions, dtype=np.uint8)
        return int(self.lib.pingoo_ring_post_verdicts(
            self.addr, tickets.ctypes.data_as(ctypes.c_void_p),
            actions.ctypes.data_as(ctypes.c_void_p), len(tickets)))

    def spill_read(self, idx: int) -> Optional[tuple[bytes, bytes]]:
        """Full (url, path) bytes of a claimed spill slot, or None."""
        url_p = ctypes.c_char_p()
        path_p = ctypes.c_char_p()
        url_n = ctypes.c_uint32()
        path_n = ctypes.c_uint32()
        if self.lib.pingoo_ring_spill_read(
                self.addr, idx, ctypes.byref(url_p), ctypes.byref(url_n),
                ctypes.byref(path_p), ctypes.byref(path_n)) != 0:
            return None
        url = ctypes.string_at(url_p, url_n.value)
        path = ctypes.string_at(path_p, path_n.value)
        return url, path

    def spill_release(self, idx: int) -> None:
        self.lib.pingoo_ring_spill_release(self.addr, idx)

    def telemetry(self) -> dict:
        """Snapshot of the shm header's atomic telemetry block (ring
        v4): queue counters, depth + high-water mark, full-ring stalls,
        and the enqueue->verdict-post wait histogram (bucket upper
        bounds WAIT_BUCKET_BOUNDS_MS, last bucket +inf)."""
        buf = (ctypes.c_uint64 * TELEMETRY_WORDS)()
        if not self.map.closed:  # post-close scrape reads zeros, not UB
            self.lib.pingoo_ring_telemetry_snapshot(self.addr, buf)
        out = {name: int(buf[i]) for i, name in enumerate(TELEMETRY_FIELDS)}
        out["wait_hist"] = [int(buf[len(TELEMETRY_FIELDS) + b])
                            for b in range(8)]
        return out

    def record_waits(self, enq_ms: np.ndarray) -> None:
        """Feed dequeued slots' enq_ms back at verdict-post time (one
        FFI hop per batch) so the telemetry wait histogram measures
        enqueue -> verdict-post per request."""
        if self.map.closed:
            return
        enq = np.ascontiguousarray(enq_ms, dtype=np.uint64)
        self.lib.pingoo_ring_record_waits(
            self.addr, enq.ctypes.data_as(ctypes.c_void_p), len(enq))

    def poll_verdict(self) -> Optional[tuple[int, int, float]]:
        ticket = ctypes.c_uint64()
        action = ctypes.c_uint8()
        score = ctypes.c_float()
        if self.lib.pingoo_ring_poll_verdict(
                self.addr, ctypes.byref(ticket), ctypes.byref(action),
                ctypes.byref(score)) != 0:
            return None
        return int(ticket.value), int(action.value), float(score.value)

    # -- body-window ring (v6, docs/BODY_STREAMING.md) ------------------------

    def enqueue_body(self, flow: int, win_seq: int, data: bytes,
                     total_len: int, flags: int = 0) -> bool:
        """Enqueue one de-framed body window for `flow` (the request
        ticket). False when the body ring is full — the producer then
        fails the flow open to metadata-only rather than stalling."""
        rc = self.lib.pingoo_ring_enqueue_body(
            self.addr, flow, win_seq, total_len, data, len(data), flags)
        if rc == -2:
            raise ValueError(
                f"body window of {len(data)} bytes exceeds the "
                f"{BODY_WINDOW_CAP}-byte slot cap")
        return rc == 0

    def dequeue_bodies(self, max_batch: int = BODY_SLOTS) -> np.ndarray:
        """-> structured BODY_SLOT_DTYPE array of dequeued windows."""
        if self._body_scratch is None:
            self._body_scratch = np.zeros(BODY_SLOTS,
                                          dtype=BODY_SLOT_DTYPE)
        n = self.lib.pingoo_ring_dequeue_bodies(
            self.addr,
            self._body_scratch.ctypes.data_as(ctypes.c_void_p),
            min(max_batch, BODY_SLOTS))
        return self._body_scratch[:n].copy()

    # -- liveness / supervision protocol (ring v5, docs/RESILIENCE.md) -------

    def sidecar_attach(self) -> int:
        """Bump the sidecar epoch (one consumer generation = one epoch),
        stamp the first heartbeat, and return the NEW epoch."""
        return int(self.lib.pingoo_ring_sidecar_attach(self.addr))

    def heartbeat(self) -> None:
        """Stamp the liveness heartbeat (called every poll cycle)."""
        if not self.map.closed:
            self.lib.pingoo_ring_heartbeat(self.addr)

    def liveness(self) -> dict:
        """One-call liveness snapshot: epoch, heartbeat_ms (0 = no
        sidecar has ever attached), posted_floor, req_tail, now_ms —
        all on the ring's own CLOCK_MONOTONIC ms time base."""
        buf = (ctypes.c_uint64 * 5)()
        if not self.map.closed:
            self.lib.pingoo_ring_liveness(self.addr, buf)
        return {"epoch": int(buf[0]), "heartbeat_ms": int(buf[1]),
                "posted_floor": int(buf[2]), "req_tail": int(buf[3]),
                "now_ms": int(buf[4])}

    def set_posted_floor(self, ticket: int) -> None:
        """Advance the posted floor (monotonic max): every ticket below
        it has a verdict posted, so a reattaching sidecar only scans
        [posted_floor, req_tail) for orphans."""
        self.lib.pingoo_ring_set_posted_floor(self.addr, ticket)

    def reclaim(self, ticket: int) -> Optional[np.ndarray]:
        """Reclaim one orphaned ticket during crash-reattach
        reconciliation: a 1-element REQUEST_SLOT_DTYPE array when the
        request bytes are still intact (re-evaluate them), or None when
        the slot was reused (fail-open the ticket). Also unwedges a
        slot whose consumer died between its tail-CAS and seq-release."""
        out = np.zeros(1, dtype=REQUEST_SLOT_DTYPE)
        if self.lib.pingoo_ring_reclaim_request(
                self.addr, ticket,
                out.ctypes.data_as(ctypes.c_void_p)) != 0:
            return None
        return out


def slots_to_arrays(slots: np.ndarray) -> dict:
    """Structured slots -> engine batch arrays (zero-parse bulk decode)."""
    arrays: dict = {}
    for field, cap in FIELD_CAPS.items():
        arrays[f"{field}_bytes"] = np.ascontiguousarray(slots[field])
        arrays[f"{field}_len"] = slots[f"{field}_len" if field != "user_agent"
                                       else "ua_len"].astype(np.int32)
    country = np.frombuffer(
        slots["country"].tobytes(), dtype=np.uint8).reshape(-1, 2)
    arrays["country_bytes"] = np.ascontiguousarray(country)
    arrays["country_len"] = np.full(len(slots), 2, dtype=np.int32)
    ip = slots["ip"].reshape(-1, 16)
    arrays["ip"] = np.ascontiguousarray(
        ip.view(">u4").reshape(-1, 4).astype(np.uint32))
    arrays["asn"] = slots["asn"].astype(np.int64)
    arrays["remote_port"] = slots["remote_port"].astype(np.int64)
    return arrays


class _TableMarker(str):
    """Identity-carrying marker for services-table upstream entries.
    A marker is recognized ONLY by `isinstance` + identity — a config-
    derived hostname that happens to equal a marker's text can never be
    mistaken for one (it raises in _append_tls with guidance to use the
    explicit (ip, port, "tls", name) form instead)."""

    __slots__ = ()


# Marks a services-table upstream as the loopback control plane: the
# C++ connector sends its per-boot internal token on hops to it, which
# is what lets the Python listener trust the injected x-forwarded-for.
INTERNAL = _TableMarker("internal")
# Marks a cleartext prior-knowledge HTTP/2 upstream (config scheme
# h2://): the C++ connector frames requests over an nghttp2 client
# session instead of h1 (reference hyper client speaks h2 upstream,
# http_proxy_service.rs:54-71).
H2 = _TableMarker("h2-prior-knowledge")


def _append_tls(lines: list, ip, port, sni, explicit: bool = False) -> None:
    if (not sni or len(sni) > 255 or any(ch.isspace() for ch in sni)):
        # 255 = the C++ reader's %255s scan width; a longer name would
        # be silently truncated into a hop that can never pass
        # hostname verification.
        raise ValueError(f"bad tls server name {sni!r}")
    if not explicit and sni in (INTERNAL, H2):
        # Reserved table keywords in the legacy 3-tuple form are
        # ambiguous: a server name that collides with a marker must use
        # the unambiguous (ip, port, "tls", name) form (explicit=True)
        # — silently re-tagging the hop would either leak the internal
        # token or downgrade TLS to cleartext h2.
        raise ValueError(
            f"tls server name {sni!r} collides with a table marker; "
            f"use the (ip, port, 'tls', name) entry form")
    lines.append(f"upstream {ip} {port} tls {sni}")


def write_services_file(path: str, services: list) -> None:
    """Publish the native plane's routing table: `services` is the
    listener's ordered [(name, [upstream, ...])] — typically registry
    snapshots (host/discovery.ServiceRegistry.get_upstreams) — or
    `(name, upstreams, static_root)` for a static-site service (the
    C++ plane serves its <=500KB files directly; bigger ones proxy to
    the upstream list). Each upstream is `(ip, port)` for plaintext,
    `(ip, port, server_name)` for a verified TLS hop (the C++
    connector dials it with SNI + hostname checks against server_name,
    reference http_proxy_service.rs:54-71), `(ip, port, H2)` for
    cleartext prior-knowledge h2, or `(ip, port, INTERNAL)` for the
    loopback control plane (token-authenticated identity headers).
    Written atomically (tmp + rename) so the C++ reader (httpd.cc
    ServiceTable) never observes a partial table; it hot-reloads on
    mtime change."""
    if len(services) > 31:
        raise ValueError(
            f"native routing supports at most 31 services (5-bit route "
            f"field, 31 = no match), got {len(services)}")
    lines = ["pingoo-services v1"]
    for order, entry in enumerate(services):
        name, ups = entry[0], entry[1]
        static_root = entry[2] if len(entry) > 2 else None
        lines.append(f"service {order} {name}")
        if static_root is not None:
            if (not static_root or len(static_root) > 383
                    or any(ch.isspace() for ch in static_root)):
                # %383s scan width; whitespace would split the token.
                raise ValueError(f"bad static root {static_root!r}")
            lines.append(f"static {static_root}")
        for up in ups:
            if len(up) == 2:
                lines.append(f"upstream {up[0]} {up[1]}")
            elif len(up) == 4 and up[2] == "tls":
                # unambiguous TLS form: (ip, port, "tls", server_name)
                _append_tls(lines, up[0], up[1], up[3], explicit=True)
            elif isinstance(up[2], _TableMarker) and up[2] is INTERNAL:
                lines.append(f"upstream {up[0]} {up[1]} internal")
            elif isinstance(up[2], _TableMarker) and up[2] is H2:
                lines.append(f"upstream {up[0]} {up[1]} h2")
            else:
                _append_tls(lines, up[0], up[1], up[2])
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write("\n".join(lines) + "\n")
    os.replace(tmp, path)


def replica_devices(n: int) -> list:
    """The chips `--replicas n` launches batches on: [None] for one,
    which leaves every placement to JAX's default device as before,
    else the first n local devices. Raises ValueError where the host
    has fewer (no code stands in for an absent chip), and beside a
    PINGOO_MESH that spans devices (that shards one batch over them
    instead: a batch goes whole to one chip or over the mesh, not
    both)."""
    n = int(n)
    if n < 1:
        raise ValueError(f"--replicas {n}: at least 1")
    if n == 1:
        return [None]
    import jax

    from .sched import mesh_env_spec

    local = jax.local_devices()
    if n > len(local):
        raise ValueError(f"--replicas {n}: this host has {len(local)} "
                         "local device(s)")
    dp, tp, sp = mesh_env_spec()
    if dp * tp * sp > 1:
        raise ValueError(f"--replicas {n} with PINGOO_MESH={dp}x{tp}x{sp}: "
                         "a batch goes whole to one chip or is sharded "
                         "over the mesh, not both")
    return list(local[:n])


def _place_on(tables, device):
    """A copy of a plan's device tables committed to `device`: every
    device array of the pytree, the rest (static leaves) as it is."""
    import jax

    return jax.tree.map(
        lambda leaf: jax.device_put(leaf, device)
        if isinstance(leaf, jax.Array) else leaf, tables)


class RingSidecar:
    """Drain loop: ring batches -> jitted verdict -> verdict ring.

    `ring` may be a single Ring or a list of Rings — the data plane
    scales across cores as N SO_REUSEPORT worker processes with one
    ring each (verdicts must return on the worker's own ring; the
    verdict queue is MPMC, so co-consumers would steal each other's
    tickets). The sidecar drains all rings into ONE merged device batch
    per cycle and scatters the verdicts back per ring.

    `replicas` N > 1 (`--replicas`) keeps a whole copy of the plan's
    device tables on each of the first N local devices and launches
    every batch whole on one of them: the chip with the fewest batches
    in flight, ties broken round-robin. `pipeline_depth` bounds each
    chip's batches in flight; a chip's batches complete in launch
    order, the chips' in whichever order their lanes are ready, and a
    pass with nothing to launch polls the chips rather than block on
    the oldest batch.
    """

    def __init__(self, ring, plan, lists, max_batch: int = 1024,
                 idle_sleep_s: float = 0.0002, pipeline_depth: int = 3,
                 services: Optional[list] = None, geoip=None,
                 ring_services: Optional[list] = None, replicas: int = 1):
        self.rings: list[Ring] = list(ring) if isinstance(
            ring, (list, tuple)) else [ring]
        self.ring = self.rings[0]  # single-ring callers' view
        self.plan = plan
        self.lists = lists
        self.max_batch = max_batch
        self.idle_sleep_s = idle_sleep_s
        # Batches dispatched-but-not-collected. Depth > 1 only pays off
        # when producers keep more than one batch of requests in flight;
        # it hides the device round trip behind the next batch's host
        # work.
        self.pipeline_depth = max(1, pipeline_depth)
        # Overlapped zero-copy executor (ISSUE 9, docs/EXECUTOR.md):
        # PINGOO_PIPELINE=on (default) dequeues straight into pooled
        # slot buffers (Ring.dequeue_batch_into) and encodes through
        # the reused StagingEncoder views — no per-batch concatenate /
        # slots_to_arrays / bucket / pad allocations; =off keeps the
        # legacy chain (the bench A/B arm and the parity oracle path).
        # PINGOO_PIPELINE_DEPTH overrides the in-flight bound for both.
        mode = os.environ.get("PINGOO_PIPELINE", "on").strip().lower()
        self.pipeline_mode = "off" if mode in ("off", "0", "false") \
            else "on"
        try:
            self.pipeline_depth = max(1, int(os.environ.get(
                "PINGOO_PIPELINE_DEPTH", str(self.pipeline_depth))))
        except ValueError:
            pass
        # Replicas (`--replicas`): the chips a batch may launch on.
        self._replica_devices = replica_devices(replicas)
        self.replicas = len(self._replica_devices)
        # every batch the loop may hold between dequeue and post: each
        # chip's in-flight bound, and the one being filled
        self._max_inflight = self.replicas * self.pipeline_depth
        self._replica_inflight = [0] * self.replicas
        self._replica_rr = -1   # the last chip chosen (ties go round)
        self._posted_high: dict = {}  # ring id -> highest ticket+1 posted
        self._zero_copy = self.pipeline_mode == "on"
        # Continuous-batching admission scheduler (ISSUE 6, docs/
        # SCHEDULER.md): replaces the fixed drain window (dispatch
        # whatever one dequeue pass returned) with the deadline-slack
        # launch policy shared with the Python plane. Timestamps come
        # from the ring's enq_ms clock (pingoo_ring_now_ms), converted
        # to seconds for the scheduler.
        from .sched import MeshUnavailable, Scheduler, SchedulerConfig

        self.sched = Scheduler(SchedulerConfig.from_env(max_batch),
                               plane="sidecar")
        # Perf ledger + cross-plane timeline + durable cost ledger
        # (ISSUE 17, docs/OBSERVABILITY.md): compile events from every
        # jitted program below become counted/persisted ledger entries
        # (no-op passthrough while PINGOO_PERF_LEDGER is off), sampled
        # batches emit cross-plane spans joined on the ring clock, and
        # the CostModel reloads the prior run's measured EWMAs keyed to
        # this backend + ruleset fingerprint.
        from .obs.perf import get_compile_ledger, plan_fingerprint
        from .obs.timeline import get_timeline
        from .sched.scheduler import load_cost_ledger

        self._plan_fp = plan_fingerprint(plan)
        self._perf = get_compile_ledger()
        self._perf.ensure_instruments("sidecar")
        self._timeline = get_timeline()
        self._timeline.ensure_instruments("sidecar")
        from .backend import backend_info

        self.backend = backend_info()
        self._backend_label = self.backend["platform"]
        self.cost_ledger_result = load_cost_ledger(
            self.sched.cost, backend=self._backend_label,
            fingerprint=self._plan_fp, plane="sidecar")
        # The sidecar uses the transfer-thin lane reduction — the
        # first-match action decision computes ON DEVICE and only four
        # int32 lanes come back, not the [B, R] match matrix (half a
        # megabyte per 1k batch).
        # `services` (the native listener's service names, in order)
        # adds the ROUTE lane so the C++ plane can dispatch each request
        # to the right service's upstream set (verdict byte bits 3-7).
        self.services = list(services) if services else None
        # `ring_services` (aligned with `rings`; entries may be None)
        # gives each worker ring its OWN service order — the reference
        # binds a service list per listener (config.rs:241-253), and the
        # native plane runs one ring per (listener, worker). The lane fn
        # computes one route lane per DISTINCT order; each row reads the
        # lane of the ring it arrived on.
        if ring_services is not None:
            if services is not None:
                raise ValueError("pass services or ring_services, not both")
            if len(ring_services) != len(self.rings):
                raise ValueError(
                    f"ring_services has {len(ring_services)} entries for "
                    f"{len(self.rings)} rings")
            per_ring = [list(s) if s else None for s in ring_services]
        else:
            per_ring = [self.services] * len(self.rings)
        self._groups: list[list] = []
        self._ring_group: list[Optional[int]] = []
        for svc in per_ring:
            if svc is None:
                self._ring_group.append(None)
                continue
            for gi, g in enumerate(self._groups):
                if g == svc:
                    break
            else:
                gi = len(self._groups)
                self._groups.append(svc)
            self._ring_group.append(gi)
        for g in self._groups:
            if len(g) > 31:
                # The verdict byte's route field is 5 bits: orders 0-30
                # plus the no-match sentinel 31. More services would
                # alias the sentinel onto a real service and invert
                # no-match into proxy-to-last-service.
                raise ValueError(
                    f"native routing supports at most 31 services, "
                    f"got {len(g)}")
        self._ring_group_of = {id(r): gi for r, gi in
                               zip(self.rings, self._ring_group)}
        # Verdict provenance (ISSUE 5): the per-rule attribution fold
        # rides the lanes' own stacked output (with_rule_hits) — the
        # match matrix itself still never leaves the device.
        from .obs.provenance import provenance_enabled

        self._provenance_on = provenance_enabled()
        # Degradation ladder (ISSUE 10, docs/RESILIENCE.md): the
        # scattered fallbacks below route through one explicit state
        # machine — demotions are counted per rung and probed back
        # with exponential backoff (engine/ladder.py).
        from .engine.ladder import DegradationLadder

        self.ladder = DegradationLadder("sidecar")
        # Streaming body inspection (ISSUE 13, docs/BODY_STREAMING.md):
        # when PINGOO_BODY_INSPECT=on the sidecar drains the v6
        # body-window ring each cycle, threads NFA/DFA carry state
        # across windows (engine/bodyscan.py), and posts body verdicts
        # on the SAME verdict ring tagged BODY_VERDICT_BIT. Off (the
        # default) the drain is skipped entirely — bit-exact status
        # quo. A scanner fault demotes the ladder's "body" rung:
        # windows fail open to metadata-only until a probe recovers.
        from .engine import bodyscan as _bodyscan

        self._bodyscan_mod = _bodyscan
        self._body_scan = None
        self.body_verdicts = 0
        if _bodyscan.body_inspect_enabled():
            try:
                self._body_scan = _bodyscan.BodyScanner()
                self._body_scan.attach_metrics("sidecar")
            except Exception as exc:
                self.ladder.note_failure("body", exc)
        # The C++ plane has no mmdb decoder: it enqueues slots with
        # asn=0 / country="XX" (its unknown markers). The reference
        # resolves geoip per request in the listener
        # (http_listener.rs:143-157); here the sidecar enriches those
        # rows from the host GeoipDB (host/geoip.py, cached) before
        # encoding, so geo/asn rules see real values for natively
        # fronted traffic too. None disables (geo rules then evaluate
        # on XX/0, the reference's missing-database behavior).
        self.geoip = geoip
        self.processed = 0
        self.truncated_rows = 0
        self.spilled_rows = 0  # overflow rows re-evaluated untruncated
        # Depth-capped rows re-evaluated over the full slot view
        # (ISSUE 15: PINGOO_STAGING=compact with a PINGOO_STAGING_DEPTH
        # clamp below a field's required depth).
        self.depth_overflow_rows = 0
        self.batches = 0
        self.device_wait_s = 0.0  # blocking time on device lane results
        self._ring_rr = -1  # rotating drain start (multi-ring fairness)
        self._thread = None  # set by run(); joined by stop()
        self._stop = False
        # Unified telemetry (obs/): per-stage drain-loop histograms plus
        # a collector that folds the rings' shm telemetry blocks into
        # the shared registry, so the Python control-plane scrape
        # carries native-plane queue state in the same exposition.
        from .obs import REGISTRY

        self._registry = REGISTRY
        # The rings by name (ISSUE 31; the file's name, which says the
        # listener and the worker): rows dequeued from each, how many
        # rings gave rows to each batch, and each ring's depth as the
        # loop last read it (once a launch: `_ring_depths`).
        from .obs.schema import SIDECAR_RING_METRICS

        names = [os.path.basename(r.path) for r in self.rings]
        if len(set(names)) < len(names):
            names = [str(i) for i in range(len(names))]
        self.ring_names = names
        self._ring_rows = [
            REGISTRY.counter(
                "pingoo_ring_rows_total",
                SIDECAR_RING_METRICS["pingoo_ring_rows_total"],
                labels={"plane": "sidecar", "ring": name})
            for name in names]
        self._batch_rings = REGISTRY.counter(
            "pingoo_batch_rings_total",
            SIDECAR_RING_METRICS["pingoo_batch_rings_total"],
            labels={"plane": "sidecar"})
        self._ring_depth_seen = [0] * len(names)
        # Pipeline executor substrate (ISSUE 9): the staging encoder's
        # rotating buffer sets must outlive every in-flight batch that
        # still reads its views (depth in flight + the one being
        # filled), and the slot-buffer pool holds one accumulation
        # buffer per in-flight batch plus the one being filled — a
        # drained pool allocates a fresh buffer (cold path only).
        from collections import deque as _deque

        from .engine.batch import StagingEncoder
        from .obs.pipeline import PipelineStats

        self._pipe = PipelineStats("sidecar", self.pipeline_depth)
        self._staging = None
        self._slot_pool: _deque = _deque()
        caps = dict(FIELD_CAPS)
        caps["country"] = 2
        if self._zero_copy:
            self._staging = self._make_staging(plan, caps)
            for _ in range(self._max_inflight + 1):
                self._slot_pool.append(
                    np.zeros(max_batch, dtype=REQUEST_SLOT_DTYPE))
        self._stage = {
            stage: REGISTRY.histogram(
                "pingoo_verdict_stage_ms",
                "verdict pipeline stage latency (ms)",
                labels={"plane": "sidecar", "stage": stage})
            for stage in ("sched", "encode", "prefilter",
                          "device_dispatch", "device_compute", "resolve",
                          "provenance")}
        # The drain loop's ONE span source (obs/pipeline.py): every
        # stage boundary below is a `self._pipe.stage(...)` block, which
        # feeds these histograms, the executor's occupancy/overlap, the
        # scheduler's stage costs, the loop-phase account and — as
        # `sidecar/<phase>` annotations — the profiler's trace. Only the
        # `sched` label is observed directly: it is an age, not a span.
        self._pipe.attach_loop(self._stage, self.sched.observe_stage_cost,
                               self.max_batch, self._ring_depths)
        self._pipe.replicas(self.replicas)
        # Compact staging (ISSUE 15): bytes staged to the device per
        # verdict batch, by PINGOO_STAGING arm — same series the Python
        # listener plane exports.
        from .obs.schema import STAGING_METRICS

        self._staged_bytes_counter = {
            mode: REGISTRY.counter(
                "pingoo_staged_bytes_total",
                STAGING_METRICS["pingoo_staged_bytes_total"],
                labels={"plane": "sidecar", "mode": mode})
            for mode in ("full", "compact")}
        # and the rows a packed batch ships against the rows the chip
        # pads them to (`_run_lanes`).
        self._staged_rows_counter = {
            kind: REGISTRY.counter(
                "pingoo_staged_rows_total",
                STAGING_METRICS["pingoo_staged_rows_total"],
                labels={"plane": "sidecar", "kind": kind})
            for kind in ("uploaded", "padded")}
        # Stage-A literal prefilter (docs/PREFILTER.md): the sidecar is
        # the native plane's verdict engine, so it exports the same
        # candidate-rate/skip metrics the Python listener plane does.
        from .obs.schema import PREFILTER_METRICS

        self._pf_rate_gauge = REGISTRY.gauge(
            "pingoo_prefilter_candidate_rate",
            PREFILTER_METRICS["pingoo_prefilter_candidate_rate"],
            labels={"plane": "sidecar"})
        self._pf_skip_counter = REGISTRY.counter(
            "pingoo_scan_banks_skipped_total",
            PREFILTER_METRICS["pingoo_scan_banks_skipped_total"],
            labels={"plane": "sidecar"})
        # Bitsplit-DFA dispatch accounting (docs/DFA.md): same series
        # the Python listener plane exports, host-static per plan+env
        # (engine/verdict.dfa_dispatch_counts), folded once per batch.
        from .obs.schema import DFA_METRICS

        self._dfa_banks_counter = {
            mode: REGISTRY.counter(
                "pingoo_dfa_banks_total",
                DFA_METRICS["pingoo_dfa_banks_total"],
                labels={"plane": "sidecar", "mode": mode})
            for mode in ("auto", "force")}
        self._dfa_recheck_counter = REGISTRY.counter(
            "pingoo_dfa_recheck_total",
            DFA_METRICS["pingoo_dfa_recheck_total"],
            labels={"plane": "sidecar"})
        # Attribution lanes + flight recorder + shadow-parity auditor
        # for the native plane's verdict engine (this drain loop).
        self._attribution = None
        self.flight_recorder = None
        self.parity = None
        # Ruleset hot-swap (ISSUE 11, docs/RESILIENCE.md): every
        # plan-derived piece of engine state (jitted lane fn, host
        # routes, mesh+tables, prefilter, attribution, dev cols) is
        # built by _build_plan_state and installed by _adopt_plan_state
        # — at init here, and again at a drain-loop batch boundary when
        # request_swap hands over a plan compiled ahead of time.
        import threading as _threading

        self._swap_lock = _threading.Lock()
        self._swap_queue: list = []
        self.ruleset_epoch = 0
        self.tenant = "default"
        # drain+flip pause per applied swap (ms) — chaos_smoke folds
        # the p99 into the bench summary (swap_pause_p99_ms).
        self.swap_pauses_ms: list = []
        self._adopt_plan_state(plan, None, self._build_plan_state(plan))
        from .engine.hotswap import set_epoch_gauge

        set_epoch_gauge("sidecar", 0)
        self._collector_live = True
        REGISTRY.register_collector(self._export_ring_telemetry)
        # -- sidecar supervision (ISSUE 10, docs/RESILIENCE.md) ---------------
        from .obs.chaos import ChaosInjector
        from .obs.schema import RESILIENCE_METRICS

        self.chaos = ChaosInjector.from_env()
        # Liveness protocol (ring v5): bump each ring's epoch so the
        # data plane can tell a restarted sidecar from a frozen one,
        # then reconcile tickets the dead epoch dequeued but never
        # answered — BEFORE the drain loop starts, so reconciliation
        # verdicts can never race this epoch's own posts.
        self._reattach_counters = {
            action: REGISTRY.counter(
                "pingoo_reattach_reconciled_total",
                RESILIENCE_METRICS["pingoo_reattach_reconciled_total"],
                labels={"plane": "sidecar", "action": action})
            for action in ("reeval", "failopen")}
        self.reconciled = {"reeval": 0, "failopen": 0}
        self.epochs = [r.sidecar_attach() for r in self.rings]
        self.epoch = max(self.epochs)
        REGISTRY.gauge(
            "pingoo_sidecar_epoch",
            RESILIENCE_METRICS["pingoo_sidecar_epoch"],
            labels={"plane": "sidecar"}).set(self.epoch)
        # Busy-window heartbeat watchdog (docs/RESILIENCE.md): the
        # drain loop legitimately blocks for seconds inside XLA
        # compiles (first call per pow2 bucket), the device-result
        # sync, interpreter fallbacks, and reattach reconciliation —
        # without this, every such window flips the data plane
        # degraded and fails live requests open. The watchdog stamps
        # ONLY while the loop is inside one of those declared windows
        # (`_hb_busy`), bounded by the grace cap: a SIGKILL silences
        # it with the process, a loop wedged anywhere else stops
        # stamping immediately, and a device call hung past the grace
        # goes dark too (per-ticket verdict timeouts bound the harm
        # meanwhile).
        import threading as _threading

        # The open busy window as ONE tuple, so the watchdog never pairs
        # one window's start with the next one's arrays: (its start in
        # monotonic s, the device arrays the loop is blocked on when the
        # window is a device->host sync, else None, and the chip they
        # live on). An overdue sync is what the watchdog probes
        # (`_probe_overdue_sync`); `_sync_probes` is what it found.
        self._busy: Optional[tuple] = None
        self._sync_probes: _deque = _deque(maxlen=8)
        self._sync_overdue_ctr = {
            ready: REGISTRY.counter(
                "pingoo_sidecar_sync_overdue_total",
                RESILIENCE_METRICS["pingoo_sidecar_sync_overdue_total"],
                labels={"plane": "sidecar", "ready": ready})
            for ready in ("true", "false")}
        self._hb_watchdog = _threading.Thread(
            target=self._heartbeat_watchdog, name="pingoo-hb-watchdog",
            daemon=True)
        self._hb_watchdog.start()
        with self._hb_busy():
            self._reconcile_orphans()

    # A device call (compile/execute) blocked longer than this is
    # treated as wedged: the watchdog stops covering for it and the
    # data plane's liveness detector takes over. Far above any real
    # XLA compile, far below "hung forever".
    _HB_BUSY_GRACE_S = 120.0

    # A device->host sync still blocked after this long is probed, and
    # again at each later mark while it stays blocked: a sync of the
    # served programs takes 1-60 ms, and the stalls that release
    # requests (the "wedge", PERF.md section 7) 2-4 s.
    _SYNC_PROBE_AT_S = (0.25, 1.0, 2.0)

    @contextlib.contextmanager
    def _hb_busy(self, sync: Optional[tuple] = None, device: int = 0):
        """Declare a known-blocking drain-loop window (XLA compile,
        device sync, interpreter fallback, reattach reconciliation):
        the heartbeat watchdog stamps only inside these. `sync` names
        the device arrays a sync window waits for, `device` the chip
        (replica index) they are on."""
        self._busy = (time.monotonic(), sync, device)
        try:
            yield
        finally:
            self._busy = None

    def _heartbeat_watchdog(self) -> None:
        import threading as _threading

        probed = (None, 0)  # (the busy window, probes made of it)
        while not self._stop:
            window = self._busy
            if window is not None \
                    and time.monotonic() - window[0] < self._HB_BUSY_GRACE_S \
                    and not self.chaos.heartbeat_frozen():
                for r in self.rings:
                    r.heartbeat()
                n = probed[1] if probed[0] is window else 0
                if window[1] is not None \
                        and n < len(self._SYNC_PROBE_AT_S) \
                        and time.monotonic() - window[0] \
                        >= self._SYNC_PROBE_AT_S[n]:
                    probed = (window, n + 1)
                    # A thread of its own: if the runtime is stuck the
                    # probe blocks too, and the heartbeat must go on.
                    _threading.Thread(
                        target=self._probe_overdue_sync,
                        args=(window, n), name="pingoo-sync-probe",
                        daemon=True).start()
            time.sleep(0.1)

    def _probe_overdue_sync(self, window: tuple, n: int) -> None:
        """The drain loop has been blocked in one device->host sync
        (`window`: its start in monotonic s, the arrays, their chip).
        Record whether the arrays it waits for are ready on the device,
        then send that chip a round trip of this thread's own (one word
        up, the same word back) and time it: a runtime that answers
        here while the loop stays blocked has lost that sync's
        completion, one that does not answer is stuck as a whole. The
        round trip is also new device traffic, which is what a lost
        completion would be waiting for."""
        import jax

        busy, arrays, device = window
        rec = {"probe": n, "device": device, "overdue_ms": round(
            (time.monotonic() - busy) * 1e3, 1)}
        try:
            rec["ready"] = [bool(a.is_ready()) for a in arrays]
            t0 = time.monotonic()
            word = jax.device_put(np.zeros(1, dtype=np.int32),
                                  self._replica_devices[device])
            word.block_until_ready()
            t1 = time.monotonic()
            np.asarray(word)
            t2 = time.monotonic()
            rec.update(up_ms=round((t1 - t0) * 1e3, 2),
                       back_ms=round((t2 - t1) * 1e3, 2),
                       ready_after=[bool(a.is_ready()) for a in arrays])
            # how soon after the round trip the loop's own sync returned
            # (None: still blocked half a second later)
            rec["loop_back_ms"] = None
            while time.monotonic() - t2 < 0.5:
                if self._busy is not window:
                    rec["loop_back_ms"] = round(
                        (time.monotonic() - t2) * 1e3, 1)
                    break
                time.sleep(0.005)
        except Exception as exc:  # a witness never takes the plane down
            rec["error"] = repr(exc)
        self._sync_probes.append(rec)
        if n == 0:
            ready = all(rec.get("ready") or [False])
            self._sync_overdue_ctr["true" if ready else "false"].inc()
        _log.warning("device sync overdue", extra={"fields": rec})

    # -- ruleset hot-swap (ISSUE 11, docs/RESILIENCE.md) ----------------------

    def _make_staging(self, plan, caps: dict):
        """The zero-copy staging encoder for a plan: plain rotating
        buffers under PINGOO_STAGING=full, packed one-copy layout under
        =compact (ISSUE 15) — slot-direct capped-prefix copies into one
        flat buffer, one device_put per batch."""
        from .engine.batch import (StagingEncoder, resolve_stage_caps,
                                   stage_overflow_thresholds)

        scaps = resolve_stage_caps(plan)
        if scaps is None:
            return StagingEncoder(self.max_batch, field_specs=caps,
                                  nbuf=self._max_inflight + 1)
        return StagingEncoder(
            self.max_batch, field_specs=caps,
            nbuf=self._max_inflight + 1, stage_caps=scaps,
            overflow_thresholds=stage_overflow_thresholds(plan, scaps))

    def _build_plan_state(self, plan) -> dict:
        """Every plan-derived piece of the sidecar's engine state, built
        OFF the drain loop (init, or a request_swap caller's thread —
        compile-ahead through compiler/cache): the drain loop's flip is
        then pointer assignment at a batch boundary, never compilation."""
        from .engine.batch import (resolve_stage_caps,
                                   stage_overflow_thresholds)
        from .engine.verdict import (cascade_banks, donate_batch_buffers,
                                     lane_rows, make_lane_fn,
                                     make_packed_lane_fn,
                                     make_packed_prefilter_fn,
                                     make_prefilter_fn)
        from .obs.perf import (instrument_jit, plan_fingerprint,
                               staging_widths)
        from .sched import MeshExecutor, MeshUnavailable

        state: dict = {"plan": plan}
        # Compile-ledger wrapping (ISSUE 17): composes AFTER jax.jit
        # (donation/static_argnums untouched); passthrough while
        # PINGOO_PERF_LEDGER is off.
        fp = plan_fingerprint(plan)
        widths = staging_widths(plan)

        def _wrap(fn, name):
            return instrument_jit(fn, name, plane="sidecar",
                                  fingerprint=fp, widths=widths)

        state["lane_fn"] = _wrap(make_lane_fn(
            plan, service_groups=self._groups or None,
            with_rule_hits=self._provenance_on,
            donate=donate_batch_buffers()), "lanes")
        state["cascade_banks"] = cascade_banks(plan)
        state["lane_rows"] = lane_rows(plan, len(self._groups),
                                       self._provenance_on)
        # Compact staging (ISSUE 15): the packed twins decode the
        # one-copy buffer on device; built only under
        # PINGOO_STAGING=compact (the default full arm traces nothing
        # new). Caps/thresholds flip with the plan at the same batch
        # boundary the fns do.
        state["stage_caps"] = resolve_stage_caps(plan)
        state["stage_thresholds"] = None
        state["packed_lane_fn"] = None
        state["packed_pf_fn"] = None
        if state["stage_caps"] is not None:
            state["stage_thresholds"] = stage_overflow_thresholds(
                plan, state["stage_caps"])
            state["packed_lane_fn"] = _wrap(make_packed_lane_fn(
                plan, service_groups=self._groups or None,
                with_rule_hits=self._provenance_on,
                donate=donate_batch_buffers()), "lanes")
            ppf = make_packed_prefilter_fn(plan)
            state["packed_pf_fn"] = \
                _wrap(ppf.fn, "prefilter") if ppf is not None else None
        # Services whose route predicate fell back to host interpretation
        # are merged into the device route lane per batch (per group).
        host_routes: list = []
        by_index = {r.index: r for r in plan.rules}
        for g in self._groups:
            hr = []
            for order, name in enumerate(g):
                ridx = plan.route_index.get(name)
                if ridx is not None and by_index[ridx].host:
                    hr.append((order, by_index[ridx].program))
            host_routes.append(hr)
        state["host_routes"] = host_routes
        # Serving mesh (ISSUE 6): tp padding must land in plan.np_tables
        # before device_tables() materializes; failures degrade to the
        # single-device path (never crash the drain) and stay visible
        # via pingoo_mesh_devices == 1.
        try:
            mesh = MeshExecutor(plan, plane="sidecar",
                                metrics=self.sched.metrics)
        except (MeshUnavailable, ValueError) as exc:
            self.ladder.note_failure("mesh", exc)
            mesh = MeshExecutor(plan, spec=(1, 1, 1), plane="sidecar",
                                metrics=self.sched.metrics)
        state["mesh"] = mesh
        tables = plan.device_tables()
        state["tables"] = (mesh.place_tables(tables)
                           if mesh.active else tables)
        # One whole copy a chip (`replicas`): the first stays where JAX
        # put it, so chip 0 runs the one-chip program; a swap builds
        # every copy anew here, off the loop.
        state["replica_tables"] = [state["tables"]] + [
            _place_on(state["tables"], device)
            for device in self._replica_devices[1:]]
        # The packed batch's row pad, one program a rung, compiled and
        # run here on every chip at the row stride the encoder will use
        # (boot, or a swap's compile-ahead), so that no served batch
        # meets its compile.
        state["pad_fns"] = {}
        if state["packed_lane_fn"] is not None and not mesh.active \
                and self._staging is not None:
            import jax

            from .engine.batch import UPLOAD_ROWS
            from .engine.verdict import make_pad_fn

            width = self._staging.packed_width(state["stage_caps"])
            for rows in UPLOAD_ROWS:
                if rows < self.max_batch:
                    pad = _wrap(make_pad_fn(self.max_batch), "pad")
                    for device in self._replica_devices:
                        np.asarray(pad(jax.device_put(
                            np.zeros((rows, width), np.uint8), device)))
                    state["pad_fns"][rows] = pad
        state["pf_fn"] = None
        state["pf_gated_banks"] = 0
        state["pf_attr"] = None
        pf = make_prefilter_fn(plan)
        if pf is not None:
            state["pf_fn"] = _wrap(pf.fn, "prefilter")
            state["pf_gated_banks"] = len(pf.gated)
            if self._provenance_on:
                from .obs.provenance import PrefilterAttribution

                state["pf_attr"] = PrefilterAttribution(
                    pf.masked, plane="sidecar")
        state["dev_cols"] = np.asarray(plan.device_rule_indices,
                                       dtype=np.int64)
        return state

    def _adopt_plan_state(self, plan, lists, state: dict) -> None:
        """Flip the drain loop onto a prebuilt plan state. Only safe at
        a batch boundary (init, or _apply_swaps after a full drain):
        _dispatch/_complete read these references per batch."""
        self.plan = plan
        if lists is not None:
            self.lists = lists
        self._lane_fn = state["lane_fn"]
        self._host_routes = state["host_routes"]
        self.mesh = state["mesh"]
        self._tables = state["tables"]
        self._replica_tables = state["replica_tables"]
        self._pf_fn = state["pf_fn"]
        self._pf_gated_banks = state["pf_gated_banks"]
        self._pf_attr = state["pf_attr"]
        self._dev_cols = state["dev_cols"]
        self._dfa_mode0 = getattr(plan, "dfa_default_mode", "auto")
        self._dfa_probe = False
        # Compact staging (ISSUE 15): re-cap the staging encoder's
        # packed layout for the new plan at the same flip — every batch
        # is encoded AND decoded under one cap set, so a swap that
        # widens a cap changes layout only at this batch boundary.
        self._stage_caps = state.get("stage_caps")
        self._packed_lane_fn = state.get("packed_lane_fn")
        self._packed_pf_fn = state.get("packed_pf_fn")
        self._pad_fns = state.get("pad_fns") or {}
        if self._staging is not None and self._stage_caps is not None:
            try:
                self._staging.set_stage_caps(
                    self._stage_caps, state.get("stage_thresholds"))
            except ValueError:
                # Encoder built without packed buffers (mode flipped
                # between boot and swap): keep the per-field path.
                self._packed_lane_fn = self._packed_pf_fn = None
        if self._stage_caps:
            from .obs import REGISTRY
            from .obs.schema import STAGING_METRICS

            for field, cap in self._stage_caps.items():
                REGISTRY.gauge(
                    "pingoo_staging_field_cap",
                    STAGING_METRICS["pingoo_staging_field_cap"],
                    labels={"field": field}).set(int(cap))
        # pingoo_scan_columns_total: staged against walked columns of
        # the fields this plan's byte loops scan (ops/live_columns.py).
        from .engine.batch import ScanColumnCounters

        self._scan_columns = ScanColumnCounters(
            "sidecar", plan, rows_sharded=self.mesh.dp > 1)
        # pingoo_cascade_rows_total / _bucket_rows_total: what the lanes
        # program counted of its own cascade, folded in `_complete`.
        from .obs.pipeline import CascadeCounters

        self._cascade = CascadeCounters("sidecar", state["cascade_banks"])
        self._lane_rows = state["lane_rows"]
        self._plan_state = state
        if self._provenance_on:
            from .obs.flightrecorder import (FlightRecorder,
                                             register_recorder)
            from .obs.provenance import ParityAuditor, RuleAttribution

            if self._attribution is not None:
                self._attribution.close()
            if self.parity is not None:
                self.parity.stop()
            self.flight_recorder = register_recorder(FlightRecorder(
                "sidecar", rule_names=plan.rule_names))
            self._attribution = RuleAttribution(plan.rule_names,
                                                plane="sidecar")
            self.parity = ParityAuditor(plan, self.lists,
                                        plane="sidecar",
                                        recorder=self.flight_recorder)

    def request_swap(self, plan, lists=None, tenant: str = "default",
                     state: Optional[dict] = None):
        """Thread-safe ruleset hot-swap request.

        Builds the new plan's engine state HERE — the caller's thread,
        off the drain loop (pair with compiler/cache's
        compile_ruleset_cached or engine/hotswap.TenantPlanStore for
        compile-ahead) — then queues a SwapHandle the drain loop flips
        to at its next batch boundary: in-flight batches finish on the
        old plan, admissions after the flip use the new one, and every
        verdict belongs to exactly one epoch. `handle.wait()` blocks
        until the flip; the loop must be running (a request made after
        shutdown resolves "rejected" at the final flush)."""
        from .engine.hotswap import SwapHandle, note_swap

        if state is None:
            try:
                state = self._build_plan_state(plan)
            except Exception as exc:
                note_swap("sidecar", tenant, "rejected")
                raise RuntimeError(
                    f"hot-swap build failed for tenant {tenant!r}: "
                    f"{exc}") from exc
        handle = SwapHandle(plan=plan, tenant=tenant, lists=lists,
                            state=state)
        with self._swap_lock:
            self._swap_queue.append(handle)
        return handle

    def _apply_swaps(self, inflight, pend_parts, pend_n,
                     oldest_enq_ms, pend_buf):
        """Apply every queued hot-swap at this batch boundary: launch
        and complete everything ADMITTED on the old plan first (each
        ticket posts exactly once, on the plan of its admission epoch —
        zero dropped, zero double-posted), then flip to the prebuilt
        state. The pause clock covers drain+flip only; the requester
        compiled ahead on its own thread (engine/hotswap.py)."""
        from .engine.hotswap import note_swap, set_epoch_gauge

        t0 = time.monotonic()
        with self._hb_busy(), self._pipe.stage("swap"):
            if pend_parts:
                self._launch(inflight, pend_parts, pend_n, oldest_enq_ms,
                             pend_buf)
                pend_parts, pend_n, oldest_enq_ms = [], 0, None
                pend_buf = self._take_slot_buf() if self._zero_copy \
                    else None
            while inflight:
                self._complete_oldest(inflight, "drain")
            while True:
                with self._swap_lock:
                    if not self._swap_queue:
                        break
                    handle = self._swap_queue.pop(0)
                try:
                    self._adopt_plan_state(handle.plan, handle.lists,
                                           handle.state)
                except Exception as exc:  # never kill the drain loop
                    note_swap("sidecar", handle.tenant, "rejected")
                    handle.resolve(self.ruleset_epoch, 0.0,
                                   result="rejected", error=exc)
                    continue
                self.ruleset_epoch += 1
                self.tenant = handle.tenant
                pause_ms = (time.monotonic() - t0) * 1e3
                set_epoch_gauge("sidecar", self.ruleset_epoch)
                note_swap("sidecar", handle.tenant, "ok")
                self._stage["sched"].observe(pause_ms)
                self.swap_pauses_ms.append(pause_ms)
                handle.resolve(self.ruleset_epoch, pause_ms)
        return pend_parts, pend_n, oldest_enq_ms, pend_buf

    def run(self, max_requests: Optional[int] = None) -> int:
        """Blocking drain loop; returns requests processed.

        A pipeline of at most `pipeline_depth` batches: a batch is
        DISPATCHED (jax is async) and the loop turns to the next dequeue
        while the device works on it. The oldest batch in flight is
        completed (host rules, lanes to the host, merge, post) as soon
        as its lanes are ready on the device (looked at after each
        dequeue pass and after each launch), and otherwise when
        `pipeline_depth` batches are in flight (the loop blocks on the
        oldest) or on a pass that launched nothing — always oldest
        first. While the device is the pace nothing is ready early and
        the depth decides: batch N+1's host work hides behind batch N's
        device time. While the host is the pace a batch leaves once the
        chip is done with it, not `pipeline_depth - 1` passes later.

        Admission (ISSUE 6): dequeued slots ACCUMULATE across drain
        cycles under the continuous-batching scheduler — a batch
        launches when it is full, or when the oldest request's
        remaining deadline slack (enq_ms clock) no longer covers the
        EWMA dispatch estimate. PINGOO_SCHED_MODE=fixed restores the
        legacy dispatch-every-pass window.
        """
        from collections import deque

        import threading as _threading

        # stop() joins this thread before callers unmap the rings — a
        # dequeue racing Ring.close() would be a use-after-munmap
        # segfault in the ctypes call.
        self._thread = _threading.current_thread()
        inflight: deque = deque()
        sched = self.sched
        continuous = sched.config.mode == "continuous"
        pend_parts: list[tuple[Ring, np.ndarray]] = []
        pend_n = 0
        oldest_enq_ms: Optional[int] = None
        # Zero-copy accumulation buffer (PINGOO_PIPELINE=on): every
        # ring's dequeue FFI lands its slots contiguously at this
        # buffer's next free row, so the merged launch batch is one
        # view — the buffer travels with the batch and returns to the
        # pool when `_complete` finishes it.
        pend_buf = self._take_slot_buf() if self._zero_copy else None
        self._pipe.loop_start()
        while not self._stop:
            # Liveness heartbeat (ring v5): one relaxed shm store per
            # ring per poll cycle. Deliberately stamped from THIS loop
            # (not a free-running helper thread): a wedged drain loop
            # must look dead to the data plane's
            # PINGOO_SIDECAR_TIMEOUT_MS detector. The one exception is
            # declared known-blocking windows (XLA compile, device
            # sync, interpreter fallback — `_hb_busy`), which the
            # bounded watchdog covers so a cold compile under live
            # traffic does not flip the plane degraded —
            # docs/RESILIENCE.md.
            if not self.chaos.heartbeat_frozen():
                for r in self.rings:
                    r.heartbeat()
            # Body-window drain (ISSUE 13): before the request drain so
            # a flow's body verdict never waits a full cycle behind the
            # metadata batch that admitted it.
            if self._body_scan is not None:
                with self._pipe.stage("bodies"):
                    self._drain_bodies()
            # Ruleset hot-swap boundary (ISSUE 11). The swap-storm
            # chaos rung re-requests the CURRENT plan so any verdict
            # drift it produces is a swap-protocol bug by construction
            # (state reused: the storm isolates drain/flip mechanics).
            if self.chaos.swap_due(self.batches):
                self.request_swap(self.plan, tenant=self.tenant,
                                  state=self._plan_state)
            if self._swap_queue:
                pend_parts, pend_n, oldest_enq_ms, pend_buf = \
                    self._apply_swaps(inflight, pend_parts, pend_n,
                                      oldest_enq_ms, pend_buf)
            # One merged dequeue pass across all worker rings, from a
            # rotating start. Each ring first gets a fair share of the
            # budget (what a shallow ring leaves of its share passes on
            # to the next), so a ring deeper than a whole batch keeps no
            # sibling's rows out of this one, let alone starves them
            # into the data plane's verdict timeout (which fails open);
            # a ring that filled its share gets a second turn at what
            # is left once every ring has had its first.
            budget = self.max_batch - pend_n
            nrings = len(self.rings)
            self._ring_rr = (self._ring_rr + 1) % nrings
            got = 0
            turns = [((self._ring_rr + i) % nrings, nrings - i)
                     for i in range(nrings)]  # (ring, rings still to share)
            while turns and budget > 0:
                ri, sharers = turns.pop(0)
                r = self.rings[ri]
                share = -(-budget // sharers)
                if pend_buf is not None:
                    fill = pend_n + got
                    k = r.dequeue_batch_into(
                        pend_buf[fill:fill + share])
                    s = pend_buf[fill:fill + k]
                else:
                    s = r.dequeue_batch(share)
                if len(s) == share and sharers > 1:
                    turns.append((ri, 1))
                if len(s):
                    if self.geoip is not None:
                        # Enrich IN the per-ring slot arrays (the
                        # sidecar owns them: dequeue_batch copies out
                        # of the ring scratch, dequeue_batch_into
                        # lands in the batch's pooled buffer) BEFORE
                        # merging: both the device batch and the
                        # overflow-spill re-interpretation
                        # (_interpret_overflow_row reads the per-ring
                        # part) must see the same geo values.
                        self._enrich_slots(s)
                    pend_parts.append((r, s))
                    self._ring_rows[ri].inc(len(s))
                    budget -= len(s)
                    got += len(s)
                    first = int(s["enq_ms"].min())
                    if oldest_enq_ms is None or first < oldest_enq_ms:
                        oldest_enq_ms = first
            pend_n += got
            if got:
                self._pipe.wake()
            # before this pass's rows are encoded
            self._complete_ready(inflight)
            launch = False
            if pend_n:
                if not continuous or pend_n >= self.max_batch:
                    launch = True
                else:
                    now_ms = int(self.ring.lib.pingoo_ring_now_ms())
                    launch = sched.should_launch(
                        pend_n, oldest_enq_ms / 1e3, now_ms / 1e3)
            if launch:
                self._launch(inflight, pend_parts, pend_n, oldest_enq_ms,
                             pend_buf)
                pend_parts, pend_n, oldest_enq_ms = [], 0, None
                if pend_buf is not None:
                    pend_buf = self._take_slot_buf()
                # what became ready while that batch was encoded
                self._complete_ready(inflight)
            if min(self._replica_inflight) >= self.pipeline_depth:
                self._complete_oldest(inflight, "depth")  # the bound
            elif inflight and not launch and self.replicas == 1:
                self._complete_oldest(inflight, "drain")
            if got == 0 and not launch and not inflight:
                if not pend_parts and max_requests is not None \
                        and self.processed >= max_requests:
                    break
                self._pipe.idle()
                time.sleep(self.idle_sleep_s)
            elif got == 0 and not launch and self.replicas > 1:
                # Several chips: nothing to launch, none of the batches
                # in flight ready. Blocking on the oldest (the drain
                # rule) would hold a batch that another chip finishes
                # meanwhile, and keep new rows off the chips that are
                # free; poll them all again instead.
                self._pipe.idle()
                time.sleep(self.idle_sleep_s)
            if max_requests is not None and self.processed >= max_requests \
                    and not inflight and not pend_parts:
                break
        # Flush: accumulated-but-unlaunched slots still get verdicts
        # (the data plane would otherwise eat a fail-open timeout).
        if pend_parts:
            self._launch(inflight, pend_parts, pend_n, oldest_enq_ms,
                         pend_buf)
        elif pend_buf is not None:
            self._slot_pool.append(pend_buf)
        while inflight:
            self._complete_oldest(inflight, "drain")
        # Final body drain: FINAL windows already in the ring still get
        # verdicts (else their held requests eat the fail-open timeout).
        if self._body_scan is not None:
            with self._pipe.stage("bodies"):
                self._drain_bodies()
        # A swap that never reached a batch boundary before shutdown is
        # rejected, not leaked: wake its requester.
        with self._swap_lock:
            leftovers, self._swap_queue = self._swap_queue, []
        if leftovers:
            from .engine.hotswap import note_swap

            for handle in leftovers:
                note_swap("sidecar", handle.tenant, "rejected")
                handle.resolve(self.ruleset_epoch, 0.0,
                               result="rejected",
                               error=RuntimeError("sidecar stopped"))
        self._pipe.loop_stop()
        return self.processed

    def _launch(self, inflight, parts, n: int,
                oldest_enq_ms: Optional[int], slot_buf) -> None:
        """Dispatch one batch into `inflight`. Its staging views are the
        StagingEncoder's next buffer set, of `nbuf` it hands out in
        turn, and `_complete` reads a batch's views (host rules, the
        overflow mask, provenance) until the batch is done: so first
        complete, in launch order, the batch still holding that set
        (`staging`). On one chip none does (at most the depth in flight
        of depth + 1 sets, completed in launch order); on several, a
        batch on a slow chip can outlast `nbuf` launches on the
        others."""
        if self._staging is not None:
            reuse = self._staging.checkouts + 1 - self._staging.nbuf
            while any(e[-1].staged is not None and e[-1].staged <= reuse
                      for e in inflight):
                self._complete_at(inflight, 0, "staging")
        inflight.append(self._dispatch(parts, n, oldest_enq_ms,
                                       slot_buf=slot_buf))

    def _complete_oldest(self, inflight, how: str) -> None:
        """Complete the oldest batch in flight, counted by the rule that
        chose it: `ready` (its lanes were there), `depth` (every chip
        holds its in-flight bound: `_complete` blocks on the device) or
        `drain` (one chip: a pass that launched nothing; the flush, a
        swap boundary)."""
        self._complete_at(inflight, 0, how)

    def _complete_at(self, inflight, i: int, how: str) -> None:
        """Take the `i`-th batch in launch order out of flight and
        complete it, counted by `how`."""
        entry = inflight[i]
        del inflight[i]
        self._replica_inflight[entry[-1].device] -= 1
        self._pipe.note_completion(how)
        self._complete(*entry, inflight=inflight)

    def _complete_ready(self, inflight) -> None:
        """Complete, in launch order, every batch in flight whose device
        lanes are already ready, but none behind an unready batch of its
        own chip: a chip runs its batches in order, so its batches
        complete FIFO; the chips' batches in whichever order their
        lanes come, so a chip that is done never waits on a slower one.
        On one chip that is: stop at the first batch not ready. `dev`
        None is a batch the interpreter serves (device rung demoted):
        nothing to wait for."""
        i, waiting = 0, set()
        while i < len(inflight):
            chip = inflight[i][-1].device
            if chip not in waiting:
                dev = inflight[i][3]
                if dev is None or dev.is_ready():
                    self._complete_at(inflight, i, "ready")
                    continue
                waiting.add(chip)
                if len(waiting) == self.replicas:
                    return
            i += 1

    def _drain_bodies(self) -> None:
        """Drain each ring's body-window ring through the streaming
        scanner and post per-flow body verdicts back on that ring's
        verdict ring, ticket-tagged with BODY_VERDICT_BIT. On the
        ladder's demoted "body" rung (or a scanner fault) every FINAL
        window fails open (action 0, metadata-only) so the data plane's
        held requests never stall on a broken scanner."""
        bs = self._bodyscan_mod
        for r in self.rings:
            slots = r.dequeue_bodies()
            if not len(slots):
                continue
            windows = [bs.BodyWindow(
                flow_id=int(s["flow"]), win_seq=int(s["win_seq"]),
                data=s["data"][:int(s["win_len"])].tobytes(),
                final=bool(s["flags"] & BODY_FLAG_FINAL),
                abort=bool(s["flags"] & BODY_FLAG_ABORT))
                for s in slots]
            verdicts = None
            if self.ladder.try_rung("body"):
                try:
                    # Busy window: the first scan per pow2 row bucket
                    # compiles the chunk kernels.
                    with self._hb_busy():
                        verdicts = self._body_scan.scan_windows(windows)
                    self.ladder.note_success("body")
                except Exception as exc:
                    self.ladder.note_failure("body", exc)
                    # Carry state is suspect after a mid-scan fault:
                    # drop every live flow (their FINAL windows fail
                    # open below or at the data plane's body sweep).
                    self._body_scan.flows.clear()
                    verdicts = None
            if verdicts is None:
                verdicts = [bs.BodyVerdict(w.flow_id, degraded=True)
                            for w in windows if w.final]
            for v in verdicts:
                ticket = v.flow_id | BODY_VERDICT_BIT
                action = 0 if v.degraded else v.action_byte()
                while not r.post_verdict(ticket, action):
                    if self._stop:
                        return
                    time.sleep(self.idle_sleep_s)
                self.body_verdicts += 1
        self._body_scan.evict_stale()

    def _take_slot_buf(self) -> np.ndarray:
        """One pooled REQUEST_SLOT_DTYPE accumulation buffer (pipeline
        hot path: pop; cold path when every pooled buffer is riding an
        in-flight batch: allocate — the pool absorbs it back later)."""
        try:
            return self._slot_pool.popleft()
        except IndexError:
            return np.zeros(self.max_batch, dtype=REQUEST_SLOT_DTYPE)

    def _ring_depths(self) -> dict:
        """Requests still waiting in each of this sidecar's rings, by
        ring name (one telemetry snapshot per ring per LAUNCH, not per
        request); `stats()` shows what this last read."""
        for i, r in enumerate(self.rings):
            try:
                self._ring_depth_seen[i] = int(r.telemetry()["depth"])
            except Exception:
                pass
        return dict(zip(self.ring_names, self._ring_depth_seen))

    def _queued_depth(self) -> int:
        """The pingoo_sched_queue_depth gauge: all rings together."""
        return sum(self._ring_depths().values())

    def _begin_batch(self, parts, n: int):
        """The batch's span record; counts the rings that gave it rows
        and puts it on a chip: the one with the fewest batches in
        flight, the tie to the next after the last chosen."""
        rings = len({id(r) for r, _ in parts})
        self._batch_rings.inc(rings)
        rec = self._pipe.begin(self.pipeline_mode, n, rings)
        load = self._replica_inflight
        low = min(load)
        start = self._replica_rr + 1
        chip = next(c % self.replicas
                    for c in range(start, start + self.replicas)
                    if load[c % self.replicas] == low)
        self._replica_rr = chip
        load[chip] += 1
        self._pipe.note_launch(chip, sum(load))
        rec.device = chip
        return rec

    def _dispatch(self, parts, n: int, oldest_enq_ms: Optional[int],
                  slot_buf=None):
        """Encode + launch one merged batch (jax dispatch is async);
        returns the in-flight tuple `_complete` consumes."""
        from .engine.batch import RequestBatch, bucket_arrays, pad_batch

        rec = self._begin_batch(parts, n)
        with self._pipe.stage("encode", rec) as sp:
            self.chaos.stage("encode")
            batch = raw = None
            if slot_buf is not None:
                # Zero-copy plane (PINGOO_PIPELINE=on): the dequeue FFI
                # already landed every part contiguously in `slot_buf`, so
                # the merged batch is one view — no concatenate — and the
                # staging encoder fills its reused bucketed+padded
                # matrices straight from the slot fields (no
                # slots_to_arrays intermediates, no bucket/pad copies).
                # `raw` is the unpadded row view of the same staging
                # arrays: bucketed columns are a superset of every row's
                # length, and every consumer (host_rule_lanes,
                # batch_to_contexts) reads data[:len].
                slots = slot_buf[:n]
                if self.ladder.try_rung("pipeline"):
                    try:
                        batch = self._staging.encode_slots(
                            slots, pad_to=self.max_batch)
                        rec.staged = self._staging.checkouts
                        raw = RequestBatch(
                            size=n,
                            arrays={k: v[:n]
                                    for k, v in batch.arrays.items()},
                            overflow=(batch.overflow[:n]
                                      if batch.overflow is not None
                                      else None))
                        self.ladder.note_success("pipeline")
                    except Exception as exc:
                        # Ladder pipeline rung: a broken staging encoder
                        # demotes THIS plane to the legacy encode chain
                        # below (bit-identical, tests/test_pipeline.py)
                        # until a backoff probe re-promotes it.
                        self.ladder.note_failure("pipeline", exc)
                        batch = raw = None
            else:
                slots = parts[0][1] if len(parts) == 1 else np.concatenate(
                    [s for _, s in parts])
            if batch is None:
                # Legacy encode chain (PINGOO_PIPELINE=off, or the ladder's
                # pipeline rung demoted): pad the batch axis to one fixed
                # shape (a partial batch would otherwise be a new XLA
                # program — compile stall on the serving path) and bucket
                # field lengths to powers of two so the NFA scan walks the
                # batch's longest value, not the 2048-byte slot capacity
                # (at most log2(cap) shapes per field).
                raw = RequestBatch(size=n, arrays=slots_to_arrays(slots))
                batch = pad_batch(
                    RequestBatch(size=n, arrays=bucket_arrays(raw.arrays)),
                    self.max_batch)
            # Mesh placement (ISSUE 6): the device programs read the
            # dp-sharded view; `raw` stays host-resident for host-rule
            # interpretation and spill re-evaluation.
            arrays = batch.arrays
            if self.mesh.active:
                arrays = self.mesh.shard_batch(arrays)
            if batch.upload_rows:
                rec.stats["upload_rows"] = batch.upload_rows
            sp.next("prefilter")
            self.chaos.stage("dispatch")
            dev = None
            self._dfa_rung_tick()
            rec.cascade, rec.lane_rows = self._cascade, self._lane_rows
            # Ladder device rung: while demoted, skip the dispatch
            # entirely (the interpreter serves in `_complete`) except for
            # backoff probes; a dispatch-time exception demotes — it no
            # longer kills the drain thread.
            if self.ladder.try_rung("device"):
                try:
                    self.chaos.maybe_xla_error(self.batches)
                    # True padded lane batch for the compile ledger's
                    # surface check (packed blobs hide the batch axis).
                    from .obs.perf import batch_leading_dim, \
                        set_dispatch_context
                    set_dispatch_context(batch=batch_leading_dim(arrays))
                    # Busy window: the jitted calls return async once
                    # compiled, but the FIRST call per pow2 bucket
                    # blocks in XLA for seconds — the watchdog heartbeats
                    # through it so the data plane doesn't flip degraded.
                    with self._hb_busy():
                        dev = self._run_lanes(batch, arrays, n,
                                              rec.device, sp)
                        # Queue the batch's ONE device->host copy behind
                        # the program: `_complete` finds the bytes there.
                        dev.copy_to_host_async()
                except Exception as exc:
                    self._note_device_failure(exc)
                    dev = None
            sp.next("dispatch")  # no-op when a branch above got there
        # Staged-bytes accounting (ISSUE 15): the transfer volume
        # behind this dispatch window, on the metrics surface AND into
        # the scheduler's bytes-keyed dispatch EWMA.
        if batch.staged_bytes:
            self._staged_bytes_counter[
                "compact" if batch.packed is not None
                else "full"].inc(batch.staged_bytes)
            self.sched.observe_dispatch_bytes(
                batch.staged_bytes, rec.span_ms("prefilter", "dispatch"))
        if batch.packed is not None:
            self._staged_rows_counter["uploaded"].inc(batch.upload_rows)
            self._staged_rows_counter["padded"].inc(batch.size)
        self._scan_columns.note(batch.arrays)
        # Scheduler accounting at launch: occupancy + queue depth, the
        # sidecar's `sched` stage (oldest enqueue -> launch hold on the
        # ring clock), and the fail-open mask for rows whose deadline
        # is unmeetable even by this immediate launch.
        now_ms = int(self.ring.lib.pingoo_ring_now_ms())
        self.sched.note_launch(n, self._queued_depth())
        if oldest_enq_ms is not None:
            self._stage["sched"].observe(
                max(0.0, float(now_ms - oldest_enq_ms)))
        skip_masks = None
        if self.sched.config.failopen == "allow":
            # Per-stage budget slice (ISSUE 9): encode+dispatch are
            # already spent at this point, so the unmeetable test
            # charges each row only the REMAINING work — the compute
            # stage's estimate — instead of the whole-batch wall (which
            # would fail open rows that could still make the deadline).
            skip_masks = self._failopen_late_rows(
                parts, now_ms,
                est_ms=self.sched.cost.estimate_stage(
                    "compute", self.max_batch))
        # `rec` rides the in-flight tuple into _complete: its recorded
        # points feed the compute window and the sampled timeline, and
        # the staging mode lands in every flight row.
        rec.tags["staging_mode"] = ("compact" if batch.packed is not None
                                    else "full")
        return (parts, slots, raw, dev, n, skip_masks, slot_buf, rec)

    def _run_lanes(self, batch, arrays, n: int, chip: int, sp=None):
        """Launch one encoded batch's device work on `chip` (async):
        Stage A, then the lanes program, over that chip's tables;
        returns the lanes' stacked output. `sp` is the batch's open
        stage, moved to `dispatch` between the two programs."""
        tables = self._replica_tables[chip]
        pf_hits = pf_aux = None
        # Compact staging (ISSUE 15): ONE device_put of the packed
        # buffer replaces the per-field transfers; the packed twins
        # slice the fields back out on device. Mesh stays on the
        # per-field path (the shard plan addresses named arrays).
        if batch.packed is not None and self._packed_lane_fn is not None \
                and not self.mesh.active:
            import jax

            # Ship the batch's upload height and pad it to the batch on
            # the chip: the pair reads the bytes a full upload would.
            rows = batch.upload_rows
            dev_packed = jax.device_put(batch.packed[:rows],
                                        self._replica_devices[chip])
            if rows < batch.size:
                dev_packed = self._pad_fns[rows](dev_packed)  # async
            if self._packed_pf_fn is not None:
                pf_hits, pf_aux = self._packed_pf_fn(
                    tables, dev_packed, batch.layout)  # async
            if sp is not None:
                sp.next("dispatch")
            return self._packed_lane_fn(
                tables, dev_packed, batch.layout, pf_hits, np.int32(n),
                pf_aux)  # async
        if self._pf_fn is not None:
            pf_hits, pf_aux = self._pf_fn(tables, arrays)  # async
        if sp is not None:
            sp.next("dispatch")
        # The traced n masks batch-padding rows out of the attribution
        # lane on device; Stage A's aux goes in as the device array it
        # is and comes back in the lanes' own rows.
        return self._lane_fn(tables, arrays, pf_hits, np.int32(n),
                             pf_aux)  # async

    def warm_replicas(self) -> None:
        """`replicas` > 1: before the listener takes traffic, compile
        and run the program pair once on every chip, over one empty
        request encoded as the loop encodes a batch, so that no batch
        of the served traffic meets a compile. Chip 0 compiles first;
        the others, which reuse its trace, compile side by side. One
        chip: nothing (its first batch compiles, as it always has)."""
        if self.replicas == 1:
            return
        import threading as _threading

        from .engine.batch import RequestBatch, bucket_arrays, pad_batch
        from .obs.perf import batch_leading_dim, set_dispatch_context

        slots = np.zeros(1, dtype=REQUEST_SLOT_DTYPE)
        if self._staging is not None:
            batch = self._staging.encode_slots(slots, pad_to=self.max_batch)
        else:
            batch = pad_batch(RequestBatch(size=1, arrays=bucket_arrays(
                slots_to_arrays(slots))), self.max_batch)
        failed: list = []

        def warm(chip):
            try:
                set_dispatch_context(batch=batch_leading_dim(batch.arrays))
                np.asarray(self._run_lanes(batch, batch.arrays, 1, chip))
            except Exception as exc:
                failed.append((chip, exc))

        warm(0)
        threads = [_threading.Thread(target=warm, args=(chip,),
                                     name=f"pingoo-warm-{chip}")
                   for chip in range(1, self.replicas)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if failed:
            chip, exc = failed[0]
            raise RuntimeError(f"warming chip {chip} failed: {exc!r}") \
                from exc
        _log.info("replicas warm", extra={"fields": {
            "replicas": self.replicas,
            "devices": [str(d) for d in self._replica_devices]}})

    def _failopen_late_rows(self, parts, now_ms: int,
                            est_ms: Optional[float] = None) -> list:
        """PINGOO_SCHED_FAILOPEN=allow: rows whose deadline cannot be
        met even by the launch happening right now get an immediate
        allow verdict (the reference's fail-open posture — attacks pass
        rather than stall the data plane); their device verdicts are
        computed but never posted. Returns one keep-mask per part.
        `est_ms` is the cost still ahead of the rows — the caller's
        stage-budget slice; defaults to the full-batch estimate."""
        if est_ms is None:
            est_ms = self.sched.cost.estimate(self.max_batch)
        deadline_ms = self.sched.config.deadline_ms
        masks = []
        for ring, part in parts:
            enq = part["enq_ms"].astype(np.int64)
            late = (now_ms + est_ms) > (enq + deadline_ms)
            if late.any():
                tickets = np.ascontiguousarray(part["ticket"][late],
                                               dtype=np.uint64)
                acts0 = np.zeros(len(tickets), dtype=np.uint8)
                done = 0
                while done < len(tickets):
                    done += ring.post_verdicts(tickets[done:],
                                               acts0[done:])
                    if done < len(tickets):
                        if self._stop:
                            break
                        time.sleep(self.idle_sleep_s)
                ring.record_waits(part["enq_ms"][late])
                self.sched.note_failopen(int(late.sum()))
            masks.append(~late)
        return masks

    def _enrich_slots(self, slots: np.ndarray) -> None:
        """Fill asn/country in place for rows the producer enqueued with
        the unknown markers (asn 0 + country "XX"). GeoipDB caches both
        hits and misses (host/geoip.py), so steady-state cost per row is
        one dict probe; everything downstream (device batch encoding AND
        overflow-spill re-interpretation) reads the enriched slots."""
        import ipaddress

        need = (slots["asn"] == 0) & (slots["country"] == b"XX")
        if not need.any():
            return
        ips16 = slots["ip"].reshape(-1, 16)
        for i in np.nonzero(need)[0]:
            addr = ipaddress.ip_address(bytes(ips16[i]))
            mapped = getattr(addr, "ipv4_mapped", None)
            try:
                rec = self.geoip.lookup(mapped or addr)
            except Exception:
                continue  # not found / loopback: keep the XX/0 markers
            slots["asn"][i] = rec.asn
            cc = rec.country.encode("ascii", "replace")[:2]
            if len(cc) == 2:
                slots["country"][i] = cc

    def _to_host(self, dev) -> np.ndarray:
        """Materialise a device array on the host, counted: a batch owes
        `pingoo_sidecar_host_copies_total` exactly one."""
        self._pipe.host_copies.inc()
        return np.asarray(dev)

    def _complete(self, parts, slots, raw_batch, dev, n: int, skip_masks,
                  slot_buf, rec, inflight=()) -> None:
        """Resolve an in-flight batch (`_dispatch`'s tuple): host rules,
        the device sync (the batch's one device->host copy: lanes,
        cascade counts, attribution lane and Stage A's counts in one
        stacked array), merge, post, provenance. `inflight` is what
        stays in flight, which the posted floor stays under."""
        from .engine.verdict import (cascade_counts, host_rule_lanes,
                                     merge_lanes, rule_hit_counts,
                                     stage_a_counts)

        with self._pipe.stage("host_rules", rec) as sp:
            # Host-interpreted rules run on the UNPADDED batch while the
            # device lanes are still in flight (jax dispatch is async).
            host = host_rule_lanes(self.plan, raw_batch, self.lists)
            dev_lanes = cascade = rule_hits = pf_aux = None
            sp.next("device_wait")
            if dev is not None:
                try:
                    with self._hb_busy(sync=(dev,), device=rec.device):
                        full = self._to_host(dev)
                    dev_lanes = full[:, :n]  # drop padding
                    # The cascade's own row counts, the attribution
                    # lane and Stage A's counts ride the same copy
                    # (`rec.lane_rows`: the rows under the lanes); the
                    # spans from here on say what the batch cost: rows
                    # the device lanes block, rows rechecked.
                    cascade = cascade_counts(full, rec.lane_rows)
                    if rec.lane_rows.rule_hits:
                        rule_hits = rule_hit_counts(full, rec.lane_rows)
                    if rec.lane_rows.stage_a:
                        pf_aux = stage_a_counts(full, rec.lane_rows)
                    rec.stats = {
                        "blocked": int((dev_lanes[1] == 1).sum()),
                        "recheck": sum(c[2] for c in cascade)}
                    self._note_device_success()
                except Exception as exc:
                    # jax dispatch is async — a device/runtime error
                    # only surfaces at this sync. Demote (ladder device
                    # rung) and serve the batch from the interpreter
                    # below instead of killing the drain thread.
                    self._note_device_failure(exc)
            # The wait's exit also fed the executor's compute window,
            # which runs dispatch-end -> results ready, NOT just this
            # residual block (it shrinks to ~0 precisely when overlap
            # works): the window other batches' host stages hide behind
            # (the overlap-ratio denominator, obs/pipeline.py) and the
            # cost a row's deadline must still cover after launch. The
            # same wall is the legacy cost-model feedback (it
            # double-counts host work overlapped with OTHER batches, so
            # it only feeds the baseline fallback).
            wait_s = sp.next("resolve")
            self.device_wait_s += wait_s
            self.sched.observe_cost(self.max_batch, rec.compute_ms)
            if cascade:
                rec.cascade.fold(cascade, n)
            if pf_aux is not None:
                denom = self.max_batch * self._pf_gated_banks
                if denom:
                    self._pf_rate_gauge.set(int(pf_aux[0]) / denom)
                self._pf_skip_counter.inc(int(pf_aux[1]))
                if self._pf_attr is not None:
                    self._pf_attr.observe(pf_aux, self.max_batch)
            from .engine.verdict import dfa_dispatch_counts

            dfa_mode, dfa_banks, dfa_rechecks = dfa_dispatch_counts(self.plan)
            if dfa_banks:
                ctr = self._dfa_banks_counter.get(dfa_mode)
                if ctr is not None:
                    ctr.inc(dfa_banks)
                if dfa_rechecks:
                    self._dfa_recheck_counter.inc(dfa_rechecks)
            self.chaos.stage("resolve")
            self.batches += 1
            route = None
            if dev_lanes is None:
                # Ladder device-rung fallback: the host interpreter — the
                # parity oracle every fast path is tested against — serves
                # the whole batch, bit-identically, at host speed.
                with self._hb_busy():  # host interpret blocks the loop
                    unverified, verified_block, route = self._interpret_batch(
                        parts, raw_batch)
            else:
                unverified, verified_block = merge_lanes(dev_lanes, host)
            # Rows the producer flagged as truncated (a field exceeded its
            # 2048-byte slot cap) were matched on the slot view — the widest
            # bytes this plane carries. Count them so the residual truncation
            # window (>2048B fields) is observable; the Python plane
            # re-evaluates such rows on fully untruncated strings
            # (engine/service.py).
            self.truncated_rows += int(
                ((slots["flags"] & SLOT_FLAG_TRUNCATED) != 0).sum())
            # Per-row route: each ring's rows read THEIR listener group's
            # route lane (make_lane_fn stacks one lane per distinct service
            # order at rows 3..3+G; the reference binds a service list per
            # listener, config.rs:241-253). Rows from rings with no service
            # group keep route 0 — their consumer never reads bits 3-7.
            if self._groups and dev_lanes is not None:
                route = np.zeros(n, dtype=np.int64)
                group_rows: list[list] = [[] for _ in self._groups]
                off = 0
                for ring, part in parts:
                    gi = self._ring_group_of.get(id(ring))
                    m = len(part)
                    if gi is not None:
                        route[off:off + m] = np.asarray(
                            dev_lanes[3 + gi][off:off + m], dtype=np.int64)
                        group_rows[gi].append(np.arange(off, off + m))
                    off += m
                contexts = None
                for gi, chunks in enumerate(group_rows):
                    if not self._host_routes[gi] or not chunks:
                        continue
                    rows = np.concatenate(chunks)
                    from .engine.batch import batch_to_contexts
                    from .expr import execute_as_bool

                    for order, prog in self._host_routes[gi]:
                        better = rows[route[rows] > order]
                        if not len(better):
                            continue
                        if contexts is None:
                            contexts = batch_to_contexts(raw_batch, self.lists)
                        for i in better:
                            try:
                                hit = prog is None or execute_as_bool(
                                    prog, contexts[i])
                            except Exception:
                                hit = False  # route errors fail to no-match
                            if hit:
                                route[i] = order
            # Rows whose url/path overflowed the slot caps carry their FULL
            # strings in the owning ring's spill area: re-evaluate every
            # lane for those rows through the host interpreter over the
            # untruncated bytes — exact parity with the reference, which
            # matches full strings (http_listener.rs:140-141). Rows flagged
            # truncated WITHOUT a spill slot (pool exhausted / > 64 KiB)
            # keep the slot-view verdict and remain visible in
            # truncated_rows above.
            off = 0
            for ring, part in parts:
                gi = self._ring_group_of.get(id(ring))
                svcs = self._groups[gi] if gi is not None else None
                spilled = np.nonzero(part["spill_idx"] != SPILL_NONE)[0]
                for j in spilled:
                    idx = int(part["spill_idx"][j])
                    full = ring.spill_read(idx)
                    if full is not None:
                        unv, vblk, rt = self._interpret_overflow_row(
                            part[j], full[0], full[1], svcs)
                        unverified[off + j] = unv
                        verified_block[off + j] = vblk
                        if route is not None and gi is not None:
                            route[off + j] = rt
                        self.spilled_rows += 1
                    ring.spill_release(idx)
                off += len(part)
            # Depth-capped rows (ISSUE 15, PINGOO_STAGING=compact with a
            # PINGOO_STAGING_DEPTH clamp below a field's required depth):
            # the device matched a plan-capped prefix narrower than the
            # slot bytes, so re-serve every lane for those rows from the
            # FULL slot view through the host interpreter — the same
            # exactness contract as the spill loop above. Spilled rows
            # already re-evaluated over their untruncated strings; with no
            # clamp the encoder's thresholds equal the slot caps and this
            # mask is empty by construction.
            over = getattr(raw_batch, "overflow", None)
            if over is not None and over[:n].any():
                off = 0
                for ring, part in parts:
                    gi = self._ring_group_of.get(id(ring))
                    svcs = self._groups[gi] if gi is not None else None
                    rows = np.nonzero(over[off:off + len(part)]
                                      & (part["spill_idx"] == SPILL_NONE))[0]
                    for j in rows:
                        s = part[j]
                        unv, vblk, rt = self._interpret_overflow_row(
                            s, bytes(s["url"][:int(s["url_len"])]),
                            bytes(s["path"][:int(s["path_len"])]), svcs)
                        unverified[off + j] = unv
                        verified_block[off + j] = vblk
                        if route is not None and gi is not None:
                            route[off + j] = rt
                        self.depth_overflow_rows += 1
                    off += len(part)
            # Verdict byte carries BOTH client-state lanes (the reference
            # action loop diverges for captcha-verified clients,
            # http_listener.rs:251-264): bits 0-1 = unverified action
            # (0 none / 1 block / 2 captcha), bit 2 = verified-block, and —
            # when this sidecar routes for a native listener — bits 3-7 =
            # the first matching service's order (31 = no service matched,
            # reference service-selection loop http_listener.rs:266-270).
            actions = unverified | (verified_block.astype(np.int32) << 2)
            if route is not None:
                actions = actions | (
                    np.minimum(route, 31).astype(np.int32) << 3)
            acts = actions[:n].astype(np.uint8)
            off = 0
            for pi, (ring, part) in enumerate(parts):  # scatter per ring
                m = len(part)
                # Rows the scheduler already failed open at launch
                # (skip_masks, PINGOO_SCHED_FAILOPEN=allow) were posted
                # then; posting again would hand their consumer a second
                # verdict for the same ticket.
                if skip_masks is not None and not skip_masks[pi].all():
                    keep = skip_masks[pi]
                    tickets = np.ascontiguousarray(part["ticket"][keep],
                                                   dtype=np.uint64)
                    pacts = np.ascontiguousarray(acts[off:off + m][keep])
                    waits = part["enq_ms"][keep]
                else:
                    tickets = np.ascontiguousarray(part["ticket"],
                                                   dtype=np.uint64)
                    pacts = acts[off:off + m]
                    waits = part["enq_ms"]
                k = len(tickets)
                done = 0
                while done < k:  # one FFI hop per batch, resume on a full ring
                    if self.chaos.verdict_full():  # injected full-ring stall
                        time.sleep(self.idle_sleep_s)
                        continue
                    done += ring.post_verdicts(tickets[done:], pacts[done:])
                    if done < k:
                        if self._stop:  # a dead consumer must not wedge stop()
                            self._pipe.finish()
                            return
                        time.sleep(self.idle_sleep_s)
                # Telemetry: enqueue -> verdict-post wall time for this
                # ring's rows lands in the shm wait histogram (one FFI hop).
                ring.record_waits(waits)
                # Posted-floor advance (ring v5, docs/RESILIENCE.md): every
                # ticket of this part now has a verdict (skip-mask rows
                # were posted at launch), and on one chip parts complete
                # in FIFO order, so posted tickets form a prefix — a
                # reattaching sidecar's orphan scan starts above this
                # mark. On several chips a batch may complete before an
                # older one: the floor then stays at the oldest ticket
                # still in flight (a ticket posted twice on reattach is
                # dropped by its consumer's unknown-ticket check).
                if m:
                    floor = int(part["ticket"].max()) + 1
                    if self.replicas > 1:
                        floor = self._floor_under_inflight(ring, floor,
                                                           inflight)
                    ring.set_posted_floor(floor)
                off += m
            # Deadline accounting on the ring clock: rows posted after
            # their PINGOO_DEADLINE_MS budget count as misses (one
            # vectorized compare per batch).
            post_ms = int(self.ring.lib.pingoo_ring_now_ms())
            self.sched.note_misses(int(
                ((post_ms - slots["enq_ms"].astype(np.int64))
                 > self.sched.config.deadline_ms).sum()))
            sp.next("provenance")
            if self._attribution is not None and dev_lanes is not None:
                # Interpreter-served batches (device rung demoted) skip
                # attribution/parity: the aux lane never ran, and auditing
                # the oracle against itself proves nothing.
                self._observe_provenance(slots, rule_hits, dev_lanes, host,
                                         raw_batch, unverified,
                                         verified_block, wait_s, n, rec)
            # Cross-plane timeline (ISSUE 17): per-batch cost while
            # unsampled is the one add+compare inside sample(). The rows'
            # enq_ms stamps are the NATIVE producer's ring clock — same
            # CLOCK_MONOTONIC timebase as the batch's recorded points, which
            # is what joins the ring-wait span across planes.
            if self._timeline.sample():
                self._timeline.batch_sidecar(
                    points=rec.points,
                    rows=[(f"t-{int(slots['ticket'][i])}",
                           int(slots["enq_ms"][i]))
                          for i in range(
                              min(n, self._timeline.rows_per_batch))],
                    args=rec.tags)
            self.processed += n
            # The batch is fully resolved: its accumulation buffer returns
            # to the pool and its pipeline slot retires.
            if slot_buf is not None:
                self._slot_pool.append(slot_buf)
            self._pipe.finish()
            self.chaos.on_batch_done(self.batches)

    def _floor_under_inflight(self, ring, floor: int, inflight) -> int:
        """`ring`'s posted floor once a part below `floor` is posted
        with the batches `inflight` still out: the highest ticket+1
        posted so far, but no higher than the oldest ticket of that ring
        still in flight."""
        high = self._posted_high[id(ring)] = max(
            self._posted_high.get(id(ring), 0), floor)
        for entry in inflight:
            for r, part in entry[0]:
                if r is ring and len(part):
                    high = min(high, int(part["ticket"].min()))
        return high

    def _observe_provenance(self, slots, rule_hits, dev_lanes, host,
                            raw_batch, unverified, verified_block,
                            device_wait_s, n: int, rec) -> None:
        """Sidecar-plane provenance (ISSUE 5): fold the on-device
        attribution lane (`rule_hits`: a host slice of the batch's one
        copy), flight-record the batch, and hand the FINAL served lanes
        (spill rewrites included) to the parity sampler. Nothing here
        touches the device. Lane-plane attribution covers the
        DEVICE-resident rules (the match matrix never leaves the chip);
        host-fallback rules are attributed on the Python plane, where
        the full matrix exists."""
        import zlib as _zlib

        from .engine.verdict import LANE_NONE

        if rule_hits is not None and len(self._dev_cols):
            self._attribution.fold_batch(rule_hits,
                                         indices=self._dev_cols)
        trace_ids = [f"t-{int(t)}" for t in slots["ticket"]]
        recorder = self.flight_recorder
        # Merged first-acting rule index per row (device lanes already
        # host-resident; host lanes are numpy) for the record's
        # matched-rule attribution — the lanes carry no full bitmap.
        act_idx = np.minimum(dev_lanes[0], host[0])
        now_ms = int(self.ring.lib.pingoo_ring_now_ms())
        enq_ms = slots["enq_ms"]
        compute_ms = round(device_wait_s * 1e3, 3)
        start = max(0, n - recorder.capacity)
        for i in range(start, n):
            crc = _zlib.crc32(slots["method"][i].tobytes())
            for f in ("host", "path", "url", "user_agent", "ip"):
                crc = _zlib.crc32(slots[f][i].tobytes(), crc)
            first = int(act_idx[i])
            stages = {
                "enqueue_to_post_ms": max(
                    0, now_ms - int(enq_ms[i])),
                "device_compute_ms": compute_ms,
            }
            # Pipeline slot id (ISSUE 9): lines this record up against
            # the pingoo_pipeline_* series and the `batch` stat of the
            # trace's sidecar/* spans; the batch's tags (staging mode)
            # ride along.
            stages["pipeline_slot"] = rec.seq
            stages.update(rec.tags)
            recorder.record(
                trace_id=trace_ids[i],
                digest=f"{crc & 0xFFFFFFFF:08x}",
                stages=stages,
                matched_rules=(first,) if first < LANE_NONE else (),
                action=int(unverified[i]),
                ticket=int(slots["ticket"][i]))
        if self.parity is not None:
            # Truncated/spilled rows were served from a different string
            # view than the slot arrays — excluded from the audit.
            skip = ((slots["flags"] & SLOT_FLAG_TRUNCATED) != 0) \
                | (slots["spill_idx"] != SPILL_NONE)
            over = getattr(raw_batch, "overflow", None)
            if over is not None:
                # Depth-capped rows (ISSUE 15) were re-served from the
                # full slot view, not the capped staging arrays the
                # audit would rebuild contexts from — excluded like
                # spilled rows.
                skip = skip | np.asarray(over[:n], dtype=bool)
            raw_for_audit = raw_batch
            if self._zero_copy and self.parity.sample > 0.0:
                # The auditor's contexts_builder runs LATER on its
                # worker thread, but zero-copy `raw_batch` arrays are
                # views into the rotating staging buffers — recycled a
                # few batches from now. Snapshot them while they are
                # still this batch's bytes (audit-mode-only copy; with
                # sampling off the closure is never invoked).
                from .engine.batch import RequestBatch

                raw_for_audit = RequestBatch(
                    size=raw_batch.size,
                    arrays={k: np.array(v, copy=True)
                            for k, v in raw_batch.arrays.items()})

            def contexts_builder(raw=raw_for_audit, lists=self.lists):
                from .engine.batch import batch_to_contexts

                contexts = batch_to_contexts(raw, lists)
                paths = [c.variables["http_request"]["path"]
                         for c in contexts]
                return contexts, paths

            self.parity.submit_lanes(
                contexts_builder, unverified[:n].copy(),
                verified_block[:n].copy(), skip_mask=skip,
                trace_ids=trace_ids)

    # -- degradation ladder (ISSUE 10, docs/RESILIENCE.md) --------------------

    def _rebuild_lane_fn(self, dfa_off: bool) -> None:
        """Re-trace the lane fn with the lowered DFAs in or out. The
        plan-level default is what `_resolve_dfa_mode` falls back to
        when PINGOO_DFA is unset, so the demotion is per-plan, not
        process-global. The next dispatch pays one re-jit (a bounded
        stall during an already-degraded event)."""
        from .engine.verdict import (cascade_banks, donate_batch_buffers,
                                     lane_rows, make_lane_fn)
        from .obs.perf import (instrument_jit, plan_fingerprint,
                               staging_widths)
        from .obs.pipeline import CascadeCounters

        self.plan.dfa_default_mode = "off" if dfa_off else self._dfa_mode0
        # The banks that recheck change with the DFAs, and the lanes'
        # rows with them: batches in flight keep the counters and the
        # row layout of the program that launched them.
        self._cascade = CascadeCounters("sidecar", cascade_banks(self.plan))
        self._lane_rows = lane_rows(self.plan, len(self._groups),
                                    self._provenance_on)
        fp = plan_fingerprint(self.plan)
        widths = staging_widths(self.plan)
        self._lane_fn = instrument_jit(make_lane_fn(
            self.plan, service_groups=self._groups or None,
            with_rule_hits=self._provenance_on,
            donate=donate_batch_buffers()), "lanes", plane="sidecar",
            fingerprint=fp, widths=widths)
        if self._packed_lane_fn is not None:
            # The packed twin embeds the same DFA dispatch decision;
            # keep it in lockstep with the per-batch program.
            from .engine.verdict import make_packed_lane_fn

            self._packed_lane_fn = instrument_jit(make_packed_lane_fn(
                self.plan, service_groups=self._groups or None,
                with_rule_hits=self._provenance_on,
                donate=donate_batch_buffers()), "lanes",
                plane="sidecar", fingerprint=fp, widths=widths)

    def _dfa_rung_tick(self) -> None:
        """Demoted-dfa probe: when the backoff window opens, restore
        the lowered-DFA dispatch for one batch; `_note_device_success`
        / `_note_device_failure` then promote or re-demote."""
        if not self.ladder.healthy("dfa") and not self._dfa_probe \
                and self.ladder.try_rung("dfa"):
            self._rebuild_lane_fn(dfa_off=False)
            self._dfa_probe = True

    def _note_device_failure(self, exc: BaseException) -> None:
        """Cheapest-rung-first demotion: a device error with lowered
        DFAs active drops them back to the exact NFA scan before
        giving up on the device entirely; only a failure with the DFAs
        already out (or pinned by PINGOO_DFA) demotes the device rung
        to the host interpreter."""
        from .engine.verdict import dfa_dispatch_counts

        if self._dfa_probe:
            self.ladder.note_failure("dfa", exc)
            self._rebuild_lane_fn(dfa_off=True)
            self._dfa_probe = False
        elif self.ladder.healthy("dfa") \
                and not os.environ.get("PINGOO_DFA") \
                and dfa_dispatch_counts(self.plan)[1] > 0:
            self.ladder.note_failure("dfa", exc)
            self._rebuild_lane_fn(dfa_off=True)
        else:
            self.ladder.note_failure("device", exc)

    def _note_device_success(self) -> None:
        if self._dfa_probe:
            self.ladder.note_success("dfa")
            self._dfa_probe = False
        self.ladder.note_success("device")

    def _interpret_batch(self, parts, raw_batch):
        """Device-rung fallback: serve the whole batch through the
        host interpreter — the parity oracle every fast path is tested
        against, so the verdict bytes are identical, just slower.
        Returns (unverified, verified_block, route-or-None), the same
        lanes `_complete` composes from the device path."""
        from .engine.batch import batch_to_contexts
        from .engine.verdict import LANE_NONE, action_lanes, \
            interpret_rules_row

        contexts = batch_to_contexts(raw_batch, self.lists)
        if contexts:
            rows = np.stack([interpret_rules_row(self.plan, c)
                             for c in contexts])
        else:
            rows = np.zeros((0, len(self.plan.rules)), dtype=bool)
        unv, vblk = action_lanes(self.plan, rows)
        route = None
        if self._groups:
            route = np.full(len(contexts), int(LANE_NONE),
                            dtype=np.int64)
            off = 0
            for ring, part in parts:
                gi = self._ring_group_of.get(id(ring))
                if gi is not None:
                    svcs = self._groups[gi]
                    for i in range(off, off + len(part)):
                        for order, name in enumerate(svcs):
                            ridx = self.plan.route_index.get(name)
                            if ridx is None or rows[i, ridx]:
                                route[i] = order
                                break
                off += len(part)
        return (np.asarray(unv, dtype=np.int32),
                np.asarray(vblk, dtype=bool), route)

    # -- crash-reattach reconciliation (ISSUE 10, docs/RESILIENCE.md) ---------

    def _reconcile_orphans(self) -> None:
        """Resolve tickets the PREVIOUS sidecar epoch dequeued but
        never answered. posted_floor only advances once a part's
        verdicts are all posted, and parts complete in FIFO order, so
        every ticket below the floor has a verdict and the orphan
        window is exactly [posted_floor, req_tail). Slots whose bytes
        survived the crash (wedged mid-dequeue, or consumed but not
        yet overwritten — the C reclaim's seqlock proves which) are
        RE-EVALUATED through the host interpreter; recycled slots fail
        open (allow), the same posture as every other unanswerable
        path. Each orphan resolves exactly once: this scan runs before
        the drain loop starts (no race with this epoch's posts), and a
        duplicate post for a ticket the data plane already timed out
        is dropped by its unknown-ticket check."""
        for ring in self.rings:
            lv = ring.liveness()
            floor, tail = lv["posted_floor"], lv["req_tail"]
            if tail <= floor:
                continue
            # A pre-v5 (or never-completing) epoch leaves the floor at
            # 0; slots more than one capacity old are certainly
            # recycled, so bound the scan — everything below `start`
            # long ago hit the data plane's own verdict timeout.
            start = max(floor, tail - ring.capacity)
            for ticket in range(start, tail):
                slot = ring.reclaim(ticket)
                action = 0
                kind = "failopen"
                if slot is not None:
                    try:
                        action = self._reeval_reclaimed(ring, slot)
                        kind = "reeval"
                    except Exception:
                        action = 0  # interpreter error: fail open
                self._post_one(ring, ticket, action)
                self.reconciled[kind] += 1
                self._reattach_counters[kind].inc()
                if self.flight_recorder is not None:
                    self.flight_recorder.record(
                        trace_id=f"t-{ticket}",
                        digest="reattach",
                        stages={"reattach": kind, "epoch": self.epoch},
                        matched_rules=(),
                        action=action & 3,
                        ticket=ticket)
            ring.set_posted_floor(tail)

    def _reeval_reclaimed(self, ring: Ring, slots1: np.ndarray) -> int:
        """Verdict byte for one reclaimed orphan slot via the host
        interpreter — the same lane composition `_complete` posts:
        bits 0-1 unverified, bit 2 verified-block, bits 3-7 route
        (when the slot's ring has a service group)."""
        if self.geoip is not None:
            self._enrich_slots(slots1)
        s = slots1[0]
        url = bytes(s["url"][:int(s["url_len"])])
        path = bytes(s["path"][:int(s["path_len"])])
        idx = int(s["spill_idx"])
        if idx != SPILL_NONE:
            full = ring.spill_read(idx)
            if full is not None:
                url, path = full
            ring.spill_release(idx)
        gi = self._ring_group_of.get(id(ring))
        svcs = self._groups[gi] if gi is not None else None
        unv, vblk, rt = self._interpret_overflow_row(s, url, path, svcs)
        action = unv | (int(vblk) << 2)
        if svcs is not None:
            action |= min(rt, 31) << 3
        return action

    def _post_one(self, ring: Ring, ticket: int, action: int) -> None:
        tickets = np.asarray([ticket], dtype=np.uint64)
        acts = np.asarray([action & 0xFF], dtype=np.uint8)
        # Bounded retry: a full verdict ring with a LIVE consumer
        # drains in microseconds; a dead consumer must not wedge
        # reattach forever (its tickets are long failed open anyway).
        for _ in range(10000):
            if ring.post_verdicts(tickets, acts):
                return
            if self._stop:
                return
            time.sleep(self.idle_sleep_s)

    def _interpret_overflow_row(self, slot, url: bytes, path: bytes,
                                services=None) -> tuple[int, bool, int]:
        """(unverified, verified_block, route) for one overflow row via
        the host interpreter over the UNTRUNCATED url/path (the parity
        oracle), reproducing the reference's full-string matching.
        `services` is the row's ring's service order (its listener's
        group) — routes evaluate against THAT order."""
        import ipaddress

        from .engine.batch import RequestTuple, tuple_to_context
        from .engine.verdict import LANE_NONE, action_lanes, \
            interpret_rules_row

        def field(name, ln):
            return bytes(slot[name][:slot[ln]]).decode("latin-1")

        addr = ipaddress.ip_address(bytes(slot["ip"]))
        v4 = getattr(addr, "ipv4_mapped", None)
        tup = RequestTuple(
            host=field("host", "host_len"),
            url=url.decode("latin-1"),
            path=path.decode("latin-1"),
            method=field("method", "method_len"),
            user_agent=field("user_agent", "ua_len"),
            ip=str(v4 or addr),
            remote_port=int(slot["remote_port"]),
            asn=int(slot["asn"]),
            country=bytes(slot["country"]).decode("latin-1"),
        )
        ctx = tuple_to_context(tup, self.lists)
        row = interpret_rules_row(self.plan, ctx)[None, :]
        unv, vblk = action_lanes(self.plan, row)
        rt = int(LANE_NONE)
        for order, name in enumerate(services or []):
            ridx = self.plan.route_index.get(name)
            if ridx is None or row[0, ridx]:
                rt = order
                break
        return int(unv[0]), bool(vblk[0]), rt

    def ring_telemetry(self) -> dict:
        """Aggregate shm telemetry across this sidecar's rings: sum the
        monotonic counters and the wait histogram, max the depth marks
        (the per-ring blocks stay available via Ring.telemetry())."""
        agg = {name: 0 for name in TELEMETRY_FIELDS}
        agg["wait_hist"] = [0] * 8
        for ring in self.rings:
            t = ring.telemetry()
            for name in TELEMETRY_FIELDS:
                if name in ("depth", "depth_hwm"):
                    agg[name] = max(agg[name], t[name])
                else:
                    agg[name] += t[name]
            agg["wait_hist"] = [a + b for a, b in
                                zip(agg["wait_hist"], t["wait_hist"])]
        return agg

    def _export_ring_telemetry(self) -> None:
        """Registry collector: fold the rings' telemetry blocks into the
        shared exposition (pingoo_ring_* metrics, obs/schema.py). Runs
        at scrape time; must never touch a ring after stop()."""
        if not self._collector_live:
            return
        from .obs import schema

        t = self.ring_telemetry()
        reg = self._registry
        lab = {"plane": "sidecar"}
        for name, field in (
                ("pingoo_ring_enqueued_total", "enqueued"),
                ("pingoo_ring_dequeued_total", "dequeued"),
                ("pingoo_ring_enqueue_full_total", "enqueue_full"),
                ("pingoo_ring_verdicts_posted_total", "verdicts_posted"),
                ("pingoo_ring_verdict_post_full_total",
                 "verdict_post_full")):
            reg.counter(name, schema.RING_METRICS[name],
                        labels=lab).set_total(t[field])
        reg.gauge("pingoo_ring_depth",
                  schema.RING_METRICS["pingoo_ring_depth"],
                  labels=lab).set(t["depth"])
        reg.gauge("pingoo_ring_depth_hwm",
                  schema.RING_METRICS["pingoo_ring_depth_hwm"],
                  labels=lab).set(t["depth_hwm"])
        reg.histogram(
            schema.SHARED_WAIT_HISTOGRAM,
            "verdict wait: ring enqueue -> verdict post (ms)",
            buckets=WAIT_BUCKET_BOUNDS_MS,
            labels=lab).set_bucket_counts(
                t["wait_hist"], total_sum=float(t["wait_sum_ms"]))

    def stats(self) -> dict:
        """Observability surface for the serving path (SURVEY §5):
        scraped by operators next to the native plane's
        /__pingoo/metrics endpoint."""
        return {
            "processed": self.processed,
            "batches": self.batches,
            "batch_occupancy": round(self.processed / self.batches, 2)
            if self.batches else 0.0,
            "device_wait_ms_per_batch": round(
                1e3 * self.device_wait_s / self.batches, 3)
            if self.batches else 0.0,
            "truncated_rows": self.truncated_rows,
            "spilled_rows": self.spilled_rows,
            "rings": len(self.rings),
            "ring_depth": dict(zip(self.ring_names, self._ring_depth_seen)),
            "ring_rows": {name: c.value for name, c in
                          zip(self.ring_names, self._ring_rows)},
            "batch_rings": self._batch_rings.value,
            "cascade": self._cascade.snapshot(),
            "completions": dict(self._pipe.completions),
            "host_copies": self._pipe.host_copies.value,
            "staged_bytes": {mode: c.value for mode, c in
                             self._staged_bytes_counter.items()},
            "staged_rows": {kind: c.value for kind, c in
                            self._staged_rows_counter.items()},
            "replicas": self.replicas,
            "replica_batches": {str(d): c.value for d, c in
                                enumerate(self._pipe.replica_batches)},
            "replica_inflight": list(self._replica_inflight),
            "inflight_at_launch": self._pipe.inflight_at_launch.value,
            "ring_telemetry": self.ring_telemetry(),
            "sched": self.sched.snapshot(),
            "mesh": self.mesh.describe(),
            "pipeline": self._pipe.snapshot(),
            "ladder": self.ladder.snapshot(),
            "supervision": {"epoch": self.epoch,
                            "reconciled": dict(self.reconciled)},
        }

    def stop(self, join_timeout_s: float = 10.0) -> None:
        """Signal the drain loop to exit and WAIT for it (when called
        from another thread): only after this returns may the caller
        close/unmap the rings — the loop may be mid-FFI into the
        mapping, and pulling it out from under the call is a segfault,
        not an exception."""
        import threading as _threading

        # Detach the registry collector FIRST: a scrape after the
        # caller unmaps the rings would be a use-after-munmap in the
        # telemetry snapshot FFI call.
        self._collector_live = False
        self._registry.unregister_collector(self._export_ring_telemetry)
        # Durable cost ledger (ISSUE 17): persist the measured EWMAs on
        # drain so the next boot estimates from THIS run's costs.
        try:
            from .sched.scheduler import save_cost_ledger

            save_cost_ledger(self.sched.cost,
                             backend=self._backend_label,
                             fingerprint=self._plan_fp, plane="sidecar")
        except Exception:
            pass
        if self.parity is not None:
            self.parity.stop()
        if self._attribution is not None:
            self._attribution.close()
        self._stop = True
        if self._sync_probes:
            # Once more at the end of the log, which is what a harness
            # keeps of it: every overdue device sync this plane probed.
            _log.warning("device syncs overdue since boot", extra={
                "fields": {"probes": list(self._sync_probes)}})
        t = self._thread
        if t is not None and t.is_alive()                 and t is not _threading.current_thread():
            t.join(timeout=join_timeout_s)
        # Join the heartbeat watchdog too (exits within one 0.1 s tick
        # of _stop): a stamp against an unmapped ring would be the same
        # use-after-munmap the drain-loop join exists to prevent.
        w = getattr(self, "_hb_watchdog", None)
        if w is not None and w.is_alive()                 and w is not _threading.current_thread():
            w.join(timeout=join_timeout_s)

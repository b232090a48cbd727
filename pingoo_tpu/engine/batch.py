"""Request batch encoding: request tuples -> fixed-shape device tensors.

The host data plane extracts one `RequestTuple` per request — the same
tuple shape the reference builds for its bel context (pingoo/rules.rs:
17-34 RequestData + ClientData, constructed at http_listener.rs:238-249)
— and batches them into zero-padded byte tensors + numeric columns.

Truncation policy: every string field is capped at its plan capacity
(compiler/lowering.DEFAULT_FIELD_SPECS; the reference caps UA/host at
256 on the hot path, http_listener.rs:159,284-296 — the listener applies
those caps before encoding). A request whose field still exceeds its
device capacity gets its row flagged in the batch's `overflow` lane and
is re-evaluated on the host interpreter over the untruncated strings
(engine/service.py), because the reference matches full path/url and
truncated matching would let padded URLs slip past content rules.
`batch_to_contexts` rebuilds the strings the device saw for the
non-overflowing rows (the parity oracle view).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Iterable, Mapping, NamedTuple, Optional

import numpy as np

from ..compiler.lowering import DEFAULT_FIELD_SPECS, NfaPred
from ..compiler.plan import quantize_stage_cap
from ..expr import Context, Ip
from ..ops.cidr import ip_to_words
from ..ops.live_columns import ROW_TILE, walked_columns, walked_rows

STRING_FIELDS = ("host", "url", "path", "method", "user_agent", "country")

# Upload heights of a packed batch (docs/EXECUTOR.md "Compact staging"):
# the host ships the first H rows, the smallest rung that holds every
# live row, and the chip pads them to the batch; past the last rung the
# whole batch ships. One pad program a rung (engine/verdict.make_pad_fn).
UPLOAD_ROWS = (64, ROW_TILE)


def upload_rows(n: int, batch: int) -> int:
    """Rows of a `batch`-row packed buffer to ship for `n` live rows."""
    for rung in UPLOAD_ROWS:
        if n <= rung < batch:
            return rung
    return batch


@dataclass
class RequestTuple:
    """One request's rule-relevant metadata (reference pingoo/rules.rs:17-34)."""

    host: str = ""
    url: str = ""
    path: str = ""
    method: str = "GET"
    user_agent: str = ""
    ip: str = "0.0.0.0"
    remote_port: int = 0
    asn: int = 0
    country: str = "XX"
    # Observability correlation id (obs/trace.py): assigned at the edge,
    # rides the tuple through batching so engine-side logs can join a
    # request to its response header / access-log line. Never encoded
    # into device arrays and never consulted by any rule.
    trace_id: str = ""


@dataclass
class RequestBatch:
    """Fixed-shape encoded batch (numpy; device transfer happens in the
    engine). A pytree-compatible dict lives in `.arrays`; `overflow` is
    host-side metadata (rows whose fields exceeded device capacity) and
    deliberately NOT part of the arrays pytree — it would otherwise ride
    every device transfer and change jit signatures for nothing."""

    size: int
    arrays: dict  # field -> np/jnp arrays
    overflow: Optional[np.ndarray] = None  # [size] bool or None
    # Compact staging (ISSUE 15): the [size, layout.width] uint8 packed
    # buffer shipped to the device as ONE async copy, and its static
    # layout. None under PINGOO_STAGING=full — `arrays` is then the
    # only device view. `arrays` stays populated either way (its byte
    # matrices are strided views into `packed` when compact) for the
    # host-side consumers: host-rule lanes, parity contexts, scorer.
    packed: Optional[np.ndarray] = None
    layout: Optional["PackedLayout"] = None
    staged_bytes: int = 0  # host->device bytes this batch stages
    # Rows of `packed` shipped to the device (`upload_rows`); the chip
    # pads them to `size` with zero rows. 0 off the packed path.
    upload_rows: int = 0

    def __getitem__(self, key: str):
        return self.arrays[key]


def _to_bytes(text: str) -> bytes:
    """Canonical byte view (latin-1, bijective); non-byte chars are
    replaced so a hostile header can't crash encoding."""
    try:
        return text.encode("latin-1")
    except UnicodeEncodeError:
        return text.encode("latin-1", errors="replace")


def encode_requests(
    requests: list[RequestTuple],
    field_specs: Optional[Mapping[str, int]] = None,
) -> RequestBatch:
    specs = dict(field_specs or DEFAULT_FIELD_SPECS)
    B = len(requests)
    arrays: dict = {}
    overflow = np.zeros(B, dtype=bool)
    for field in STRING_FIELDS:
        L = specs.get(field, 256)
        data = np.zeros((B, L), dtype=np.uint8)
        lens = np.zeros(B, dtype=np.int32)
        for i, req in enumerate(requests):
            full = _to_bytes(getattr(req, field))
            if len(full) > L:
                overflow[i] = True
            raw = full[:L]
            data[i, : len(raw)] = np.frombuffer(raw, dtype=np.uint8)
            lens[i] = len(raw)
        arrays[f"{field}_bytes"] = data
        arrays[f"{field}_len"] = lens

    ip_words = np.zeros((B, 4), dtype=np.uint32)
    for i, req in enumerate(requests):
        try:
            ip_words[i], _ = ip_to_words(Ip(req.ip))
        except Exception:
            ip_words[i] = 0  # unparseable -> never matches any predicate
    arrays["ip"] = ip_words
    arrays["asn"] = np.array(
        [_clamp_i64(r.asn) for r in requests], dtype=np.int64)
    arrays["remote_port"] = np.array(
        [_clamp_i64(r.remote_port) for r in requests], dtype=np.int64)
    return RequestBatch(size=B, arrays=arrays, overflow=overflow)


def _clamp_i64(v: int) -> int:
    return max(min(int(v), 2**63 - 1), -(2**63))


def bucket_arrays(arrays: dict, min_len: int = 16) -> dict:
    """Slice each field's byte matrix to the next power-of-2 >= the batch's
    longest value. The NFA scan is O(L), so not walking padding is the
    single biggest throughput lever for real traffic (URLs average tens of
    bytes against a 512-byte capacity). Produces a small set of static
    shapes, so jit recompiles at most log2(cap) times per field.
    """
    out = dict(arrays)
    for field in STRING_FIELDS:
        data = arrays[f"{field}_bytes"]
        lens = arrays[f"{field}_len"]
        cap = data.shape[1]
        longest = int(np.max(lens)) if len(lens) else 0
        L = min_len
        while L < longest:
            L *= 2
        L = min(L, cap)
        out[f"{field}_bytes"] = np.ascontiguousarray(data[:, :L])
    return out


def pow2_batch_size(n: int, max_batch: int, multiple: int = 1) -> int:
    """The engine's padded launch size for an n-row batch: the next
    power of two (floor 8, so tiny batches share one compiled shape),
    capped at `max_batch` but never below n, then rounded up to
    `multiple` — the mesh executor passes its dp extent so the batch
    axis shards evenly (sched/mesh_exec.py; 1 = single device, where
    this reproduces the historical pow2 ladder exactly)."""
    target = 1
    while target < n:
        target *= 2
    size = max(min(max(target, 8), max_batch), n)
    if multiple > 1:
        rem = size % multiple
        if rem:
            size += multiple - rem
    return size


def pad_batch(batch: RequestBatch, to_size: int) -> RequestBatch:
    """Pad a batch to a fixed size (jit shape stability); padded rows are
    inert (zero-length fields, ip 0, no overflow)."""
    B = batch.size
    if B == to_size:
        return batch
    assert to_size > B
    arrays = {}
    for key, arr in batch.arrays.items():
        pad_shape = (to_size - B,) + arr.shape[1:]
        arrays[key] = np.concatenate([arr, np.zeros(pad_shape, dtype=arr.dtype)])
    overflow = batch.overflow
    if overflow is not None:
        overflow = np.concatenate(
            [overflow, np.zeros(to_size - B, dtype=bool)])
    return RequestBatch(size=to_size, arrays=arrays, overflow=overflow)


# Shm-slot length-field names per string field (native_ring
# REQUEST_SLOT_DTYPE; `country` is a fixed 2-byte code with no length
# field). Lives here, not in native_ring.py, so the zero-copy fill is
# inside the analyze-linted tree (tools/analyze/lint_config.py).
SLOT_LEN_KEYS = {
    "method": "method_len",
    "host": "host_len",
    "path": "path_len",
    "url": "url_len",
    "user_agent": "ua_len",
}


def bucket_len(longest: int, cap: int, min_len: int = 16) -> int:
    """The pow2 column count `bucket_arrays` would pick for a field
    whose longest value is `longest` under capacity `cap`."""
    L = min_len
    while L < longest:
        L *= 2
    return min(L, cap)


def scan_columns(arrays: Mapping[str, np.ndarray],
                 fields: Iterable[str]) -> dict[str, tuple[int, int]]:
    """{field: (staged, walked)} for an encoded batch: the columns each
    scanned field is staged at, and those the device's byte loops walk
    (ops/live_columns: they stop at the batch's longest row). Padding
    rows carry length 0, so the whole `_len` array is the device's."""
    out = {}
    for field in fields:
        width = arrays[f"{field}_bytes"].shape[1]
        out[field] = (width,
                      walked_columns(arrays[f"{field}_len"], width))
    return out


def scan_rows(arrays: Mapping[str, np.ndarray], fields: Iterable[str],
              sharded: bool = False) -> dict[str, tuple[int, int]]:
    """{field: (staged, walked)} rows of an encoded batch: the rows the
    batch is padded to, and those the device's byte loops walk for the
    field (ops/live_columns: the row tiles up to the last row with a
    byte; every row of a batch `sharded` over a mesh)."""
    out = {}
    for field in fields:
        rows = arrays[f"{field}_bytes"].shape[0]
        out[field] = (rows, walked_rows(arrays[f"{field}_len"], rows,
                                        sharded))
    return out


class ScanColumnCounters:
    """`pingoo_scan_columns_total{plane, field, kind}` and
    `pingoo_scan_rows_total{plane, field, kind}` (obs/schema.py): per
    batch and field some contains/regex rule of the plan scans,
    kind="staged" counts the field's staged width (the padded batch's
    rows) and kind="walked" the columns (rows) the dfa/* and pf/* byte
    loops walk for that batch. `rows_sharded`: the plane's mesh shards
    batches (dp > 1), where the loops walk every row."""

    def __init__(self, plane: str, plan, rows_sharded: bool = False):
        from ..obs import REGISTRY
        from ..obs.schema import STAGING_METRICS

        self.fields = tuple(sorted(
            {leaf.field for leaf in plan.leaves
             if isinstance(leaf, NfaPred)}))
        self.rows_sharded = rows_sharded
        self._counters = {
            (name, field, kind): REGISTRY.counter(
                name, STAGING_METRICS[name],
                labels={"plane": plane, "field": field, "kind": kind})
            for name in ("pingoo_scan_columns_total",
                         "pingoo_scan_rows_total")
            for field in self.fields for kind in ("staged", "walked")}

    def note(self, arrays: Mapping[str, np.ndarray]) -> None:
        for name, extents in (
                ("pingoo_scan_columns_total",
                 scan_columns(arrays, self.fields)),
                ("pingoo_scan_rows_total",
                 scan_rows(arrays, self.fields, self.rows_sharded))):
            for field, (staged, walked) in extents.items():
                self._counters[name, field, "staged"].inc(staged)
                self._counters[name, field, "walked"].inc(walked)


# -- Compact staging (ISSUE 15, docs/EXECUTOR.md "Compact staging") ----------


def resolve_staging_mode() -> str:
    """PINGOO_STAGING: `full` (default; the bit-exact oracle — every
    field stages its full spec width as separate arrays) or `compact`
    (plan-derived capped widths in ONE packed buffer per batch)."""
    mode = os.environ.get("PINGOO_STAGING", "full").strip().lower()
    return "compact" if mode == "compact" else "full"


def resolve_stage_caps(plan) -> Optional[dict[str, int]]:
    """The per-field staged widths this plan serves under, or None in
    full mode. Starts from the compile pass's quantized caps
    (plan.staging_caps; full spec on plans cached before v11), then
    applies the PINGOO_STAGING_DEPTH operator clamp (0 = off) —
    re-quantized to the rung ladder so clamped tenants still share
    XLA compiles."""
    if resolve_staging_mode() != "compact":
        return None
    specs = dict(getattr(plan, "field_specs", None)
                 or DEFAULT_FIELD_SPECS)
    caps = dict(getattr(plan, "staging_caps", None) or {})
    try:
        depth = int(os.environ.get("PINGOO_STAGING_DEPTH", "0"))
    except ValueError:
        depth = 0
    eff: dict[str, int] = {}
    for field in STRING_FIELDS:
        spec = int(specs.get(field, 256))
        cap = min(int(caps.get(field, spec)), spec)
        if depth > 0:
            cap = min(cap, quantize_stage_cap(min(depth, spec), spec))
        eff[field] = max(1, cap)
    return eff


def stage_overflow_thresholds(plan,
                              eff: Mapping[str, int]) -> dict[str, int]:
    """Per-field TRUE-length threshold beyond which a row must be
    re-interpreted from its untruncated source. With caps at or above
    the plan's required depth the threshold is the full spec (exactly
    full mode's over-capacity rule); a cap clamped BELOW the required
    depth (PINGOO_STAGING_DEPTH) drops bytes some scanner depends on,
    so any row longer than the cap reroutes through the interpreter
    backstop — which is what keeps clamped serving verdict-identical."""
    specs = dict(getattr(plan, "field_specs", None)
                 or DEFAULT_FIELD_SPECS)
    required = getattr(plan, "staging_required", None) or {}
    out: dict[str, int] = {}
    for field in STRING_FIELDS:
        spec = int(specs.get(field, 256))
        need = min(int(required.get(field, spec)), spec)
        cap = int(eff.get(field, spec))
        out[field] = cap if cap < need else spec
    return out


class PackedLayout(NamedTuple):
    """Static byte layout of one packed staging row (hashable — rides
    the jitted packed fns as a static argument, so one XLA compile per
    distinct caps rung-tuple). Per row: the capped byte region of each
    string field, then a metadata tail — u16-LE true lens, the 16
    big-endian IP bytes, and the i64-LE asn / remote_port words (full
    width: numeric predicates must stay exact)."""

    fields: tuple  # ((field, offset, width), ...) capped byte regions
    lens: tuple    # ((field, offset), ...) u16 LE true lengths
    ip_off: int    # 16 bytes, big-endian v6-mapped words
    asn_off: int   # 8 bytes, i64 LE
    port_off: int  # 8 bytes, i64 LE
    width: int     # total row stride


_LAYOUT_CACHE: dict[tuple, PackedLayout] = {}


def build_packed_layout(stage_caps: Mapping[str, int]) -> PackedLayout:
    """PackedLayout for a caps assignment; cached per widths-tuple so
    hot-swaps between plans on the same rungs return the SAME (hash-
    equal) layout and reuse the packed fns' XLA compile."""
    widths = tuple(int(stage_caps[f]) for f in STRING_FIELDS)
    cached = _LAYOUT_CACHE.get(widths)
    if cached is not None:
        return cached
    fields = []
    off = 0
    for field, w in zip(STRING_FIELDS, widths):
        fields.append((field, off, w))
        off += w
    lens = []
    for field in STRING_FIELDS:
        lens.append((field, off))
        off += 2
    ip_off = off
    off += 16
    asn_off = off
    off += 8
    port_off = off
    off += 8
    layout = PackedLayout(fields=tuple(fields), lens=tuple(lens),
                          ip_off=ip_off, asn_off=asn_off,
                          port_off=port_off, width=off)
    _LAYOUT_CACHE[widths] = layout
    return layout


class StagingEncoder:
    """Pre-allocated, reused staging buffers for the zero-copy encode
    path (ISSUE 9, docs/EXECUTOR.md).

    The legacy chain allocates per batch: `encode_requests` builds
    fresh (B, cap) matrices, `bucket_arrays` copies the pow2 column
    slice contiguous, and `pad_batch` concatenates zero rows — three
    full-batch copies before the device sees a byte. This encoder owns
    (max_batch, cap) matrices per field and fills them IN PLACE,
    handing out views already bucketed (pow2 columns) and padded (pow2
    rows), value-identical to the legacy chain (the bit-identity suite
    in tests/test_pipeline.py is the contract).

    Double-buffered: `nbuf` rotating buffer sets, so batch N+1's host
    fill cannot overwrite buffers a still-in-flight batch N hands to
    the device or reads at resolve time. Planes size `nbuf` to their
    executor depth + 1; `checkouts` counts the sets handed out, so a
    plane that may complete out of order can tell which batch holds
    the set the next encode takes (the one `nbuf` checkouts back).

    Two fill paths:
      * `encode_requests` — RequestTuple list (Python listener plane);
        same per-request loop as module-level `encode_requests`, minus
        the allocations.
      * `encode_slots` — a structured shm-slot array view
        (native_ring.REQUEST_SLOT_DTYPE rows, sidecar plane): per-field
        vectorized strided copies straight out of the ring slots, no
        per-slot Python tuple materialization.
    """

    def __init__(self, max_batch: int,
                 field_specs: Optional[Mapping[str, int]] = None,
                 nbuf: int = 2,
                 stage_caps: Optional[Mapping[str, int]] = None,
                 overflow_thresholds: Optional[Mapping[str, int]] = None):
        specs = dict(field_specs or DEFAULT_FIELD_SPECS)
        self.max_batch = int(max_batch)
        self.specs = specs
        self.nbuf = max(1, int(nbuf))
        self._cursor = 0
        self.checkouts = 0
        self._bufs: list[dict] = []
        for _ in range(self.nbuf):
            bufs: dict = {}
            for field in STRING_FIELDS:
                cap = specs.get(field, 256)
                bufs[f"{field}_bytes"] = np.zeros(
                    (self.max_batch, cap), dtype=np.uint8)
                bufs[f"{field}_len"] = np.zeros(
                    self.max_batch, dtype=np.int32)
            bufs["ip"] = np.zeros((self.max_batch, 4), dtype=np.uint32)
            bufs["asn"] = np.zeros(self.max_batch, dtype=np.int64)
            bufs["remote_port"] = np.zeros(self.max_batch, dtype=np.int64)
            bufs["overflow"] = np.zeros(self.max_batch, dtype=bool)
            self._bufs.append(bufs)
        # Compact staging (ISSUE 15): flat packed rows, FULL-spec-sized
        # once at boot so a hot-swap that widens caps never reallocates
        # — per batch only the current layout's [P, width] prefix is
        # touched and shipped. `packed_dirty` is how far into its flat
        # buffer a set may hold a byte other than 0: a slot encode
        # zeroes from its own live rows' end up to there, not the whole
        # [P, width] (everything past the mark is zero already). In
        # bytes of the flat buffer, not rows: a swap may change the row
        # stride. `set_stage_caps` marks every set wholly dirty.
        self.stage_caps: Optional[dict[str, int]] = None
        self._thresholds: dict[str, int] = dict(specs)
        self._layout: Optional[PackedLayout] = None
        if stage_caps is not None:
            full_w = build_packed_layout(
                {f: specs.get(f, 256) for f in STRING_FIELDS}).width
            for bufs in self._bufs:
                bufs["packed"] = np.zeros(
                    self.max_batch * full_w, dtype=np.uint8)
            self.set_stage_caps(stage_caps, overflow_thresholds)

    def set_stage_caps(
            self, stage_caps: Mapping[str, int],
            overflow_thresholds: Optional[Mapping[str, int]] = None
    ) -> None:
        """Install a plan's staged widths (hot-swap flip point: called
        only between batches, like _adopt_*_state). The packed buffers
        are spec-sized, so widening is just a new layout."""
        if "packed" not in self._bufs[0]:
            raise ValueError(
                "encoder was built without packed staging buffers")
        self.stage_caps = self._clamp_caps(stage_caps)
        self._layout = build_packed_layout(self.stage_caps)
        for bufs in self._bufs:
            bufs["packed_dirty"] = bufs["packed"].size
        self._thresholds = dict(self.specs)
        if overflow_thresholds is not None:
            for f in STRING_FIELDS:
                self._thresholds[f] = min(
                    int(overflow_thresholds.get(
                        f, self.specs.get(f, 256))),
                    self.specs.get(f, 256))

    def _clamp_caps(self, stage_caps: Mapping[str, int]) -> dict[str, int]:
        return {f: min(int(stage_caps.get(f, self.specs.get(f, 256))),
                       self.specs.get(f, 256)) for f in STRING_FIELDS}

    def packed_width(self, stage_caps: Mapping[str, int]) -> int:
        """The packed row stride `set_stage_caps(stage_caps)` gives."""
        return build_packed_layout(self._clamp_caps(stage_caps)).width

    def _checkout(self) -> dict:
        buf = self._bufs[self._cursor]
        self._cursor = (self._cursor + 1) % self.nbuf
        self.checkouts += 1
        return buf

    def encode_requests(
        self, requests: list[RequestTuple], pad_to: Optional[int] = None,
    ) -> RequestBatch:
        """RequestTuples -> bucketed+padded staging views (hot).

        Value-identical to
        `pad_batch(bucket of encode_requests(requests), pad_to)`; the
        returned arrays are views into this encoder's rotating buffers
        and stay valid until the buffer set cycles back (nbuf - 1
        later checkouts)."""
        B = len(requests)
        P = B if pad_to is None else int(pad_to)
        if not B or P < B or P > self.max_batch:
            raise ValueError(f"bad staging shape: B={B} pad_to={pad_to} "
                             f"max_batch={self.max_batch}")
        buf = self._checkout()
        if self._layout is not None:
            return self._encode_requests_packed(requests, B, P, buf)
        arrays: dict = {}
        overflow = buf["overflow"][:P]
        overflow[:] = False
        for field in STRING_FIELDS:
            cap = self.specs.get(field, 256)
            raws = []
            longest = 0
            for i, req in enumerate(requests):
                full = _to_bytes(getattr(req, field))
                if len(full) > cap:
                    overflow[i] = True
                raw = full[:cap]
                raws.append(raw)
                if len(raw) > longest:
                    longest = len(raw)
            L = bucket_len(longest, cap)
            data = buf[f"{field}_bytes"][:P, :L]
            lens = buf[f"{field}_len"][:P]
            data[:] = 0
            lens[B:] = 0
            for i, raw in enumerate(raws):
                data[i, : len(raw)] = np.frombuffer(raw, dtype=np.uint8)
                lens[i] = len(raw)
            arrays[f"{field}_bytes"] = data
            arrays[f"{field}_len"] = lens
        ip = buf["ip"][:P]
        ip[B:] = 0
        for i, req in enumerate(requests):
            try:
                ip[i], _ = ip_to_words(Ip(req.ip))
            except Exception:
                ip[i] = 0  # unparseable -> never matches any predicate
        arrays["ip"] = ip
        asn = buf["asn"][:P]
        port = buf["remote_port"][:P]
        asn[B:] = 0
        port[B:] = 0
        for i, req in enumerate(requests):
            asn[i] = _clamp_i64(req.asn)
            port[i] = _clamp_i64(req.remote_port)
        arrays["asn"] = asn
        arrays["remote_port"] = port
        staged = sum(a.nbytes for a in arrays.values())
        return RequestBatch(size=P, arrays=arrays, overflow=overflow,
                            staged_bytes=staged)

    def _encode_requests_packed(self, requests, B: int, P: int,
                                buf: dict) -> RequestBatch:
        """Compact-mode tuple encode (hot): capped field prefixes +
        metadata tail into ONE flat [P, width] packed buffer; the
        returned arrays' byte matrices are strided views into it, so
        host consumers (host-rule lanes, parity contexts, the scorer)
        read the exact bytes the device decodes."""
        layout = self._layout
        W = layout.width
        pk = buf["packed"][: P * W].reshape(P, W)
        pk[:] = 0
        # a slot encode into this set next clears all of it
        buf["packed_dirty"] = buf["packed"].size
        arrays: dict = {}
        overflow = buf["overflow"][:P]
        overflow[:] = False
        for field, off, w in layout.fields:
            spec = self.specs.get(field, 256)
            limit = self._thresholds.get(field, spec)
            data = pk[:, off:off + w]
            lens = buf[f"{field}_len"][:P]
            lens[B:] = 0
            for i, req in enumerate(requests):
                full = _to_bytes(getattr(req, field))
                if len(full) > limit:
                    overflow[i] = True
                raw = full[:w]
                data[i, : len(raw)] = np.frombuffer(raw, dtype=np.uint8)
                # TRUE length (up to spec) regardless of the staged
                # width: device length predicates must stay exact.
                lens[i] = min(len(full), spec)
            arrays[f"{field}_bytes"] = data
            arrays[f"{field}_len"] = lens
        ip = buf["ip"][:P]
        ip[B:] = 0
        for i, req in enumerate(requests):
            try:
                ip[i], _ = ip_to_words(Ip(req.ip))
            except Exception:
                ip[i] = 0  # unparseable -> never matches any predicate
        arrays["ip"] = ip
        asn = buf["asn"][:P]
        port = buf["remote_port"][:P]
        asn[B:] = 0
        port[B:] = 0
        for i, req in enumerate(requests):
            asn[i] = _clamp_i64(req.asn)
            port[i] = _clamp_i64(req.remote_port)
        arrays["asn"] = asn
        arrays["remote_port"] = port
        self._pack_meta(pk, P, buf, layout)
        return RequestBatch(size=P, arrays=arrays, overflow=overflow,
                            packed=pk, layout=layout,
                            staged_bytes=P * W, upload_rows=P)

    def _pack_meta(self, pk: np.ndarray, rows: int, buf: dict,
                   layout: PackedLayout) -> None:
        """Write the metadata tail of the first `rows` packed rows from
        the side arrays (hot): u16-LE lens columns, big-endian IP bytes,
        i64-LE asn/port bytes. The side arrays stay authoritative for
        host consumers; the tail is what the device decodes."""
        pk = pk[:rows]
        for field, off in layout.lens:
            lens = buf[f"{field}_len"][:rows]
            pk[:, off] = lens & 0xFF
            pk[:, off + 1] = (lens >> 8) & 0xFF
        pk[:, layout.ip_off:layout.ip_off + 16] = \
            buf["ip"][:rows].astype(">u4").view(np.uint8)
        pk[:, layout.asn_off:layout.asn_off + 8] = \
            buf["asn"][:rows].view(np.uint8).reshape(rows, 8)
        pk[:, layout.port_off:layout.port_off + 8] = \
            buf["remote_port"][:rows].view(np.uint8).reshape(rows, 8)

    def encode_slots(self, slots: np.ndarray,
                     pad_to: Optional[int] = None) -> RequestBatch:
        """Shm slot rows -> bucketed+padded staging views (hot).

        `slots` is a structured-array view over n REQUEST_SLOT_DTYPE
        rows (native_ring.Ring.dequeue_batch_into buffers). Per field:
        one vectorized strided copy out of the slots, lens cast in the
        same assignment — value-identical to the legacy
        slots_to_arrays -> bucket_arrays -> pad_batch chain, with no
        intermediate matrices and no per-slot tuples."""
        n = len(slots)
        P = n if pad_to is None else int(pad_to)
        if not n or P < n or P > self.max_batch:
            raise ValueError(f"bad staging shape: n={n} pad_to={pad_to} "
                             f"max_batch={self.max_batch}")
        buf = self._checkout()
        if self._layout is not None:
            return self._encode_slots_packed(slots, n, P, buf)
        arrays: dict = {}
        for field, len_key in SLOT_LEN_KEYS.items():
            cap = self.specs.get(field, 256)
            lens = buf[f"{field}_len"][:P]
            lens[:n] = slots[len_key]
            lens[n:] = 0
            longest = int(lens[:n].max()) if n else 0
            L = bucket_len(longest, cap)
            data = buf[f"{field}_bytes"][:P, :L]
            data[:n] = slots[field][:, :L]
            data[n:] = 0
            arrays[f"{field}_bytes"] = data
            arrays[f"{field}_len"] = lens
        # country: fixed 2-byte code, no slot length field (the legacy
        # path reports len 2 for live rows, 0 for padding).
        cdata = buf["country_bytes"][:P, :2]
        cdata[:n] = np.frombuffer(
            slots["country"].tobytes(), dtype=np.uint8).reshape(-1, 2)
        cdata[n:] = 0
        clens = buf["country_len"][:P]
        clens[:n] = 2
        clens[n:] = 0
        arrays["country_bytes"] = cdata
        arrays["country_len"] = clens
        ip = buf["ip"][:P]
        # big-endian slot words -> native u32 in one casting assignment.
        ip[:n] = slots["ip"].view(">u4")
        ip[n:] = 0
        arrays["ip"] = ip
        asn = buf["asn"][:P]
        asn[:n] = slots["asn"]
        asn[n:] = 0
        arrays["asn"] = asn
        port = buf["remote_port"][:P]
        port[:n] = slots["remote_port"]
        port[n:] = 0
        arrays["remote_port"] = port
        staged = sum(a.nbytes for a in arrays.values())
        return RequestBatch(size=P, arrays=arrays, overflow=None,
                            staged_bytes=staged)

    def _encode_slots_packed(self, slots: np.ndarray, n: int, P: int,
                             buf: dict) -> RequestBatch:
        """Compact-mode slot encode (hot): the capped prefix of every
        string field copied STRAIGHT from the shm slot rows into the
        packed buffer — one strided copy per field region, no
        intermediate per-field staging matrices. Depth-overflow rows
        (true slot length beyond a clamped cap) are flagged for the
        sidecar's interpreter backstop; with unclamped plan caps the
        thresholds equal the specs and no slot row can exceed them
        (over-spec requests already ride the TRUNCATED/spill flags).

        Only the bytes an earlier batch left past this batch's `n` rows
        are zeroed (the set's `packed_dirty` mark): the `n` rows are
        overwritten whole, field regions and tail, and the rest of the
        [P, width] view is zero, as a full clear would leave it. The
        batch ships its first `upload_rows(n, P)` rows."""
        layout = self._layout
        W = layout.width
        pk = buf["packed"][: P * W].reshape(P, W)
        live, dirty = n * W, buf["packed_dirty"]
        if dirty > live:
            buf["packed"][live:dirty] = 0
        buf["packed_dirty"] = live
        arrays: dict = {}
        overflow = buf["overflow"][:P]
        overflow[:] = False
        for field, off, w in layout.fields:
            data = pk[:, off:off + w]
            if field == "country":
                data[:n] = np.frombuffer(
                    slots["country"].tobytes(),
                    dtype=np.uint8).reshape(-1, 2)[:, :w]
                clens = buf["country_len"][:P]
                clens[:n] = 2
                clens[n:] = 0
                arrays["country_bytes"] = data
                arrays["country_len"] = clens
                continue
            spec = self.specs.get(field, 256)
            limit = self._thresholds.get(field, spec)
            lens = buf[f"{field}_len"][:P]
            lens[:n] = slots[SLOT_LEN_KEYS[field]]
            lens[n:] = 0
            if limit < spec:
                overflow[:n] |= lens[:n] > limit
            data[:n] = slots[field][:, :w]
            arrays[f"{field}_bytes"] = data
            arrays[f"{field}_len"] = lens
        ip = buf["ip"][:P]
        ip[:n] = slots["ip"].view(">u4")
        ip[n:] = 0
        arrays["ip"] = ip
        asn = buf["asn"][:P]
        asn[:n] = slots["asn"]
        asn[n:] = 0
        arrays["asn"] = asn
        port = buf["remote_port"][:P]
        port[:n] = slots["remote_port"]
        port[n:] = 0
        arrays["remote_port"] = port
        self._pack_meta(pk, n, buf, layout)
        rows = upload_rows(n, P)
        return RequestBatch(size=P, arrays=arrays, overflow=overflow,
                            packed=pk, layout=layout,
                            staged_bytes=rows * W, upload_rows=rows)


def batch_to_contexts(
    batch: RequestBatch, lists: Mapping[str, list]
) -> list[Context]:
    """Rebuild interpreter contexts from the encoded batch — the parity
    oracle sees exactly the (truncated) bytes the device saw."""
    out = []
    B = batch.size
    for i in range(B):
        fields = {}
        for field in STRING_FIELDS:
            data = batch[f"{field}_bytes"][i]
            n = int(batch[f"{field}_len"][i])
            fields[field] = bytes(data[:n]).decode("latin-1")
        ip = _words_to_ip(batch["ip"][i])
        ctx = Context(
            {
                "http_request": {
                    "host": fields["host"],
                    "url": fields["url"],
                    "path": fields["path"],
                    "method": fields["method"],
                    "user_agent": fields["user_agent"],
                },
                "client": {
                    "ip": ip,
                    "remote_port": int(batch["remote_port"][i]),
                    "asn": int(batch["asn"][i]),
                    "country": fields["country"],
                },
                "lists": dict(lists),
            }
        )
        out.append(ctx)
    return out


def tuple_to_context(tup: RequestTuple, lists: Mapping[str, list]) -> Context:
    """Interpreter context straight from the UNTRUNCATED request tuple —
    used for overflow-row re-evaluation and route matching. The reference
    builds the same variable shape at http_listener.rs:238-249."""
    try:
        ip = Ip(tup.ip)
    except Exception:
        ip = Ip("0.0.0.0")
    return Context({
        "http_request": {
            "host": tup.host, "url": tup.url, "path": tup.path,
            "method": tup.method, "user_agent": tup.user_agent,
        },
        "client": {
            "ip": ip, "remote_port": tup.remote_port,
            "asn": tup.asn, "country": tup.country,
        },
        "lists": dict(lists),
    })


def _words_to_ip(words: np.ndarray) -> Ip:
    value = 0
    for w in words:
        value = (value << 32) | int(w)
    import ipaddress

    if (value >> 32) == 0xFFFF:  # v4-mapped
        return Ip(ipaddress.ip_address(value & 0xFFFFFFFF))
    return Ip(ipaddress.ip_address(value))


def requests_from_dicts(rows: Iterable[Mapping]) -> list[RequestTuple]:
    return [RequestTuple(**row) for row in rows]

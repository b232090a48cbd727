"""Compact staging (ISSUE 15, docs/EXECUTOR.md "Compact staging").

Covers the tentpole's cap-soundness contract and the satellites:

  * Plan-derived caps: the compile pass's per-field dependent-depth
    scan, the pow2 rung quantization, the PINGOO_STAGING_DEPTH clamp,
    and the two-threshold overflow rule (cap at or above the plan's
    required depth -> threshold is the spec, exactly full mode's
    over-capacity rule; clamped below it -> every longer row reroutes
    through the interpreter backstop).
  * Packed one-copy dispatch: the PackedLayout byte map, the layout
    cache that keys XLA compiles by caps rung-tuple, and device-side
    decode (verdict.unpack_staged) bit-identical to the side arrays
    the host keeps.
  * Randomized full|compact verdict bit-identity across seeds and odd
    batch shapes at the verdict-program level, plus the pinned
    last-dependent-byte-exactly-at-cap case.
  * Sidecar end-to-end: full|compact served-verdict checksums through
    real shm rings (ring wraparound, spill slots) and a mid-run
    hot-swap onto a plan with WIDER caps.
  * The upload height: a slot batch ships its first 64, 256 or all of
    its rows, the chip pads them, and what the program pair reads is
    the full upload byte for byte, whatever the set held before.
  * The staged-bytes-bucketed dispatch EWMA.
  * The analyze-lint hot registration of the packed encode path, with
    a mutation proof that a fresh per-batch allocation there fails
    `make analyze`.
"""

import os
import random
import threading
import time

import numpy as np
import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.compiler import compile_ruleset
from pingoo_tpu.compiler.plan import STAGING_RUNGS, quantize_stage_cap
from pingoo_tpu.engine.batch import (
    STRING_FIELDS,
    PackedLayout,
    RequestTuple,
    StagingEncoder,
    build_packed_layout,
    pow2_batch_size,
    resolve_stage_caps,
    resolve_staging_mode,
    stage_overflow_thresholds,
    upload_rows,
)
from pingoo_tpu.sched.scheduler import CostModel, _pow2_kb_bucket
from test_parity import LISTS, RULE_SOURCES, make_rules, random_requests

HAVE_NATIVE = native_ring.ensure_built()
needs_native = pytest.mark.skipif(
    not HAVE_NATIVE, reason="native toolchain unavailable")


def _make_plan(sources=None, lists=None):
    return compile_ruleset(make_rules(sources or RULE_SOURCES),
                           LISTS if lists is None else lists)


def _rule(name, src):
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    return RuleConfig(name=name, actions=(Action.BLOCK,),
                      expression=compile_expression(src))


# -- plan-derived caps -------------------------------------------------------


class TestCapDerivation:
    def test_rung_quantization(self):
        assert STAGING_RUNGS == (16, 32, 64, 128, 256, 512, 1024, 2048)
        assert quantize_stage_cap(1, 2048) == 16
        assert quantize_stage_cap(16, 2048) == 16
        assert quantize_stage_cap(17, 2048) == 32
        assert quantize_stage_cap(300, 2048) == 512
        # The spec bounds the ladder: never stage wider than the field.
        assert quantize_stage_cap(300, 256) == 256
        assert quantize_stage_cap(4096, 2048) == 2048

    def test_shallow_plan_derives_shallow_caps(self):
        """A prefix rule depends on |pattern| bytes: the cap lands on
        the smallest rung covering it, far below the 2048 spec."""
        plan = compile_ruleset(
            [_rule("p", 'http_request.path.starts_with("/admin/")')], {})
        assert plan.staging_required["path"] <= 16
        assert plan.staging_caps["path"] == 16
        # Fields no rule reads stage at the minimum rung.
        assert plan.staging_caps["user_agent"] == STAGING_RUNGS[0]

    def test_regex_pins_field_to_spec(self):
        """An NFA scan can depend on any byte up to the scan window:
        the compile pass must pin the field to its full spec."""
        plan = _make_plan()  # RULE_SOURCES carries regex/contains rules
        specs = plan.field_specs
        assert plan.staging_caps["url"] == specs["url"]

    def test_resolve_mode_and_env_clamp(self, monkeypatch):
        plan = _make_plan()
        monkeypatch.delenv("PINGOO_STAGING", raising=False)
        assert resolve_staging_mode() == "full"
        assert resolve_stage_caps(plan) is None  # full = no caps
        monkeypatch.setenv("PINGOO_STAGING", "compact")
        monkeypatch.delenv("PINGOO_STAGING_DEPTH", raising=False)
        caps = resolve_stage_caps(plan)
        assert caps is not None
        for field in STRING_FIELDS:
            assert 1 <= caps[field] <= plan.field_specs.get(field, 256)
        monkeypatch.setenv("PINGOO_STAGING_DEPTH", "64")
        clamped = resolve_stage_caps(plan)
        assert all(clamped[f] <= max(64, 2) for f in STRING_FIELDS)

    def test_overflow_thresholds_two_regimes(self, monkeypatch):
        plan = _make_plan()
        monkeypatch.setenv("PINGOO_STAGING", "compact")
        monkeypatch.delenv("PINGOO_STAGING_DEPTH", raising=False)
        caps = resolve_stage_caps(plan)
        th = stage_overflow_thresholds(plan, caps)
        # Unclamped: every cap covers the plan's required depth, so the
        # thresholds equal the specs — overflow is full mode's rule.
        for field in STRING_FIELDS:
            assert th[field] == plan.field_specs.get(field, 256), field
        monkeypatch.setenv("PINGOO_STAGING_DEPTH", "64")
        caps64 = resolve_stage_caps(plan)
        th64 = stage_overflow_thresholds(plan, caps64)
        clamped_fields = [f for f in STRING_FIELDS
                         if caps64[f] < min(plan.staging_required.get(
                             f, 10**9), plan.field_specs.get(f, 256))]
        assert clamped_fields  # the regex-pinned url/path must clamp
        for field in clamped_fields:
            assert th64[field] == caps64[field], field


# -- packed layout + device decode ------------------------------------------


class TestPackedLayout:
    CAPS = {"host": 32, "url": 64, "path": 32, "method": 16,
            "user_agent": 32, "country": 2}

    def test_layout_geometry(self):
        layout = build_packed_layout(self.CAPS)
        off = 0
        for field, f_off, w in layout.fields:
            assert f_off == off and w == self.CAPS[field]
            off += w
        for _field, l_off in layout.lens:
            assert l_off == off
            off += 2
        assert layout.ip_off == off
        assert layout.asn_off == off + 16
        assert layout.port_off == off + 24
        assert layout.width == off + 32

    def test_layout_cache_reuses_hash_equal_instances(self):
        """Hot-swaps between plans on the same rungs must hand the
        jitted packed fns the SAME static layout (no retrace)."""
        a = build_packed_layout(dict(self.CAPS))
        b = build_packed_layout(dict(self.CAPS))
        assert a is b
        assert isinstance(a, PackedLayout) and hash(a) == hash(b)

    def test_device_decode_matches_host_arrays(self):
        """unpack_staged over a packed batch must reproduce the side
        arrays byte-for-byte — lens, big-endian IP words and the i64
        asn/port included (negative asn exercises the bitcast)."""
        from pingoo_tpu.engine.verdict import unpack_staged

        enc = StagingEncoder(16, stage_caps=self.CAPS)
        reqs = [
            RequestTuple(host="h.example", url="/x" * 40, path="/deep",
                         method="POST", user_agent="UA " + "y" * 50,
                         ip="203.0.113.9", remote_port=443,
                         asn=-64500, country="DE"),
            RequestTuple(host="b", url="/", path="/", ip="::1",
                         remote_port=65535, asn=2 ** 40, country="FR"),
        ]
        batch = enc.encode_requests(reqs, pad_to=4)
        assert batch.packed is not None
        dec = unpack_staged(np.asarray(batch.packed), batch.layout)
        for key, host_arr in batch.arrays.items():
            got = np.asarray(dec[key])
            want = np.asarray(host_arr)
            if key.endswith("_len"):
                # Device lens are exact only up to u16 (spec <= 2048).
                want = want.astype(np.int32)
            assert np.array_equal(got, want), key

    def test_staged_bytes_accounting(self):
        caps_enc = StagingEncoder(16, stage_caps=self.CAPS)
        full_enc = StagingEncoder(16)
        reqs = [RequestTuple(host="h", url="/" + "a" * 900, path="/p",
                             user_agent="ua", ip="10.0.0.1")]
        packed = caps_enc.encode_requests(reqs, pad_to=1)
        full = full_enc.encode_requests(reqs, pad_to=1)
        assert packed.staged_bytes == build_packed_layout(self.CAPS).width
        assert full.staged_bytes == sum(
            a.nbytes for a in full.arrays.values())
        # The long-URL row bucketed full mode to 1024 url columns; the
        # capped packed row stays at the layout stride.
        assert packed.staged_bytes < full.staged_bytes


# -- full|compact verdict bit-identity --------------------------------------


def _packed_batch(plan, reqs, pad, monkeypatch, depth=None):
    monkeypatch.setenv("PINGOO_STAGING", "compact")
    if depth is None:
        monkeypatch.delenv("PINGOO_STAGING_DEPTH", raising=False)
    else:
        monkeypatch.setenv("PINGOO_STAGING_DEPTH", str(depth))
    caps = resolve_stage_caps(plan)
    enc = StagingEncoder(
        max(64, pad), plan.field_specs, stage_caps=caps,
        overflow_thresholds=stage_overflow_thresholds(plan, caps))
    return enc.encode_requests(reqs, pad_to=pad)


class TestVerdictBitIdentity:
    """make_packed_verdict_fn over the packed buffer vs make_verdict_fn
    over full staging arrays: the device matrices must be bit-equal."""

    def _matrices(self, plan, reqs, pad, monkeypatch, depth=None):
        import jax

        from pingoo_tpu.engine.verdict import (
            make_packed_prefilter_fn,
            make_packed_verdict_fn,
            make_prefilter_fn,
            make_verdict_fn,
        )

        tables = jax.device_put(plan.device_tables())
        full_enc = StagingEncoder(max(64, pad), plan.field_specs)
        full = full_enc.encode_requests(reqs, pad_to=pad)
        dev_arrays = {k: jax.device_put(v) for k, v in full.arrays.items()}
        pf = make_prefilter_fn(plan)
        pf_hits = pf.fn(tables, dev_arrays)[0] if pf is not None else None
        ref = np.asarray(make_verdict_fn(plan)(
            tables, dev_arrays, pf_hits))
        self._full_overflow = np.asarray(full.overflow, dtype=bool)

        batch = _packed_batch(plan, reqs, pad, monkeypatch, depth=depth)
        assert batch.packed is not None
        dev_packed = jax.device_put(batch.packed)
        ppf = make_packed_prefilter_fn(plan)
        p_hits = (ppf.fn(tables, dev_packed, batch.layout)[0]
                  if ppf is not None else None)
        got = np.asarray(make_packed_verdict_fn(plan)(
            tables, dev_packed, batch.layout, p_hits))
        return ref, got, batch

    def test_random_rulesets_and_seeds(self, monkeypatch):
        plan = _make_plan()
        for seed, n in ((0, 7), (1, 13), (2, 31), (3, 64)):
            reqs = random_requests(random.Random(seed), n)
            pad = pow2_batch_size(n, 64)
            ref, got, batch = self._matrices(plan, reqs, pad, monkeypatch)
            assert np.array_equal(ref, got), (seed, n)
            # Unclamped caps: overflow is exactly full mode's over-spec
            # rule — no extra depth-overflow rows.
            assert np.array_equal(np.asarray(batch.overflow, dtype=bool),
                                  self._full_overflow), (seed, n)

    def test_clamped_caps_stay_identical_off_overflow_rows(
            self, monkeypatch):
        """Under a hard 64-byte clamp the unflagged rows must still be
        bit-identical (cap-decidability); flagged rows are the
        interpreter backstop's job and are excluded here."""
        plan = _make_plan()
        reqs = random_requests(random.Random(11), 48)
        ref, got, batch = self._matrices(plan, reqs, 64, monkeypatch,
                                         depth=64)
        clean = ~np.asarray(batch.overflow[:48], dtype=bool)
        assert clean.any()
        assert np.array_equal(ref[:48][clean], got[:48][clean])

    def test_last_dependent_byte_exactly_at_cap(self, monkeypatch):
        """Pinned boundary case: a 16-byte prefix pattern derives a
        16-byte cap; a row whose match is decided BY byte 15 (and a
        near-miss whose first divergence is byte 15) must verdict
        identically when the staged width is exactly 16."""
        pat = "/abcdefghijklmn/"  # 16 bytes
        plan = compile_ruleset(
            [_rule("edge", f'http_request.path.starts_with("{pat}")')],
            {})
        assert plan.staging_caps["path"] == 16
        reqs = [
            RequestTuple(path=pat + "tail/x", url=pat, ip="10.0.0.1"),
            RequestTuple(path=pat[:-1] + "X" + "tail", url="/",
                         ip="10.0.0.2"),
            RequestTuple(path=pat, url="/", ip="10.0.0.3"),
        ]
        ref, got, batch = self._matrices(plan, reqs, 4, monkeypatch)
        assert np.array_equal(ref, got)
        assert ref[:3, 0].tolist() == [True, False, True]
        assert not batch.overflow[:3].any()


# -- encoder overflow + hot-swap cap flips -----------------------------------


class TestPackedEncoder:
    def test_depth_overflow_flags_only_clamped_fields(self, monkeypatch):
        plan = _make_plan()
        monkeypatch.setenv("PINGOO_STAGING", "compact")
        monkeypatch.setenv("PINGOO_STAGING_DEPTH", "64")
        caps = resolve_stage_caps(plan)
        th = stage_overflow_thresholds(plan, caps)
        enc = StagingEncoder(8, plan.field_specs, stage_caps=caps,
                             overflow_thresholds=th)
        reqs = [
            RequestTuple(host="h", url="/" + "q" * 200, path="/short",
                         ip="10.0.0.1"),
            RequestTuple(host="h", url="/ok", path="/ok",
                         ip="10.0.0.2"),
        ]
        batch = enc.encode_requests(reqs, pad_to=2)
        assert batch.overflow[:2].tolist() == [True, False]
        # TRUE length rides the meta tail even though bytes are capped.
        assert int(batch.arrays["url_len"][0]) == 201
        assert batch.arrays["url_bytes"].shape[1] == caps["url"]

    def test_set_stage_caps_widens_at_flip(self):
        # Caps are clamped to each field's spec at install time (e.g.
        # method's spec is below 64): compare against the encoder's
        # APPLIED caps, and assert the url region genuinely widened.
        caps16 = {f: 16 if f != "country" else 2 for f in STRING_FIELDS}
        caps64 = {f: 64 if f != "country" else 2 for f in STRING_FIELDS}
        enc = StagingEncoder(8, stage_caps=caps16)
        r = [RequestTuple(url="/" + "z" * 60, path="/p", ip="10.0.0.1")]
        narrow = enc.encode_requests(r, pad_to=1)
        assert narrow.layout.width == build_packed_layout(caps16).width
        assert int(narrow.arrays["url_bytes"].shape[1]) == 16
        enc.set_stage_caps(caps64)
        wide = enc.encode_requests(r, pad_to=1)
        assert wide.layout.width == \
            build_packed_layout(enc.stage_caps).width
        assert wide.layout.width > narrow.layout.width
        assert int(wide.arrays["url_bytes"].shape[1]) == 64
        # The widened view carries the bytes the narrow one clipped.
        assert bytes(wide.arrays["url_bytes"][0][:61]) == \
            b"/" + b"z" * 60

    def test_encoder_without_packed_buffers_rejects_caps(self):
        enc = StagingEncoder(8)
        with pytest.raises(ValueError):
            enc.set_stage_caps({f: 16 for f in STRING_FIELDS})


# -- sidecar end-to-end ------------------------------------------------------


@needs_native
@pytest.mark.slow
class TestSidecarStagingParity:
    """PINGOO_STAGING full|compact through real shm rings: identical
    served actions over a stream that exercises ring wraparound and
    spill slots (over-spec URLs), plus a mid-run hot-swap onto a plan
    with wider caps."""

    def _drive(self, tmp_path, tag, env, n=260):
        from pingoo_tpu.native_ring import Ring, RingSidecar

        plan = compile_ruleset(make_rules(RULE_SOURCES[:23]), LISTS)
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            ring = Ring(str(tmp_path / f"ring-{tag}"), capacity=64,
                        create=True)  # small: forces wraparound
            sidecar = RingSidecar(ring, plan, LISTS, max_batch=16,
                                  pipeline_depth=3)
            th = threading.Thread(target=sidecar.run, daemon=True)
            th.start()
            rng = random.Random(31)
            paths = []
            for k in range(n):
                r = rng.random()
                if r < 0.25:
                    paths.append(b"/admin/.env")
                elif r < 0.30:  # over-spec url -> TRUNCATED+spill slot
                    paths.append(b"/long/" + b"a" * 4000)
                elif r < 0.40:  # in-spec but beyond a 64-byte clamp
                    paths.append(b"/mid/" + b"m" * 150)
                else:
                    paths.append(f"/ok/{k}".encode())
            actions = {}
            sent = 0
            deadline = time.time() + 120
            while len(actions) < n and time.time() < deadline:
                if sent < n:
                    p = paths[sent]
                    t = ring.enqueue(
                        method=b"GET", host=b"h.test", path=p, url=p,
                        user_agent=b"Mozilla/5.0 t",
                        ip=b"\x00" * 10 + b"\xff\xff" + bytes(
                            [172, 16, sent % 256, 7]),
                        port=4100 + sent, asn=64496, country=b"FR")
                    if t is not None:
                        sent += 1
                v = ring.poll_verdict()
                while v is not None:
                    actions[v[0]] = v[1]
                    v = ring.poll_verdict()
            parity = sidecar.parity
            if parity is not None:
                parity.flush(30)
                checked = parity.checked_total.value
                mismatches = parity.mismatch_total.value
            else:
                checked = mismatches = 0
            overflow_rows = sidecar.depth_overflow_rows
            sidecar.stop()
            ring.close()
            assert len(actions) == n, f"{tag}: {len(actions)}/{n}"
            return ([actions[t] for t in sorted(actions)],
                    checked, mismatches, overflow_rows)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def test_full_compact_checksum_parity_with_auditor(self, tmp_path):
        base = {"PINGOO_PIPELINE": "on", "PINGOO_PARITY_SAMPLE": "1",
                "PINGOO_PROVENANCE": "1"}
        full, chk_f, mm_f, _ = self._drive(
            tmp_path, "full", {**base, "PINGOO_STAGING": "full"})
        compact, chk_c, mm_c, _ = self._drive(
            tmp_path, "compact", {**base, "PINGOO_STAGING": "compact"})
        assert full == compact
        assert len(set(full)) > 1  # mixed allow/block stream
        assert chk_f > 0 and mm_f == 0
        assert chk_c > 0 and mm_c == 0

    def test_clamped_compact_stays_identical(self, tmp_path):
        """PINGOO_STAGING_DEPTH below the plan's regex pins: the
        interpreter backstop re-serves the deep rows and the served
        stream stays bit-identical to full mode."""
        base = {"PINGOO_PIPELINE": "on"}
        full, _, _, _ = self._drive(
            tmp_path, "full64", {**base, "PINGOO_STAGING": "full"})
        compact, _, _, over = self._drive(
            tmp_path, "comp64",
            {**base, "PINGOO_STAGING": "compact",
             "PINGOO_STAGING_DEPTH": "64"})
        assert full == compact
        assert over > 0  # the clamp actually rerouted deep rows

    def test_hot_swap_widens_caps_mid_run(self, tmp_path, monkeypatch):
        """Swap from a shallow-cap plan to one whose rules need wider
        staging: the encoder re-caps at the batch boundary and the
        post-swap phase is bit-exact under the NEW plan."""
        from pingoo_tpu.native_ring import Ring, RingSidecar

        monkeypatch.setenv("PINGOO_STAGING", "compact")
        shallow = compile_ruleset(
            [_rule("blk", 'http_request.path.starts_with("/alpha")')], {})
        deep_pat = "/beta/" + "d" * 90  # needs a 128-rung path cap
        deep = compile_ruleset(
            [_rule("blk", f'http_request.path.starts_with("{deep_pat}")')],
            {})
        assert deep.staging_caps["path"] > shallow.staging_caps["path"]
        ring = Ring(str(tmp_path / "ring-swap"), capacity=128,
                    create=True)
        sidecar = RingSidecar(ring, shallow, {}, max_batch=16)
        n = 40

        def enq(i, phase):
            if i % 3 == 0:
                p = (b"/alpha/x" if phase == "a"
                     else deep_pat.encode() + b"/x")
            else:
                p = b"/ok/%d" % i
            return ring.enqueue(method=b"GET", host=b"r.test", path=p,
                                url=p, user_agent=b"Mozilla/5.0")

        def poll_all(need, timeout=120.0):
            got = {}
            end = time.monotonic() + timeout
            while len(got) < need and time.monotonic() < end:
                v = ring.poll_verdict()
                if v is None:
                    time.sleep(0.002)
                    continue
                got[v[0]] = v[1]
            return got

        try:
            worker = threading.Thread(target=sidecar.run, daemon=True)
            worker.start()
            for i in range(n):
                assert enq(i, "a") is not None
            got_a = poll_all(n)
            handle = sidecar.request_swap(deep)
            assert handle.wait(120) and handle.result == "ok"
            for i in range(n, 2 * n):
                assert enq(i, "b") is not None
            got_b = poll_all(n)
            sidecar.stop()
            worker.join(30)
            assert sorted(got_a) == list(range(n))
            assert sorted(got_b) == list(range(n, 2 * n))
            for i in range(n):
                assert got_a[i] & 3 == (1 if i % 3 == 0 else 0), i
            for i in range(n, 2 * n):
                assert got_b[i] & 3 == (1 if i % 3 == 0 else 0), i
        finally:
            sidecar.stop()
            ring.close()


# -- CostModel: dispatch-bytes EWMA -------------------------------------------


class TestCostModelStaging:
    def test_dispatch_bytes_ewma_buckets(self):
        cm = CostModel(max_batch=64)
        assert _pow2_kb_bucket(40 * 1024) == _pow2_kb_bucket(60 * 1024)
        assert _pow2_kb_bucket(40 * 1024) != _pow2_kb_bucket(600 * 1024)
        cm.observe_stage("dispatch", 32, 5.0)
        # Same row count, different staged bytes: the bytes bucket wins
        # once observed, the row bucket covers the rest.
        cm.observe_dispatch_bytes(40 * 1024, 0.5)
        assert cm.estimate_dispatch(32, 40 * 1024) == 0.5
        assert cm.estimate_dispatch(32, 600 * 1024) == 5.0
        assert cm.estimate_dispatch(32, None) == 5.0
        cm.observe_dispatch_bytes(40 * 1024, 1.5)
        est = cm.estimate_dispatch(32, 40 * 1024)
        assert 0.5 < est < 1.5
        snap = cm.snapshot()
        assert list(snap["dispatch_bytes_ewma_ms"]) == \
            [f"{_pow2_kb_bucket(40 * 1024)}kb"]
        # Garbage observations are dropped, not crashed on.
        cm.observe_dispatch_bytes(0, 1.0)
        cm.observe_dispatch_bytes(1024, -1.0)


# -- obs + lint satellites ---------------------------------------------------


class TestStagingObs:
    def test_metrics_in_schema_inventory(self):
        from pingoo_tpu.obs import schema

        assert "pingoo_staged_bytes_total" in schema.STAGING_METRICS
        assert "pingoo_staging_field_cap" in schema.STAGING_METRICS
        assert set(schema.STAGING_METRICS) <= schema.all_metric_names()


class TestStagingLintRegistry:
    def test_packed_encode_registered_hot(self):
        from tools.analyze import lint_config

        for fn in (
            "pingoo_tpu/engine/batch.py::"
            "StagingEncoder._encode_requests_packed",
            "pingoo_tpu/engine/batch.py::"
            "StagingEncoder._encode_slots_packed",
            "pingoo_tpu/engine/batch.py::StagingEncoder._pack_meta",
        ):
            assert fn in lint_config.HOT_FUNCTIONS, fn

    def test_mutated_packed_encode_alloc_fails_lint(self):
        """Mutation proof: the packed encode fills ONE reused buffer;
        a fresh per-batch matrix there must fail the hot-alloc lint."""
        from tools.analyze import REPO_ROOT, lint

        with open(os.path.join(REPO_ROOT, "pingoo_tpu", "engine",
                               "batch.py")) as f:
            src = f.read()
        needle = ("        layout = self._layout\n"
                  "        W = layout.width\n"
                  "        pk = buf[\"packed\"][: P * W].reshape(P, W)")
        assert src.count(needle) == 2  # both packed fill paths
        mutated = src.replace(
            needle,
            needle + "\n        scratch = np.zeros((P, W))", 1)
        assert "scratch = np.zeros" in mutated
        findings, _ = lint.lint_source(mutated,
                                       "pingoo_tpu/engine/batch.py")
        assert any(f.rule == "hot-alloc" for f in findings), findings


# -- the upload height: ship the live rows, pad them on the chip -----------

UPLOAD_BATCH = 1024
# (live rows, rows shipped): each side of each rung
UPLOAD_CASES = [(1, 64), (7, 64), (63, 64), (64, 64), (65, 256),
                (255, 256), (256, 256), (257, 1024), (1024, 1024)]


def _slots_of(reqs):
    """The shm slot rows the native plane enqueues for `reqs`."""
    import ipaddress

    from pingoo_tpu.engine.batch import SLOT_LEN_KEYS

    out = np.zeros(len(reqs), dtype=native_ring.REQUEST_SLOT_DTYPE)
    for i, req in enumerate(reqs):
        for field, key in SLOT_LEN_KEYS.items():
            raw = getattr(req, field).encode("latin-1")[
                :native_ring.FIELD_CAPS[field]]
            out[field][i, :len(raw)] = np.frombuffer(raw, dtype=np.uint8)
            out[key][i] = len(raw)
        ip = ipaddress.ip_address(req.ip)
        if ip.version == 4:
            ip = ipaddress.IPv6Address(f"::ffff:{ip}")
        out["ip"][i] = np.frombuffer(ip.packed, dtype=np.uint8)
        out["asn"][i] = req.asn
        out["remote_port"][i] = req.remote_port
        out["country"][i] = req.country.encode()
    return out


class _UploadRig:
    """A plan with routes, its packed program pair (attribution lane
    on) and a pad program a rung, built once: the sidecar's encoder
    and programs at the served batch of 1,024 rows."""

    def __init__(self):
        import jax

        from pingoo_tpu.engine.batch import UPLOAD_ROWS
        from pingoo_tpu.engine.verdict import (make_packed_lane_fn,
                                               make_packed_prefilter_fn,
                                               make_pad_fn)
        from pingoo_tpu.expr import compile_expression

        saved = os.environ.get("PINGOO_STAGING")
        os.environ["PINGOO_STAGING"] = "compact"
        try:
            self.plan = compile_ruleset(
                make_rules(RULE_SOURCES), LISTS,
                routes=[("api", compile_expression(
                    'http_request.host == "api.example.com"')),
                    ("app", None)])
            self.caps = resolve_stage_caps(self.plan)
        finally:
            if saved is None:
                os.environ.pop("PINGOO_STAGING", None)
            else:
                os.environ["PINGOO_STAGING"] = saved
        self.thresholds = stage_overflow_thresholds(self.plan, self.caps)
        self.tables = jax.device_put(self.plan.device_tables())
        self.pf = make_packed_prefilter_fn(self.plan)
        self.lane_fn = make_packed_lane_fn(
            self.plan, service_groups=[["api", "app"]], with_rule_hits=True)
        self.pad = {rows: make_pad_fn(UPLOAD_BATCH) for rows in UPLOAD_ROWS}

    def encoder(self):
        specs = dict(native_ring.FIELD_CAPS, country=2)
        return StagingEncoder(UPLOAD_BATCH, field_specs=specs, nbuf=1,
                              stage_caps=self.caps,
                              overflow_thresholds=self.thresholds)

    def lanes(self, dev_packed, layout, n):
        pf_hits = pf_aux = None
        if self.pf is not None:
            pf_hits, pf_aux = self.pf.fn(self.tables, dev_packed, layout)
        return np.asarray(self.lane_fn(self.tables, dev_packed, layout,
                                       pf_hits, np.int32(n), pf_aux))


@pytest.fixture(scope="module")
def upload_rig():
    return _UploadRig()


class TestUploadHeight:
    @pytest.mark.parametrize("n,rows", UPLOAD_CASES)
    def test_the_padded_upload_is_the_full_upload(self, upload_rig, n,
                                                  rows):
        """A slot batch of `n` rows, encoded into a set a full batch of
        longer rows dirtied, ships its first `rows` rows: padded on the
        device they equal the full upload of a freshly zeroed set byte
        for byte, the host views equal that set's, every byte past the
        live rows is zero, and the program pair reads the same verdict,
        route, cascade and attribution rows from both."""
        import jax

        rig = upload_rig
        slots = _slots_of(random_requests(random.Random(n), n))
        enc = rig.encoder()
        enc.encode_slots(_slots_of(random_requests(
            random.Random(41), UPLOAD_BATCH)), pad_to=UPLOAD_BATCH)
        batch = enc.encode_slots(slots, pad_to=UPLOAD_BATCH)
        ref = rig.encoder().encode_slots(slots, pad_to=UPLOAD_BATCH)
        width = batch.layout.width
        assert upload_rows(n, UPLOAD_BATCH) == batch.upload_rows == rows
        assert batch.staged_bytes == rows * width
        assert np.array_equal(batch.packed, ref.packed)
        for key, arr in ref.arrays.items():
            assert np.array_equal(batch.arrays[key], arr), key
        assert not enc._bufs[0]["packed"][n * width:].any()

        shipped = jax.device_put(batch.packed[:rows])
        assert shipped.shape == (rows, width)
        padded = rig.pad[rows](shipped) if rows < UPLOAD_BATCH else shipped
        full = jax.device_put(ref.packed)
        assert (padded.shape, padded.dtype) == (full.shape, full.dtype)
        assert np.array_equal(np.asarray(padded), np.asarray(full))
        got = rig.lanes(padded, batch.layout, n)
        want = rig.lanes(full, ref.layout, n)
        assert got.shape[0] > 4  # verdict, route, cascade, attribution
        assert np.array_equal(got, want)

    def test_a_swap_of_caps_clears_what_the_old_stride_left(self,
                                                            upload_rig):
        """The set's mark is in bytes of its flat buffer: rows written
        under one row stride leave no byte behind under another."""
        rig = upload_rig
        enc, fresh = rig.encoder(), rig.encoder()
        narrow = {f: 16 for f in STRING_FIELDS}
        fresh.set_stage_caps(narrow)
        enc.encode_slots(_slots_of(random_requests(random.Random(3), 300)),
                         pad_to=UPLOAD_BATCH)
        enc.set_stage_caps(narrow)
        slots = _slots_of(random_requests(random.Random(4), 5))
        batch = enc.encode_slots(slots, pad_to=UPLOAD_BATCH)
        ref = fresh.encode_slots(slots, pad_to=UPLOAD_BATCH)
        assert batch.layout.width < build_packed_layout(rig.caps).width
        assert np.array_equal(batch.packed, ref.packed)
        assert not enc._bufs[0]["packed"][5 * batch.layout.width:].any()

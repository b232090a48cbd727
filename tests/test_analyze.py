"""pingoo-analyze static-analysis suite (tools/analyze, make analyze).

Covers the three passes themselves AND the acceptance mutations from
ISSUE 3: adding a field to pingoo_ring.h alone must fail the ABI
check; inserting a bare .item() into engine/verdict.py must fail the
hot-path lint.
"""

import copy
import os

import pytest

from tools.analyze import REPO_ROOT, abi, lint
from tools.analyze import native as analyze_native

HAVE_CXX = abi.compiler() is not None
needs_cxx = pytest.mark.skipif(not HAVE_CXX,
                               reason="no C++ compiler available")


# -- ABI/layout checker ------------------------------------------------------


class TestAbiChecker:
    def test_python_dtypes_match_golden(self):
        assert abi.diff_tables(abi.python_table(), abi.load_golden(),
                               "python", "golden") == []

    @needs_cxx
    def test_emitter_matches_golden_and_python(self):
        c = abi.emitter_table()
        assert c is not None
        assert abi.diff_tables(c, abi.load_golden(), "C", "golden") == []
        assert abi.diff_tables(c, abi.python_table(), "C", "python") == []

    def test_native_ring_constants_assert_against_golden(self):
        """The former hand-maintained 4688-byte comments are now
        constants; they must equal the golden table's compiler truth."""
        from pingoo_tpu import native_ring as nr

        golden = abi.load_golden()
        sizes = {name: s["size"] for name, s in golden["structs"].items()}
        assert nr.REQUEST_SLOT_SIZE == sizes["PingooRequestSlot"] == 4688
        assert nr.VERDICT_SLOT_SIZE == sizes["PingooVerdictSlot"]
        assert nr.RING_HEADER_SIZE == sizes["PingooRingHeader"]
        assert nr.TELEMETRY_BLOCK_SIZE == sizes["PingooRingTelemetry"]
        assert nr.SPILL_SLOT_SIZE == sizes["PingooSpillSlot"]
        assert nr.RING_FORMAT_VERSION == golden["format_version"]
        consts = golden["constants"]
        assert nr.TELEMETRY_WORDS == consts["PINGOO_TELEMETRY_WORDS"]
        assert nr.SPILL_NONE == consts["PINGOO_SPILL_NONE"]
        assert len(nr.WAIT_BUCKET_BOUNDS_MS) + 1 == \
            consts["PINGOO_WAIT_BUCKETS"]

    @needs_cxx
    def test_added_header_field_alone_fails(self, tmp_path):
        """ISSUE 3 acceptance mutation: a field added to pingoo_ring.h
        without touching the dtype or golden must fail the check."""
        header = os.path.join(REPO_ROOT, "pingoo_tpu", "native",
                              "pingoo_ring.h")
        with open(header) as f:
            src = f.read()
        marker = "  uint32_t asn;\n"
        assert marker in src
        (tmp_path / "pingoo_ring.h").write_text(
            src.replace(marker, marker + "  uint32_t intruder;\n"))
        mutated = abi.emitter_table(header_dir=str(tmp_path))
        assert mutated is not None
        drift = abi.diff_tables(mutated, abi.load_golden(), "C", "golden")
        assert drift, "mutated header must not match the golden"
        assert any("PingooRequestSlot" in d for d in drift)
        # ... and against the live python dtype, not just the golden.
        assert abi.diff_tables(mutated, abi.python_table(), "C", "python")

    def test_dtype_drift_alone_fails(self):
        """Moving or dropping a field on the PYTHON side must fail."""
        table = abi.python_table()
        moved = copy.deepcopy(table)
        slot = moved["structs"]["PingooRequestSlot"]
        field = next(f for f in slot["fields"] if f["name"] == "asn")
        field["offset"] += 2
        assert any("asn" in d for d in abi.diff_tables(
            moved, abi.load_golden(), "python", "golden"))

        dropped = copy.deepcopy(table)
        slot = dropped["structs"]["PingooRequestSlot"]
        slot["fields"] = [f for f in slot["fields"]
                          if f["name"] != "enq_ms"]
        assert any("enq_ms" in d and "missing" in d
                   for d in abi.diff_tables(dropped, abi.load_golden(),
                                            "python", "golden"))

    def test_constant_drift_fails(self):
        table = copy.deepcopy(abi.python_table())
        table["constants"]["PINGOO_SPILL_SLOTS"] = 128
        assert any("PINGOO_SPILL_SLOTS" in d for d in abi.diff_tables(
            table, abi.load_golden(), "python", "golden"))


# -- JAX hot-path linter -----------------------------------------------------


def _lint(source: str, path: str = "pingoo_tpu/engine/sample.py"):
    findings, _warnings = lint.lint_source(source, path)
    return findings


class TestHotPathLinter:
    def test_current_tree_is_clean(self):
        findings, warnings = lint.lint_paths()
        assert findings == [], "\n".join(str(f) for f in findings)
        assert warnings == [], "\n".join(warnings)

    def test_inserted_item_into_verdict_fails(self):
        """ISSUE 3 acceptance mutation: a bare .item() added to
        engine/verdict.py must fail the lint."""
        with open(os.path.join(REPO_ROOT, "pingoo_tpu", "engine",
                               "verdict.py")) as f:
            src = f.read()
        mutated = src + "\n\ndef _leak(x):\n    return x.item()\n"
        findings = _lint(mutated, "pingoo_tpu/engine/verdict.py")
        assert [f.rule for f in findings] == ["sync-item"]

    def test_tolist_and_device_get_flagged(self):
        findings = _lint("def f(x):\n"
                         "    import jax\n"
                         "    return x.tolist(), jax.device_get(x)\n")
        assert {f.rule for f in findings} == {"sync-tolist",
                                              "sync-device-get"}

    def test_block_until_ready_allowlist(self):
        body = "def f(dev):\n    dev.block_until_ready()\n"
        assert [f.rule for f in _lint(body)] == ["sync-block"]
        # The same call inside the blessed _await_device (the one
        # sanctioned sync primitive finish_batch routes through) is
        # allowed.
        blessed = "def _await_device(dev):\n    dev.block_until_ready()\n"
        assert _lint(blessed, "pingoo_tpu/engine/verdict.py") == []
        # finish_batch itself is no longer blessed — a direct sync
        # there must go through _await_device.
        direct = "def finish_batch(dev):\n    dev.block_until_ready()\n"
        assert [f.rule for f in
                _lint(direct, "pingoo_tpu/engine/verdict.py")] \
            == ["sync-block"]
        # getattr() spelling is caught too.
        indirect = ("def f(dev):\n"
                    "    b = getattr(dev, 'block_until_ready', None)\n")
        assert [f.rule for f in _lint(indirect)] == ["sync-block"]

    def test_hot_function_asarray_and_alloc(self):
        src = ("import numpy as np\n"
               "class VerdictService:\n"
               "    def _evaluate_sync(self, dev):\n"
               "        buf = np.zeros(8)\n"
               "        return np.asarray(dev), buf\n")
        rules = sorted(f.rule for f in
                       _lint(src, "pingoo_tpu/engine/service.py"))
        assert rules == ["hot-alloc", "sync-asarray-hot"]
        # Identical code outside a registered hot function is fine.
        cold = src.replace("_evaluate_sync", "offline_helper")
        assert _lint(cold, "pingoo_tpu/engine/service.py") == []

    def test_recompile_const_upload_and_hoist(self):
        captured = ("import jax\n"
                    "import jax.numpy as jnp\n"
                    "TABLE = [1, 2, 3]\n"
                    "def make():\n"
                    "    @jax.jit\n"
                    "    def f(x):\n"
                    "        return x + jnp.asarray(TABLE)\n"
                    "    return f\n")
        assert [f.rule for f in _lint(captured)] == \
            ["recompile-const-upload"]
        hoisted = ("import jax\n"
                   "import jax.numpy as jnp\n"
                   "TABLE = [1, 2, 3]\n"
                   "def make():\n"
                   "    table = jnp.asarray(TABLE)\n"
                   "    @jax.jit\n"
                   "    def f(x):\n"
                   "        return x + table\n"
                   "    return f\n")
        assert _lint(hoisted) == []

    def test_scalar_cast_of_dispatch_result(self):
        src = ("class S:\n"
               "    def g(self, t, a):\n"
               "        dev = self._verdict_fn(t, a)\n"
               "        return float(dev)\n")
        assert [f.rule for f in _lint(src)] == ["sync-scalar-cast"]

    def test_jit_inside_loop(self):
        src = ("import jax\n"
               "def f(fns):\n"
               "    out = []\n"
               "    for fn in fns:\n"
               "        out.append(jax.jit(fn))\n"
               "    return out\n")
        assert [f.rule for f in _lint(src)] == ["recompile-jit-in-loop"]

    def test_suppression_requires_reason(self):
        bare = "def f(x):\n    return x.item()  # pingoo: allow(sync-item)\n"
        rules = sorted(f.rule for f in _lint(bare))
        # Reasonless allow() suppresses nothing and is itself flagged.
        assert rules == ["suppression-missing-reason", "sync-item"]
        good = ("def f(x):\n"
                "    return x.item()  "
                "# pingoo: allow(sync-item): batch of one, cold path\n")
        assert _lint(good) == []

    def test_standalone_suppression_covers_next_line(self):
        src = ("def f(x):\n"
               "    # pingoo: allow(sync-item): documented cold path\n"
               "    return x.item()\n")
        assert _lint(src) == []

    def test_unknown_rule_flagged(self):
        src = "x = 1  # pingoo: allow(no-such-rule): whatever\n"
        assert [f.rule for f in _lint(src)] == \
            ["suppression-missing-reason"]

    def test_unused_suppression_is_a_finding(self):
        """ISSUE 18 satellite: a reasoned allow() that matches nothing
        is dead weight that would swallow the NEXT finding on its line
        — a stale-suppression FINDING now, not a warning."""
        src = "x = 1  # pingoo: allow(sync-item): nothing here\n"
        findings, warnings = lint.lint_source(src, "pingoo_tpu/x.py")
        assert [f.rule for f in findings] == ["stale-suppression"]
        assert warnings == []

    def test_used_suppression_is_not_stale(self):
        src = ("def f(x):\n"
               "    return x.item()  "
               "# pingoo: allow(sync-item): cold path\n")
        assert _lint(src) == []

    def test_unquantized_len_into_dispatch_flagged(self):
        src = ("class S:\n"
               "    def go(self, data, x):\n"
               "        return self._verdict_fn(data, len(x))\n")
        assert [f.rule for f in _lint(src)] == ["unbounded-compile-axis"]

    def test_shape_attr_into_dispatch_flagged(self):
        src = ("class S:\n"
               "    def go(self, data, a):\n"
               "        return self._lane_fn(data, a.shape[0])\n")
        assert [f.rule for f in _lint(src)] == ["unbounded-compile-axis"]

    def test_quantized_shape_arg_is_clean(self):
        src = ("class S:\n"
               "    def go(self, data, x):\n"
               "        return self._verdict_fn(\n"
               "            data, pow2_batch_size(len(x), 1024))\n")
        assert _lint(src) == []

    def test_walker_skips_pycache_and_binaries(self, tmp_path):
        base = tmp_path / "pingoo_tpu" / "engine"
        (base / "__pycache__").mkdir(parents=True)
        (base / "__pycache__" / "junk.py").write_text("x.item()\n")
        (base / "ok.py").write_text("x = 1\n")
        (base / "blob.pyc").write_bytes(b"\x00\x01")
        files = list(lint.iter_lint_files(repo_root=str(tmp_path)))
        assert files == [str(base / "ok.py")]


# -- clang-tidy baseline plumbing -------------------------------------------


class TestTidyBaseline:
    SAMPLE = (
        "pingoo_tpu/native/pingoo_ring.cc:45:3: warning: avoid thing"
        " [bugprone-foo]\n"
        "junk line without structure\n"
        "/usr/include/c++/10/bits/stl_vector.h:99:5: warning: sys hdr"
        " [bugprone-bar]\n"
        "pingoo_tpu/native/pingoo_ring.cc:45:3: warning: avoid thing"
        " [bugprone-foo]\n")

    def test_normalize_dedups_and_drops_system_headers(self):
        keys = analyze_native.normalize_tidy_output(self.SAMPLE)
        assert keys == [
            "pingoo_tpu/native/pingoo_ring.cc:bugprone-foo: avoid thing"]

    def test_diff_against_baseline(self):
        findings = ["a.cc:bugprone-x: one", "b.cc:concurrency-y: two"]
        fresh, stale = analyze_native.diff_against_baseline(
            findings, ["a.cc:bugprone-x: one", "c.cc:bugprone-z: gone"])
        assert fresh == ["b.cc:concurrency-y: two"]
        assert stale == ["c.cc:bugprone-z: gone"]

    def test_committed_baseline_parses(self):
        # Comments only today; entries must be normalized keys.
        for entry in analyze_native.load_baseline():
            assert ":" in entry

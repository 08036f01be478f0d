"""Static-analysis suite (repro.analysis): every analyzer runs clean on
the repo as it stands, AND catches a seeded violation — the second half
is what makes the first half evidence instead of vacuity."""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ANALYZERS, run_analyzers
from repro.analysis.report import (Finding, Report, apply_suppressions,
                                   line_suppressed)
from repro.core.hlo_analysis import parse_donation

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------
def test_report_json_shape():
    rep = Report()
    rep.analyzers_run.append("donation")
    rep.extend([Finding("donation", "DON001", "x/y", "msg")])
    data = json.loads(rep.to_json())
    assert data["schema"] == 1
    assert data["counts"] == {"errors": 1, "warnings": 0}
    assert data["findings"][0]["code"] == "DON001"
    assert not rep.ok


def test_line_suppression_same_line_and_above():
    lines = ["a = 1", "x = sync()  # analysis: allow(host-sync)",
             "# analysis: allow(concurrency)", "y = 2"]
    assert line_suppressed(lines, 2, "host-sync")
    assert not line_suppressed(lines, 2, "concurrency")
    assert line_suppressed(lines, 4, "concurrency")
    assert not line_suppressed(lines, 1, "host-sync")


def test_code_suppression():
    fs = [Finding("kernels", "KRN002", "a", "m"),
          Finding("kernels", "KRN004", "b", "m")]
    assert [f.code for f in apply_suppressions(fs, ["KRN002"])] \
        == ["KRN004"]


def test_unknown_analyzer_rejected():
    with pytest.raises(KeyError):
        run_analyzers(["not-an-analyzer"])


# ---------------------------------------------------------------------------
# donation auditor
# ---------------------------------------------------------------------------
def test_donation_audit_clean_all_families():
    from repro.analysis import donation
    findings = donation.run()
    assert findings == [], "\n".join(map(str, findings))


def test_donation_catches_silent_copy():
    """A donated operand whose buffer cannot be reused (shape-changing
    slice) lowers WITHOUT an aliasing marker — the exact silent-copy
    the auditor exists to flag."""
    from repro.analysis.donation import _check
    buf = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    with pytest.warns(UserWarning, match="donated"):
        low = jax.jit(lambda b: b[:1, :4] * 2.0,
                      donate_argnums=0).lower(buf)
    findings = _check("seed/silent-copy", low, buf)
    assert [f.code for f in findings] == ["DON001"]


def test_donation_catches_alias_on_pure_read():
    low = jax.jit(lambda b: b + 1.0, donate_argnums=0).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    from repro.analysis.donation import _check
    findings = _check("seed/aliased-read", low, None, expect_none=True)
    assert [f.code for f in findings] == ["DON002"]


# ---------------------------------------------------------------------------
# host-sync auditor
# ---------------------------------------------------------------------------
def test_host_sync_clean():
    from repro.analysis import host_sync
    findings = host_sync.run()
    assert findings == [], "\n".join(map(str, findings))


def test_host_sync_catches_stray_device_get(tmp_path):
    from repro.analysis import host_sync
    engine_src = textwrap.dedent("""
        import jax
        class ServingEngine:
            def step(self):
                self._decode_chunk()
                self._collect()
            def _decode_chunk(self):
                block, emitted = jax.device_get((1, 2))
                return block
            def _collect(self):
                stats = jax.device_get(self.window)   # stray sync
                return stats
    """)
    cache_src = "class DenseCache:\n    pass\nclass PagedCache:\n    pass\n"
    ep = tmp_path / "engine.py"
    cp = tmp_path / "cache.py"
    ep.write_text(engine_src)
    cp.write_text(cache_src)
    findings = host_sync.run(ep, cp)
    assert [f.code for f in findings] == ["SYN001"]
    assert "_collect" in findings[0].message

    # the same sync under an allow marker passes
    ep.write_text(engine_src.replace(
        "jax.device_get(self.window)   # stray sync",
        "jax.device_get(self.window)  # analysis: allow(host-sync)"))
    assert host_sync.run(ep, cp) == []


def test_host_sync_budget_is_exact():
    """Two device_gets in _decode_chunk (allowance: one) is a finding."""
    import textwrap as tw

    from repro.analysis import host_sync
    src = tw.dedent("""
        import jax
        class ServingEngine:
            def step(self):
                self._decode_chunk()
            def _decode_chunk(self):
                a = jax.device_get(1)
                b = jax.device_get(2)
                return a, b
    """)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        ep = pathlib.Path(d, "engine.py")
        cp = pathlib.Path(d, "cache.py")
        ep.write_text(src)
        cp.write_text("class DenseCache: pass\nclass PagedCache: pass\n")
        findings = host_sync.run(ep, cp)
    assert [f.code for f in findings] == ["SYN001"]


# ---------------------------------------------------------------------------
# compile-key enumerator
# ---------------------------------------------------------------------------
def test_compile_keys_clean_and_bounded():
    from repro.analysis import compile_keys
    findings = compile_keys.run()
    assert findings == [], "\n".join(map(str, findings))
    counts = compile_keys.count_keys()
    assert sum(counts.values()) <= compile_keys.DEFAULT_BUDGET
    assert set(counts) == compile_keys.KNOWN_KINDS


def test_compile_keys_catches_unmodelled_kind(tmp_path):
    from repro.analysis import compile_keys
    src = textwrap.dedent("""
        class ServingEngine:
            def _decode_chunk(self):
                n_tokens = 1 << (4).bit_length() - 1
                key = ("chunk", n_tokens)
                if key not in self._jits:
                    pass
                return self._jits[key]
            def _novel(self, n):
                key = ("per_prompt_exact", n)
                return self._jits[key]
    """)
    ep = tmp_path / "engine.py"
    cp = tmp_path / "cache.py"
    ep.write_text(src)
    cp.write_text("")
    findings = compile_keys.run(ep, cp)
    assert [f.code for f in findings] == ["KEY001"]
    assert "per_prompt_exact" in findings[0].message


def test_compile_keys_catches_lost_pow2_rounding(tmp_path):
    from repro.analysis import compile_keys
    src = textwrap.dedent("""
        class ServingEngine:
            def _decode_chunk(self, exact):
                n_tokens = exact          # "use the exact clamp"
                key = ("chunk", n_tokens)
                return self._jits[key]
    """)
    ep = tmp_path / "engine.py"
    cp = tmp_path / "cache.py"
    ep.write_text(src)
    cp.write_text("")
    findings = compile_keys.run(ep, cp)
    assert "KEY003" in [f.code for f in findings]


# ---------------------------------------------------------------------------
# Pallas kernel checkers
# ---------------------------------------------------------------------------
def test_kernel_checks_clean():
    from repro.analysis import kernels
    findings = kernels.run()
    assert findings == [], "\n".join(map(str, findings))


def _toy_spec(block, index_map, shape=(8, 128), grid=(2,)):
    import types

    from repro.analysis.kernels import KernelSpec
    aval = jax.ShapeDtypeStruct(shape, jnp.float32)
    bs = types.SimpleNamespace(block_shape=block, index_map=index_map)
    out_bs = types.SimpleNamespace(block_shape=block, index_map=index_map)
    return KernelSpec(name="toy", grid=grid, in_specs=[bs],
                      out_specs=[out_bs], scratch_shapes=[],
                      num_scalar_prefetch=0, prefetch_args=[],
                      operands=[aval], out_shapes=[aval])


def test_kernel_check_catches_oob_index_map():
    from repro.analysis.kernels import check_spec
    spec = _toy_spec((4, 128), lambda i: (i + 1, 0))   # last block OOB
    assert "KRN004" in [f.code for f in check_spec(spec)]


def test_kernel_check_catches_non_dividing_block():
    from repro.analysis.kernels import check_spec
    spec = _toy_spec((3, 128), lambda i: (i, 0))       # 3 does not divide 8
    assert "KRN002" in [f.code for f in check_spec(spec)]


def test_kernel_check_catches_untiled_last_two_dims():
    """A (1, block_k) mask block over a (B, W) mask: tiles exactly, but
    the TPU lowering refuses it (1 is neither a multiple of 8 nor B)."""
    from repro.analysis.kernels import check_spec
    spec = _toy_spec((1, 256), lambda i: (i, 0), shape=(2, 512), grid=(2,))
    assert [f.code for f in check_spec(spec)] == ["KRN011", "KRN011"]


def test_kernel_check_passes_valid_spec():
    from repro.analysis.kernels import check_spec
    spec = _toy_spec((8, 128), lambda i: (i, 0), shape=(16, 128))
    assert check_spec(spec) == []


# ---------------------------------------------------------------------------
# concurrency lint
# ---------------------------------------------------------------------------
def test_concurrency_clean():
    from repro.analysis import concurrency
    findings = concurrency.run()
    assert findings == [], "\n".join(map(str, findings))


def test_concurrency_catches_cross_thread_write(tmp_path):
    from repro.analysis import concurrency
    bad = textwrap.dedent("""
        import threading
        class Pool:
            def start(self):
                threading.Thread(target=self._pump, daemon=True).start()
            def _pump(self):
                self.alive = True
            def stop(self):
                self.alive = False
            def fan(self):
                for i in range(3):
                    t = threading.Thread(target=self._work)
                    t.start()
                    t.join()
            def _work(self):
                self.count += 1
    """)
    p = tmp_path / "bad.py"
    p.write_text(bad)
    codes = sorted({f.code for f in concurrency.run((p,))})
    assert codes == ["CON001", "CON002"]


def test_concurrency_respects_lock_and_suppression(tmp_path):
    from repro.analysis import concurrency
    good = textwrap.dedent("""
        import threading
        class Pool:
            def start(self):
                threading.Thread(target=self._pump, daemon=True).start()
            def _pump(self):
                with self._lock:
                    self.alive = True
            def stop(self):
                with self._lock:
                    self.alive = False
            def mark(self):
                self.seen = True  # analysis: allow(concurrency)
            def bg(self):
                threading.Thread(target=self._set).start()
            def _set(self):
                self.seen = False  # analysis: allow(concurrency)
    """)
    p = tmp_path / "good.py"
    p.write_text(good)
    assert concurrency.run((p,)) == []


# ---------------------------------------------------------------------------
# wire: pre-affinity imports + pipe picklability
# ---------------------------------------------------------------------------
def test_wire_clean():
    from repro.analysis import wire
    findings = wire.run()
    assert findings == [], "\n".join(map(str, findings))


def test_wire_catches_module_scope_jax(tmp_path, monkeypatch):
    import repro.analysis.wire as wire
    pkg = tmp_path / "repro" / "fake"
    pkg.mkdir(parents=True)
    (tmp_path / "repro" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "leaf.py").write_text("import jax\n")
    (pkg / "root.py").write_text("from repro.fake import leaf\n")
    monkeypatch.setattr(wire, "_SRC", tmp_path)
    findings = wire._closure_findings("repro.fake.root")
    assert [f.code for f in findings] == ["WIR001"]
    assert "leaf.py" in findings[0].location


def test_wire_function_local_import_is_fine(tmp_path, monkeypatch):
    import repro.analysis.wire as wire
    pkg = tmp_path / "repro" / "fake"
    pkg.mkdir(parents=True)
    (tmp_path / "repro" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "root.py").write_text(
        "def body():\n    import jax\n    return jax\n")
    monkeypatch.setattr(wire, "_SRC", tmp_path)
    assert wire._closure_findings("repro.fake.root") == []


def test_wire_catches_unpicklable_dataclass():
    import dataclasses

    import repro.analysis.wire as wire

    @dataclasses.dataclass
    class Bad:
        fn: object = lambda: None      # local lambda: not picklable

    inst = wire._dummy_instance(Bad)
    import pickle
    with pytest.raises(Exception):
        pickle.dumps(inst)


def test_child_module_is_import_light():
    """The spawn payload's import closure must load with jax blocked —
    this is the property that keeps XLA's threadpool sized from the
    child's cpuset (regression: _serving_child used to live in
    backend.py, whose module scope imports the engine and hence jax)."""
    script = textwrap.dedent("""
        import importlib.abc, sys
        class Blk(importlib.abc.MetaPathFinder):
            def find_spec(self, name, path, target=None):
                if name.split(".")[0] in ("jax", "jaxlib"):
                    raise ImportError("jax imported pre-affinity")
        sys.meta_path.insert(0, Blk())
        import pickle
        import repro.serving.child as child
        import repro.core.testbed as testbed
        assert pickle.dumps(child._serving_child)
        assert pickle.dumps(testbed._pinned_main)
        print("import-light ok")
    """)
    env_src = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    assert "import-light ok" in out.stdout


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_report_and_exit_codes(tmp_path, capsys):
    from repro.analysis.cli import main
    report = tmp_path / "report.json"
    rc = main(["--only", "compile-keys", "--only", "concurrency",
               "--report", str(report)])
    assert rc == 0
    data = json.loads(report.read_text())
    assert data["analyzers_run"] == ["compile-keys", "concurrency"]
    assert data["counts"]["errors"] == 0
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in ANALYZERS:
        assert name in out


def test_cli_rejects_unknown_analyzer():
    from repro.analysis.cli import main
    assert main(["--only", "nope"]) == 2

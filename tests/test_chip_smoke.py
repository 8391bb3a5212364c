"""chip_smoke.py's CPU-side contract and the two helpers it leans on.

The smoke itself only means something on a TPU; what can be held here is
that it refuses to run anywhere else, that the compile cache is placed
from outside, that the native core is rebuilt from what git commits, and
that the serve leg's reading of a compiled program finds a KV-pool slab
move where there is one.
"""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from paddle_tpu.device import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "'cpu'" in proc.stderr            # names the platform it found
    assert proc.stdout == ""                 # no header, no phase, no result
    assert "paddle_tpu" not in proc.stderr   # stopped before the package


_POOL = (1025, 24, 16, 16, 128)
_HLO_CASES = {
    # a relapse: a layer's slab sliced out, copied, put back; the pool re-laid out
    "slab_slice": ("%constant_dynamic-slice_fusion.4 = bf16[1025,16,16,128]"
                   "{3,2,1,0:T(8,128)(2,1)} fusion(%p, %i), kind=kLoop", 1),
    "slab_copy": ("%copy.63 = bf16[1025,16,16,128]{3,1,2,0} copy(%f)", 1),
    "slab_put_back": ("ROOT %copy_dynamic-update-slice_fusion.5 = "
                      "bf16[1025,24,16,16,128]{4,3,2,1,0} fusion(%a, %b)", 1),
    "pool_relayout": ("%copy.9 = bf16[1025,24,16,16,128]{4,2,3,1,0} "
                      "copy(%pool_0_.1)", 1),
    # in place: a row written into the carried pool, blocks gathered
    "row_write": ("%dynamic_update_slice.608 = bf16[1025,24,16,16,128]"
                  "{4,3,2,1,0} dynamic-update-slice(%g, %u, %a, %b, %c)", 0),
    "block_gather": ("%fusion.12 = bf16[64,16,16,128]{3,2,1,0} "
                     "fusion(%param_0.5, %tables)", 0),
    "slab_parameter": ("%param_0.1 = bf16[1025,16,16,128]{3,2,1,0} "
                       "parameter(0)", 0),
    # the decode tick's row writer: one custom call a layer, K and V
    # aliased to its outputs, and what reads them
    "writer_call": ("%pool_write_rows.1 = (bf16[1025,24,16,16,128]"
                    "{4,3,2,1,0:T(8,128)(2,1)}, bf16[1025,24,16,16,128]"
                    "{4,3,2,1,0:T(8,128)(2,1)}) custom-call(%lane, %blk, "
                    "%off, %li, %k, %v, %kb, %vb), "
                    "custom_call_target=\"tpu_custom_call\"", 0),
    "writer_result": ("%get-tuple-element.7 = bf16[1025,24,16,16,128]"
                      "{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element("
                      "%pool_write_rows.1), index=0", 0),
    # a relapse: the pool copied because the call could not take it in place
    "writer_unaliased": ("%copy.12 = bf16[1025,24,16,16,128]{4,3,2,1,0:"
                         "T(8,128)(2,1)} copy(%get-tuple-element.3)", 1),
}


@pytest.mark.parametrize("case", sorted(_HLO_CASES))
def test_pool_slab_moves_reads_a_compiled_program(case):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line, want = _HLO_CASES[case]
    hlo = "HloModule m\n\nENTRY %main {\n  " + line + "\n}\n"
    assert len(chip_smoke.pool_slab_moves(hlo, _POOL)) == want


class TestCompileCachePlacement:
    def _record_updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        return calls

    def test_env_var_set_leaves_jax_config_alone(self, monkeypatch):
        calls = self._record_updates(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert enable_compile_cache() == "/some/dir"
        assert calls == []

    def test_default_is_jax_cache_in_the_checkout(self, monkeypatch):
        calls = self._record_updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]


def test_native_loader_rebuilds_a_stale_so(tmp_path):
    """The .so is gitignored, so a copied tree can carry one older than
    csrc/: importing must rebuild it. Runs on a private copy of core/ so
    the library this process has mapped is never rewritten."""
    core = os.path.join(REPO, "paddle_tpu", "core")
    work = tmp_path / "core"
    work.mkdir()
    shutil.copy(os.path.join(core, "native.py"), work)
    shutil.copy(os.path.join(core, "Makefile"), work)
    shutil.copytree(os.path.join(core, "csrc"), work / "csrc")
    lib = work / "lib" / "libptpu_core.so"
    src = work / "csrc" / "ptpu_core.cc"

    # unoptimized: the test is about when the build runs, not its output
    env = dict(os.environ, CXXFLAGS="-O0 -std=c++17 -fPIC -pthread")

    def load():
        proc = subprocess.run(
            [sys.executable, "-W", "error", "-c",
             "import native; assert native.NATIVE_AVAILABLE"],
            cwd=work, env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]

    load()                                   # missing -> built
    assert lib.exists()
    # a stale artifact: not a library at all, and older than its source
    lib.write_bytes(b"stale")
    old = src.stat().st_mtime - 100
    os.utime(lib, (old, old))
    load()                                   # stale -> rebuilt and loadable
    assert lib.stat().st_mtime >= src.stat().st_mtime
    assert lib.stat().st_size > 1000

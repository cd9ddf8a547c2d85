"""chip_smoke.py, small, on the CPU backend.

The chip run is the proof; this keeps the program that produces it from
rotting between chip runs: every phase function at a tiny size with the
Pallas kernels in interpret mode, ``main()`` refusing to pass without a
TPU, and the compile-cache helper's placement rule.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FLAGSHIP = dict(rows=700, batch=256, lr=0.5)
ALS = dict(num_users=256, num_items=200, factors=8, max_nnz=8, batch=32,
           steps=2)


@pytest.fixture
def interpret_kernels(monkeypatch):
    """Open the auto-routes' hardware gates and run both kernels in the
    Pallas interpreter — the only mode the CPU backend has."""
    import dmlc_tpu.ops.device_decode as dd
    import dmlc_tpu.ops.pallas_sparse as ps

    real_ell, real_widen = ps.ell_matvec_pallas, dd.widen_span_pallas
    calls = {"ell": 0, "widen": 0}

    def ell(w, i, v, **kw):
        calls["ell"] += 1
        return real_ell(w, i, v, **dict(kw, interpret=True))

    def widen(seg, rows, cols, dtype_str, **kw):
        calls["widen"] += 1
        return real_widen(seg, rows, cols, dtype_str,
                          **dict(kw, interpret=True))

    monkeypatch.setattr(ps, "_on_tpu_backend", lambda: True)
    monkeypatch.setattr(dd, "_on_tpu_backend", lambda: True)
    monkeypatch.setattr(ps, "ell_matvec_pallas", ell)
    monkeypatch.setattr(dd, "widen_span_pallas", widen)
    return calls


def test_phase1_flagship_three_tiers(tmp_path):
    losses = chip_smoke.phase1_flagship(str(tmp_path), **FLAGSHIP)
    assert len(losses) == 9  # 3 epochs x 3 batches (the last one padded)
    assert losses[-1] < losses[0]


def test_phase2_ell_kernel_in_train_step(tmp_path, interpret_kernels):
    chip_smoke.phase2_ell_kernel(str(tmp_path), weight_dim=512, max_nnz=8,
                                 batch=256, steps=2, also=((640, 8),))
    assert interpret_kernels["ell"] > 0  # the kernel route was traced


def test_phase3_device_decode_routes(tmp_path, interpret_kernels):
    chip_smoke.phase3_device_decode(str(tmp_path), narrow_batch=32,
                                    wide_batch=32, wide_cols=256,
                                    higgs_rows=100, higgs_batch=64)
    assert interpret_kernels["widen"] > 0


def test_phase4_als(tmp_path):
    losses = chip_smoke.phase4_als(str(tmp_path), **ALS)
    assert len(losses) == 2 * ALS["steps"]


def test_phase5_mesh_matches_one_device(tmp_path):
    """Eight virtual CPU devices stand in for the four chips."""
    work = str(tmp_path)
    linear = chip_smoke.phase1_flagship(work, **FLAGSHIP)
    als = chip_smoke.phase4_als(work, **ALS)
    chip_smoke.phase5_mesh(work, linear, als, flagship_kw=FLAGSHIP,
                           als_kw=ALS)


def test_a_wrong_checksum_fails_the_phase(tmp_path, monkeypatch):
    """The smoke must be able to fail: a batch delivered twice (what a
    staging buffer recycled too early looks like) trips the checksum."""
    real_next = chip_smoke._Audited.__next__
    state = {}

    def twice(self):
        batch = real_next(self)
        if "first" not in state:
            state["first"] = batch
            return batch
        return state["first"]

    monkeypatch.setattr(chip_smoke._Audited, "__next__", twice)
    with pytest.raises(AssertionError, match="loss deviates|checksums"):
        chip_smoke.phase1_flagship(str(tmp_path), **FLAGSHIP)


def _run_main(**env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=full, capture_output=True, text=True,
                          timeout=300)


def test_main_fails_without_a_tpu():
    proc = _run_main()
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line
    assert "platform=cpu" in proc.stdout  # but it said what it found


def test_main_fails_without_the_native_engine():
    proc = _run_main(DMLC_TPU_NO_NATIVE="1")
    assert proc.returncode != 0
    assert "native parse engine unavailable" in proc.stderr
    assert "engine=numpy" in proc.stdout
    assert '"ok"' not in proc.stdout


# ---------------- the compile-cache helper ----------------

def test_compile_cache_leaves_an_env_directory_alone(monkeypatch, tmp_path):
    import jax

    from dmlc_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "x")
    # no code sets the directory; the small-program thresholds still drop
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_compile_cache_path_is_fixed_inside_the_checkout():
    code = ("import sys; sys.path.insert(0, %r); "
            "from dmlc_tpu.utils.compile_cache import enable_compile_cache; "
            "import json, jax; d = enable_compile_cache(); "
            "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))"
            % REPO)
    # the helper touches configuration only, never a backend, so the
    # platform can be left unpinned here even without an accelerator
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS")}
    seen = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True).stdout
        seen.append(json.loads(out.strip().splitlines()[-1]))
    assert seen[0] == seen[1] == [os.path.join(REPO, ".jax_cache")] * 2


def test_compile_cache_is_off_for_a_cpu_pinned_run(monkeypatch):
    import jax

    from dmlc_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compile_cache.enable_compile_cache() is None
    assert updates == {}

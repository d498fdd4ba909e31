"""`chip_smoke.py` off the chip: its phases at a tiny size on the CPU with
the Pallas kernels in interpret mode, its refusal to run without a TPU,
and the compile-cache placement it relies on.

The tests steer the platform themselves: the `auto` backend is pointed
at the (interpreted) Pallas kernels, and the phase functions are called
directly with small sizes — the script has no CPU option.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def pallas_auto(monkeypatch):
    """Resolve backend="auto" to the Pallas kernels, which run in
    interpret mode off the TPU (`default_interpret`)."""
    from repro.core.engine import clear_program_cache
    from repro.kernels import ops
    orig = ops._resolve
    monkeypatch.setattr(ops, "_resolve",
                        lambda b: "pallas" if b == "auto" else orig(b))
    yield
    clear_program_cache()


def _cpu_env(**extra) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"),
               **extra)
    return env


@pytest.mark.slow
def test_one_chip_phases_tiny_interpret(pallas_auto):
    """Phases 2-4 at a tiny size: every count agrees with the oracle, the
    stream equals run, the LSH route probes on device, and the gateway
    tickets equal their plans' runs (the phases raise otherwise)."""
    smoke = _load_smoke()
    xjoin, resident, gateway = smoke.run_one_chip(
        0, smoke.CompileClock(), paper_n=1500, resident_n=5000,
        resident_q=300, resident_check=100, gateway_batches=1)
    assert xjoin["backend"] == "pallas" and xjoin["n_r"] == 1200
    assert xjoin["exact_mismatches"] == 0 and xjoin["stream_equals_run"]
    assert xjoin["lsh_probe"] == "device" and 0.0 < xjoin["skip_frac"] < 1.0
    assert xjoin["lsh_host_probe"] == "host"
    assert xjoin["lsh_device_vs_host_mismatches"] == 0
    assert resident["backend"] == "pallas" and resident["mismatches"] == 0
    # interpret mode holds no Mosaic kernel: only the chip run can say yes
    assert not resident["sweep_kernel_compiled"]
    assert gateway["tenants"] == {"default": "exact", "lsh": "lsh"}
    assert gateway["mismatches"] == 0 and gateway["requests"] == 2


@pytest.mark.slow
def test_sharded_phase_tiny_4dev():
    """The four-chip phase on four forced host devices: ring and
    replicated counts bit-identical and equal to the oracle, R split
    over the four devices in the partitioned program."""
    code = (
        "import os, sys\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "import chip_smoke as cs\n"
        "from repro.kernels import ops\n"
        "orig = ops._resolve\n"
        "ops._resolve = lambda b: 'pallas' if b == 'auto' else orig(b)\n"
        "cs.phase_sharded(0, cs.CompileClock(), chips=4, n_r=3000, "
        "n_q=256, n_check=64)\n")
    out = subprocess.run([sys.executable, "-c", code], env=_cpu_env(),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["phase"] == "sharded"
    assert all(res["ring_equals_replicated"].values())
    ring = res["placement"]["ring"]
    assert ring["devices"] == 4 and ring["r_shards"] == 4
    assert ring["hlo_r_operand_local"]
    assert res["sweep_mismatches"] == 0 and res["exact_mismatches"] == 0
    assert all(p["lsh_device_vs_host_mismatches"] == 0
               for p in res["placement"].values())


def test_refuses_to_run_without_tpu():
    """On the CPU the script names the platform, runs no phase, prints no
    result line and exits non-zero."""
    out = subprocess.run([sys.executable, SCRIPT], env=_cpu_env(),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    lines = [json.loads(x) for x in out.stdout.strip().splitlines()]
    assert [x.get("phase") for x in lines] == ["platform"]
    assert not any("ok" in x for x in lines)


def test_fails_without_the_repository(tmp_path):
    """Alone in a directory the script cannot import the system and
    exits non-zero without a result line."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = _cpu_env()
    env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_peak_log_marks_the_step_that_raised_the_peak(monkeypatch):
    """`PeakLog` records a step only when the peak rose, also after each
    call of a function it wraps, and unwraps it on exit."""
    import types
    smoke = _load_smoke()
    readings = iter([5, 5, 9, 7])
    monkeypatch.setattr(smoke, "peak_device_bytes", lambda: next(readings))
    ns = types.SimpleNamespace(f=lambda x: x + 1)
    plain = ns.f
    peaks = smoke.PeakLog()
    with peaks.inside({"f_step": (ns, "f")}):
        assert ns.f(1) == 2 and ns.f(2) == 3
    peaks.mark("a")
    peaks.mark("b")
    assert peaks.grew == [("f_step", 5), ("a", 9)]
    assert ns.f is plain


def test_oracle_chunks_agree_with_unchunked():
    """The chunked oracle sums to the one-shot `ref` histogram."""
    from repro.kernels import ref
    smoke = _load_smoke()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(700, 16)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    eps = [0.5, 0.8, 1.0]
    got = smoke.oracle_counts(x[:90], x[90:], eps, q_chunk=32, r_chunk=100)
    want = np.asarray(ref.range_count_hist(x[:90], x[90:],
                                           np.asarray(eps, np.float32)))
    np.testing.assert_array_equal(got, want)


def test_compile_cache_dir_follows_env(monkeypatch):
    """`init_compile_cache`: JAX_COMPILATION_CACHE_DIR wins when set;
    otherwise one fixed directory inside the checkout that git ignores."""
    import jax
    from repro import utils
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/srv/xla-cache")
        assert utils.init_compile_cache() == "/srv/xla-cache"
        assert jax.config.jax_compilation_cache_dir == "/srv/xla-cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = utils.init_compile_cache()
        assert path == os.path.join(REPO, ".cache", "jax_compile")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

"""Device selection, compile-cache placement, party-process environments and
the benchmark runner's exit code — the pieces that decide where and how the
engine runs. CPU only: platforms other than the CPU are simulated by
monkeypatching ``jax.default_backend``."""
import os
import subprocess
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.kernels import interpret_mode

REPO = Path(__file__).resolve().parents[1]


# -----------------------------------------------------------------------------
# interpret_mode: compiled on tpu, interpreted on cpu, nothing else
# -----------------------------------------------------------------------------

def test_interpret_mode_on_cpu():
    assert jax.default_backend() == "cpu"
    assert interpret_mode(np.uint32) is True


def test_interpret_mode_on_tpu_compiles(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode(np.uint32) is False


def test_interpret_mode_refuses_ring64_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(NotImplementedError, match="32-bit ring"):
        interpret_mode(np.uint64)


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "metal"])
def test_interpret_mode_refuses_other_platforms(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    with pytest.raises(RuntimeError, match=platform):
        interpret_mode(np.uint32)


def test_kernel_wrapper_raises_on_unsupported_platform(monkeypatch):
    """The wrappers ask interpret_mode before launching: no silent
    interpreter run off the CPU."""
    from repro.kernels.rss_gate.ops import gate

    x = np.ones((3, 8), np.uint32)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        gate(x, x, x)


# -----------------------------------------------------------------------------
# compile cache: env var honoured, else one fixed in-checkout directory
# -----------------------------------------------------------------------------

def _cache_dir_in_child(env_value):
    """enable_compile_cache()'s directory, and JAX's setting after it, in a
    fresh process started with the given JAX_COMPILATION_CACHE_DIR (None:
    unset). Nothing is compiled there, so nothing is cached."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    env["PYTHONPATH"] = str(REPO / "src")
    code = (
        "import jax; from repro.compile_cache import enable_compile_cache; "
        "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        check=True, timeout=120,
    )
    return out.stdout.split()


def test_compile_cache_dir_honours_env():
    assert _cache_dir_in_child("/cache/x") == ["/cache/x", "/cache/x"]


def test_compile_cache_default_is_fixed_in_checkout():
    from repro.compile_cache import DEFAULT_CACHE_DIR

    assert Path(DEFAULT_CACHE_DIR) == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
    for unset in (None, ""):
        assert _cache_dir_in_child(unset) == [DEFAULT_CACHE_DIR] * 2


class _FakeConfig:
    """Stands in for ``jax.config``: the cache directory JAX read from its
    environment, and every update made."""

    def __init__(self, cache_dir):
        self.jax_compilation_cache_dir = cache_dir
        self.calls = []

    def update(self, name, value):
        self.calls.append((name, value))
        setattr(self, name, value)


@pytest.mark.parametrize("env_dir", [None, "/cache/from-env"])
def test_enable_compile_cache_sets_one_directory(monkeypatch, env_dir):
    from repro import compile_cache

    config = _FakeConfig(env_dir)
    monkeypatch.setattr(compile_cache, "jax", types.SimpleNamespace(config=config))
    path = compile_cache.enable_compile_cache()
    assert path == (env_dir or compile_cache.DEFAULT_CACHE_DIR)
    dirs = [(k, v) for k, v in config.calls if k.endswith("_dir")]
    # a directory JAX already took from the environment is left alone
    assert dirs == ([] if env_dir else [("jax_compilation_cache_dir", path)])


# -----------------------------------------------------------------------------
# party processes: each one's platform is explicit
# -----------------------------------------------------------------------------

def test_party_env_cpu_pins_every_party():
    from repro.runtime import party_env

    for p in range(3):
        env = party_env(p, "cpu", base={"PATH": "/bin", "TPU_VISIBLE_CHIPS": "9"})
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["PATH"] == "/bin"


def test_party_env_tpu_gives_each_party_its_own_chip():
    from repro.runtime import party_env

    envs = [party_env(p, "tpu", base={}) for p in range(3)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 3
    for e in envs:
        assert e["JAX_PLATFORMS"] == "tpu"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"


def test_party_env_rejects_unknown_platform():
    from repro.runtime import party_env

    with pytest.raises(ValueError, match="gpu"):
        party_env(0, "gpu")


def test_hello_reports_party_devices():
    from repro.runtime import launch_loopback_mesh

    coord, _servers, _threads = launch_loopback_mesh()
    try:
        replies = coord.hello()
    finally:
        coord.shutdown()
        coord.close()
    assert sorted(r["party"] for r in replies) == [0, 1, 2]
    for r in replies:
        assert r["device"] == {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }


# -----------------------------------------------------------------------------
# benchmarks/run.py: a failed module fails the run
# -----------------------------------------------------------------------------

@pytest.fixture()
def bench_runner(monkeypatch):
    monkeypatch.syspath_prepend(str(REPO))
    from benchmarks import run

    def ok_run():
        return [("fake_ok_row", 1.0, "x=1")]

    def bad_run():
        raise RuntimeError("boom")

    for name, fn in (("bench_fake_ok", ok_run), ("bench_fake_bad", bad_run)):
        mod = types.ModuleType(f"benchmarks.{name}")
        mod.run = fn
        monkeypatch.setitem(sys.modules, f"benchmarks.{name}", mod)
    return run


def test_bench_runner_exits_zero_when_all_pass(bench_runner, monkeypatch, capsys):
    monkeypatch.setattr(bench_runner, "MODULES", ["bench_fake_ok"])
    assert bench_runner.main([]) == 0
    assert "fake_ok_row,1.0,x=1" in capsys.readouterr().out


@pytest.mark.parametrize("broken", ["bench_fake_bad", "bench_fake_missing"])
def test_bench_runner_exits_nonzero_on_failed_module(
    bench_runner, monkeypatch, capsys, broken
):
    monkeypatch.setattr(bench_runner, "MODULES", ["bench_fake_ok", broken])
    assert bench_runner.main([]) == 1
    out = capsys.readouterr().out
    assert f"{broken}_FAILED" in out
    assert "fake_ok_row" in out  # the other modules still ran

"""Entry-point plumbing: the persistent compile cache and the benchmark
driver's exit code.

`enable_compile_cache` changes process-wide JAX config, so it runs in child
processes pinned to the CPU (`JAX_PLATFORMS=cpu`): a child must never reach
for an accelerator the test process may hold.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_child(script: str, **env_overrides) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.abspath(SRC))
    env.update(env_overrides)
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    return res.stdout


def test_compile_cache_uses_env_dir_only(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache and
    the compiled programs land there, however fast they compiled."""
    out = _run_child("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)))
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    returned, configured = out.split()[:2]
    assert returned == configured == str(tmp_path)
    assert any(name.endswith("-cache") for name in os.listdir(tmp_path))


def test_compile_cache_defaults_to_checkout_dir():
    """Without the variable the cache is the fixed `<checkout>/.jax_cache`,
    which git ignores."""
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

    root = os.path.abspath(os.path.join(SRC, ".."))
    assert str(CHECKOUT_CACHE_DIR) == os.path.join(root, ".jax_cache")
    out = _run_child("""
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        print(jax.config.jax_persistent_cache_min_compile_time_secs)
    """)
    returned, configured, min_secs = out.split()[:3]
    assert returned == configured == str(CHECKOUT_CACHE_DIR)
    assert float(min_secs) == 0.0
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


class _Phase:
    def __init__(self, name, rows=(), error=None):
        self.__name__ = name
        self._rows, self._error = list(rows), error

    def run(self):
        if self._error is not None:
            raise self._error
        return self._rows


@pytest.mark.parametrize("fail", [False, True])
def test_benchmark_driver_exit_code(monkeypatch, capsys, fail):
    """A failing phase is reported, the later phases still run, and the
    driver exits 1; with every phase green it exits 0."""
    from benchmarks import run

    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    phases = [_Phase("first", ["first,1.0,ok"]),
              _Phase("broken", error=RuntimeError("boom") if fail else None),
              _Phase("last", ["last,2.0,ok"])]
    monkeypatch.setattr(run, "MODULES", phases)
    assert run.main() == (1 if fail else 0)
    out = capsys.readouterr().out
    assert "first,1.0,ok" in out and "last,2.0,ok" in out
    assert ("broken,0.0,ERROR:RuntimeError:boom" in out) == fail

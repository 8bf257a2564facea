"""`chip_smoke.py` rehearsed on the CPU: its phases at a tiny scale with
the kernels in interpret mode, and its refusal to run without a TPU.

The four-chip phase needs four devices, so it runs in a child process with
four virtual CPU devices (the test process locked JAX to one at import),
pinned to the CPU so it never reaches for an accelerator.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import chip_smoke

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCALE = 1e-5   # kV2a x 1e-5: 550 vertices, six segments per pass


def test_smoke_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "no TPU found" in str(exc.value)
    assert capsys.readouterr().out == ""   # no result line


def test_smoke_train_and_serve_phases(capsys):
    a, budget = chip_smoke.build_graph(SCALE, seed=0)
    chip_smoke.train_phase(a, budget, seed=0, interpret=True)
    chip_smoke.serve_phase(a, budget, seed=0, interpret=True)
    out = capsys.readouterr().out
    assert "forward pass: 6 segments" in out
    assert "transposed pass: 6 segments" in out
    assert "train step 3: loss" in out
    assert "serve epoch 2 vs float32 reference" in out


def test_smoke_four_chip_phase_on_virtual_devices():
    script = textwrap.dedent("""
        import sys
        sys.path[:0] = [%r, %r]
        import chip_smoke
        a, budget = chip_smoke.build_graph(%r, seed=0)
        chip_smoke.four_chip_phase(a, budget, seed=0, interpret=True)
    """) % (ROOT, os.path.join(ROOT, "src"), SCALE)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "4-shard outputs equal the 1-shard control: True" in res.stdout
    assert "ici 0 B" not in res.stdout.split("4-shard mesh epoch 1")[1]

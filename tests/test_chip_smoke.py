"""chip_smoke.py's phases at tiny widths on the CPU backend: the control
flow and checks of the chip smoke, so that the script keeps up with the
code it drives. The slice phase needs four devices, which the CPU backend
gives only to a fresh process, so it runs in a child."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = (("num_layers", 2), ("d_model", 32), ("num_heads", 4),
        ("num_kv_heads", 2), ("head_dim", 8), ("d_ff", 64),
        ("vocab_size", 64), ("tie_embeddings", True))


def test_chip_smoke_refuses_a_host_without_tpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.tpu_devices()


def test_served_phase_at_tiny_widths(capsys):
    chip_smoke.served_phase(TINY)
    out = capsys.readouterr().out
    assert '"job": "jobA"' in out and '"job": "jobB"' in out
    assert '"switches"' in out


def test_slice_phase_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke; "
            f"chip_smoke.slice_phase({TINY!r}); print('SLICES OK')")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SLICES OK" in proc.stdout

"""``chip_smoke.py`` guarded on the CPU: its phases at tiny sizes (Pallas
kernels in interpret mode), its refusal to run without a TPU, and the
``--chips 4`` phase on four virtual CPU devices in a subprocess."""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_phase_kernels_tiny(smoke):
    errs = smoke.phase_kernels(n_clients=3, rows=20, seg=256,
                               transformer=(2, 8, 2, 16),
                               mixer=(2, 4, 256, 16), on_tpu=False)
    assert set(errs) == {"layer_agg", "rmsnorm",
                         "flash_attention/transformer",
                         "flash_attention/set_mixer"}


def test_phase_cnn_tiny(smoke):
    hist = smoke.phase_cnn(n_devices=64, width_mult=0.125, hw=8,
                           n_train=1280, local_epochs=1, on_tpu=False)
    assert len(hist["acc_mean"]) == 2


def test_phase_transformer_tiny(smoke):
    from repro.models.transformer_family import kernel_mode
    with kernel_mode("pallas"):
        hist = smoke.phase_transformer(n_devices=64, width_mult=0.25, hw=8,
                                       n_train=1280, local_epochs=1,
                                       on_tpu=False)
    assert np.isfinite(hist["acc_mean"]).all()


def test_phase_fleet_sharded_on_four_virtual_devices():
    code = textwrap.dedent("""
        import importlib.util, sys
        import jax
        assert len(jax.devices()) == 4, jax.devices()
        spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.phase_fleet_sharded(n=4096, n_chips=4)
        print("FLEET-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-c", code, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FLEET-OK" in out.stdout
    assert "sharded == single" in out.stdout

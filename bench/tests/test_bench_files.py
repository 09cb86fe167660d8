"""The harness finds a cell's configuration, traffic, limits, metric
readers and kernel counts by name, so new ones are files added beside the
old, with no other file edited; and the command refuses to run without a
TPU or without the system under test."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from harness import files  # noqa: E402


def _copy_bench(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__",
                                                  "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    return tmp_path / "bench"


def test_new_cell_config_metric_and_kernel_are_found_by_name(tmp_path,
                                                             monkeypatch):
    bench = _copy_bench(tmp_path)
    conf = json.loads((bench / "configs" / "resnet18-cifar10.json").read_text())
    conf["name"] = "tiny-cnn"
    conf["fl"]["width_mult"] = 0.25
    (bench / "configs" / "tiny-cnn.json").write_text(json.dumps(conf))
    shutil.copy(bench / "configs" / "resnet18-cifar10.py",
                bench / "configs" / "tiny-cnn.py")
    (bench / "traffic" / "sync-n8.json").write_text(json.dumps(
        {"fl": {"n_devices": 8}, "deployment_seed": 1}))
    (bench / "limits" / "tiny-sync-n8.json").write_text(json.dumps(
        {"loss_gap": 1.0}))
    (bench / "metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx['record']))\n")
    (bench / "kernels" / "twice.py").write_text(
        "def cost(n):\n    return 2.0 * n, 4.0 * n\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-cnn"})
    spec["workloads"].append({"name": "tiny-sync-n8", "config": "tiny-cnn",
                              "traffic": "sync-n8", "chips": 1})
    spec["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                              "workloads": ["tiny-sync-n8"]})
    monkeypatch.setattr(files, "BENCH_DIR", str(bench))
    loaded = bench_run.load_cell("tiny-sync-n8", spec)
    assert loaded["config"]["fl"]["width_mult"] == 0.25
    assert loaded["reference"].num_submodels() == 4
    assert bench_run.fl_settings(loaded, 5)["n_devices"] == 8
    assert loaded["limits"] == {"loss_gap": 1.0}
    assert [m["name"] for m in loaded["per_layer"]] == ["rounds_seen"]
    assert files.metric_reader("rounds_seen").read({"record": [1, 2]}) == 2.0
    assert files.kernel_cost("twice").cost(n=3) == (6.0, 12.0)
    # the cells already there are found as before
    assert bench_run.load_cell("resnet18-sync-n100", spec)["traffic"]


def test_unknown_device_kind_raises():
    import pytest
    assert files.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        files.peaks("cpu")


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet18-sync-n100",
         "--seed", "2147483711", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_fails_and_prints_no_result():
    out = _run(REPO, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in out.stdout.splitlines())


def test_without_the_system_under_test_it_fails(tmp_path):
    _copy_bench(tmp_path)
    out = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""

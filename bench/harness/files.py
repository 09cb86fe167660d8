"""Finds what belongs to a cell by name: ``BENCHMARK.json`` names the
workload's configuration and traffic, and each of those, each per-layer
metric's reader and each kernel's operation count is a file of its own
under ``bench/``.  Nothing here lists them, so a later change adds a
configuration, a traffic mix, a metric or a kernel count by adding files.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def _path(*parts):
    return os.path.join(BENCH_DIR, *parts)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(repo_dir=REPO_DIR):
    return load_json(os.path.join(repo_dir, "BENCHMARK.json"))


def workload(spec, name):
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in spec["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def config(name):
    """The configuration's sizes (``configs/<name>.json``) and its plain
    reference module (``configs/<name>.py``)."""
    return (load_json(_path("configs", name + ".json")),
            load_module(_path("configs", name + ".py"), "config_" +
                        name.replace("-", "_").replace(".", "_")))


def traffic(name):
    return load_json(_path("traffic", name + ".json"))


def limits(workload_name):
    return load_json(_path("limits", workload_name + ".json"))


def metric_reader(name):
    """``metrics/<name>.py``: ``read(ctx)`` gives the metric's value, or
    None where the run holds nothing for it to read."""
    return load_module(_path("metrics", name + ".py"),
                       "metric_" + name.replace(".", "_").replace("-", "_"))


def kernel_cost(name):
    """``kernels/<name>.py``: ``cost(**shape)`` gives the (FLOPs, bytes)
    the algorithm needs for one call."""
    return load_module(_path("kernels", name + ".py"),
                       "kernel_" + name.replace("-", "_"))


def peaks(device_kind):
    table = load_json(_path("peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json ({', '.join(table['devices'])})")
    return table["devices"][device_kind]

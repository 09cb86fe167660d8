"""Cells small enough for the CPU test run: a cell's own files, with the
widths, input size, data and fleet shrunk."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run as bench_run  # noqa: E402

SHRINK_CONFIG = {
    "resnet18-cifar10": {"width_mult": 0.125, "hw": 16, "n_train": 1200,
                         "local_epochs": 2},
}
SHRINK_TRAFFIC = {"n_devices": 16, "participation": 0.25, "n_rounds": 10,
                  "client_executor": "batched"}


def loaded(workload="resnet18-sync-n100"):
    cell = bench_run.load_cell(workload)
    cell["config"]["fl"].update(SHRINK_CONFIG[cell["cell"]["config"]])
    cell["traffic"]["fl"].update(SHRINK_TRAFFIC)
    return cell

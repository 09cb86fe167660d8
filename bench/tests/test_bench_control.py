"""The controls at a size a CPU test can hold: the plain reference put in
the program's place in bfloat16 (parameters too, or float32 parameters
with every pass in bfloat16) or with each step on half its batch comes out
not correct against the float32 reference under the cell's limits, while
the program itself comes out correct."""
import functools

import pytest

import tiny_cell
from harness import check as chk
from harness.drive import Runner
from readings import SIDES
from reference import fl as ref_fl
from repro.fl import FLConfig

WORKLOAD = "resnet18-sync-n100"
SEEDS = [3, 2 ** 31 + 11]


@functools.lru_cache(maxsize=None)
def _program(seed):
    """(cell, fl settings, program record, decisions, float32 reference)."""
    cell = tiny_cell.loaded(WORKLOAD)
    fl = tiny_cell.bench_run.fl_settings(cell, seed)
    drv = Runner(FLConfig(**fl), seconds=0.0,
                 deployment_seed=cell["traffic"]["deployment_seed"],
                 warmup=False)
    rec = drv.run()
    decisions = [(c.participants, c.model_choice, c.k) for c in rec.check]
    want = ref_fl.Reference(cell["reference"], fl, seed,
                            cell["traffic"]["deployment_seed"]).run(decisions)
    return cell, fl, tiny_cell.bench_run._program_record(rec), decisions, want


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes(seed):
    cell, _, program, _, want = _program(seed)
    ok, rows = chk.verdict(chk.compare(program, want, ref_fl.BATTERY_J),
                           cell["limits"])
    assert ok, rows


@pytest.mark.parametrize("side", sorted(SIDES))
@pytest.mark.parametrize("seed", SEEDS)
def test_control_and_fault_fail(seed, side):
    cell, fl, _, decisions, want = _program(seed)
    got = ref_fl.Reference(cell["reference"], fl, seed,
                           cell["traffic"]["deployment_seed"],
                           **SIDES[side]).run(decisions)
    ok, rows = chk.verdict(chk.compare(got, want, ref_fl.BATTERY_J),
                           cell["limits"])
    assert not ok, rows

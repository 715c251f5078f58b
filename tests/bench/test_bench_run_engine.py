"""The engine cells run end to end on the CPU at tiny sizes: correct as
they stand, not correct with the timed path broken underneath, and the
entry point refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest
from _tiny import control_tool, patch, run, tiny
from harness import faults, spec

ENGINE_CELLS = [w["name"] for w in spec.benchmark()["workloads"]
                if spec.traffic(w["traffic"])["kind"] == "engine_passes"]


@pytest.mark.parametrize("cell", ENGINE_CELLS)
def test_tiny_engine_cell_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"sim_instr_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", ENGINE_CELLS)
def test_tiny_engine_cell_catches_fault(monkeypatch, cell, fault):
    patch(monkeypatch, fault)
    res = run(cell)
    assert not res["correct"], res["checks"]


def test_engine_int4_control_fails_the_limit():
    """The control: the engine's int8 tier runs on the weights put on the
    int4 grid, and the cell's own check compares its answers with the
    reference of the stated int8 weights."""
    bench, conf, cfg = tiny(ENGINE_CELLS[0])
    tool = control_tool()
    program = tool.engine_reading(ENGINE_CELLS[0], conf, 5, bench,
                                  "program", 0.0, cfg=cfg)[0]
    control = tool.engine_reading(ENGINE_CELLS[0], conf, 5, bench, "int4",
                                  0.0, cfg=cfg)[0]
    assert program.ok and not control.ok, (program, control)


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", ENGINE_CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            pass
    return False


def test_entry_point_without_a_tpu_exits_nonzero_with_no_result():
    p = _run_entry(spec.ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_entry_point_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in spec.benchmark()["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p)
    p = _run_entry(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)

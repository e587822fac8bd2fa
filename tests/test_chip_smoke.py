"""chip_smoke.py's phases at smoke size on the CPU (interpret-mode kernels),
and its refusal to run anywhere but on a TPU."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.launch import steps
from repro.launch.mesh import make_host_mesh

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke_run(chip_smoke):
    """The driver's RunConfig on the smoke model: LRD down to 16-wide
    matrices (without Algorithm 1, whose MXU-tile cost model keeps every
    smoke-sized layer dense), interpret-mode kernels, and blocks that
    split every smoke dim."""
    run = chip_smoke.driver_run()
    return dataclasses.replace(
        run, model=get_smoke_config("smollm-360m"),
        shape=ShapeConfig("t", 16, 4, "train"),
        lrd=dataclasses.replace(run.lrd, min_dim=16, rank_quantize=False,
                                pallas_interpret=True,
                                pallas_block_m=16, pallas_block_k=32,
                                pallas_block_n=32))


def test_train_and_serve_phases_run_the_kernels(chip_smoke, smoke_run):
    params, plan = steps.init_params(smoke_run)
    assert any(lp.use_decomposed for lp in plan.layers.values())
    t = chip_smoke.train_phase(smoke_run, make_host_mesh(1, 1), params,
                               steps_per_phase=2)
    assert [len(t["losses"][p]) for p in (0, 1)] == [2, 2]
    assert t["fallbacks"] == {}  # finite losses are checked inside
    s = chip_smoke.serve_phase(smoke_run, t["params"], slots=2,
                               prompt_len=16, max_new=4, requests=3)
    assert s["compiles"]["decode"] == 1
    assert s["layout"] == "paged" and s["generated"] == 12
    assert s["fallbacks"] == {}


@pytest.mark.parametrize("where", ["checkout", "script-alone"])
def test_entry_refuses_without_tpu(where, tmp_path):
    """On a CPU-only platform, or beside no other file of the repo, the
    script exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if where == "script-alone":
        shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
        env.pop("PYTHONPATH", None)
        cwd = tmp_path
    else:
        cwd = ROOT
    proc = subprocess.run([sys.executable, SCRIPT.name], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout

"""Shared set-up of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest bench/tests

The tiny configuration keeps every structural choice of
``configs/smollm-360m.json`` (LRD, sequential freezing, fused kernels in
Pallas interpret mode, the rank-quantized serving export) at widths an
interpreter can run, in float32, so that a sound run reads near zero
against the float32 reference and what the control or a fault costs
stands out against the cells' own limits.
"""

import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)


def tiny_config() -> dict:
    cfg = json.load(open(BENCH / "configs" / "smollm-360m.json"))
    cfg.update(TINY)
    cfg["lrd"] = dict(cfg["lrd"], min_dim=16, rank_quantize=False)
    return cfg


def smoke_model():
    from repro.configs.archs import get_config
    return dataclasses.replace(
        get_config("smollm-360m"), num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=256, attention_impl="dense",
        param_dtype="float32", compute_dtype="float32")


TRAIN_JOB = {"kind": "train_job", "global_batch": 2, "seq_len": 32,
             "remat": "none"}
CHAT = {"kind": "open_loop", "rate": 20.0,
        "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
        "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
        "slots": 4, "prefill_len": 32, "max_len": 48,
        "check": {"tokens": 40, "max_requests": 6}}


def limits(cell: str) -> dict:
    return json.load(open(BENCH / "limits" / f"{cell}.json"))


def context(traffic: dict, cell_limits: dict, seconds: float = 1.0,
            seed: int = 2 ** 31 + 7):
    from harness import core
    return core.Context(workload="tiny", config=tiny_config(),
                        traffic=traffic, limits=cell_limits, seed=seed,
                        seconds=seconds, trace=False,
                        t_start=time.perf_counter(), interpret=True,
                        smoke_model=smoke_model())


@pytest.fixture
def run_cell(monkeypatch):
    """Drive a whole run of a tiny cell, the chip check skipped and the
    persistent compile cache left off."""
    from harness import core
    monkeypatch.setattr(core, "enable_cache", lambda: "")

    def run(ctx):
        bm = {"end_to_end": [{"name": k, "unit": "x"} for k in
                             ("setup_s",)], "per_layer": []}
        return core.execute(ctx, bm, check_chips=None)

    return run

"""Dry-run machinery at reduced scale (subprocess: needs its own device
count flag before jax init). Covers lower+compile with shardings, the
costing extrapolation, and the roofline artifact schema for one arch of
each family kind."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax
from repro.config import get_arch, SHAPES, TrainConfig
from repro.launch.costing import extrapolated_costs
from repro.launch.roofline import roofline_report, model_flops, param_counts
from repro.launch.specs import input_specs, cell_is_applicable
from repro.models import build_model
from repro.sharding import param_shardings, batch_shardings

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

arch = "__ARCH__"
shape_name = "__SHAPE__"
cfg = get_arch(arch).reduced(
    d_model=64, n_heads=4, n_kv_heads=4 if get_arch(arch).n_kv_heads > 1 else 1,
    head_dim=16, d_ff=128, vocab_size=512,
)
shape = dataclasses.replace(
    SHAPES[shape_name], global_batch=8, seq_len=256
)
tc = TrainConfig(remat="full", microbatches=2)

model = build_model(cfg)
ext = extrapolated_costs(cfg, shape, mesh, tc if shape.kind == "train" else None)
assert ext["flops_per_device"] > 0
assert ext["bytes_per_device"] > 0
rep = roofline_report(
    flops_per_device=ext["flops_per_device"],
    bytes_per_device=ext["bytes_per_device"],
    wire_bytes_per_device=ext["wire_bytes_per_device"],
    n_devices=8,
    model_flops_global=model_flops(cfg, shape),
)
assert rep["dominant"] in ("compute", "memory", "collective")
print(json.dumps({"ok": True, "dominant": rep["dominant"],
                  "flops": ext["flops_per_device"]}))
"""


def _run(arch: str, shape: str):
    code = SCRIPT.replace("__ARCH__", arch).replace("__SHAPE__", shape)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("qwen1.5-0.5b", "train_4k"),      # dense train
    ("olmoe-1b-7b", "decode_32k"),     # MoE decode
    ("recurrentgemma-2b", "prefill_32k"),  # hybrid prefill
])
def test_reduced_dryrun_cell(arch, shape):
    res = _run(arch, shape)
    assert res["ok"] and res["flops"] > 0

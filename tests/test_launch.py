"""Launch/dry-run machinery tests that don't need 512 devices: the HLO
collective parser, input spec generation for all 40 (arch x shape) pairs,
and a real mesh lowering on a small forced-host-device subprocess."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, list_archs
from repro.configs.shapes import SHAPES
from repro.launch.dryrun import parse_collective_bytes, runnable
from repro.models import model as M

HLO = """
ENTRY %main {
  %ag = f32[16,1024]{1,0} all-gather(%p0), replica_groups={{0,1}}
  %ar = (bf16[512]{0}, bf16[512]{0}) all-reduce(%a, %b), to_apply=%sum
  %rs = f32[64,32]{1,0} reduce-scatter(f32[64,256]{1,0} %big), dimensions={1}
  %a2a = s32[128]{0} all-to-all(%c)
  %cp = f32[8,8]{1,0} collective-permute(%d)
  %agd = f32[4]{0} all-gather-done(%x)
}
"""


def test_parse_collective_bytes_kinds():
    out = parse_collective_bytes(HLO)
    assert out["all-gather"] == 16 * 1024 * 4
    assert out["all-reduce"] == 2 * (512 * 2 + 512 * 2)   # 2x ring factor
    assert out["reduce-scatter"] == 64 * 256 * 4          # operand, not result
    assert out["all-to-all"] == 128 * 4
    assert out["collective-permute"] == 8 * 8 * 4
    assert out["total"] == sum(out[k] for k in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute"))


def test_input_specs_all_pairs_abstract():
    """All 44 pairs produce allocation-free specs with coherent shapes."""
    count = 0
    for arch in list_archs():
        cfg = get_config(arch)
        for name, shape in SHAPES.items():
            ok, _ = runnable(cfg, shape)
            count += 1
            if shape.kind == "decode":
                continue   # decode inputs built in build_case
            inputs, axes = M.input_specs(cfg, shape, abstract=True)
            assert set(inputs) == set(axes)
            for k, v in inputs.items():
                assert isinstance(v, jax.ShapeDtypeStruct), (arch, name, k)
                assert v.shape[0] == shape.global_batch
    assert count == 44


def test_runnable_long_500k_policy():
    runs = {a: runnable(get_config(a), SHAPES["long_500k"])[0]
            for a in list_archs()}
    assert runs["mamba2-780m"] and runs["zamba2-2.7b"]
    assert runs["h2o-danube-3-4b"]            # native SWA
    assert not runs["llama3-405b"] and not runs["qwen1.5-110b"]


SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from repro.configs.base import get_config
from repro.configs.shapes import SHAPES
from repro.launch.dryrun import build_case
from repro.launch.mesh import make_mesh
from repro.sharding import use_mesh
import dataclasses

mesh = make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(get_config("h2o-danube-3-4b").reduced(),
                          vocab=512, d_model=256, n_heads=4, n_kv_heads=4,
                          head_dim=64, d_ff=512)
shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
with use_mesh(mesh):
    fn, args, sh = build_case(cfg, shape, mesh, remat=False)
    compiled = jax.jit(fn, in_shardings=sh).lower(*args).compile()
cost = compiled.cost_analysis()
print(json.dumps({"flops": cost.get("flops", -1),
                  "ndev": mesh.devices.size}))
"""


def test_small_mesh_lowering_subprocess():
    """A reduced arch lowers+compiles on a real 8-device (2x4) mesh."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ndev"] == 8
    assert rec["flops"] > 0


EP_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json, dataclasses
import jax, jax.numpy as jnp
from repro.configs.base import get_config
from repro.models import moe as moe_mod
from repro.launch.mesh import make_mesh
from repro.models.layers import init_from_schema
from repro.sharding import use_mesh

cfg = dataclasses.replace(get_config("olmoe-1b-7b").reduced(),
                          capacity_factor=64.0)
key = jax.random.PRNGKey(0)
p = init_from_schema(moe_mod.moe_schema(cfg), key, "float32")
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model))
y_ref, _ = moe_mod.apply_moe(cfg, p, x)
mesh = make_mesh((2, 2), ("data", "model"))
with use_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, x: moe_mod.apply_moe_ep(
        cfg, p, x, mesh=mesh, batch_axes=("data",)))(p, x)
err = float(jnp.abs(y_ref - y_ep).max())
print(json.dumps({"err": err}))
"""


def test_moe_expert_parallel_matches_spmd_reference():
    """apply_moe_ep (shard_map + all_to_all dispatch, §Perf B2/B3) equals
    the SPMD apply_moe bit-for-bit on a real 2x2 device mesh."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", EP_SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-5, rec

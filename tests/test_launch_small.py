"""Launch machinery on the local 1-device mesh: build_cell lowers+compiles
for every family x step kind (full production path, toy sizes), and the HLO
collective parser handles real and synthetic inputs."""

import dataclasses

import jax

import pytest

from repro.configs import SHAPES, get_arch
from repro.launch.hlo_stats import collective_stats, collective_seconds
from repro.launch.steps import build_cell

MESH = jax.make_mesh((1, 1), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

TINY = {
    "train": dataclasses.replace(SHAPES["train_4k"], seq_len=32,
                                 global_batch=4),
    "prefill": dataclasses.replace(SHAPES["prefill_32k"], seq_len=64,
                                   global_batch=2),
    "decode": dataclasses.replace(SHAPES["decode_32k"], seq_len=64,
                                  global_batch=2),
}

FAMILY_REPS = ["yi-9b-smoke", "deepseek-v3-671b-smoke", "mamba2-1.3b-smoke",
               "recurrentgemma-9b-smoke", "seamless-m4t-large-v2-smoke",
               "llava-next-mistral-7b-smoke"]


@pytest.mark.parametrize("arch", FAMILY_REPS)
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_build_cell_lowers_and_compiles(arch, kind):
    cfg = get_arch(arch)
    shape = TINY[kind]
    cell = build_cell(cfg, shape, MESH, num_microbatches=2
                      if kind == "train" else 1)
    compiled = cell.lower().compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    ca = compiled.cost_analysis()
    if isinstance(ca, list):         # older JAX: one entry per device
        ca = ca[0] if ca else {}
    assert ca.get("flops", 0) > 0


def test_collective_parser_synthetic():
    hlo = """
  %ag = bf16[2048,512]{1,0} all-gather(%p0), dimensions={0}
  %ar = f32[1024]{0} all-reduce(%x), to_apply=%add
  %rs = (f32[16,16]{1,0}, f32[16,16]{1,0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = u32[8]{0} collective-permute(%y), source_target_pairs={{0,1}}
  %a2a = bf16[64,64]{1,0} all-to-all(%z), dimensions={1}
"""
    st = collective_stats(hlo)
    assert st["counts"] == {"all-gather": 1, "all-reduce": 1,
                            "reduce-scatter": 1, "collective-permute": 1,
                            "all-to-all": 1}
    assert st["bytes"]["all-gather"] == 2048 * 512 * 2
    assert st["bytes"]["all-reduce"] == 4096
    assert st["bytes"]["reduce-scatter"] == 2 * 16 * 16 * 4
    secs = collective_seconds(st, ici_bw=1e9)
    assert secs > 0


def test_roofline_depth_plan_all_families():
    from repro.launch.roofline import depth_plan

    for arch in FAMILY_REPS + ["qwen3-8b-smoke"]:
        cfg = get_arch(arch)
        probes, units, solve, base = depth_plan(cfg)
        assert probes and units
        for u in units:
            assert u in solve


def test_mesh_factories():
    # NOTE: cannot build 256/512-device meshes here (1 CPU device) — the
    # production meshes are exercised by launch/dryrun.py; here we check the
    # local factory only.
    from repro.launch.mesh import make_local_mesh

    m = make_local_mesh()
    assert set(m.axis_names) == {"data", "model"}

"""Sharding rules + a miniature end-to-end dry-run on a small host mesh.

The production 512-device dry-run runs via `python -m repro.launch.dryrun`;
this test exercises the same code path at (2, 2) so it runs in CI seconds.
Device count is per-process, so the multi-device cells run in a subprocess
with XLA_FLAGS (the suite itself must keep seeing 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest


from repro.configs import get_smoke_config
from repro.models.lm import LM
from repro.parallel import sharding as shd


def test_param_specs_cover_tree():
    cfg = get_smoke_config("qwen3-8b")
    model = LM(cfg)
    ap = model.init_abstract()
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    specs = shd.param_specs(cfg, ap, mesh, shd.ShardingPolicy())
    n_specs = len(jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    n_params = len(jax.tree_util.tree_leaves(ap))
    assert n_specs == n_params


def test_tp_rules_shard_heads_and_ffn():
    import dataclasses
    cfg = dataclasses.replace(get_smoke_config("qwen3-8b"), n_kv_heads=4)
    model = LM(cfg)
    ap = model.init_abstract()
    # AbstractMesh: rule evaluation needs only axis sizes, not real devices
    mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
    specs = shd.param_specs(cfg, ap, mesh, shd.ShardingPolicy(tp=True))
    seg = specs["seg0_attn_dense"]
    assert seg["attn"]["wq"] == jax.sharding.PartitionSpec(None, None, "model", None)
    assert seg["ffn"]["w_gate"] == jax.sharding.PartitionSpec(None, None, "model")
    assert seg["ffn"]["w_down"] == jax.sharding.PartitionSpec(None, "model", None)
    assert specs["embed"] == jax.sharding.PartitionSpec("model", None)


def test_indivisible_heads_stay_replicated():
    cfg = get_smoke_config("qwen2-vl-2b")  # 4 q heads, 2 kv heads
    model = LM(cfg)
    ap = model.init_abstract()
    mesh = jax.sharding.AbstractMesh((1, 8), ("data", "model"))
    specs = shd.param_specs(cfg, ap, mesh, shd.ShardingPolicy(tp=True))
    # 4 heads % 8 != 0 -> replicated, but ffn 128 % 8 == 0 -> sharded
    assert specs["seg0_attn_dense"]["attn"]["wq"] == jax.sharding.PartitionSpec(None, None, None, None)
    assert specs["seg0_attn_dense"]["ffn"]["w_gate"] == jax.sharding.PartitionSpec(None, None, "model")


_SUBPROCESS_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json, dataclasses
    sys.path.insert(0, {src!r})
    import jax
    from repro.launch.steps import build_step_cfg
    from repro.launch.roofline import collective_stats
    from repro.configs import get_smoke_config
    from repro.configs.shapes import SHAPES, Shape

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = get_smoke_config({arch!r})
    shape = Shape("t", 32, 8, {kind!r})
    import repro.configs.shapes as shp
    shp.SHAPES["t"] = shape
    with jax.set_mesh(mesh):
        (fn, args), cfg, shape = build_step_cfg(cfg, "t", mesh)
        compiled = fn.lower(*args).compile()
        coll = collective_stats(compiled.as_text(), default_group=2)
        mem = compiled.memory_analysis()
    print(json.dumps({{
        "ok": True,
        "collective_kinds": sorted(coll["ops"].keys()),
        "wire": coll["wire_bytes_per_device"],
        "args_bytes": mem.argument_size_in_bytes,
    }}))
""")


@pytest.mark.parametrize("arch,kind,expect_coll", [
    ("qwen3-8b", "train", "all-reduce"),          # DP gradient sync
    ("deepseek-v2-lite-16b", "train", "all-to-all"),  # EP dispatch
    ("falcon-mamba-7b", "decode", None),
])
def test_mini_dryrun_multipod(arch, kind, expect_coll, tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SUBPROCESS_DRYRUN.format(src=os.path.abspath(src), arch=arch, kind=kind)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]
    if expect_coll is not None:
        assert expect_coll in out["collective_kinds"], out

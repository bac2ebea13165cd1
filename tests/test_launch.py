"""Launch-layer tests: sharding spec selection, input specs, and a
small-mesh dry-run smoke (subprocess with 8 fake devices)."""
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, assigned_pairs
from repro.launch.hlo_analysis import (RooflineTerms,
                                       collective_bytes_from_hlo,
                                       model_flops)
from repro.launch.sharding import _divides, _spec_candidates, param_pspec


class TestSpecCandidates:
    def test_attention_heads_divisible(self):
        axis = {"data": 16, "model": 16}
        # 32 heads: shard heads over model
        spec = param_pspec(
            (jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq")),
            jax.ShapeDtypeStruct((4096, 32, 128), jnp.float32),
            axis_sizes=axis, train=False)
        assert tuple(spec) == (None, "model", None)

    def test_attention_heads_indivisible_falls_back(self):
        axis = {"data": 16, "model": 16}
        spec = param_pspec(
            (jax.tree_util.DictKey("attn"), jax.tree_util.DictKey("wq")),
            jax.ShapeDtypeStruct((576, 9, 64), jnp.float32),
            axis_sizes=axis, train=False)
        # 9 heads % 16 != 0 -> shard d_model contraction dim instead
        assert tuple(spec) == ("model", None, None)

    def test_train_adds_worker_axis(self):
        axis = {"data": 16, "model": 16}
        spec = param_pspec(
            (jax.tree_util.DictKey("mlp"), jax.tree_util.DictKey("gate")),
            jax.ShapeDtypeStruct((16, 4096, 12288), jnp.float32),
            axis_sizes=axis, train=True)
        assert tuple(spec) == ("data", None, "model")

    def test_divides(self):
        assert _divides(("model", None), (32, 7), {"model": 16})
        assert not _divides(("model", None), (9, 7), {"model": 16})


class TestModelFlops:
    def test_train_is_6nd(self):
        cfg = ARCHS["smollm-135m"]
        shape = SHAPES["train_4k"]
        f = model_flops(cfg, shape, chips=1)
        assert f == pytest.approx(
            6 * cfg.active_param_count() * 256 * 4096, rel=1e-6)

    def test_decode_counts_one_token(self):
        cfg = ARCHS["smollm-135m"]
        f = model_flops(cfg, SHAPES["decode_32k"], chips=1)
        assert f == pytest.approx(
            2 * cfg.active_param_count() * 128, rel=1e-6)

    def test_moe_active_lt_total(self):
        cfg = ARCHS["phi3.5-moe-42b-a6.6b"]
        f_active = model_flops(cfg, SHAPES["train_4k"])
        n_total = cfg.param_count()
        assert f_active < 6 * n_total * 256 * 4096


class TestHloParse:
    def test_collective_bytes_parse(self):
        hlo = textwrap.dedent("""
        ENTRY %main {
          %ag = bf16[8,128]{1,0} all-gather(%x), replica_groups={}
          %ar = f32[16,16]{1,0} all-reduce(%y), to_apply=%add
          %cp = f32[4,4]{1,0} collective-permute(%z), source_target_pairs={}
        }
        """)
        out = collective_bytes_from_hlo(hlo)
        assert out["by_op"]["all-gather"] == 8 * 128 * 2
        assert out["by_op"]["all-reduce"] == 16 * 16 * 4 * 2  # 2x wire
        assert out["by_op"]["collective-permute"] == 4 * 4 * 4
        assert out["count"] == 3

    def test_roofline_terms_dominant(self):
        t = RooflineTerms(arch="a", shape="s", mesh="m", chips=256,
                          hlo_flops=197e12, hlo_bytes=819e9 * 10,
                          collective_bytes=50e9, model_flops=197e12)
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(10.0)
        assert t.collective_s == pytest.approx(1.0)
        assert t.dominant == "memory"
        assert t.useful_ratio == pytest.approx(1.0)


class TestAssignedPairs:
    def test_grid_covers_spec(self):
        pairs = assigned_pairs()
        # 10 archs x 3 shapes + 3 long_500k = 33
        assert len(pairs) == 33
        longs = {c.name for c, s in pairs if s.name == "long_500k"}
        assert longs == {"recurrentgemma-9b", "mamba2-370m", "gemma3-1b"}


DRYRUN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses as dc
    import jax
    from repro.configs.registry import get_arch, get_shape
    from repro.core.gossip import GossipConfig
    from repro.launch import steps as ST
    from repro.launch.mesh import make_host_mesh

    # reduced smollm on a (4, 2) host mesh: the same lower+compile path as
    # the 512-device production dry-run
    cfg = dc.replace(get_arch("smollm-135m").reduced(),
                     name="smollm-135m-smoke")
    shape = dc.replace(get_shape("train_4k"), seq_len=64, global_batch=8)
    mesh = make_host_mesh(data=4, model=2)
    fn, specs = ST.step_and_args(cfg, shape, mesh, GossipConfig(
        shifts=(1, 2), partial_blocks=2))
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*specs.values()).compile()
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0
    assert "collective-permute" in compiled.as_text()
    print("DRYRUN-SMOKE-OK")
""")


@pytest.mark.slow
def test_dryrun_smoke_small_mesh(run_cpu_script):
    r = run_cpu_script(DRYRUN_SCRIPT, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DRYRUN-SMOKE-OK" in r.stdout


TRAIN_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.train import setup, train

    argv = ["--arch", "smollm-135m", "--reduced", "--steps", "4",
            "--workers", "4", "--batch", "1", "--seq", "32",
            "--wire-format", "int8", "--pipelined", "--log-every", "100"]
    spread = setup(argv)
    assert dict(spread.mesh.shape) == {"data": 4, "model": 1}
    assert spread.state["params"].sharding.spec == P("data")
    # stacked pipelined FIFO: the worker axis is its second
    assert spread.state["gossip"].buf.sharding.spec == P(None, "data")
    losses4 = train(spread)
    losses1 = train(setup(argv, devices=jax.devices()[:1]))
    np.testing.assert_allclose(losses4, losses1, rtol=1e-5)
    print("TRAIN-MESH-OK")
""")


def test_trainer_splits_workers_over_devices(run_cpu_script):
    """4 fake devices: the trainer places one worker per device and runs
    the packed kernel per device (shard_map); the losses match the same
    run with every worker on one device."""
    r = run_cpu_script(TRAIN_MESH_SCRIPT, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "TRAIN-MESH-OK" in r.stdout


CACHE_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.cache import enable_compile_cache
    print(enable_compile_cache())
    # cache even a tiny compile (steering for the test only)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
""")


def test_compile_cache_follows_env(run_cpu_script, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there and the helper
    sets nothing; unset: the fixed <repo>/.jax_cache."""
    from repro.launch.cache import REPO_CACHE_DIR, enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    r = run_cpu_script(CACHE_SCRIPT, timeout=300,
                       env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split()[0] == str(tmp_path)
    assert any(tmp_path.iterdir())

    old = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert (REPO_CACHE_DIR.parent / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)

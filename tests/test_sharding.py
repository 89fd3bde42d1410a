"""Sharding rules: dedup, divisibility, cache-axes trees, cost parser."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from repro.configs import get_config, list_archs
from repro.distributed import sharding as shd
from repro.models import nn, transformer as tfm


def test_spec_dedup():
    rules = {"batch": "data", "embed": "data", "mlp": "model"}
    spec = shd.spec_from_axes(("batch", "seq", "embed"), rules)
    assert spec == PartitionSpec("data", None, None)


def test_spec_divisibility_drop():
    rules = {"kv_heads": "model", "embed": "data"}
    sizes = {"data": 16, "model": 16}
    spec = shd.spec_from_axes(("embed", "kv_heads"), rules,
                              shape=(64, 2), axis_sizes=sizes)
    assert spec == PartitionSpec("data", None)
    spec2 = shd.spec_from_axes(("embed", "kv_heads"), rules,
                               shape=(64, 32), axis_sizes=sizes)
    assert spec2 == PartitionSpec("data", "model")


def test_multi_pod_tuple_axes():
    rules = shd.make_rules("train", multi_pod=True)
    spec = shd.spec_from_axes(("batch", None), rules)
    assert spec == PartitionSpec(("pod", "data"), None)


def test_rules_cover_all_logical_axes_used_by_models():
    rules = shd.make_rules("train")
    # collect every logical axis name from one representative arch family
    for arch in ["jamba-1.5-large-398b", "whisper-base",
                 "llama-3.2-vision-90b", "qwen3-moe-235b-a22b"]:
        from repro.configs import smoke_config
        cfg = smoke_config(arch)
        params, specs = tfm.init_model(jax.random.PRNGKey(0), cfg)
        is_axes = lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)
        leaves = jax.tree_util.tree_flatten(specs, is_leaf=is_axes)[0]
        for axes in leaves:
            assert is_axes(axes)
            for a in axes:
                assert a is None or a in rules, (arch, a)


@pytest.mark.parametrize("arch", list_archs())
def test_cache_axes_tree_matches_cache_structure(arch):
    from repro.configs import smoke_config
    cfg = smoke_config(arch)
    caches = jax.eval_shape(lambda: tfm.init_caches(cfg, 2, 16))
    axes = shd.cache_logical_axes(cfg)
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    c_flat = jax.tree_util.tree_flatten_with_path(caches)[0]
    a_flat = jax.tree_util.tree_flatten_with_path(axes, is_leaf=is_axes)[0]
    assert [jax.tree_util.keystr(p) for p, _ in c_flat] == \
        [jax.tree_util.keystr(p) for p, _ in a_flat]
    for (_, leaf), (_, ax) in zip(c_flat, a_flat):
        assert len(ax) == len(leaf.shape)


def test_shard_act_noop_without_rules():
    x = jnp.ones((4, 4))
    assert nn.shard_act(x, "batch", "embed") is x


def test_collective_parser():
    from repro.launch import roofline as rl
    hlo = """
  %ag = bf16[128,1024]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[256]{0} all-reduce(%y), to_apply=%add
  %rs = f32[64,64]{1,0} reduce-scatter(%z), dimensions={0}
  %done = bf16[8]{0} all-gather-done(%w)
  %cp = bf16[32]{0} collective-permute(%v)
"""
    out = rl.collective_bytes(hlo)
    assert out["all-gather"] == 128 * 1024 * 2
    assert out["all-reduce"] == 256 * 4 * 2          # 2× ring factor
    assert out["reduce-scatter"] == 64 * 64 * 4
    assert out["collective-permute"] == 32 * 2
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce",
                                "reduce-scatter", "all-to-all",
                                "collective-permute"))


def test_kernel_platform_check_refuses_cpu():
    """Off the TPU the kernels interpret, and the chip smoke's device
    check fails instead of carrying on."""
    from repro.kernels import platform
    assert platform.resolve_interpret(None) is True
    assert platform.resolve_interpret(False) is False
    with pytest.raises(RuntimeError, match="no TPU"):
        platform.check_tpu()


def test_tpu_latency_flags_append_to_libtpu_init_args():
    from repro.launch import flags
    env = {"LIBTPU_INIT_ARGS": "--machine_flag=1", "XLA_FLAGS": "--x=1"}
    merged = flags.apply_latency_flags("tpu", env=env)
    assert merged.split()[0] == "--machine_flag=1"
    assert env["XLA_FLAGS"] == "--x=1"
    assert set(merged.split()[1:]) == set(flags.LATENCY_FLAGS["tpu"])


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    from repro.launch import serve
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = serve.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    assert path == (str(tmp_path) if env_dir
                    else str(serve.REPO_ROOT / ".jax_cache"))


def test_host_mesh_spans_present_devices_with_auto_axes():
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert all(t == AxisType.Auto for t in mesh.axis_types)

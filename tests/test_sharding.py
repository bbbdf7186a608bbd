"""Sharding rules: spec validity for every arch × mesh; divisibility guards."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.data import make_batch_spec
from repro import compat
from repro.launch import sharding as shg
from repro.models import lm

ALL_ARCHS = [
    "rwkv6-7b", "llama3.2-3b", "phi3-mini-3.8b", "qwen1.5-110b",
    "qwen1.5-0.5b", "zamba2-7b", "whisper-tiny", "granite-moe-1b-a400m",
    "grok-1-314b", "internvl2-26b",
]


class FakeMesh:
    """Shape-only stand-in so spec derivation needs no real devices."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESH1 = FakeMesh({"data": 16, "model": 16})
MESH2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _axis_total(mesh, entry):
    axes = entry if isinstance(entry, tuple) else (entry,)
    return int(np.prod([mesh.shape[a] for a in axes]))


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("mesh", [MESH1, MESH2], ids=["1pod", "2pod"])
def test_param_specs_no_overshard(arch, mesh):
    """No dim is sharded across more shards than its size; ranks match."""
    cfg = get_config(arch)
    tp = 16
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(lambda k: lm.init_params(cfg, k, tp=tp), key)
    specs = shg.param_specs(cfg, mesh, tp, params_shape)

    def check(path, leaf, spec):
        assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
        for dim, entry in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if entry is None:
                continue
            assert dim >= _axis_total(mesh, entry), (path, spec, leaf.shape)

    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params_shape, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


@pytest.mark.parametrize("arch", ["llama3.2-3b", "grok-1-314b", "rwkv6-7b"])
def test_big_weights_are_sharded(arch):
    """Multi-GB tensors must not be replicated at tp=16."""
    cfg = get_config(arch)
    tp = 16
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(lambda k: lm.init_params(cfg, k, tp=tp), key)
    specs = shg.param_specs(cfg, MESH2, tp, params_shape)
    flat_p = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_p, flat_s):
        nbytes = leaf.size * leaf.dtype.itemsize
        if nbytes > 512e6:  # anything >0.5 GB must shard
            assert any(ax is not None for ax in spec), (path, leaf.shape, spec)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_cache_specs_cover_cache(arch):
    cfg = get_config(arch)
    tp = 16
    cache_shape = jax.eval_shape(lambda: lm.init_cache(cfg, 128, 1024, tp=tp))
    specs = shg.cache_specs(cfg, MESH1, tp, cache_shape)
    flat_c = jax.tree.leaves(cache_shape)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_c) == len(flat_s)
    for leaf, spec in zip(flat_c, flat_s):
        assert len(spec) <= len(leaf.shape)


def test_batch_specs_respect_divisibility():
    cfg = get_config("rwkv6-7b")
    tok = {"tokens": jax.ShapeDtypeStruct((1, 42), jnp.int32)}  # batch 1
    specs = shg.batch_specs(cfg, MESH1, tok)
    assert specs["tokens"][0] is None  # 1 % 16 != 0 -> replicated
    tok = {"tokens": jax.ShapeDtypeStruct((256, 42), jnp.int32)}
    specs = shg.batch_specs(cfg, MESH1, tok)
    assert specs["tokens"][0] == "data"


def test_head_policy_table():
    """Attention TP policies chosen per arch at tp=16 (documented table)."""
    expect = {
        "llama3.2-3b": "pad",        # 24 Q heads -> 32
        "phi3-mini-3.8b": "shard",   # 32/32
        "qwen1.5-110b": "shard_q",   # 64 Q, 8 KV replicated
        "whisper-tiny": "replicate",  # 6 heads, padding too wasteful
        "grok-1-314b": "shard_q",
        "qwen1.5-0.5b": "shard",
    }
    for arch, policy in expect.items():
        cfg = get_config(arch)
        assert cfg.padded_heads(16)[2] == policy, arch


def test_jit_with_specs_runs_on_local_mesh():
    """End-to-end: reduced arch jitted with derived shardings on 1 device."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0), tp=1)
    specs = shg.param_specs(cfg, mesh, 1, params)
    shard = shg.to_shardings(mesh, specs)
    params = jax.device_put(params, shard)
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    with mesh:
        loss, _ = jax.jit(lambda p, b: lm.loss_fn(p, cfg, b))(params, batch)
    assert np.isfinite(float(loss))

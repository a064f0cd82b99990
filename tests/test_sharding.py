"""Sharding rules: every parameter/cache leaf of every architecture gets a
rank-consistent PartitionSpec whose named axes divide the dims (validated
structurally against an AbstractMesh — no devices needed)."""
import functools

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.distributed.sharding import (batch_pspecs, cache_pspecs,
                                        state_pspecs, tree_pspecs)
from repro.launch.specs import (decode_specs, params_struct, state_struct,
                                train_specs)

MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _flat_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in kp), v) for kp, v in flat]


def _check(specs, shapes):
    s_flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    v_flat = jax.tree.leaves(shapes)
    assert len(s_flat) == len(v_flat)
    for spec, leaf in zip(s_flat, v_flat):
        assert len(spec) <= leaf.ndim, (spec, leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                size *= dict(zip(MESH.axis_names, MESH.axis_sizes)).get(a, 1)
            assert dim % size == 0, (spec, leaf.shape)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_valid(arch):
    cfg = get_config(arch)
    params = params_struct(cfg)
    specs = tree_pspecs(params, cfg, MESH)
    _check(specs, params)


@pytest.mark.parametrize("arch", ["qwen2_5_7b", "deepseek_v2_236b",
                                  "falcon_mamba_7b", "recurrentgemma_9b"])
def test_state_specs_valid(arch):
    cfg = get_config(arch)
    st = state_struct(cfg)
    specs = state_pspecs(st, cfg, MESH)
    _check(specs.params, st.params)
    _check(specs.opt_state["m"], st.opt_state["m"])


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", ["decode_32k", "long_500k"])
def test_cache_specs_valid(arch, shape):
    cfg = get_config(arch)
    if shape == "long_500k" and not cfg.supports_long_decode:
        pytest.skip("whisper long_500k skipped by design")
    cache, token, pos, ring = decode_specs(cfg, shape)
    B = token.shape[0]
    specs = cache_pspecs(cache, cfg, MESH, batch=B)
    _check(specs, cache)
    # kv_seq_shard variant also valid
    specs2 = cache_pspecs(cache, cfg, MESH, batch=B, kv_seq_shard=True)
    _check(specs2, cache)


def test_batch_specs_shard_leading_dim():
    cfg = get_config("qwen2_5_7b")
    batch = train_specs(cfg, "train_4k")
    specs = batch_pspecs(batch, cfg, POD_MESH)
    assert specs["tokens"] == P(("pod", "data"), None)
    assert specs["advantage"] == P(("pod", "data"))


def test_tp_fsdp_pattern():
    """Attention/MLP weights must shard d_model-ish over data and the
    parallel dim over model (Megatron x FSDP)."""
    cfg = get_config("qwen2_5_7b")
    params = params_struct(cfg)
    specs = tree_pspecs(params, cfg, MESH)
    flat = dict(_flat_with_paths(specs))

    def get(path):
        for k, v in flat.items():
            if k.endswith(path):
                return v
        raise KeyError(path)

    assert get("attn/wq/w") == P(None, "data", "model")   # stacked layers
    assert get("attn/wo/w") == P(None, "model", "data")
    assert get("ffn/up/w") == P(None, "data", "model")
    assert get("ffn/down/w") == P(None, "model", "data")
    assert get("embed/table") == P("model", "data")

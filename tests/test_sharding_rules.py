"""Sharding-rule unit tests (pure: a 1-device (1,1) mesh carries the
axis names; specs are data, no lowering happens)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config, input_specs, SHAPES
from repro.configs.base import padded_vocab
from repro.launch import sharding as sh
from repro.models import build_model


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh: full production shape without needing 256 devices —
    # the spec functions only read mesh.shape / axis_names.
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def _params(arch):
    cfg = get_config(arch)
    model = build_model(cfg)
    return cfg, jax.eval_shape(lambda k: model.init(k), jax.random.key(0))


def test_vocab_padding():
    assert padded_vocab(get_config("whisper-tiny")) == 51968
    assert padded_vocab(get_config("mamba2-2.7b")) == 50432
    assert padded_vocab(get_config("tinyllama-1.1b")) == 32000
    for arch in ("llama4-maverick-400b-a17b", "deepseek-v3-671b"):
        assert padded_vocab(get_config(arch)) % 16 == 0


def test_untied_embed_sharded_on_feature_dim(mesh):
    _, params = _params("tinyllama-1.1b")       # untied
    specs = sh.param_specs(params, mesh)
    assert specs["embed"] == P(None, "model")
    assert specs["unembed"] == P(None, "model")


def test_tied_embed_keeps_vocab_sharding(mesh):
    _, params = _params("command-r-35b")        # tied
    assert "unembed" not in params
    specs = sh.param_specs(params, mesh)
    assert specs["embed"] == P("model", None)


def test_col_row_rules_on_stacked_layers(mesh):
    _, params = _params("tinyllama-1.1b")
    specs = sh.param_specs(params, mesh)
    layer = specs["stack"][0]
    # stacked params get a leading None for the layer-cycle dim
    assert layer["mixer"]["wq"]["w"] == P(None, None, "model")
    assert layer["mixer"]["wo"]["w"] == P(None, "model", None)
    assert layer["channel"]["w_up"]["w"] == P(None, None, "model")
    assert layer["channel"]["w_down"]["w"] == P(None, "model", None)
    # norms replicated
    assert layer["mixer_norm"]["scale"] == P(None, None)


def test_moe_expert_parallel_rule(mesh):
    _, params = _params("llama4-maverick-400b-a17b")
    specs = sh.param_specs(params, mesh)
    layer = specs["stack"][0]
    assert layer["channel"]["w_up"] == P(None, "model", None, None)
    assert layer["channel"]["w_down"] == P(None, "model", None, None)
    assert layer["channel"]["router"]["w"] == P(None, None, None)


def test_fsdp_adds_data_axis(mesh):
    _, params = _params("qwen2-vl-72b")
    specs = sh.param_specs(params, mesh, fsdp=True)
    w = specs["stack"][0]["mixer"]["wq"]["w"]
    assert "data" in jax.tree.leaves(tuple(w), is_leaf=lambda x: True) \
        or w == P(None, "data", "model")


def test_batch_specs_shard_leading_dim(mesh):
    cfg = get_config("tinyllama-1.1b")
    batch = input_specs(cfg, SHAPES["train_4k"])
    specs = sh.batch_specs(batch, mesh)
    assert specs["tokens"][0] in ("data", ("data",))
    # batch=1 long-context tokens stay replicated
    b1 = {"t": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    s1 = sh.batch_specs(b1, mesh)
    assert s1["t"] == P(None, None)


def test_state_specs_cache_rules(mesh):
    cfg = get_config("tinyllama-1.1b")
    model = build_model(cfg)
    states = jax.eval_shape(lambda: model.init_states(None, 128, 32768))
    specs = sh.state_specs(states, mesh)
    k_spec = specs[0]["k"]
    assert k_spec[0] in ("data", ("data",)) and k_spec[1] == "model"
    # B=1 long context: sequence over everything
    states1 = jax.eval_shape(lambda: model.init_states(None, 1, 524288))
    specs1 = sh.state_specs(states1, mesh)
    assert specs1[0]["k"][1] == ("data", "model")

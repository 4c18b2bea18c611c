"""``models/decoder``: a mixer is one module and one entry of ``MIXERS``, a
feed-forward one of ``FEED_FORWARDS``; a sublayer's parameter names are
written once, so its ``init`` and ``specs`` hold the same keys; and the
configuration's fields, which the benchmark's job files build by keyword,
are a fixed surface."""

import dataclasses
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec

TINY = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
            embed_dim=32, mlp_dim=64, dtype=jnp.float32, tp_axis=None,
            sp_axis=None, attention="dense")

# The per-model test files' own configurations, by the file and the name
# that builds one: between them every mixer, both routers, a shared expert,
# a selection bias, the latent experts, the residual scaling, LayerNorms and
# the values that cross layers.
MODELS = {"trinity": "test_gpt_window_moe", "zaya": "test_gpt_cca_moe",
          "moonlight": "test_gpt_mla_moe",
          "nemotron": "test_gpt_latent_moe_hybrid",
          "smallthinker": "test_gpt_prerouted_moe",
          "TINY": "test_gpt_linear_moe", "sambay12": "test_gpt_sambay",
          "ling": "test_gpt_kda_mla_moe"}


def model(name):
    built = getattr(importlib.import_module(MODELS[name]), name)
    return gpt.GPTConfig(**built) if isinstance(built, dict) else built()


def _is_spec(x):
    return isinstance(x, P)


def _dense(key, shape, fan_in):
    return jnp.zeros(shape, jnp.float32)


def _norm(shape):
    return jnp.ones(shape, jnp.float32)


def _same_keys(values, specs):
    """Leaf for leaf: one PartitionSpec a value, no longer than its rank."""
    assert jax.tree.structure(values) == jax.tree.structure(
        specs, is_leaf=_is_spec)
    for value, spec in zip(jax.tree.leaves(values),
                           jax.tree.leaves(specs, is_leaf=_is_spec)):
        assert _is_spec(spec) and len(spec) <= len(value.shape)


@pytest.mark.parametrize("mixer,name", [
    ("attention", "trinity"), ("attention", "TINY"), ("cca", "zaya"),
    ("mla", "moonlight"), ("ssm", "nemotron"), ("gdn", "TINY"),
    ("kda", "ling"), ("mla", "ling"),
    ("s6", "sambay12"), ("gmu", "sambay12"), ("diff_attention", "sambay12"),
    ("diff_cross", "sambay12")])
def test_a_mixers_init_and_specs_hold_the_same_keys(mixer, name):
    cfg, module = model(name), gpt.MIXERS[mixer]
    assert mixer in {spec.mixer for spec in cfg.plan}
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    values = jax.eval_shape(lambda: module.init(keys, cfg, _dense, _norm))
    _same_keys(values, module.specs(cfg))


@pytest.mark.parametrize("name", ["trinity", "zaya", "moonlight", "nemotron",
                                  "smallthinker", "TINY", "ling"])
def test_the_expert_blocks_init_and_specs_hold_the_same_keys(name):
    cfg, experts = model(name), gpt.FEED_FORWARDS["experts"]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    for carry in (False, True):
        values = jax.eval_shape(
            lambda: experts.init(keys, cfg, None, carry, _dense))
        _same_keys(values, experts.specs(cfg, None, carry))
        if cfg.router_kind == "mlp":
            assert ("carry" in values["router"]) == carry


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_models_specs_match_its_parameters(name):
    cfg = model(name)
    _same_keys(jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)),
        gpt.param_specs(cfg))


def test_a_mixer_is_one_entry_of_the_table(monkeypatch):
    """A mixer the tables have not, added as one entry and nothing else:
    the plan takes its name, ``init_params`` and ``param_specs`` its
    parameters under its key and its norm's, ``forward`` its branch."""
    toy = types.SimpleNamespace(
        KEY="toy", NORM="toy_norm", SAVED_NAMES=(),
        scope=lambda spec: "toy",
        init=lambda keys, cfg, dense, norm: {
            "scale": jnp.full((cfg.embed_dim,), 2.0, jnp.float32)},
        specs=lambda cfg: {"scale": P()},
        apply=lambda cfg, spec, p, h, positions: h * p["scale"])
    cfg = gpt.GPTConfig(**TINY, layers=(LayerSpec(mixer="toy"), LayerSpec()))
    with pytest.raises(ValueError, match="mixer one of"):
        gpt.init_params(jax.random.PRNGKey(0), cfg)
    monkeypatch.setitem(gpt.MIXERS, "toy", toy)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    assert sorted(params["layers"][0]) == [
        "mlp_norm", "toy", "toy_norm", "w_down", "w_up"]
    _same_keys(params, gpt.param_specs(cfg))
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % cfg.vocab_size
    positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
    logits = gpt.forward(params, tokens, positions, cfg)
    assert logits.shape == (2, 8, cfg.vocab_size)
    # The toy's branch is in the stream: another scale, other logits.
    params["layers"][0]["toy"]["scale"] = jnp.zeros((cfg.embed_dim,))
    assert not np.allclose(logits, gpt.forward(params, tokens, positions,
                                               cfg))
    assert "layer0/toy" in jax.jit(
        lambda p: gpt.forward(p, tokens, positions, cfg)).lower(
            params).as_text(debug_info=True)


# ``GPTConfig`` as the benchmark's job files build it, by keyword: the
# fields and their defaults (a new one is an option: simplicity-review).
FIELDS = {
    "vocab_size": 32000, "num_layers": 4, "num_heads": 8,
    "num_kv_heads": None, "head_dim": 64, "embed_dim": 512, "mlp_dim": 2048,
    "dtype": jnp.bfloat16, "tp_axis": "tp", "sp_axis": "sp", "ep_axis": None,
    "attention": "ring", "moe_every": 0, "num_experts": 8,
    "experts_per_token": 1, "load_balance_coef": 0.0, "router_z_coef": 0.0,
    "experts_held": None, "first_expert": 0, "renormalize_experts": False,
    "shared_expert_dim": 0, "qk_norm": False, "qk_head_norm": False,
    "norm_eps": 1e-06, "norm_zero_centered": False, "remat": "none",
    "layer_kinds": None, "ssm_heads": 8, "ssm_head_dim": 64,
    "ssm_state": 128, "ssm_groups": 1, "ssm_conv": 4, "ssm_chunk": 256,
    "gdn_key_heads": 4, "gdn_value_heads": 8, "gdn_key_dim": 64,
    "gdn_value_dim": 64, "gdn_conv": 4, "gdn_chunk": 64,
    "gdn_allow_neg_eigval": False, "gated_mlp": False, "rope": True,
    "rope_theta": 10000.0, "rotary_dim": None, "attention_gate": False,
    "tie_embeddings": False, "embedding_multiplier": 1.0,
    "attention_multiplier": None, "residual_multiplier": 1.0,
    "logits_scaling": 1.0, "layers": None, "expert_dim": None,
    "post_norm": False, "norms": None, "shared_expert_gate": True,
    "router_score": "softmax", "router_bias": False, "route_scale": 1.0,
    "router_probe": False, "cca_taps": (2, 2), "mla_kv_rank": 512,
    "mla_rope_dim": 64, "mla_value_dim": 128, "router_kind": "linear",
    "router_dim": 256, "router_reads": "ff_input",
    "expert_activation": "silu", "moe_latent_dim": 0,
    "residual_scaling": False, "norm_kind": "rms", "s6_inner": None,
    "s6_dt_rank": None, "kda_heads": 8, "kda_key_dim": 128,
    "kda_value_dim": 128, "kda_conv": 4, "kda_chunk": 64,
    "kda_lower_bound": -5.0,
    "mla_head_gate": False, "router_groups": 1, "router_groups_kept": 1,
    "diffusion_block": None, "loop_passes": 1, "exit_entropy_coef": 0.0,
}


def test_the_configurations_fields_are_the_frozen_list():
    assert len(FIELDS) == 84
    assert {f.name: f.default for f in dataclasses.fields(gpt.GPTConfig)} \
        == FIELDS
    cfg = gpt.GPTConfig(num_kv_heads=2, expert_dim=48)
    assert (cfg.kv_heads, cfg.expert_width) == (2, 48)

"""The seam between the sequence trunk and its operators: one table
(``models/layers``), one declaration of every arch key
(``models/arch_keys.py``). What an operator computes is tested where it always
was (test_attention / test_kv_cache / the ``*_reference`` suites, test_ssd,
test_gdn, test_conv); here, that every kind resolves to the whole interface,
that the key lists are the declarations and nothing else, and each operator's
parameter tree and state by STRUCTURE (init bytes differ by jax version)."""

import jax
import jax.numpy as jnp
import pytest

from relayrl_tpu.models import (
    arch_keys,
    base,
    build_policy,
    layers,
    transformer,
)

INTERFACE = ("apply", "init_cache", "ROW_READOUT", "KERNELS")


@pytest.mark.parametrize("kind", sorted(layers.LAYER_KINDS))
def test_every_layer_type_resolves_to_a_whole_operator(kind):
    op, has_ffn = layers.LAYER_KINDS[kind]
    module = layers.OPERATORS[op]
    assert isinstance(has_ffn, bool)
    assert [n for n in INTERFACE if not hasattr(module, n)] == []
    # its settings are its declared keys at their declared defaults (or as
    # the arch gives them) — after those of the operator it extends, where
    # it extends one —, beside the trunk's head count and rotation
    base_op = arch_keys.OPERATOR_BASE.get(op)
    declared = {**arch_keys.OPERATOR_KEYS.get(base_op, {}),
                **arch_keys.OPERATOR_KEYS[op]}
    cfg = transformer._operator_settings({})[op]
    assert cfg == {**declared, "n_heads": 4, "rope_theta": None}
    given = {k: object() for k in declared}
    cfg = transformer._operator_settings({**given, "positions": "rope"})[op]
    assert cfg == {**given, "n_heads": 4, "rope_theta": 10000.0}


def test_the_tables_name_the_same_operators():
    assert set(layers.OPERATORS) == set(arch_keys.OPERATOR_KEYS) == {
        op for op, _ in layers.LAYER_KINDS.values()}
    # an operator extends one of the table, and declares no key of its twice
    for op, base_op in arch_keys.OPERATOR_BASE.items():
        assert op in layers.OPERATORS and base_op in layers.OPERATORS
        assert not set(arch_keys.OPERATOR_KEYS[op]) & set(
            arch_keys.OPERATOR_KEYS[base_op])


def test_the_passthrough_keys_are_the_declarations():
    declared = (list(arch_keys.CORE_KEYS) + list(arch_keys.BLOCK_KEYS)
                + list(arch_keys.MOE_KEYS)
                + [k for keys in arch_keys.OPERATOR_KEYS.values()
                   for k in keys])
    assert list(arch_keys.DECLARED) == declared
    assert base.ARCH_PASSTHROUGH_KEYS == arch_keys.TRUNK_KEYS + tuple(declared)
    # no key declared twice, and those the configurations' references ask
    # base for (benchmark/reference/*.py) are all there (58 + kda's five and
    # latent attention's four, PR 55; + moe_latent, PR 57; + latent
    # attention's q_lora_rank and rope_interleave, PR 62; + Granite's four
    # scalars and held_params, PR 68)
    assert len(set(base.ARCH_PASSTHROUGH_KEYS)) == len(
        base.ARCH_PASSTHROUGH_KEYS) == 74


@pytest.mark.parametrize("key", arch_keys.DECLARED)
def test_the_pipeline_family_refuses_every_declared_key(key):
    arch = {"kind": "transformer_pp_discrete", "obs_dim": 6, "act_dim": 3}
    with pytest.raises(ValueError, match=f"does not take .*{key}"):
        build_policy({**arch, key: None})


def test_the_pipeline_family_takes_the_trunks_own_keys():
    own = {"d_model": 16, "n_layers": 2, "n_heads": 2, "mlp_ratio": 2,
           "max_seq_len": 8, "attention": "dense", "attention_block": 8,
           "actor_context": 4, "moe_experts": 0, "moe_top_k": 2,
           "pp_microbatches": 2}
    assert tuple(own) == arch_keys.TRUNK_KEYS
    policy = build_policy({"kind": "transformer_pp_discrete", "obs_dim": 6,
                           "act_dim": 3, **own})
    # a trunk without such layers has no record of them
    assert policy.attention_backends == {} and policy.scan_backends is None


# -- one-layer trunks, one an operator ---------------------------------------

BASE = {"kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_heads": 2, "max_seq_len": 8, "n_layers": 1,
        "norm": "rms", "has_critic": False, "precision": "bfloat16"}
FFN = {"ln_mlp/scale": (16,), "mlp_up/kernel": (16, 64), "mlp_up/bias": (64,),
       "mlp_down/kernel": (64, 16), "mlp_down/bias": (16,)}
# operator -> (arch keys, block_0's leaves: path -> shape, all float32)
TRUNKS = {
    "attention": (
        {"layer_types": ["sliding_attention"], "sliding_window": 4,
         "positions": "rope", "n_kv_heads": 1, "head_dim": 4,
         "qk_norm": "head", "attn_gate": True},
        {"ln_attn/scale": (16,), "q_proj/kernel": (16, 16),
         "q_proj/bias": (16,), "k_proj/kernel": (16, 4), "k_proj/bias": (4,),
         "v_proj/kernel": (16, 4), "v_proj/bias": (4,), "q_norm/scale": (4,),
         "k_norm/scale": (4,), "attn_out/kernel": (8, 16),
         "attn_out/bias": (16,), **FFN}),
    "conv": (
        {"layer_types": ["conv"], "conv_taps": 4},
        {"ln_attn/scale": (16,), "conv_w": (4, 16),
         "conv_in/kernel": (16, 48), "conv_in/bias": (48,),
         "conv_out/kernel": (16, 16), "conv_out/bias": (16,), **FFN}),
    "mamba2": (
        {"layer_types": ["mamba2"], "mamba_heads": 4, "mamba_head_dim": 8,
         "mamba_state": 8, "mamba_groups": 2, "mamba_chunk": 4,
         "positions": "none"},
        {"ln_attn/scale": (16,), "mamba_in": (16, 100),
         "mamba_conv_w": (4, 64), "mamba_conv_b": (64,),
         "mamba_dt_bias": (4,), "mamba_A_log": (4,), "mamba_D": (4,),
         "mamba_norm": (32,), "mamba_out": (32, 16)}),
    "gdn": (
        {"layer_types": ["linear_attention"], "gdn_key_heads": 2,
         "gdn_value_heads": 4, "gdn_key_dim": 8, "gdn_value_dim": 8,
         "gdn_chunk": 4, "positions": "none"},
        {"ln_attn/scale": (16,), "gdn_in_qkvz": (16, 96),
         "gdn_in_ba": (16, 8), "gdn_conv_w": (4, 64), "gdn_dt_bias": (4,),
         "gdn_A_log": (4,), "gdn_norm": (8,), "gdn_out": (32, 16), **FFN}),
    "kda": (
        {"layer_types": ["kda"], "kda_heads": 4, "kda_head_dim": 8,
         "kda_chunk": 4, "positions": "none"},
        {"ln_attn/scale": (16,), "kda_in_qkv": (16, 96),
         "kda_in_beta": (16, 4), "kda_f_down": (16, 8), "kda_f_up": (8, 32),
         "kda_g_down": (16, 8), "kda_g_up": (8, 32), "kda_g_bias": (32,),
         "kda_conv_w": (4, 96), "kda_dt_bias": (32,), "kda_A_log": (4,),
         "kda_norm": (8,), "kda_out": (32, 16), **FFN}),
    "latent_attention": (
        {"layer_types": ["latent_attention"], "positions": "none",
         "kv_lora_rank": 6, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
         "v_head_dim": 3},
        {"ln_attn/scale": (16,), "q_proj/kernel": (16, 12),
         "q_proj/bias": (12,), "kv_a/kernel": (16, 8), "kv_a/bias": (8,),
         "kv_a_norm/scale": (6,), "kv_b/kernel": (6, 14),
         "kv_b/bias": (14,), "attn_out/kernel": (6, 16),
         "attn_out/bias": (16,), **FFN}),
    "sparse_attention": (
        {"layer_types": ["sparse_attention"], "positions": "rope",
         "n_kv_heads": 1, "head_dim": 4, "qk_norm": "head",
         "index_heads": 3, "index_head_dim": 2, "index_topk": 2,
         "index_chunk": 4},
        {"ln_attn/scale": (16,), "q_proj/kernel": (16, 8),
         "q_proj/bias": (8,), "k_proj/kernel": (16, 4), "k_proj/bias": (4,),
         "v_proj/kernel": (16, 4), "v_proj/bias": (4,), "q_norm/scale": (4,),
         "k_norm/scale": (4,), "index_q/kernel": (16, 6),
         "index_q/bias": (6,), "index_k/kernel": (16, 2),
         "index_k/bias": (2,), "index_k_norm/scale": (2,),
         "index_k_norm/bias": (2,), "index_w/kernel": (16, 3),
         "index_w/bias": (3,), "attn_out/kernel": (8, 16),
         "attn_out/bias": (16,), **FFN}),
    "none": (
        {"layer_types": ["ffn"], "ffn": "swiglu", "d_ff": 24,
         "positions": "none"},
        {"ln_mlp/scale": (16,), "mlp_up/kernel": (16, 24),
         "mlp_up/bias": (24,), "mlp_gate/kernel": (16, 24),
         "mlp_gate/bias": (24,), "mlp_down/kernel": (24, 16),
         "mlp_down/bias": (16,)}),
}


def _structure(tree):
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): (leaf.shape, leaf.dtype.name)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_a_trunk_for_every_operator():
    assert set(TRUNKS) == set(layers.OPERATORS)


def test_only_an_operator_with_a_loss_of_its_own_gives_the_policy_one():
    """``Policy.own_loss`` follows from the operators' ``OWN_LOSS``, and a
    trunk that has one has ``evaluate_stats``, where its rows come from."""
    for op, (arch, _leaves) in TRUNKS.items():
        policy = build_policy({**BASE, **arch})
        own = getattr(layers.OPERATORS[op], "OWN_LOSS", None)
        assert policy.own_loss == own, op
        assert (policy.evaluate_stats is not None) == (own is not None), op
    assert [op for op in layers.OPERATORS if hasattr(
        layers.OPERATORS[op], "OWN_LOSS")] == ["sparse_attention"]


@pytest.mark.parametrize("op", sorted(TRUNKS))
def test_an_operators_parameter_tree(op):
    arch, leaves = TRUNKS[op]
    policy = build_policy({**BASE, **arch})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    assert _structure(params["params"]["block_0"]) == {
        path: (shape, "float32") for path, shape in leaves.items()}


@pytest.mark.parametrize("op", sorted(TRUNKS))
def test_an_operators_state_is_what_a_prefill_returns(op):
    policy = build_policy({**BASE, **TRUNKS[op][0]})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    cache = policy.init_cache(8, 2)
    assert len(cache) == 1
    filled = jax.eval_shape(policy.prefill_cache, params, cache,
                            jnp.zeros((2, 8, 6)), jnp.int32(5))
    assert jax.tree_util.tree_structure(filled) == (
        jax.tree_util.tree_structure(cache))
    assert _structure(filled) == _structure(cache)
    # ... and what one cached step hands on
    _, _, stepped = jax.eval_shape(
        policy.step_cached, params, jax.random.PRNGKey(1), cache,
        jnp.zeros((2, 6)), jnp.int32(3), jnp.ones((2, 3), bool))
    assert _structure(stepped) == _structure(cache)


# -- the looped trunk's keys: loop_steps, norm_sandwich, block_checkpoint -----

LOOP_DEFAULTS = {"loop_steps": 1, "norm_sandwich": False,
                 "block_checkpoint": False}
PRESETS = {
    "dense": {**BASE, "n_layers": 2, "norm": "layer", "has_critic": True},
    "expert": {**BASE, "kind": "transformer_moe_discrete", "n_layers": 2,
               "moe_experts": 4, "moe_top_k": 2, "moe_dense_layers": 1,
               "positions": "rope", "use_bias": False, "ffn": "swiglu",
               "has_critic": True},
}


def _lowered_grad(arch):
    policy = build_policy(arch)
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    obs = jax.ShapeDtypeStruct((2, 8, 6), jnp.float32)
    act = jax.ShapeDtypeStruct((2, 8), jnp.int32)

    def loss(p, o, a):
        logp, ent, v = policy.evaluate(p, o, a)
        return jnp.sum(logp) + jnp.sum(ent) + jnp.sum(v)

    return jax.jit(jax.value_and_grad(loss)).lower(params, obs, act).as_text()


def test_the_loop_keys_defaults_are_the_declared_ones():
    assert {k: v for k, v in arch_keys.BLOCK_KEYS.items()
            if k in LOOP_DEFAULTS} == {"norm_sandwich": False}
    assert set(LOOP_DEFAULTS) - {"norm_sandwich"} <= set(arch_keys.CORE_KEYS)
    core = transformer._make_core({**PRESETS["dense"]})
    assert (core.loop_steps, core.block_checkpoint) == (1, False)
    assert core.block_kw["norm_sandwich"] is False


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_the_loop_keys_at_their_defaults_leave_the_program_as_it_was(preset):
    """An arch that spells the three keys out at their defaults lowers,
    forward and backward, to the text of an arch without them."""
    arch = PRESETS[preset]
    assert _lowered_grad({**arch, **LOOP_DEFAULTS}) == _lowered_grad(arch)


@pytest.mark.parametrize("key,value", [
    ("loop_steps", 2), ("norm_sandwich", True), ("block_checkpoint", True)])
def test_a_loop_key_off_its_default_is_another_program(key, value):
    arch = PRESETS["dense"]
    assert _lowered_grad({**arch, key: value}) != _lowered_grad(arch)


@pytest.mark.parametrize("op", sorted(TRUNKS))
def test_sandwich_norms_are_every_operators(op):
    """``norm_sandwich`` is the block's, not the attention's: each half of
    a layer gains a norm on its output and nothing else, whatever the
    operator; a loop adds no parameter at all (an operator that brings a
    loss of its own is not looped: below)."""
    keys, leaves = TRUNKS[op]
    loop = {} if op == "sparse_attention" else {"loop_steps": 3}
    plain = jax.eval_shape(
        build_policy({**BASE, **keys}).init_params, jax.random.PRNGKey(0))
    looped = jax.eval_shape(
        build_policy({**BASE, **keys, "norm_sandwich": True,
                      **loop}).init_params, jax.random.PRNGKey(0))
    gained = set(_structure(looped["params"]["block_0"])) - set(
        _structure(plain["params"]["block_0"]))
    assert gained == ({"ln_attn_out/scale"} if op != "none" else set()) | (
        {"ln_mlp_out/scale"} if "ln_mlp/scale" in leaves else set())
    assert set(looped["params"]) == set(plain["params"])


@pytest.mark.parametrize("what", ["experts", "own_loss"])
def test_a_loop_over_layers_that_count_for_the_update_is_refused(what):
    """The expert load and a sparse-attention layer's loss rows are sown a
    layer; a pass and layer is not built."""
    arch = ({**PRESETS["expert"]} if what == "experts"
            else {**BASE, **TRUNKS["sparse_attention"][0]})
    build_policy(arch)
    with pytest.raises(ValueError, match="loop_steps > 1 over a trunk"):
        build_policy({**arch, "loop_steps": 2})


def test_a_loop_of_no_pass_is_refused():
    with pytest.raises(ValueError, match="at least one pass"):
        build_policy({**BASE, "loop_steps": 0}).init_params(
            jax.random.PRNGKey(0))

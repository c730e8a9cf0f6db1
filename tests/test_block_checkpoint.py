"""What the trunk's checkpoint round a whole layer (``block_checkpoint``)
keeps by name beside the layer's input (``models/transformer.py``; the names:
``models/layers/recurrent.BLOCK_KEPT``, ``models/moe.BLOCK_KEPT``): how often
the gradient's program makes the dear values with the names listed and
without, that the gradient is the unchecked trunk's, what the policy's record
says, and that the names are nothing to a program that lists none of them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _util import without_symbol_counters
from jax.ad_checkpoint import checkpoint_name

from relayrl_tpu.models import build_policy, moe
from relayrl_tpu.models.layers import gdn, kda, mamba2, recurrent

B, T, D = 2, 32, 16
BASE = {"kind": "transformer_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": D, "n_heads": 2, "max_seq_len": T, "n_layers": 1,
        "norm": "rms", "positions": "none", "has_critic": True}
EXPERTS = {"kind": "transformer_moe_discrete", "ffn": "relu2",
           "use_bias": False, "moe_experts": 12, "moe_top_k": 3,
           "moe_d_ff": 32, "moe_router": "sigmoid", "moe_expert_bias": True,
           "moe_shared_d_ff": 40, "moe_latent": 24, "moe_held": [0, 2],
           "moe_dense_layers": 0}
KDA = {"kda_heads": 3, "kda_head_dim": 8, "kda_chunk": 16}
MAMBA = {"mamba_heads": 4, "mamba_head_dim": 8, "mamba_state": 8,
         "mamba_groups": 2, "mamba_chunk": 16}
GDN = {"gdn_key_heads": 2, "gdn_value_heads": 2, "gdn_key_dim": 8,
       "gdn_value_dim": 12, "gdn_chunk": 16}
ARCHS = {
    # a mixer and a dense FFN behind it (kimi-linear-policy's first layer)
    "kda+dense": {**BASE, "layer_types": ["kda"], **KDA},
    "gdn+dense": {**BASE, "layer_types": ["linear_attention"], **GDN},
    # a mixer alone, experts in a latent alone (nemotron3-super-policy's)
    "mamba2": {**BASE, "layer_types": ["mamba2"], **MAMBA},
    "experts": {**BASE, **EXPERTS, "layer_types": ["ffn"]},
    # a mixer and experts behind it (kimi-linear-policy's other layers)
    "kda+experts": {**BASE, **EXPERTS, "moe_latent": None, "ffn": "swiglu",
                    "layer_types": ["kda"], **KDA},
}
# a forward matmul by its weight's shape: the mixers' input projections
# (3 H K; 2 H P + 2 G N + H; 2 Hk K + 2 H V), the router, the latent's way
# down, the shared expert's way up
IN_PROJ = {"kda": (D, 72), "mamba2": (D, 100), "gdn": (D, 80)}
ROUTER, LATENT, SHARED = (D, 12), (D, 24), (D, 40)


def _loss(policy):
    obs = jax.random.normal(jax.random.PRNGKey(1), (B, T, 6))
    act = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, 3)

    def loss(params):
        logp, ent, v = policy.evaluate(params, obs, act)
        return jnp.sum(logp) + 0.1 * jnp.sum(ent) + jnp.sum(jnp.square(v))

    return loss


def _grad_jaxpr(arch):
    policy = build_policy(arch)
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    return policy, jax.make_jaxpr(jax.grad(_loss(policy)))(params).jaxpr


def _made(jaxpr, want) -> int:
    """How many equations of a jaxpr, inner jaxprs included, ``want``."""
    return sum(bool(want(eqn)) + sum(
        _made(inner, want) for inner in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


def _forward_dot(weight):
    """``rows @ weight`` as the forward writes it (a backward's products by
    the same weight contract its other axis)."""
    def want(eqn):
        if eqn.primitive.name != "dot_general":
            return False
        lhs, rhs = (v.aval for v in eqn.invars)
        return rhs.shape == weight and eqn.params["dimension_numbers"] == (
            ((lhs.ndim - 1,), (0,)), ((), ()))
    return want


def _top_k(eqn):
    return eqn.primitive.name == "top_k"


def _without_the_names(monkeypatch):
    """The parent's list: what the block checkpoint kept before PR 61."""
    monkeypatch.setattr(recurrent, "BLOCK_KEPT", ())
    monkeypatch.setattr(moe, "BLOCK_KEPT", ())


COUNTS = [  # (arch, what, times made: unchecked, without the names, with)
    ("kda+dense", _forward_dot(IN_PROJ["kda"]), 2, 2, 1),
    ("gdn+dense", _forward_dot(IN_PROJ["gdn"]), 2, 3, 1),
    ("mamba2", _forward_dot(IN_PROJ["mamba2"]), 2, 2, 1),
    ("kda+experts", _forward_dot(IN_PROJ["kda"]), 2, 2, 1),
    ("kda+experts", _top_k, 1, 2, 1),
    ("kda+experts", _forward_dot(ROUTER), 1, 2, 1),
    ("experts", _top_k, 1, 2, 1),
    ("experts", _forward_dot(ROUTER), 1, 2, 1),
    ("experts", _forward_dot(LATENT), 1, 2, 1),
    ("experts", _forward_dot(SHARED), 1, 2, 1),
]


@pytest.mark.parametrize("arch,what,unchecked,before,after", COUNTS, ids=[
    f"{arch}-{i}" for i, (arch, *_) in enumerate(COUNTS)])
def test_the_gradient_makes_a_kept_value_once(monkeypatch, arch, what,
                                              unchecked, before, after):
    """Under ``block_checkpoint`` the gradient's program makes a mixer's
    input projection, the router's scores and choice, the latent's rows and
    the shared expert's first product ONCE; without the names in the
    checkpoint's list (the program before PR 61) twice — the forward, and
    again in the backward. A mixer's own checkpoint makes its projection
    the second time, in a trunk without the key too; the checkpoint round
    the layer made it a THIRD time only where the FFN's input needs it (the
    delta rule's gate ``z`` is a slice of it; KDA's gate has a path of its
    own and the recurrence's output was kept already)."""
    assert _made(_grad_jaxpr(ARCHS[arch])[1], what) == unchecked
    checked = {**ARCHS[arch], "block_checkpoint": True}
    assert _made(_grad_jaxpr(checked)[1], what) == after
    _without_the_names(monkeypatch)
    assert _made(_grad_jaxpr(checked)[1], what) == before


def test_a_mixers_output_feeds_the_ffn_without_the_mixer(monkeypatch):
    """With the mixer's output kept, the checkpoint's backward reaches the
    FFN's input without the mixer's gate, norm and output projection: the
    output projection (``[H K, d]``) is made once, twice without."""
    out_proj = _forward_dot((24, D))
    checked = {**ARCHS["kda+dense"], "block_checkpoint": True}
    assert _made(_grad_jaxpr(checked)[1], out_proj) == 1
    _without_the_names(monkeypatch)
    assert _made(_grad_jaxpr(checked)[1], out_proj) == 2


# float32: ``tests/test_ouro_reference.py``'s tolerance for the same
# statement. bfloat16: a kept product is rounded as its dtype says (jax puts
# a ``reduce_precision`` on a checkpoint's residuals) where XLA's excess
# precision lets the unchecked program skip that rounding between a matmul
# and the float32 pass behind it: the two differ by bfloat16's rounding
# (measured: 1.6 % of a leaf's largest entry at most, the loss by 6e-4).
CLOSE = {"float32": (1e-6, 1e-5, 1e-7), "bfloat16": (5e-3, 0.0, 5e-2)}


@functools.cache
def _seeded(arch):
    """One tree an arch: the seed fixes it, whatever the trunk computes in
    and whether it checkpoints (bit for bit: the leaves are float32)."""
    return build_policy(ARCHS[arch]).init_params(jax.random.PRNGKey(0))


# (slow: bfloat16 is the same statement under a looser bound; tier-1 keeps
# each arch's float32 sibling, held to 1e-6 of the loss)
@pytest.mark.parametrize("precision", [
    pytest.param("bfloat16", marks=pytest.mark.slow), "float32"])
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_the_gradient_is_the_unchecked_trunks(arch, precision):
    """What is kept is the value the forward made: the loss and every
    gradient of the trunk under ``block_checkpoint`` are the unchecked
    trunk's."""
    got = {}
    for checked in (False, True):
        policy = build_policy({**ARCHS[arch], "precision": precision,
                               "block_checkpoint": checked})
        got[checked] = jax.jit(jax.value_and_grad(_loss(policy)))(
            _seeded(arch))
    loss_rel, rtol, atol_of_max = CLOSE[precision]
    assert float(got[True][0]) == pytest.approx(float(got[False][0]),
                                                rel=loss_rel)
    leaves = jax.tree_util.tree_leaves_with_path(got[True][1])
    plain = jax.tree_util.tree_leaves(got[False][1])
    assert any(float(jnp.abs(g).max()) > 0 for _, g in leaves)
    for (path, a), b in zip(leaves, plain):
        np.testing.assert_allclose(
            a, b, rtol=rtol, atol=atol_of_max * max(
                float(jnp.abs(b).max()), 1e-6 if rtol else 0.0),
            err_msg=jax.tree_util.keystr(path))


def _bytes(*shape, width=4):
    return int(np.prod(shape)) * width


RECORDS = {
    "kda+dense": {("kda", "dense"): {
        recurrent.MIXER_IN: _bytes(B, T, 72), "relayrl_kda_out":
        _bytes(B, T, 24), recurrent.MIXER_OUT: _bytes(B, T, D)}},
    # a mixer alone: nothing behind it reads its output, which has no name
    "mamba2": {("mamba2", "none"): {
        recurrent.MIXER_IN: _bytes(B, T, 100), "relayrl_ssd_out":
        _bytes(B, T, 32)}},
    "experts": {("none", "experts"): {
        moe.ROUTER_LOGITS: _bytes(B * T, 12), moe.ROUTER_CHOICE:
        _bytes(B * T, 3), moe.LATENT_ROWS: _bytes(B * T, 24),
        moe.SHARED_UP: _bytes(B * T, 40)}},
    "kda+experts": {("kda", "experts"): {
        recurrent.MIXER_IN: _bytes(B, T, 72), "relayrl_kda_out":
        _bytes(B, T, 24), recurrent.MIXER_OUT: _bytes(B, T, D),
        moe.ROUTER_LOGITS: _bytes(B * T, 12), moe.ROUTER_CHOICE:
        _bytes(B * T, 3), moe.SHARED_UP: _bytes(B * T, 40),
        moe.SHARED_GATE: _bytes(B * T, 40)}},
}


@pytest.mark.parametrize("arch", sorted(RECORDS))
def test_the_policy_records_what_the_checkpoint_keeps(capsys, arch):
    """``Policy.checkpoint_kept``: ``{(operator, FFN kind): {name: bytes}}``
    once a gradient was traced through the layer, and one ``[checkpoint]``
    line a kind with the names and their MB; a forward alone keeps nothing
    and a trunk without the key never says a line."""
    policy = build_policy({**ARCHS[arch], "block_checkpoint": True})
    params = policy.init_params(jax.random.PRNGKey(0))
    loss = _loss(policy)
    loss(params)
    assert policy.checkpoint_kept == {}
    assert "[checkpoint]" not in capsys.readouterr().out
    jax.make_jaxpr(jax.grad(loss))(params)
    assert policy.checkpoint_kept == RECORDS[arch]
    (kind, kept), = RECORDS[arch].items()
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("[checkpoint]")]
    assert len(lines) == 1
    assert lines[0].startswith(f"[checkpoint] {kind[0]}+{kind[1]} keeps")
    for name, size in kept.items():
        assert f"{name} {size / 1e6:.1f}" in lines[0]
    assert f"= {sum(kept.values()) / 1e6:.1f} MB by name" in lines[0]
    # said once: a second trace of the same shapes repeats nothing
    jax.make_jaxpr(jax.grad(loss))(params)
    assert "[checkpoint]" not in capsys.readouterr().out
    plain = build_policy(ARCHS[arch])
    jax.make_jaxpr(jax.grad(_loss(plain)))(params)
    assert plain.checkpoint_kept == {}
    assert "[checkpoint]" not in capsys.readouterr().out


def _lowered_grad(arch):
    """The gradient's lowered text, less the counters the lowering gives
    its private functions (``@silu_117``)."""
    policy = build_policy(arch)
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    return without_symbol_counters(
        jax.jit(jax.grad(_loss(policy))).lower(params).as_text())


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_the_names_are_nothing_to_a_trunk_without_the_key(monkeypatch, arch):
    """A ``checkpoint_name`` no policy lists lowers to nothing: the update
    of a trunk without ``block_checkpoint`` is, text for text, the one of a
    program that never named the values."""
    named = _lowered_grad(ARCHS[arch])
    new = recurrent.BLOCK_KEPT + moe.BLOCK_KEPT

    def not_these(x, name):
        return x if name in new else checkpoint_name(x, name)

    for module in (recurrent, kda, gdn, mamba2, moe):
        monkeypatch.setattr(module, "checkpoint_name", not_these)
    assert _lowered_grad(ARCHS[arch]) == named


OURO = {**BASE, "n_layers": 2, "positions": "rope", "use_bias": False,
        "ffn": "swiglu", "norm_sandwich": True, "loop_steps": 4,
        "block_checkpoint": True}


@pytest.mark.parametrize("arch", [OURO, {**OURO, "loop_steps": 1}],
                         ids=["looped", "written-out"])
def test_a_trunk_of_attention_and_dense_ffns_keeps_what_it_kept(
        monkeypatch, capsys, arch):
    """``ouro-policy``'s tiny form — attention and a dense FFN a layer,
    under the block checkpoint — has no mixer, router or shared expert: its
    update is the same text with the new names in the list and without, and
    its record names nothing (dense attention on this CPU: no flash
    kernel's output either)."""
    listed = _lowered_grad(arch)
    assert "[checkpoint]" not in capsys.readouterr().out
    _without_the_names(monkeypatch)
    assert _lowered_grad(arch) == listed

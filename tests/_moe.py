"""What the expert layer's test files share (``test_moe.py``: routing, the
sparse dispatch, the later layer forms; ``test_moe_held.py``: the layer that
is told which experts it holds; ``test_moe_walks.py``: that layer's two walks
against the dense form): the toy policy, the held layer and its parameters,
the stand-ins for the row buffer's length and for the rows the TPU kernels
never write, and two fixtures a test file imports to use."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relayrl_tpu.models import build_policy

ARCH = {"kind": "transformer_moe_discrete", "obs_dim": 6, "act_dim": 3,
        "d_model": 16, "n_layers": 2, "n_heads": 2, "max_seq_len": 8,
        "moe_experts": 4}
DISPATCHES = ("sparse", "dense")


def _policy_params(seed=0, **arch):
    policy = build_policy({**ARCH, **arch})
    return policy, policy.init_params(jax.random.PRNGKey(seed))


_N, _D, _FF = 24, 16, 8


def _held_layer(held, dispatch="sparse", e=8, k=2, bias=True):
    from relayrl_tpu.models.moe import MoEMLP

    return MoEMLP(_D, _FF, e, k, jnp.float32, norm_topk_prob=True,
                  ffn="swiglu", dispatch=dispatch, use_bias=False,
                  router="sigmoid", expert_bias=bias, held=held)


def _held_params(e=8, k=2, seed=0):
    """The whole layer's parameters (every expert held) and tokens."""
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, _N // 2, _D)), jnp.float32)
    params = _held_layer(None, e=e, k=k).init(jax.random.PRNGKey(seed), x)
    return jax.tree_util.tree_map(lambda a: a, params), x


def _share_of(params, first, count):
    """One chip's parameters of the whole layer's: its slice of the expert
    stacks; the router and its bias whole."""
    p = dict(params["params"])
    for name in ("moe_w_gate", "moe_w_up", "moe_w_down"):
        p[name] = p[name][first:first + count]
    return {"params": p}


def _row_buffer_of(monkeypatch, rows, n_slots, n_held, n_exp):
    """Make the held layer's buffers ``rows`` long at this shape — through
    the module's margin and row tile, as a router's imbalance would at the
    real ones (no arch key sets them)."""
    from relayrl_tpu.models import moe

    monkeypatch.setattr(moe, "_ROW_TILE", 1)
    monkeypatch.setattr(moe, "_ROW_MARGIN",
                        (rows - 0.5) * n_exp / (n_slots * n_held))
    assert moe.row_buffer(n_slots, n_held, n_exp) == rows


def _poison_unwritten_rows(monkeypatch):
    """The TPU kernels never write the rows past the last group, in the
    product and in its transpose to the rows (``d_lhs``), and never read
    them (the transpose to the stacks selects its groups' rows);
    ``ragged_dot`` zero-fills and multiplies by masks. This stand-in puts
    NaN where the kernels leave whatever was there: every read of such a
    row shows in the result."""
    from relayrl_tpu.models import moe

    plain = moe.grouped_matmul

    def written(rows, group_sizes):
        return (jnp.arange(rows.shape[0]) < group_sizes.sum())[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        return jnp.where(written(lhs, group_sizes),
                         plain(lhs, rhs, group_sizes), jnp.nan)

    def fwd(lhs, rhs, group_sizes):
        return poisoned(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(res, g):
        lhs, rhs, group_sizes = res
        live = written(lhs, group_sizes)
        _, transpose = jax.vjp(
            lambda a, b: plain(a, b, group_sizes),
            jnp.where(live, lhs, 0), rhs)
        d_lhs, d_rhs = transpose(jnp.where(live, g, 0).astype(lhs.dtype))
        return jnp.where(live, d_lhs, jnp.nan), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(moe, "grouped_matmul", poisoned)


@pytest.fixture(autouse=True)
def _fresh_expert_traces():
    """The held layers of a process share one trace of their experts a
    shape (``moe._shared_experts``): a test that stands something in for
    what that trace calls must start, and leave, with none."""
    from relayrl_tpu.models import moe

    def clear():
        moe._shared_experts.clear_cache()
        moe._shared_experts_vjp.clear_cache()

    clear()
    yield
    clear()


@pytest.fixture(params=["sorted", "counted"])
def walk(request, monkeypatch):
    """Both walks of a held layer, each FORCED whatever the shapes' rule
    (``moe.held_form``) would pick: the sort of all N k slots with the
    absent experts' behind, and the rows counted into expert order."""
    from relayrl_tpu.models import moe

    monkeypatch.setattr(moe, "held_form", lambda *shape: request.param)
    return request.param


def _impala_update_of(policy):
    """``(update, state of shapes)``: IMPALA's update for ``policy`` as the
    learner builds it, and a state to lower or (given real parameters) run
    it with."""
    from relayrl_tpu.algorithms.impala import (
        ImpalaState, make_impala_tx, make_impala_update)

    tx = make_impala_tx(1e-4, 1.0)

    def state_of(params):
        return ImpalaState(params=params, opt_state=tx.init(params),
                           rng=jax.random.PRNGKey(0), step=jnp.int32(0))

    update = make_impala_update(
        policy, lr=1e-4, gamma=0.99, vf_coef=0.5, ent_coef=0.01,
        rho_bar=1.0, c_bar=1.0, max_grad_norm=1.0)
    return update, state_of

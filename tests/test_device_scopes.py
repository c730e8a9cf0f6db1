"""The update names its parts on the device: every ``jax.named_scope`` of
``relayrl_tpu/ops/scopes.py`` reaches the instructions it is written round,
forward and backward, and changes nothing else.

IMPALA's update is lowered and compiled (XLA:CPU) at tiny sizes for each
model family the benchmark runs. An instruction's ``op_name`` is the scope
path it was traced under; a transform wraps the outermost scope entered
under it (``transpose(jvp(relayrl_ffn))/...``), so a backward instruction is
one whose path holds ``transpose(``. The flash and grouped-matmul kernels
lower on a TPU only: their names and the glue round them are checked where
they compile, in ``tests/test_flash_tpu_compile.py``.
"""

import contextlib
import re
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from relayrl_tpu.algorithms.impala import (
    ImpalaState,
    make_impala_tx,
    make_impala_update,
)
from relayrl_tpu.data.batching import TrajectoryBatch
from relayrl_tpu.models import build_policy, moe
from relayrl_tpu.ops import scopes
from relayrl_tpu.ops.scopes import (
    CONV,
    DEVICE_SCOPES,
    EMBED,
    FFN,
    GDN_CONV_NAME,
    GDN_NAME,
    HEADS,
    INDEX,
    KDA_CONV_NAME,
    KDA_NAME,
    LATENT_ROPE,
    LOSS,
    MAMBA_CONV_NAME,
    MOE_ELEMENTWISE,
    MOE_LATENT,
    MOE_ROUTE,
    MOE_ROWS,
    OBS_PREP,
    OP_PROJ,
    OPTIMIZER,
    SHORT_CONV_NAME,
    SPARSE_ATTN,
    SSD_NAME,
    VTRACE,
)

REPO = Path(__file__).resolve().parent.parent
SEQ = {"obs_dim": 6, "act_dim": 3, "d_model": 16, "n_heads": 2,
       "max_seq_len": 8}
EVERY_UPDATE = (OPTIMIZER, VTRACE, LOSS, HEADS)
TRUNK = EVERY_UPDATE + (EMBED, OP_PROJ)
MOE = (MOE_ROUTE, MOE_ROWS, MOE_ELEMENTWISE)
# plain XLA operators that keep a name of their own, as the kernels do
OWN_NAMES = (SHORT_CONV_NAME, SSD_NAME, MAMBA_CONV_NAME, GDN_NAME,
             GDN_CONV_NAME, KDA_NAME, KDA_CONV_NAME)
# family -> (arch, the scopes its update uses, observation width)
FAMILIES = {
    # the GPT-2 shaped block (gpt2m-policy)
    "gpt2": ({**SEQ, "kind": "transformer_discrete", "n_layers": 2},
             TRUNK + (FFN,)),
    # every expert held: the plain sparse dispatch (olmoe-policy)
    "moe": ({**SEQ, "kind": "transformer_moe_discrete", "n_layers": 1,
             "moe_experts": 4, "moe_top_k": 2, "norm": "rms",
             "positions": "rope", "qk_norm": True, "use_bias": False,
             "ffn": "swiglu", "moe_norm_topk_prob": False},
            TRUNK + MOE),
    # a share of the experts held, a conv layer with a dense FFN, grouped
    # heads, a windowed layer (lfm2-policy, smallthinker-policy)
    "moe_held": ({**SEQ, "kind": "transformer_moe_discrete", "n_layers": 3,
                  "n_heads": 4, "n_kv_heads": 2, "moe_experts": 8,
                  "moe_top_k": 2, "moe_held": [2, 4], "moe_dense_layers": 1,
                  "moe_router": "sigmoid", "moe_expert_bias": True,
                  "layer_types": ["conv", "full_attention",
                                  "sliding_attention"],
                  "sliding_window": 4, "norm": "rms", "positions": "rope",
                  "qk_norm": "head", "use_bias": False, "ffn": "swiglu",
                  "moe_router_input": "layer"},
                 TRUNK + MOE + (FFN, SHORT_CONV_NAME)),
    # layers of one part each: Mamba-2 (a scan over two chunks), attention
    # alone, relu2 experts beside a shared expert (nemotron-twotower-policy)
    "one_part": ({**SEQ, "kind": "transformer_moe_discrete", "n_layers": 4,
                  "n_heads": 4, "n_kv_heads": 1, "head_dim": 8,
                  "layer_types": ["mamba2", "ffn", "attention", "ffn"],
                  "mamba_heads": 4, "mamba_head_dim": 8, "mamba_state": 8,
                  "mamba_groups": 2, "mamba_chunk": 4, "moe_experts": 8,
                  "moe_top_k": 2, "moe_held": [2, 4], "moe_d_ff": 12,
                  "moe_router": "sigmoid", "moe_expert_bias": True,
                  "moe_routed_scaling": 2.5, "moe_shared_d_ff": 24,
                  "norm": "rms", "positions": "none", "use_bias": False,
                  "ffn": "relu2"},
                 TRUNK + MOE + (FFN, SSD_NAME, MAMBA_CONV_NAME)),
    # experts in a latent narrower than the stream beside a shared expert
    # at the stream's width, under the block checkpoint
    # (nemotron3-super-policy)
    "latent_experts": ({**SEQ, "kind": "transformer_moe_discrete",
                        "n_layers": 3, "n_heads": 4, "n_kv_heads": 1,
                        "head_dim": 8,
                        "layer_types": ["ffn", "mamba2", "attention"],
                        "block_checkpoint": True,
                        "mamba_heads": 4, "mamba_head_dim": 8,
                        "mamba_state": 8, "mamba_groups": 2,
                        "mamba_chunk": 4, "moe_experts": 8, "moe_top_k": 3,
                        "moe_held": [2, 4], "moe_d_ff": 12, "moe_latent": 8,
                        "moe_router": "sigmoid", "moe_expert_bias": True,
                        "moe_routed_scaling": 5.0, "moe_shared_d_ff": 24,
                        "norm": "rms", "positions": "none",
                        "use_bias": False, "ffn": "relu2"},
                       TRUNK + MOE + (MOE_LATENT, FFN, SSD_NAME,
                                      MAMBA_CONV_NAME)),
    # linear-attention layers (a delta rule over two chunks) beside a gated
    # attention layer with a partial rotary and zero-centred norms, a gated
    # shared expert (qwen3next-policy)
    "linear": ({**SEQ, "kind": "transformer_moe_discrete", "n_layers": 2,
                "n_heads": 4, "n_kv_heads": 1, "head_dim": 8,
                "layer_types": ["linear_attention", "full_attention"],
                "gdn_key_heads": 2, "gdn_value_heads": 4, "gdn_key_dim": 8,
                "gdn_value_dim": 8, "gdn_chunk": 4, "moe_experts": 8,
                "moe_top_k": 3, "moe_held": [2, 4], "moe_d_ff": 12,
                "moe_shared_d_ff": 12, "moe_shared_expert_gate": True,
                "norm": "rms", "norm_zero_centred": True,
                "positions": "rope", "rope_share": 0.5, "qk_norm": "head",
                "attn_gate": True, "use_bias": False, "ffn": "swiglu"},
               TRUNK + MOE + (FFN, GDN_NAME, GDN_CONV_NAME)),
    # a delta rule under a decay a key lane (two chunks) with the dense FFN,
    # then latent attention (q / k 6 wide, v 4) with sigmoid-routed experts
    # beside a shared expert, top-8: the held layer counts its rows
    # (kimi-linear-policy)
    "latent": ({**SEQ, "kind": "transformer_moe_discrete", "n_layers": 2,
                "n_heads": 4, "layer_types": ["kda", "latent_attention"],
                "kda_heads": 2, "kda_head_dim": 8, "kda_chunk": 4,
                "kv_lora_rank": 8, "qk_nope_head_dim": 4,
                "qk_rope_head_dim": 2, "v_head_dim": 4,
                "moe_dense_layers": 1, "moe_experts": 16, "moe_top_k": 8,
                "moe_held": [2, 4], "moe_d_ff": 12, "moe_shared_d_ff": 12,
                "moe_router": "sigmoid", "moe_expert_bias": True,
                "moe_routed_scaling": 2.446, "norm": "rms",
                "positions": "none", "use_bias": False, "ffn": "swiglu"},
               TRUNK + MOE + (FFN, KDA_NAME, KDA_CONV_NAME)),
    # latent attention on every layer, a low-rank query path, the shared key
    # lanes and the queries' matching lanes rotated in interleaved pairs,
    # under the block checkpoint (joyai-flash-policy)
    "latent_rope": ({**SEQ, "kind": "transformer_moe_discrete",
                     "n_layers": 2, "n_heads": 4,
                     "layer_types": ["latent_attention"] * 2,
                     "block_checkpoint": True, "q_lora_rank": 12,
                     "kv_lora_rank": 8, "qk_nope_head_dim": 4,
                     "qk_rope_head_dim": 4, "v_head_dim": 4,
                     "rope_interleave": True, "moe_dense_layers": 1,
                     "moe_experts": 16, "moe_top_k": 8, "moe_held": [2, 4],
                     "moe_d_ff": 12, "moe_shared_d_ff": 12,
                     "moe_router": "sigmoid", "moe_expert_bias": True,
                     "moe_routed_scaling": 2.5, "norm": "rms",
                     "positions": "rope", "rope_theta": 100.0,
                     "use_bias": False, "ffn": "swiglu"},
                    TRUNK + MOE + (FFN, LATENT_ROPE)),
    # attention over the keys an indexer picks, 2 of up to 8, in two tiles;
    # the indexers' own loss under the loss's name; top-8 experts, the held
    # layer counting its rows (keye-vl2-policy)
    "sparse": ({**SEQ, "kind": "transformer_moe_discrete", "n_layers": 2,
                "n_heads": 4, "n_kv_heads": 2, "head_dim": 8,
                "layer_types": ["sparse_attention"] * 2, "index_heads": 2,
                "index_head_dim": 8, "index_topk": 2, "index_chunk": 4,
                "moe_experts": 16, "moe_top_k": 8, "moe_held": [2, 4],
                "moe_d_ff": 12, "norm": "rms", "positions": "rope",
                "qk_norm": "head", "use_bias": False, "ffn": "swiglu"},
               TRUNK + MOE + (INDEX, SPARSE_ATTN)),
    # the stack run three times over one tree under the block checkpoint,
    # sandwich norms (ouro-policy): a pass scope OUTSIDE the parts
    "looped": ({**SEQ, "kind": "transformer_discrete", "n_layers": 2,
                "loop_steps": 3, "block_checkpoint": True,
                "norm_sandwich": True, "norm": "rms", "positions": "rope",
                "use_bias": False, "ffn": "swiglu"},
               TRUNK + (FFN,)),
    # the pixel learner (nature-cnn)
    "cnn": ({"kind": "cnn_discrete", "obs_shape": [36, 36, 2],
             "obs_dim": 36 * 36 * 2, "act_dim": 3},
            EVERY_UPDATE + (OBS_PREP, CONV)),
}
# parts nothing is differentiated through: no parameter lies before the
# frames' way in, V-trace reads stopped gradients, the optimizer comes after
NO_BACKWARD = (OPTIMIZER, VTRACE, OBS_PREP)
# Share of a compiled update's instructions that carry an ``op_name`` (XLA's
# own expansions carry none) under a relayrl_ name, at least. Read 0.93-0.99
# over the six families: what is left is the attention itself (XLA
# operations here, a kernel of its own name on the chip), the MoE load
# statistics and the step counter.
SCOPED_FLOOR = 0.9
TRIVIAL = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "copy", "while", "call", "conditional")


def _lower(family: str):
    arch, _ = FAMILIES[family]
    policy = build_policy({**arch, "has_critic": True})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    tx = make_impala_tx(1e-4, 1.0)
    state = ImpalaState(params=params,
                        opt_state=jax.eval_shape(tx.init, params),
                        rng=jax.ShapeDtypeStruct((2,), jnp.uint32),
                        step=jax.ShapeDtypeStruct((), jnp.int32))
    update = make_impala_update(policy, lr=1e-4, gamma=0.99, vf_coef=0.5,
                                ent_coef=0.01, rho_bar=1.0, c_bar=1.0,
                                max_grad_norm=1.0)
    batch = TrajectoryBatch.zeros(2, 8, arch["obs_dim"], arch["act_dim"],
                                  True)
    # 16 tokens: under the kernels' row tile of 512 a held layer's buffers
    # would be N k rows long and every such layer would sort; a tile of 8
    # gives them the cells' proportions (R < N k), so that top-8 counts
    with mock.patch.object(moe, "_ROW_TILE", 8):
        return jax.jit(update, donate_argnums=0).lower(state, batch)


def _once(make):
    """``make(family)``, kept: a family's update is lowered once a module."""
    cache: dict = {}

    def of(family: str):
        if family not in cache:
            cache[family] = make(family)
        return cache[family]

    return of


@pytest.fixture(scope="module")
def lowered():
    return _once(_lower)


@pytest.fixture(scope="module")
def compiled(lowered):
    """family -> the text of the update compiled for XLA:CPU, where the
    inner ``jit`` calls are inlined and their paths composed."""
    return _once(lambda family: lowered(family).compile().as_text())


@pytest.fixture(scope="module")
def paths(lowered, compiled):
    """family -> the ``op_name`` paths of the lowered update's operations
    (the named locations of its text with debug info) and, for the family
    whose tiles run inside a loop's body (the text names a body's
    operations from the body's own start), of the compiled update's."""
    def of(family):
        found = set(re.findall(r'loc\("(jit\([^"]*)"',
                               lowered(family).as_text(debug_info=True)))
        if family in ("sparse", "looped"):
            found |= set(re.findall(r'op_name="([^"]*)"', compiled(family)))
        return sorted(found)

    return _once(of)


def _part_names(path: str) -> set:
    return {name for name in DEVICE_SCOPES
            if re.search(rf"{name}(?!\w)", path)}


USES = [(family, scope) for family, (_arch, used) in FAMILIES.items()
        for scope in used]


def test_one_list_of_names():
    """Every name a ``with`` line can open is a constant of the one module,
    and the lists hold each once."""
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES) == 16
    assert not set(DEVICE_SCOPES) & set(scopes.KERNEL_SCOPES)
    used = {scope for _family, scope in USES}
    assert used == set(DEVICE_SCOPES) | set(OWN_NAMES)
    program = "".join(p.read_text() for p in (REPO / "relayrl_tpu").rglob(
        "*.py") if p.name != "scopes.py")
    # no scope is spelled out where it is opened
    assert re.findall(r'named_scope\(\s*["\']', program) == []


@pytest.mark.parametrize("family,scope", USES)
def test_scope_reaches_forward_and_backward(paths, family, scope):
    mine = [p for p in paths(family) if scope in _part_names(p)
            or (scope in OWN_NAMES and scope in p)]
    forward = [p for p in mine if "transpose(" not in p]
    backward = [p for p in mine if "transpose(" in p]
    assert forward, f"{scope} is on no forward operation of {family}"
    if scope in NO_BACKWARD:
        # jvp( stays: V-trace's scope is entered under value_and_grad
        assert backward == [], backward[:3]
        if scope == OPTIMIZER:
            assert not [p for p in mine if "jvp(" in p]
    else:
        assert backward, f"{scope} is on no backward operation of {family}"


@pytest.mark.parametrize("family", FAMILIES)
def test_no_other_part_in_this_family(paths, family):
    found = set().union(*(_part_names(p) for p in paths(family)))
    assert found == set(FAMILIES[family][1]) - set(OWN_NAMES)


@pytest.mark.parametrize("family", FAMILIES)
def test_parts_are_siblings(paths, family):
    """No operation's path holds two parts, nor one part twice."""
    for path in paths(family):
        assert len(_part_names(path)) <= 1, path
        for name in _part_names(path):
            assert len(re.findall(rf"{name}(?!\w)", path)) == 1, path


def test_a_pass_is_named_outside_the_parts(paths):
    """The one scope that is no sibling: ``relayrl_loop_pass`` is the
    outermost ``relayrl_`` name of every operation of a pass (the learner's
    passes are one body of a scan, so the name has no number), the part
    stays the innermost (what ``scope_table`` counts an operation for), and
    a trunk that runs its stack once opens none."""
    looped = [p for p in paths("looped") if scopes.LOOP_PASS in p]
    for path in looped:
        names = re.findall(r"relayrl_\w+", path)
        assert names[0] == scopes.LOOP_PASS, path
    blocks = [p for p in looped if re.search(r"/block_\d", p)]
    assert {scope for p in blocks for scope in _part_names(p)} == {
        OP_PROJ, FFN}
    # the blocks, the final norm between passes, and the loop's own glue
    # (the scan's carry, the checkpoint's call, the sum of a tied weight's
    # gradients): nothing else is under a pass without a part
    rest = [p for p in looped if not re.search(r"/block_\d", p)]
    assert any(_part_names(p) == {HEADS} for p in rest)
    assert not [p for p in rest if _part_names(p) - {HEADS}]
    # every block operation of the update is inside the pass, forward and
    # transposed: one body, run loop_steps times
    assert not [p for p in paths("looped") if re.search(r"/block_\d", p)
                and scopes.LOOP_PASS not in p]
    assert [p for p in looped if "transpose(" in p]
    for family in FAMILIES:
        if family != "looped":
            assert not [p for p in paths(family) if scopes.LOOP_PASS in p]


def test_the_learners_passes_are_one_body(compiled):
    """32 block applications written out are 0.8 GB of executable at the
    benchmark's looped configuration: the update holds each block's
    matmuls ONCE forward (and once transposed), whatever ``loop_steps``."""
    text = compiled("looped")
    up = re.findall(r'op_name="[^"]*block_0/relayrl_ffn/mlp_up/dot_general"',
                    text)
    forward = [p for p in up if "transpose(" not in p]
    assert len({p for p in forward if "rematted" not in p}) == 1
    assert scopes.LOOP_PASS in forward[0] and "while" in forward[0]


def test_the_checkpoints_second_forward_is_told_apart(paths):
    """What ``loop_recompute_pct`` reads: ``jax.checkpoint`` names the
    forward it runs again in the backward ``rematted_computation``; the
    first forward's paths hold no ``checkpoint`` and the backward proper's
    hold it without that name. All three carry the pass and the part."""
    from benchmark import loop_trace

    assert loop_trace.RECOMPUTED == "rematted_computation"
    assert loop_trace.PASS == scopes.LOOP_PASS
    in_blocks = [p for p in paths("looped") if re.search(r"/block_\d", p)]
    again = [p for p in in_blocks if loop_trace.RECOMPUTED in p]
    first = [p for p in in_blocks if "checkpoint" not in p]
    back = [p for p in in_blocks
            if "checkpoint/" in p and loop_trace.RECOMPUTED not in p]
    assert again and first and back
    # (a loop body's operations are named from the body's own start in the
    # compiled text: the whole path is the lowered text's)
    whole = [p for p in again + back if p.startswith("jit(")]
    assert whole and all("transpose(" in p for p in whole)
    assert not [p for p in first if "transpose(" in p]
    for kind in (again, first, back):
        assert {scope for p in kind for scope in _part_names(p)} >= {
            OP_PROJ, FFN}
        assert all(loop_trace.PASS in p for p in kind)
    # a trunk without the key is not checkpointed a block
    assert not [p for p in paths("gpt2") if loop_trace.RECOMPUTED in p]


def test_custom_vjp_backwards_carry_their_parts(paths, compiled):
    """The rules that are traced on their own: the plain dispatch's two
    gathers take the scope round their CALLS, the held layer's loops open
    theirs in both rules."""
    def backward(family, scope, what):
        return [p for p in paths(family) if "transpose(" in p
                and scope in _part_names(p) and what in p]

    assert backward("moe", MOE_ROWS, "_take")    # the gather, a call here
    for scope, what in ((MOE_ROUTE, "cumsum"), (MOE_ROWS, "gather"),
                        (MOE_ELEMENTWISE, "dynamic_update_slice")):
        assert backward("moe_held", scope, what), (scope, what)
    # ... the walk that sorts; and the one that counts (top-8): a pass's
    # rows found by compares, gathered, their weight gradients added
    for scope, what in ((MOE_ROUTE, "cumsum"), (MOE_ROUTE, "le"),
                        (MOE_ROWS, "gather"), (MOE_ROWS, "scatter-add"),
                        (MOE_ELEMENTWISE, "_where")):
        assert backward("latent", scope, what), (scope, what)
    # ... and a held pass's experts sit inside the element-wise part, the
    # vjp's own wrapper absorbed by the name made for it
    assert re.search(rf'op_name="[^"]*{MOE_ELEMENTWISE}/[^"]*'
                     rf'jvp\({scopes.HELD_EXPERTS_NAME}\)/', compiled("moe_held"))


def _under(jaxpr, scope: str):
    """Every equation of ``jaxpr``, and of the jaxprs its equations hold,
    traced under ``scope``."""
    for eqn in jaxpr.eqns:
        inner = [v for v in eqn.params.values()
                 if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        for sub in inner:
            yield from _under(getattr(sub, "jaxpr", sub), scope)
        if not inner and scope in str(eqn.source_info.name_stack):
            yield eqn


def _walked(eqns):
    """``(the largest value an equation reads or writes, in elements; the
    lane gathers: ``gather`` and strided ``slice`` equations)``."""
    largest, gathers = 0, []
    for eqn in eqns:
        sizes = [v.aval.size for v in (*eqn.invars, *eqn.outvars)
                 if hasattr(v.aval, "size")]
        largest = max([largest, *sizes])
        strided = eqn.primitive.name == "slice" and any(
            s != 1 for s in eqn.params["strides"] or ())
        if eqn.primitive.name == "gather" or strided:
            gathers.append((eqn.primitive.name, max(sizes)))
    return largest, gathers


def test_the_rotation_walks_the_rotary_lanes_alone():
    """In the update of the tiny rotary latent trunk — forward, the block
    checkpoint's second forward and the backward — nothing under
    ``relayrl_latent_rope`` reads or writes a value wider than half of q's
    rotary lanes (2 x 8 rows of 4 heads of 4 lanes, where q whole is 4 + 4 a
    head), and no ``gather`` or strided ``slice`` is there: the pairing was
    taken on the weights' columns. The walk is held to the rotation as it was
    written before (``test_joyai_flash_reference._row_form``), which fails
    both."""
    from test_joyai_flash_reference import _row_form

    arch, _ = FAMILIES["latent_rope"]
    heads, pe, nope = (arch["n_heads"], arch["qk_rope_head_dim"],
                       arch["qk_nope_head_dim"])
    lanes = 2 * 8 * heads * pe
    policy = build_policy({**arch, "has_critic": True})
    params = jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    obs = jnp.zeros((2, 8, arch["obs_dim"]), jnp.float32)
    act = jnp.zeros((2, 8), jnp.int32)

    def loss(p):
        logp, _ent, v = policy.evaluate(p, obs, act)
        return jnp.sum(logp) + jnp.sum(v)

    with mock.patch.object(moe, "_ROW_TILE", 8):
        traced = jax.make_jaxpr(jax.grad(loss))(params)
    mine = list(_under(traced.jaxpr, LATENT_ROPE))
    names = {str(eqn.source_info.name_stack) for eqn in mine}
    # the forward, the checkpoint's second forward, and (a jaxpr names a
    # transposed equation by its scopes alone) the backward
    for making in ("jvp(TransformerCore)/", "rematted_computation/", ""):
        assert f"{making}block_1/{LATENT_ROPE}" in names, making
    largest, gathers = _walked(mine)
    assert gathers == []
    # the first (or the second) of q's rotated pairs, never one array with
    # the other; k's are a head's
    assert largest == lanes // 2
    assert dict(policy.latent_rope) == {
        ("latent_attention", "dense"): "columns",
        ("latent_attention", "experts"): "columns"}

    # the same walk over every row's lanes, as it was
    cfg = {"rope_theta": 100.0, "qk_rope_head_dim": pe,
           "rope_interleave": True}

    def rows_form(q):
        with jax.named_scope(LATENT_ROPE):
            return jnp.sum(_row_form(cfg, q, 0))

    q = jnp.zeros((2, 8, heads, nope + pe), jnp.float32)
    was = list(_under(jax.make_jaxpr(jax.grad(rows_form))(q).jaxpr,
                      LATENT_ROPE))
    largest, gathers = _walked(was)
    assert largest == q.size == 2 * lanes
    assert ("gather", lanes) in gathers     # the stride-2 lane pick


# sha256 of the tiny Kimi-Linear-shaped update ("latent": a KDA layer, then a
# latent layer that rotates nothing, held experts) as the parent of PR 63
# lowered it, less the counters of the lowering's private functions: the
# latent layer takes its columns apart only where lanes turn
_NO_LANE_TURNS_AS_IT_WAS = (
    "edc4d9c62b65c9249a4ea02d10b4e180d5ab6dd3346748b3fcbc1edf3522c2cc")


def test_a_latent_layer_that_rotates_nothing_lowers_the_update_it_was(
        lowered):
    """``kimi-linear-policy``'s shape: whole projections, the code path as
    it was, text for text — and no record of a rotation. An edit that
    changes this family's program on purpose pins the hash it then reads."""
    import hashlib

    from _util import without_symbol_counters

    text = without_symbol_counters(lowered("latent").as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == (
        _NO_LANE_TURNS_AS_IT_WAS)
    arch, _ = FAMILIES["latent"]
    policy = build_policy({**arch, "has_critic": True})
    jax.eval_shape(policy.init_params, jax.random.PRNGKey(0))
    assert policy.latent_rope == {}
    assert policy.attention_backends       # the latent layer was traced


@pytest.mark.parametrize("family", FAMILIES)
def test_compiled_update_is_scoped(compiled, family):
    scoped = total = 0
    for line in compiled(family).splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*? ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if m and name and m.group(1) not in TRIVIAL:
            total += 1
            scoped += "relayrl_" in name.group(1)
    assert total > 50
    assert scoped / total >= SCOPED_FLOOR, (scoped, total)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_device_program_is_the_same_without_the_scopes(lowered, family,
                                                           monkeypatch):
    """A scope is metadata: the lowered text without locations is equal,
    byte for byte, with ``jax.named_scope`` patched away."""
    named = lowered(family).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower(family)
    with_names = bare.as_text(debug_info=True)
    assert not [name for name in DEVICE_SCOPES if name in with_names]
    assert bare.as_text() == named
    assert "relayrl_" not in named      # no name leaks into the program


def _lower_scan_kernels():
    """The scan's Pallas kernels (what ``one_part``'s Mamba-2 layers run at
    shapes that tile, on a TPU), lowered through the interpreter: forward
    and the ``custom_vjp``'s backward, every gradient."""
    from relayrl_tpu.ops import ssd_pallas

    for cached in (ssd_pallas._build, ssd_pallas._make_scan):
        cached.cache_clear()        # a call is named where it is built
    b, T, H, P, G, N = 1, 256, 8, 64, 1, 128
    S = jax.ShapeDtypeStruct
    args = [S(shape, jnp.float32) for shape in (
        (b, T, H, P), (b, T, H), (H,), (b, T, G, N), (b, T, G, N), (H,),
        (b, H, P, N))]

    def loss(*a):
        y, last = ssd_pallas.ssd_pallas(*a[:6], state=a[6], interpret=True)
        return jnp.sum(y) + jnp.sum(last)

    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)))).lower(
        *args)


def test_the_scan_kernels_count_for_the_scans_name(monkeypatch):
    """Every kernel call's innermost ``relayrl_`` name is ``relayrl_ssd``
    — ``ssd_ms`` reads the exact scope, so a kernel named ``relayrl_ssd_fwd``
    would leave it the glue alone — in the forward and in the backward
    rule, which opens the scope itself; and the names are metadata."""
    from relayrl_tpu.ops import ssd_pallas

    named = _lower_scan_kernels()
    paths = set(re.findall(r'loc\("(jit\([^"]*)"',
                           named.as_text(debug_info=True)))
    for kernel, backward in ((ssd_pallas.FWD_NAME, False),
                             (ssd_pallas.STATES_NAME, True),
                             (ssd_pallas.BWD_NAME, True)):
        mine = [p for p in paths if re.search(rf"/{kernel}(/|$)", p)]
        assert mine, kernel
        for path in mine:
            assert re.findall(r"relayrl_\w+", path)[-1] == SSD_NAME, path
            assert ("transpose(" in path) == backward, path
    assert not [p for p in paths if "relayrl_flash_" in p]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_scan_kernels()
    assert SSD_NAME not in bare.as_text(debug_info=True)
    assert bare.as_text() == named.as_text()


def _lower_rule_kernels():
    """The delta rule's Pallas kernels (what ``one_part``'s linear-attention
    layers run at shapes that tile, on a TPU), lowered through the
    interpreter: forward and the ``custom_vjp``'s backward, every
    gradient."""
    from relayrl_tpu.ops import gdn_pallas

    for cached in (gdn_pallas._build, gdn_pallas._make_rule):
        cached.cache_clear()        # a call is named where it is built
    b, T, Hk, H, K = 1, 128, 4, 8, 128
    S = jax.ShapeDtypeStruct
    args = [S(shape, jnp.float32) for shape in (
        (b, T, Hk, K), (b, T, Hk, K), (b, T, H, K), (b, T, H), (b, T, H),
        (b, H, K, K))]

    def loss(*a):
        o, last = gdn_pallas.gdn_pallas(*a[:5], state=a[5], interpret=True)
        return jnp.sum(o) + jnp.sum(last)

    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
        *args)


def test_the_rule_kernels_count_for_the_rules_name(monkeypatch):
    """Every kernel call's innermost ``relayrl_`` name is ``relayrl_gdn``
    — ``gdn_ms`` reads the exact scope — in the forward and in the backward
    rule, which opens the scope itself; and the names are metadata."""
    from relayrl_tpu.ops import gdn_pallas

    named = _lower_rule_kernels()
    paths = set(re.findall(r'loc\("(jit\([^"]*)"',
                           named.as_text(debug_info=True)))
    for kernel, backward in ((gdn_pallas.FWD_NAME, False),
                             (gdn_pallas.STATES_NAME, True),
                             (gdn_pallas.BWD_NAME, True)):
        mine = [p for p in paths if re.search(rf"/{kernel}(/|$)", p)]
        assert mine, kernel
        for path in mine:
            assert re.findall(r"relayrl_\w+", path)[-1] == scopes.GDN_NAME, (
                path)
            assert ("transpose(" in path) == backward, path
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_rule_kernels()
    assert scopes.GDN_NAME not in bare.as_text(debug_info=True)
    assert bare.as_text() == named.as_text()


def _lower_sparse_attention_kernels():
    """The sparse attention's Pallas kernels (what a ``sparse_attention``
    layer's tiles run at shapes that tile, on a TPU), lowered through the
    interpreter: forward, ``p^`` and the ``custom_vjp``'s backward."""
    from relayrl_tpu.ops import sparse_attn_pallas

    for cached in (sparse_attn_pallas._build, sparse_attn_pallas._make_rule):
        cached.cache_clear()        # a call is named where it is built
    S = jax.ShapeDtypeStruct
    args = [S((128, 4, 128), jnp.float32), S((256, 2, 128), jnp.float32),
            S((256, 2, 128), jnp.float32)]
    pos = 128 + jnp.arange(128)
    keep = pos[:, None] >= jnp.arange(256)[None, :]

    def loss(q, k, v):
        with jax.named_scope(scopes.SPARSE_ATTN):   # as ``sparse_rows`` does
            out, p_hat, _ = sparse_attn_pallas.masked_attention_pallas(
                q, k, v, keep, pos, True, interpret=True)
        return jnp.sum(out) + jnp.sum(p_hat)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args)


def test_the_sparse_attention_kernels_count_for_the_scopes_name(monkeypatch):
    """Every kernel call's innermost ``relayrl_`` name is
    ``relayrl_sparse_attn`` — ``sparse_attn_ms`` reads the exact scope, so
    the kernels' own names carry no such prefix (and nothing of
    ``relayrl_flash_``, which the flash readers match anywhere) — in the
    forward and in the backward rule, which opens the scope itself; and the
    names are metadata."""
    from relayrl_tpu.ops import sparse_attn_pallas

    named = _lower_sparse_attention_kernels()
    paths = set(re.findall(r'loc\("(jit\([^"]*)"',
                           named.as_text(debug_info=True)))
    for kernel, backward in ((sparse_attn_pallas.FWD_NAME, False),
                             (sparse_attn_pallas.PHAT_NAME, False),
                             (sparse_attn_pallas.BWD_NAME, True)):
        assert not kernel.startswith("relayrl_")
        mine = [p for p in paths if re.search(rf"/{kernel}(/|$)", p)]
        assert mine, kernel
        for path in mine:
            assert set(re.findall(r"relayrl_\w+", path)) == {
                scopes.SPARSE_ATTN}, path
            assert ("transpose(" in path) == backward, path
    assert not [p for p in paths if "relayrl_flash_" in p]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _lower_sparse_attention_kernels()
    assert scopes.SPARSE_ATTN not in bare.as_text(debug_info=True)
    assert bare.as_text() == named.as_text()


def test_the_doc_names_every_scope():
    """``docs/observability.md``, "Device names": every part and every
    kernel name of the one list, each beside what reads it."""
    doc = (REPO / "docs" / "observability.md").read_text()
    section = doc[doc.index("## Device names"):]
    missing = [name for name in DEVICE_SCOPES + scopes.KERNEL_SCOPES
               if f"`{name}`" not in section]
    assert missing == []

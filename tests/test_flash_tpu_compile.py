"""The flash kernels at the benchmark's widths, compiled for a DESCRIBED
v5e chip: no chip attached, nothing runs (on-chip-measurement guide,
rehearsal 3). What the chip's compiler refuses — a slice off the tiling,
more VMEM than a kernel may hold — fails here and costs no chip time. A
compile that passes is not a chip run: ``chip_smoke.py`` phase B" runs them.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file. Keep these tests in this one file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from _util import without_symbol_counters

from relayrl_tpu.ops import flash, scopes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,sub,kv_heads,layout,window", [
    # gpt2m-policy.update: one block a head
    ((8, 1024, 16, 64), 256, 16, 2, None),
    ((4, 4096, 16, 128), 256, 16, None, None),  # olmoe-policy.update: 4 x 4
    ((1, 1000, 2, 64), None, 2, 2, None),   # a default bucket: one tile a step
    ((2, 8192, 32, 64), 256, 8, 2, None),   # lfm2-policy.update: 4 q heads a k/v
    ((1, 1024, 4, 64), 256, 1, None, None),  # one k/v head: half a lane block
    # smallthinker-policy.update: 7 q heads a k/v head, its global layer...
    ((1, 16384, 28, 128), 256, 4, None, None),
    ((1, 16384, 28, 128), 256, 4, None, 4096),  # ...and the band: 5 of 16
    ((1, 16384, 28, 128), 256, 4, None, 1000),  # an edge inside a block
    ((2, 2048, 4, 64), 256, 2, 2, 512),     # both edges in one block, lanes
    # qwen3next-policy.update: head_dim 256, 8 q heads a k/v head, the
    # default 1024 blocks in 256-row strips, head-major
    ((2, 8192, 16, 256), 256, 2, None, None),
    ((1, 1024, 8, 256), 256, 1, None, None),    # one block a head at 256
    # nemotron-twotower-policy.update: 16 q heads a k/v head
    ((2, 8192, 32, 128), 256, 2, None, None),
    ((1, 8192, 16, 128), 256, 16, None, None),  # ouro-policy.update: plain
])
def test_flash_kernels_compile_for_v5e(one_chip, shape, sub, kv_heads,
                                       layout, window):
    assert flash.tiling(shape[1], window=window)[2] == sub
    assert flash.lane_layout(shape[2], kv_heads, shape[3]) == layout
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct(shape[:2] + (kv_heads, shape[3]),
                              jnp.bfloat16, sharding=one_chip)

    def value_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(
                flash.flash_attention(q, k, v, window=window).astype(
                    jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    # the chip's compiler refuses a kernel that asks for more VMEM than its
    # limit: the backward holds a q head's dq and a k/v head's dk and dv
    # over all of T beside its blocks and score tiles
    compiled = jax.jit(value_and_grads).lower(x, kv, kv).compile()
    text = compiled.as_text()
    lanes = (layout or 1) * shape[3]
    # float32 over all of T: dq, and dk and dv — one each a k/v head of the
    # block where a step's two q heads share one
    shared = flash._shares_kv(layout or 1, shape[2] // kv_heads)
    acc = (5 if shared else 3) * shape[1] * lanes * 4
    assert acc <= flash._MAX_ACC_BYTES < flash._VMEM_LIMIT
    (line,) = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
               and f"/{flash.BWD_NAME}" in ln and " = " in ln]
    # (XLA may keep operands of its own in VMEM below the kernel's scope:
    # the scope's offset, which the used size counts too)
    config = r'"%s":\[{"memory_space":"1","offset":"(\d+)","size":"(\d+)"'
    below, asked = map(int, re.search(
        config % "scoped_memory_configs", line).groups())
    used = int(re.search(
        config % "used_scoped_memory_configs", line).group(2)) - below
    # a step's blocks and tiles fit in what a kernel has by default, so the
    # limit holds whatever accumulators the builder lets through
    assert asked == flash._VMEM_LIMIT, asked
    assert acc < used <= acc + 16 * 2 ** 20, (acc, used)
    print(f"relayrl_flash_bwd at {shape} window {window}: "
          f"{used / 2 ** 20:.1f} of {asked / 2 ** 20:.1f} MiB of VMEM")
    # forward, and ONE backward kernel for dq, dk and dv
    assert text.count("tpu_custom_call") == 2
    # a windowed call's kernels say so by name, the others' names are bare
    for name in (flash.FWD_NAME, flash.BWD_NAME):
        assert name in text
        assert (name + flash.WINDOW_SUFFIX in text) == bool(window)
    assert "relayrl_flash_dq" not in text and "relayrl_flash_dkv" not in text
    # What the module does round the kernels is the operator's glue, named
    # in the forward and in the backward rule; no kernel sits in a part.
    glue = re.findall(rf'op_name="([^"]*{scopes.OP_PROJ}[^"]*)"', text)
    assert [p for p in glue if "transpose(" in p]
    assert [p for p in glue if "transpose(" not in p]
    assert not [p for p in glue if "relayrl_flash" in p]
    # In the lane layout the head axis never leaves the lanes: nothing in
    # the compiled program (no transpose, no copy, no operand of a kernel)
    # is [B, H, T, D]-shaped or its flat form. A shape that falls back is
    # turned head-major, as every shape was before.
    B, T, H, D = shape
    head_major = [f"bf16[{B},{h},{T},{D}]" for h in {H, kv_heads}] + [
        f"bf16[{B * h},{T},{D}]" for h in {H, kv_heads}]
    found = [s for s in head_major if s in text]
    assert (found == []) if layout else found, found
    # grouped k/v are never repeated: dk and dv come back at their heads
    assert [o.shape for o in compiled.out_info[1]] == [
        x.shape, kv.shape, kv.shape]


def test_the_latent_attention_kernels_compile_for_v5e(one_chip):
    """``kimi-linear-policy.update``'s latent-attention layer: 32 heads, q
    and k 192 lanes a head, v 128, one 16,384-token episode. The same two
    kernels, head-major, under names that say so (``_mla``): the backward's
    float32 sums over all of T are dq and dk at 192 lanes and dv at 128, and
    the gradients come back at the operands' own widths."""
    B, T, H, D, Dv = 1, 16384, 32, 192, 128
    S = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                            sharding=one_chip)
    q, k, v = S(B, T, H, D), S(B, T, H, D), S(B, T, H, Dv)

    def value_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(
                flash.flash_attention(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(value_and_grads).lower(q, k, v).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for name in (flash.FWD_NAME, flash.BWD_NAME):
        assert name + flash.LATENT_SUFFIX in text
    assert 4 * T * (2 * D + Dv) <= flash._MAX_ACC_BYTES
    assert [o.shape for o in compiled.out_info[1]] == [
        q.shape, k.shape, v.shape]


def test_equal_widths_lower_the_kernels_that_were_there():
    """``D_qk = D_v`` (every shape but latent attention's): no ``_mla`` name
    and no block of another width anywhere in the traced call — the
    ``pallas_call``s' block shapes are the q / k width's throughout."""
    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash.flash_attention(
            q, k, v, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, q, q))
    assert flash.FWD_NAME in jaxpr and flash.BWD_NAME in jaxpr
    assert flash.LATENT_SUFFIX not in jaxpr
    own = str(jax.make_jaxpr(lambda q, k, v: flash.flash_attention(
        q, k, v, interpret=True))(q, q, q[..., :64]))
    assert flash.FWD_NAME + flash.LATENT_SUFFIX in own


def _on(chip, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree)


def _layer_and_every_gradient(one_chip, layer, *rows_in):
    """``jit(value_and_grad)`` of an expert layer's summed output, with
    respect to its parameters and the rows it reads, lowered for the
    described chip (the caller answers "tpu" for the backend)."""
    from relayrl_tpu.models import moe

    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), *rows_in)
    try:
        return jax.jit(jax.value_and_grad(
            lambda p, *a: jnp.sum(layer.apply(p, *a).astype(jnp.float32)),
            (0, 1))).lower(_on(one_chip, params), *_on(one_chip, rows_in))
    finally:  # traces made under the answer "tpu" stay in this test
        moe._shared_experts.clear_cache()
        moe._shared_experts_vjp.clear_cache()


def test_held_experts_layer_compiles_for_v5e(one_chip, monkeypatch):
    """The held-experts layer (``models/moe.py``) at ``lfm2-policy``'s
    widths, 8 of 64 experts held, a quarter of its tokens: ONE copy of the
    layer's grouped matmuls in each of its two pass loops — twelve Mosaic
    calls, under the three names a device trace finds them by (the
    backward's ``jax.vjp`` inside the loop must not wrap them: the
    benchmark's ``moe_ffn_ms`` matches ``relayrl_moe_gmm`` at the start of
    an instruction's name) — and no N*k-row buffer of model width."""
    import collections
    import re

    from relayrl_tpu.models import moe

    # the layer asks the backend whether the kernels may run; here the
    # test (not the program) answers for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, k = 4096, 2048, 4
    layer = moe.MoEMLP(d, 1536, 64, k, jnp.bfloat16, ffn="swiglu",
                       use_bias=False, router="sigmoid", expert_bias=True,
                       held=(0, 8))
    rows = moe.row_buffer(n * k, 8, 64)
    assert rows == 4096
    text = _layer_and_every_gradient(
        one_chip, layer, jnp.zeros((1, n, d), jnp.bfloat16)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 12
    names = collections.Counter(
        re.sub(r"[._]\d+$", "", name)
        for name in re.findall(r"%(\S*relayrl_moe_gmm\S*) = ", text))
    assert names == {"relayrl_moe_gmm_fwd": 6, "relayrl_moe_gmm_dlhs": 3,
                     "relayrl_moe_gmm_drhs": 3}
    # each kernel's own name is the innermost of its path, inside the
    # element-wise part that holds what lies between them; the layer's
    # three parts are on both loops' operations
    # (an inner jit's shared computations keep paths of their own)
    paths = re.findall(r'op_name="([^"]*)"', text)
    inside = [p for p in paths
              if "relayrl_moe_gmm" in p and scopes.MOE_ELEMENTWISE in p]
    assert inside
    for path in inside:
        assert path.rindex(scopes.MOE_ELEMENTWISE) < path.index(
            "relayrl_moe_gmm"), path
    for part in (scopes.MOE_ROUTE, scopes.MOE_ROWS, scopes.MOE_ELEMENTWISE):
        mine = [p for p in paths if f"/{part}/" in p
                and "relayrl_moe_gmm" not in p]
        assert [p for p in mine if "transpose(" in p], part
        assert [p for p in mine if "transpose(" not in p], part
    assert f"bf16[{rows},{d}]" in text
    # row -> token is one N*k-row gather a pass and direction, read by the
    # sum over k that follows it: nothing else is N*k rows of d
    assert len(set(re.findall(rf"\w+\[{n * k},{d}\]", text))) <= 1
    assert f"f32[{k},{n},{d}]" not in text and f"f32[{n * k},{d}]" not in text


def test_held_experts_of_a_width_no_128_divides_compile_for_v5e(
        one_chip, monkeypatch):
    """The held-experts layer at ``nemotron-twotower-policy``'s widths:
    hidden 2688, ``relu^2`` experts of 1856 = 14.5 x 128 (two stacks), 8 of
    128 held, top-6, a quarter of its tokens. The grouped matmuls are the
    Mosaic kernels — eight a layer, the forward's two again in the backward
    —, NOT ``lax.ragged_dot``: the last of three 640-wide tiles hangs over
    the stacks' edge, and the stacks, the rows and the results keep the
    published width (no array 1920 or 2048 wide)."""
    import collections
    import re

    from relayrl_tpu.models import moe
    from relayrl_tpu.ops import grouped_matmul

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, ff, k = 4096, 2688, 1856, 6
    assert grouped_matmul._tiling(d, ff) == (512, 896, 640)
    assert grouped_matmul._tiling(ff, d) == (512, 640, 896)
    rows = moe.row_buffer(n * k, 8, 128)
    assert rows == 3072 and grouped_matmul.fits(rows, d, ff)
    # a width under one tile, or a handful of decode rows, stays with XLA
    assert not grouped_matmul.fits(rows, 64, ff)
    assert not grouped_matmul.fits(k, d, ff)
    layer = moe.MoEMLP(d, ff, 128, k, jnp.bfloat16, ffn="relu2",
                       use_bias=False, router="sigmoid", expert_bias=True,
                       held=(0, 8), routed_scaling=2.5, shared_d_ff=3712)
    x = jnp.zeros((1, n, d), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert params["params"]["moe_w_up"].shape == (8, d, ff)
    text = _layer_and_every_gradient(one_chip, layer, x).compile().as_text()
    assert "ragged" not in text
    assert text.count("tpu_custom_call") == 8
    names = collections.Counter(
        re.sub(r"[._]\d+$", "", name)
        for name in re.findall(r"%(\S*relayrl_moe_gmm\S*) = ", text))
    assert names == {"relayrl_moe_gmm_fwd": 4, "relayrl_moe_gmm_dlhs": 2,
                     "relayrl_moe_gmm_drhs": 2}
    assert f"bf16[{rows},{ff}]" in text
    assert not re.findall(r"\[(?:\d+,)*(?:1920|2048)\]", text)
    # the shared expert is dense matmuls under the dense FFN's part
    assert re.search(rf'op_name="[^"]*/{scopes.FFN}/[^"]*dot_general',
                     text)


def _sorts_by_keys(text, rows):
    """``(the router's, the N k-key ones outside the loops, the others')``
    key counts of a compiled layer's sorts."""
    router, whole, mine = [], [], []
    for line in text.splitlines():
        if " sort(" not in line:
            continue
        shape = re.search(r"= \(\w+\[([\d,]+)\]", line).group(1).split(",")
        keys = int(shape[int(re.search(r"dimensions=\{(\d)\}",
                                       line).group(1))])
        if "top_k" in line:
            router.append(keys)
        elif keys > rows:
            assert "while" not in line, line
            whole.append(keys)
        else:
            mine.append(keys)
    return router, whole, mine


def test_a_held_layer_at_top_22_of_512_counts_the_rows_it_holds_on_v5e(
        one_chip, monkeypatch):
    """``nemotron3-super-policy``'s expert layer at the cell's shape —
    16,384 tokens, top-22 of 512 sigmoid-routed ``relu^2`` experts in a
    latent of 1024, 8 held, the shared expert beside them — forward and
    every gradient: 360,448 slots, buffers of 11,264 rows, N k = 32 R, so
    the layer COUNTS its rows (``moe.held_form``) and adds them back at
    their tokens. It sorts nothing: the sorts left are the index sorts
    of R keys XLA puts before the R-row scatter-adds, and the router's own —
    ``top_k`` (each token's 512 scores) and the transpose of its
    ``take_along_axis`` (N k keys), the only operations over all N k slots;
    nothing has N k rows of the latent's width."""
    from relayrl_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, k, latent = 16384, 4096, 22, 1024
    rows = moe.row_buffer(n * k, 8, 512)
    assert (n * k, rows) == (360448, 11264)
    assert moe.dispatch_form(n, k, 512, (0, 8), None)[1:] == (
        "counted", "slots=360448 rows=11264 held=8/512 k=22")
    layer = moe.MoEMLP(d, 2688, 512, k, jnp.bfloat16, ffn="relu2",
                       use_bias=False, router="sigmoid", expert_bias=True,
                       held=(0, 8), routed_scaling=5.0, shared_d_ff=5376,
                       latent=latent)
    text = _layer_and_every_gradient(
        one_chip, layer, jnp.zeros((2, n // 2, d), jnp.bfloat16)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 8   # two stacks: 4 + 2 + 2
    router, whole, mine = _sorts_by_keys(text, rows)
    assert set(router) == {512} and set(whole) <= {n * k}
    # the index sorts of the R-row scatter-adds (y, d tokens)
    assert mine and set(mine) == {rows}
    assert f"bf16[{rows},{latent}]" in text
    # nothing of the latent's width has N k rows, in either slot order
    for lead in (f"{n * k}", f"{k},{n}", f"{n},{k}"):
        assert not re.findall(rf"\w+\[{lead},{latent}\]", text), lead


def test_a_held_layer_at_top_8_counts_the_rows_it_holds_on_v5e(
        one_chip, monkeypatch):
    """``keye-vl2-policy``'s expert layer at the cell's shape — 16,384
    tokens, top-8 of 128 SwiGLU experts, 16 held: N k is only 4 R, but 8
    divides k, so the layer counts its rows. Forward and every gradient
    compile for the chip: twelve Mosaic calls, no sort of the layer's own
    but the R-row scatter-adds' index sorts, nothing N k rows long at the
    model's width."""
    from relayrl_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, d, k = 16384, 2048, 8
    rows = moe.row_buffer(n * k, 16, 128)
    assert rows == 32768
    assert moe.dispatch_form(n, k, 128, (0, 16), None)[1] == "counted"
    layer = moe.MoEMLP(d, 768, 128, k, jnp.bfloat16, ffn="swiglu",
                       use_bias=False, held=(0, 16))
    text = _layer_and_every_gradient(
        one_chip, layer, jnp.zeros((1, n, d), jnp.bfloat16)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 12
    router, whole, mine = _sorts_by_keys(text, rows)
    # the R-row scatter-adds (y, d tokens, the weights' gradients) sort
    # their R indices
    assert set(router) == {128} and mine and set(mine) == {rows}
    assert set(whole) <= {n * k}
    assert f"bf16[{rows},{d}]" in text
    for lead in (f"{n * k}", f"{k},{n}", f"{n},{k}"):
        assert not re.findall(rf"\w+\[{lead},{d}\]", text), lead


# sha256 of the lowered layer and every gradient (Mosaic bodies left out:
# they hold line numbers) as the parent of PR 59 lowered it, before
# ``models/moe.py`` was given its second held walk — since PR 61 less the
# counters the lowering gives its private functions (``@_where_38``), which
# a ``checkpoint_name`` no policy lists moves by one and nothing else does
# (the parent of PR 61 reads these two values, as that PR's tree does; with
# the counters in they were 12598113... and 39bfe868...)
_SORTED_WALK_AS_IT_WAS = {
    "smallthinker-policy":
        "80cf8b5b929f411cfcc9d72d9669fb9640612b27fede5c2151ad33aba33a4bf0",
    "lfm2-policy":
        "ed01bc9f10343085b38ef05487f60f1ae593037481bcb62bfacee06f78f65f9e",
}


@pytest.mark.parametrize("cell", sorted(_SORTED_WALK_AS_IT_WAS))
def test_a_held_layer_that_sorts_lowers_the_program_it_was(one_chip,
                                                           monkeypatch,
                                                           cell):
    """The two cells with the fewest slots a held row (N k / R = 2 and 4:
    ``smallthinker-policy``, ``lfm2-policy``) keep the sorted walk, and
    keep it text for text: the layer at the cell's shape, forward and every
    gradient, lowers to the StableHLO it lowered to before the counted walk
    existed (PR 58 was refused on the warm set-up of the first; a set-up
    moves with the program's text and with the frames above a lowering —
    ROADMAP 1.5 — so neither may move here). An edit that changes the
    sorted walk's program on purpose pins the hash it then reads: the
    failed assertion shows it."""
    import hashlib

    from relayrl_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if cell == "smallthinker-policy":  # its router reads the layer's input
        layer = moe.MoEMLP(2560, 768, 64, 6, jnp.bfloat16, ffn="reglu",
                           use_bias=False, held=(0, 16))
        x = jnp.zeros((1, 16384, 2560), jnp.bfloat16)
        rows_in = (x, x)
    else:
        layer = moe.MoEMLP(2048, 1536, 64, 4, jnp.bfloat16, ffn="swiglu",
                           use_bias=False, router="sigmoid",
                           expert_bias=True, held=(0, 8))
        rows_in = (jnp.zeros((2, 8192, 2048), jnp.bfloat16),)
    assert moe.held_form(16384, layer.top_k, layer.held[1], 64) == "sorted"
    text = _layer_and_every_gradient(one_chip, layer, *rows_in).as_text()
    assert text.count("tpu_custom_call") == 12
    bare = re.sub(r'\\22body\\22: \\22[^\\]*\\22',
                  r'\\22body\\22: \\22<mosaic>\\22', text)
    assert bare != text
    bare = without_symbol_counters(bare)
    assert hashlib.sha256(bare.encode()).hexdigest() == (
        _SORTED_WALK_AS_IT_WAS[cell])


def _scan_at_the_cells_shape(one_chip, fn, wrt):
    """``fn`` (a form of ``ops/ssd.py``'s scan) at
    ``nemotron-twotower-policy.update``'s shape — two 8192-token episodes,
    64 heads of 64, a state of 128, 8 groups, chunks of 128, bfloat16 from
    a carried state —, a loss that reads both results differentiated with
    respect to ``wrt`` (none: the two results themselves), compiled for
    the described chip."""
    b, t, h, p, g, n = 2, 8192, 64, 64, 8, 128
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    args = (S((b, t, h, p), jnp.bfloat16), S((b, t, h), jnp.float32),
            S((h,), jnp.float32), S((b, t, g, n), jnp.bfloat16),
            S((b, t, g, n), jnp.bfloat16), S((h,), jnp.float32),
            S((b, h, p, n), jnp.float32))

    def loss(*a):
        y, last = fn(*a[:6], state=a[6])
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(last)

    step = (jax.value_and_grad(loss, argnums=wrt) if wrt
            else lambda *a: fn(*a[:6], state=a[6]))
    return jax.jit(step).lower(*args).compile()


def _scan_paths(compiled):
    paths = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return [path for path in paths if scopes.SSD_NAME in path]


def test_the_state_space_scan_compiles_for_v5e(one_chip):
    """The scan's Pallas kernels (``ops/ssd_pallas.py``), forward and every
    gradient: three Mosaic calls — ``ssd_fwd``, and in the backward
    ``ssd_states`` + ``ssd_bwd`` — each under ``relayrl_ssd`` and under no
    deeper ``relayrl_`` name (the benchmark's ``ssd_ms`` reads the exact
    scope), no loop left under the scope, and of chunk-shaped arrays only
    the chunk-start states (0.27 GB) in HBM."""
    from relayrl_tpu.ops import ssd_pallas

    compiled = _scan_at_the_cells_shape(one_chip, ssd_pallas.ssd_pallas,
                                        tuple(range(7)))
    text = compiled.as_text()
    calls = re.findall(r'(%[\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _ in calls) == [
        "%" + ssd_pallas.BWD_NAME, "%" + ssd_pallas.FWD_NAME,
        "%" + ssd_pallas.STATES_NAME]
    for name, path in calls:
        assert re.findall(r"relayrl_\w+", path)[-1] == scopes.SSD_NAME, path
        assert ("transpose(" in path) == (ssd_pallas.FWD_NAME not in name)
        assert "relayrl_flash_" not in name + path
    mine = _scan_paths(compiled)
    assert [path for path in mine if "transpose(" in path]
    assert [path for path in mine if "transpose(" not in path]
    assert not re.findall(r"\bwhile\(", text)
    # arguments, cotangents and the chunk-start states
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7e9


def test_a_scan_nobody_differentiates_writes_no_state(one_chip):
    """The prefill's call: ``ssd_fwd`` alone, no chunk-shaped temporary."""
    from relayrl_tpu.ops import ssd_pallas

    compiled = _scan_at_the_cells_shape(one_chip, ssd_pallas.ssd_pallas, ())
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and ssd_pallas.FWD_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


def test_the_plain_scan_compiles_for_v5e(one_chip):
    """``ops/ssd.ssd_xla`` at the same shape — what a shape that does not
    tile runs on a TPU: plain XLA under its own name in both directions,
    one group's score tiles alive at a time (a whole layer's would be 0.27
    GB an array and a dozen arrays)."""
    from relayrl_tpu.ops.ssd import ssd_xla

    compiled = _scan_at_the_cells_shape(one_chip, ssd_xla, tuple(range(6)))
    mine = _scan_paths(compiled)
    assert [path for path in mine if "transpose(" in path]
    assert [path for path in mine if "transpose(" not in path]
    assert "tpu_custom_call" not in compiled.as_text()
    # arguments, cotangents and one group's intermediates: under 1.5 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def _rule_compiled(one_chip, fn, wrt, operands):
    """``fn`` (a form of a delta rule: ``ops/gdn.py``'s or ``ops/kda.py``'s)
    over ``operands`` — ``(shape, dtype)`` of q, k, v, g, beta and the
    carried state —, chunks of 64, a loss that reads both results
    differentiated with respect to ``wrt`` (none: the two results
    themselves), compiled for the described chip."""
    args = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape, dtype in operands)

    def loss(*a):
        o, last = fn(*a[:5], chunk=64, state=a[5])
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(last)

    step = (jax.value_and_grad(loss, argnums=wrt) if wrt
            else lambda *a: fn(*a[:5], chunk=64, state=a[5]))
    return jax.jit(step).lower(*args).compile()


def _rule_at_the_cells_shape(one_chip, fn, wrt):
    """``qwen3next-policy.update``'s shape: two 8192-token episodes, 32
    value heads over 16 key heads of 128, bfloat16 from a carried state."""
    b, t, hk, h, w = 2, 8192, 16, 32, 128
    lo, f32 = jnp.bfloat16, jnp.float32
    return _rule_compiled(one_chip, fn, wrt, (
        ((b, t, hk, w), lo), ((b, t, hk, w), lo), ((b, t, h, w), lo),
        ((b, t, h), f32), ((b, t, h), f32), ((b, h, w, w), f32)))


def _rule_paths(compiled):
    paths = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return [path for path in paths if scopes.GDN_NAME in path]


def test_the_delta_rule_compiles_for_v5e(one_chip):
    """The rule's Pallas kernels (``ops/gdn_pallas.py``), forward and every
    gradient: three Mosaic calls — ``gdn_fwd``, and in the backward
    ``gdn_states`` + ``gdn_bwd`` — each under ``relayrl_gdn`` and under no
    deeper ``relayrl_`` name (the benchmark's ``gdn_ms`` reads the exact
    scope), no loop left under the scope, and of chunk-shaped arrays only
    the chunk-start states (0.54 GB), the solve's tiles (0.13 GB as the
    chip pads their 64 lanes) and the 4 MB columns in HBM: temporaries
    under 1.2 GB."""
    from relayrl_tpu.ops import gdn_pallas

    compiled = _rule_at_the_cells_shape(one_chip, gdn_pallas.gdn_pallas,
                                        tuple(range(6)))
    text = compiled.as_text()
    calls = re.findall(r'(%[\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _ in calls) == [
        "%" + gdn_pallas.BWD_NAME, "%" + gdn_pallas.FWD_NAME,
        "%" + gdn_pallas.STATES_NAME]
    for name, path in calls:
        assert re.findall(r"relayrl_\w+", path)[-1] == scopes.GDN_NAME, path
        assert ("transpose(" in path) == (gdn_pallas.FWD_NAME not in name)
    mine = _rule_paths(compiled)
    assert [path for path in mine if "transpose(" in path]
    assert [path for path in mine if "transpose(" not in path]
    assert not re.findall(r"\bwhile\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def test_a_rule_nobody_differentiates_writes_no_state(one_chip):
    """The prefill's call: ``gdn_fwd`` alone, no chunk-shaped temporary but
    the columns."""
    from relayrl_tpu.ops import gdn_pallas

    compiled = _rule_at_the_cells_shape(one_chip, gdn_pallas.gdn_pallas, ())
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and gdn_pallas.FWD_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25e9


def test_the_plain_rule_compiles_for_v5e(one_chip):
    """``ops/gdn.gdn_xla`` at the same shape — what a shape that does not
    tile runs on a TPU: plain XLA under its own name in both directions,
    eight heads' intermediates alive at a time."""
    from relayrl_tpu.ops.gdn import gdn_xla

    compiled = _rule_at_the_cells_shape(one_chip, gdn_xla, tuple(range(5)))
    mine = _rule_paths(compiled)
    assert [path for path in mine if "transpose(" in path]
    assert [path for path in mine if "transpose(" not in path]
    assert "tpu_custom_call" not in compiled.as_text()
    # arguments, cotangents and a step's intermediates: 2.3 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6e9


def _lane_rule_at_the_cells_shape(one_chip, fn, wrt):
    """``kimi-linear-policy.update``'s shape: one 16,384-token episode, 32
    heads of 128, a decay a key lane, bfloat16 from a carried state."""
    b, t, h, w = 1, 16384, 32, 128
    lo, f32 = jnp.bfloat16, jnp.float32
    return _rule_compiled(one_chip, fn, wrt, (
        ((b, t, h, w), lo),) * 3 + (
        ((b, t, h, w), f32), ((b, t, h), f32), ((b, h, w, w), f32)))


def test_the_lane_decay_rule_compiles_for_v5e(one_chip):
    """The rule's Pallas kernels (``ops/kda_pallas.py``), forward and every
    gradient: three Mosaic calls — ``kda_fwd``, and in the backward
    ``kda_states`` + ``kda_bwd`` — each under ``relayrl_kda`` and under no
    deeper ``relayrl_`` name (the benchmark's ``kda_ms`` reads the exact
    scope), no loop left under the scope, and of chunk-shaped arrays only
    the chunk-start states (0.54 GB) and the solve's tiles (0.13 GB as the
    chip pads their 64 lanes) in HBM beside the views of this test's
    head-by-head operands: temporaries under 1.7 GB."""
    from relayrl_tpu.ops import kda_pallas

    compiled = _lane_rule_at_the_cells_shape(
        one_chip, kda_pallas.kda_pallas, tuple(range(6)))
    text = compiled.as_text()
    calls = re.findall(r'(%[\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _ in calls) == [
        "%" + kda_pallas.BWD_NAME, "%" + kda_pallas.FWD_NAME,
        "%" + kda_pallas.STATES_NAME]
    for name, path in calls:
        assert re.findall(r"relayrl_\w+", path)[-1] == scopes.KDA_NAME, path
        assert ("transpose(" in path) == (kda_pallas.FWD_NAME not in name)
    assert not re.findall(r"\bwhile\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.7e9


def test_a_lane_decay_rule_nobody_differentiates_is_one_call(one_chip):
    """The prefill's call: ``kda_fwd`` alone, no solve's tiles and no
    chunk-start states written."""
    from relayrl_tpu.ops import kda_pallas

    compiled = _lane_rule_at_the_cells_shape(one_chip,
                                             kda_pallas.kda_pallas, ())
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and kda_pallas.FWD_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9


@pytest.mark.parametrize("columns,bias,scope", [
    (6144, True, scopes.MAMBA_CONV_NAME),    # nemotron-twotower-policy.update
    (8192, False, scopes.GDN_CONV_NAME),     # qwen3next-policy.update
    (12288, False, scopes.KDA_CONV_NAME),    # kimi-linear-policy.update
])
def test_the_mixers_convolution_compiles_for_v5e(one_chip, columns, bias,
                                                 scope):
    """The convolution's Pallas kernels (``ops/conv_pallas.py``), forward
    and every gradient at the cells' ``[2, 8192, C]`` rows: two Mosaic calls
    — ``conv_fwd``, and in the backward ``conv_bwd`` — each under the
    caller's scope (the benchmark's ``mamba_conv_ms`` / ``gdn_conv_ms`` read
    it), and no copy of the rows in HBM in any dtype: the temporaries hold a
    direction's cotangents and the taps' partial sums, nothing of ``[T, C]``
    in float32."""
    from relayrl_tpu.ops import conv_pallas

    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    x = S((2, 8192, columns), jnp.bfloat16)
    w = S((4, columns), jnp.float32)
    b = S((columns,), jnp.float32) if bias else None

    def loss(x, w, b):
        return jnp.sum(conv_pallas.conv_pallas(x, w, b, scope).astype(
            jnp.float32))

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2) if bias else (0, 1))).lower(x, w, b).compile()
    text = compiled.as_text()
    calls = re.findall(r'(%[\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _ in calls) == [
        "%" + conv_pallas.BWD_NAME, "%" + conv_pallas.FWD_NAME]
    for name, path in calls:
        assert re.findall(r"relayrl_\w+", path)[-1] == scope, path
        assert ("transpose(" in path) == (conv_pallas.BWD_NAME in name)
    # the cotangent of the sum (one bfloat16 array of the rows' size) and
    # the sums' float32 partials
    rows = 2 * 8192 * columns * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * rows


def test_the_sparse_attention_kernels_compile_for_v5e(one_chip):
    """The attention over the selected keys (``ops/sparse_attn_pallas.py``)
    at ``keye-vl2-policy.update``'s last tile — 512 queries of 32 heads of
    128 over 16,384 keys of 4, bfloat16 —, forward, ``p^`` and every
    gradient: three Mosaic calls, ``sparse_attn_fwd`` and
    ``sparse_attn_phat`` forward, ``sparse_attn_bwd`` (``dq``, ``dk`` and
    ``dv`` from one score tile) in the backward, each under
    ``relayrl_sparse_attn`` and under no other ``relayrl_`` name (the
    benchmark's ``sparse_attn_ms`` reads the exact scope; a name holding
    ``relayrl_flash_`` would be read as flash), and
    nothing of a score tile's size in HBM but ``p^`` itself: one float32
    ``[512, 16384]`` (33.6 MB) beside the operands."""
    from relayrl_tpu.ops import sparse_attn_pallas as kernels

    tq, tk, heads, kv, width = 512, 16_384, 32, 4, 128
    assert kernels.fits(tq, tk, heads, kv, width)
    assert kernels.key_block(tk) == 512
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)

    def value_and_grads(q, k, v, keep, pos):
        def loss(q, k, v):
            with jax.named_scope(scopes.SPARSE_ATTN):   # ``sparse_rows``'
                out, p_hat, _ = kernels.masked_attention_pallas(q, k, v, keep,
                                                             pos, True)
            return jnp.sum(out.astype(jnp.float32)), p_hat

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    compiled = jax.jit(value_and_grads).lower(
        S((tq, heads, width), jnp.bfloat16), S((tk, kv, width), jnp.bfloat16),
        S((tk, kv, width), jnp.bfloat16), S((tq, tk), jnp.bool_),
        S((tq,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r'(%[\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _ in calls) == sorted(
        "%" + name for name in (kernels.FWD_NAME, kernels.PHAT_NAME,
                                kernels.BWD_NAME))
    for name, path in calls:
        assert set(re.findall(r"relayrl_\w+", path)) == {
            scopes.SPARSE_ATTN}, path
        assert ("transpose(" in path) == (kernels.BWD_NAME in name), path
    assert "relayrl_flash" not in text
    assert not re.findall(r"\bwhile\(", text)
    # the int8 mask, the scaled and turned q / do / out, delta: well under a
    # second score tile
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * tq * tk * 4


@pytest.mark.parametrize("tk", [16_384, 4_096])
def test_the_indexers_kernels_compile_for_v5e(one_chip, tk):
    """The indexer's scores, selection and backward (``ops/index_pallas.py``)
    at ``keye-vl2-policy.update``'s last and first stages' tiles — 512
    queries of 16 index heads of 64 over ``tk`` keys, bfloat16 —: four
    Mosaic calls, ``index_kth`` and ``index_select`` forward, ``index_select``
    again under a checkpoint that keeps the thresholds (and NO second
    ``index_kth``), ``index_bwd`` in the backward, each under
    ``relayrl_index`` and under no other ``relayrl_`` name (the benchmark's
    ``index_ms`` reads the exact scope), and nothing of a score tile's size
    in HBM but what the callers read: ``keep`` (int8), the scores and their
    cotangent."""
    from relayrl_tpu.ops import index_pallas as kernels
    from relayrl_tpu.ops import sparse_attn

    tq, heads, width, topk = 512, 16, 64, 2_048
    assert kernels.fits(tq, tk, heads, width)
    assert kernels.key_block(tk) == 512
    assert kernels.query_block(tq, tk, heads, width) == 512
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)

    def grads(qi, ki, w, pos, weight):
        @functools.partial(
            jax.checkpoint,
            policy=jax.checkpoint_policies.save_only_these_names(
                sparse_attn.LSE_NAME, sparse_attn.KTH_NAME))
        def tile(qi, ki, w):
            with jax.named_scope(scopes.INDEX):     # ``sparse_attn.indexer``'s
                keep, scores = kernels.index_select(qi, ki, w, pos, topk)
            return jnp.sum(jnp.where(keep != 0, scores * weight, 0.0))

        return jax.value_and_grad(tile, argnums=(0, 1, 2))(qi, ki, w)

    compiled = jax.jit(grads).lower(
        S((tq, heads, width), jnp.bfloat16), S((tk, width), jnp.bfloat16),
        S((tq, heads), jnp.bfloat16), S((tq,), jnp.int32),
        S((tq, tk), jnp.float32)).compile()
    text = compiled.as_text()
    calls = re.findall(r'(%[\w.\-]+) = [^\n]*custom_call_target='
                       r'"tpu_custom_call"[^\n]*op_name="([^"]*)"', text)
    assert sorted(re.sub(r"\.\d+$", "", name) for name, _ in calls) == sorted(
        "%" + name for name in (kernels.SEARCH_NAME, kernels.SELECT_NAME,
                                kernels.SELECT_NAME, kernels.BWD_NAME))
    for name, path in calls:
        assert set(re.findall(r"relayrl_\w+", path)) == {scopes.INDEX}, path
    assert "relayrl_flash" not in text and "relayrl_sparse" not in text
    assert not re.findall(r"\bwhile\(", text)
    # the scores, their cotangent and the weight, keep, and the operands as
    # the kernels take them: no third float32 tile
    assert compiled.memory_analysis().temp_size_in_bytes < 2.6 * tq * tk * 4


@pytest.mark.parametrize("dtype,lanes,rows,width", [
    # gpt2m-policy.rollout: 64 lanes' (k, v) rows of 16 heads x 64
    (jnp.bfloat16, 64, 1024, 1024),
    (jnp.float32, 4, 64, 256),      # a float32 host: tiles of 8 rows
])
def test_the_cache_row_writer_compiles_for_v5e(one_chip, dtype, lanes, rows,
                                               width):
    """``ops/cache_rows.write_rows_pallas``: one Mosaic call for every
    lane's new row, its result aliased to the cache it is given."""
    from relayrl_tpu.ops import cache_rows

    assert cache_rows.tiles((lanes, 1, rows, width), dtype)
    cache = jax.ShapeDtypeStruct((lanes, rows, width), dtype,
                                 sharding=one_chip)
    new = jax.ShapeDtypeStruct((lanes, 1, width), dtype, sharding=one_chip)
    t = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(cache_rows.write_rows_pallas,
                       donate_argnums=0).lower(cache, new, t).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert scopes.CACHE_WRITE_ROW in text
    stats = compiled.memory_analysis()
    # in place: the donated cache is the result, nothing of its size beside
    assert stats.alias_size_in_bytes >= lanes * rows * width * jnp.dtype(
        dtype).itemsize
    assert stats.temp_size_in_bytes < 2**20


def test_the_whole_rotary_is_todays_function_bit_for_bit():
    """``apply_rope`` at a share of 1.0 (the default) is the function every
    accepted configuration has run: the same bits as its lines written out
    here, at a traced start too; a quarter turns the first quarter's lanes
    as a head of that width and passes the rest through untouched. (CPU;
    in this file beside the D-256 kernels it serves.)"""
    from relayrl_tpu.models.layers.attention import apply_rope

    def as_it_was(x, start, theta):
        hd = x.shape[-1]
        inv_freq = 1.0 / (theta ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        pos = jnp.asarray(start, jnp.float32) + jnp.arange(
            x.shape[1], dtype=jnp.float32)
        ang = pos[:, None] * inv_freq[None, :]
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)

    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16), dtype)
        for start in (0, 5):
            want = as_it_was(x, start, 1e4)
            assert (apply_rope(x, start, 1e4) == want).all()
            assert (apply_rope(x, start, 1e4, 1.0) == want).all()
            assert (jax.jit(apply_rope, static_argnums=2)(
                x, jnp.int32(start), 1e4) == jax.jit(
                    as_it_was, static_argnums=2)(
                        x, jnp.int32(start), 1e4)).all()
        quarter = apply_rope(x, 3, 1e7, 0.25)
        assert (quarter[..., 4:] == x[..., 4:]).all()
        assert (quarter[..., :4] == as_it_was(x[..., :4], 3, 1e7)).all()
        assert float(jnp.abs(quarter[..., :4].astype(jnp.float32)
                             - x[..., :4].astype(jnp.float32)).max()) > 0.1
    with pytest.raises(ValueError, match="rope_share"):
        apply_rope(x, 0, 1e4, 0.2)          # 3.2 lanes of 16

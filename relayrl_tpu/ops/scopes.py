"""The names the program gives its work on the device: the ONE list that the
program's ``jax.named_scope`` lines, the tests, ``docs/observability.md`` and
the benchmark's readers are held to.

A ``jax.named_scope`` round plain XLA operations reaches every instruction's
``op_name`` in the compiled module's metadata, which the profiler saves
beside a trace's events (``benchmark/scope_trace.py`` maps the events back).
A scope is metadata: it is always on, costs nothing at run time and leaves
the device program as it was (``tests/test_device_scopes.py`` compares the
lowered text with ``jax.named_scope`` patched away).

**Parts** (:data:`DEVICE_SCOPES`) are siblings — none is opened inside
another — and each is one ``with`` line where the work is written. A
transform wraps the outermost scope entered under it
(``transpose(jvp(relayrl_ffn))/...``), so a part's backward is found by the
substring; a ``custom_vjp``'s backward carries the scopes round the CALL, not
those opened inside its forward, which is why the held-experts layer and the
flash kernels' glue open theirs in both rules.

**Kernels** (:data:`KERNEL_SCOPES`) keep their own innermost names inside
whatever part calls them: a Mosaic call is named after the innermost scope,
and the device trace's readers match those names.

**A pass** (:data:`LOOP_PASS`) is the one scope opened OUTSIDE the parts: a
looped trunk (``loop_steps`` > 1) opens ``relayrl_loop_pass`` round a pass of
its stack (the learner's passes are ONE body of a scan, so the name carries
no number), the parts stay siblings of each other inside it, and a reader
that takes an operation's innermost ``relayrl_`` name still finds the part.
No other program opens it, so no other program's ``op_name`` moves.
"""

from __future__ import annotations

# -- parts of the jitted update (who opens each: docs/observability.md) ------
OPTIMIZER = "relayrl_optimizer"      # global-norm clip, Adam, the apply
VTRACE = "relayrl_vtrace"            # ratios, delta, the reverse recursion, pg_adv
LOSS = "relayrl_loss"                # the loss sums, a trunk's own, RhoMean, KL
EMBED = "relayrl_embed"              # obs embedding + learned positions
OP_PROJ = "relayrl_op_proj"          # a layer's operator less its kernel
LATENT_ROPE = "relayrl_latent_rope"  # latent attention's two rotations
INDEX = "relayrl_index"              # an indexer: projections, scores, selection
SPARSE_ATTN = "relayrl_sparse_attn"  # attention over the selected keys, and p^
FFN = "relayrl_ffn"                  # a layer's dense FFN, norm and residual
MOE_ROUTE = "relayrl_moe_route"      # router, top-k, the sort, load counts
MOE_ROWS = "relayrl_moe_rows"        # tokens -> rows, rows -> tokens
MOE_ELEMENTWISE = "relayrl_moe_elementwise"  # between and after the matmuls
MOE_LATENT = "relayrl_moe_latent"    # experts in a latent: down before, up after
HEADS = "relayrl_heads"              # final norm, pi / vf heads, logp, entropy
OBS_PREP = "relayrl_obs_prep"        # cnn: cast, scale, relayout on entry
CONV = "relayrl_conv"                # cnn: the conv stack and its dense layer

DEVICE_SCOPES = (OPTIMIZER, VTRACE, LOSS, EMBED, OP_PROJ, LATENT_ROPE, INDEX,
                 SPARSE_ATTN, FFN, MOE_ROUTE, MOE_ROWS, MOE_ELEMENTWISE,
                 MOE_LATENT, HEADS, OBS_PREP, CONV)

# -- a looped trunk's pass, outside the parts ---------------------------------
LOOP_PASS = "relayrl_loop_pass"      # models/transformer.py's loop

# -- kernels and the operators that keep a name of their own -----------------
SHORT_CONV_NAME = "relayrl_short_conv"   # models/layers/short_conv.py
SSD_NAME = "relayrl_ssd"                 # ops/ssd.py: the Mamba-2 scan
MAMBA_CONV_NAME = "relayrl_mamba_conv"   # models/layers/mamba2.py's
GDN_NAME = "relayrl_gdn"                 # ops/gdn.py: the gated delta rule
GDN_CONV_NAME = "relayrl_gdn_conv"       # models/layers/gdn.py's
KDA_NAME = "relayrl_kda"                 # ops/kda.py: the per-lane-decay rule
KDA_CONV_NAME = "relayrl_kda_conv"       # models/layers/kda.py's
FWD_NAME = "relayrl_flash_fwd"           # ops/flash.py, also the calls' name
BWD_NAME = "relayrl_flash_bwd"           # dq, dk, dv from one score tile
# A windowed call's kernels carry the same names with this suffix: a reader
# that matches ``relayrl_flash_fwd`` finds them too, one that wants the band
# calls alone asks for the suffix.
WINDOW_SUFFIX = "_win"
# ... and a call whose values have a width of their own (latent attention:
# q / k 192 lanes a head, v 128) this one, after the window's if both
LATENT_SUFFIX = "_mla"
GMM_FWD_NAME = "relayrl_moe_gmm_fwd"     # ops/grouped_matmul.py
GMM_DLHS_NAME = "relayrl_moe_gmm_dlhs"
GMM_DRHS_NAME = "relayrl_moe_gmm_drhs"
# absorbs the vjp's name transform round a held pass's experts (models/moe.py)
HELD_EXPERTS_NAME = "held_experts"
CACHE_WRITE_ROW = "relayrl_cache_write_row"  # ops/cache_rows.py, the call's name

KERNEL_SCOPES = (SHORT_CONV_NAME, SSD_NAME, MAMBA_CONV_NAME, GDN_NAME,
                 GDN_CONV_NAME, KDA_NAME, KDA_CONV_NAME, FWD_NAME, BWD_NAME, GMM_FWD_NAME,
                 GMM_DLHS_NAME, GMM_DRHS_NAME, CACHE_WRITE_ROW)

"""Device time per update of the latent projections of an expert layer whose
routed experts work in a space narrower than the residual stream: the
down-projection of every token before the sort and the up-projection of the
weighted sum after the un-sort, two dense matmuls a layer, forward and
backward — the operations under the program's scope ``relayrl_moe_latent``
(``relayrl_tpu/models/moe.py``, arch ``moe_latent``) —
``benchmark/scope_table.py``. The grouped matmuls between them keep their
own names and are ``moe_ffn_ms``'s. None where the trace holds no module
metadata or the program has no such scope (a program without ``moe_latent``,
a configuration that does not set it)."""

from benchmark import scope_table

SCOPE = "relayrl_moe_latent"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)

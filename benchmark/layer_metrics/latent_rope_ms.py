"""Device time per update of the latent-attention layers' two rotations: the
shared ``k_pe`` lanes and each query's matching lanes turned at their
positions (the static de-interleave of the published pairing, the cosines
and sines in float32, the lanes put back beside the others), forward and
backward — the operations under the program's scope ``relayrl_latent_rope``
(``relayrl_tpu/models/layers/mla.py``) — ``benchmark/scope_table.py``. None
where the trace holds no module metadata or the program has no such scope
(a trunk whose latent layers rotate nothing: Kimi Linear's)."""

from benchmark import scope_table

SCOPE = "relayrl_latent_rope"


def read(run):
    return scope_table.ms_per_update(run, SCOPE)

"""Device time a scan step of the state-space recurrence's one step, all
lanes and every Mamba-2 layer: the operations under the program's scope
``relayrl_ssd`` (``relayrl_tpu/ops/ssd.ssd_step``: the read-modify-write of
each lane's float32 ``[H, P, N]`` state and ``C . h``) inside the rollout
module's ``while`` — ``benchmark/rollout_scopes.py``, self time a dispatch
over ``unroll_length``. The projections, the convolution, the gate and the
norm round it carry other names and are not in it. None where the trace holds
no module metadata or the program has no such scope in its rollout."""

from benchmark import rollout_scopes

SCOPE = "relayrl_ssd"


def read(run):
    return rollout_scopes.ms_per_scan_step(run, SCOPE)

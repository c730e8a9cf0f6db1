"""RLHF workload plane: the generate → score → update dataflow
(ISSUE 13; RLAX arXiv:2512.06392 and MindSpeed RL arXiv:2507.19017
organize LLM-scale RL exactly this way).

Pieces:

* :mod:`relayrl_tpu.envs.scorers`   — the pluggable terminal-boundary
  scorer interface with two built-ins (programmatic CI scorer, frozen
  transformer reward model);
* :mod:`relayrl_tpu.rlhf.scheduler` — the dataflow scheduler wiring
  token generation through the existing actor tiers, decoupled scoring,
  and emission into the live spool/seq/ingest machinery; off-policy lag
  between behavior and learner versions is corrected by the existing
  V-trace learner (``algorithms/impala.py`` over ``ops/vtrace.py``)
  using the behavior log-probs recorded per token at generation time.

The environment half lives in the env registries (``TokenGen-v0`` —
``envs/tokengen.py`` + the pure-JAX twin), the frozen-layer optimizer
masks in ``algorithms/freeze.py`` (the ``learner.freeze`` knob), and
the live dataflow test in ``tests/test_rlhf.py::TestLivePlane``.
"""

from relayrl_tpu.envs.scorers import (  # noqa: F401
    SCORERS,
    ProgrammaticScorer,
    RewardModelScorer,
    make_scorer,
)

__all__ = ["SCORERS", "ProgrammaticScorer", "RewardModelScorer",
           "make_scorer"]

"""Plants a fault UNDER a benchmark run for ``benchmark/tests/
test_loop_replay.py``: put this directory on ``PYTHONPATH`` and name the
fault in ``BENCH_PLANT``. It wraps the program by import, so ``run.py`` and
the driver run as they are. Without ``BENCH_PLANT``, and in every process but
``benchmark/run.py`` itself (the replay children), it does nothing.

``lose_trajectories``: the server's ingest silently loses the 10th, 20th and
30th payload it receives after the wire (an answer altered where it is
produced: the relay acknowledges nothing, so only the accounting can tell).
"""

import os
import sys

PLANT = os.environ.get("BENCH_PLANT", "")

if PLANT and sys.argv and sys.argv[0].endswith(os.path.join("benchmark",
                                                            "run.py")):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))))
    if PLANT == "lose_trajectories":
        from relayrl_tpu.runtime import server as _server

        _inner = _server.TrainingServer._on_trajectory
        _seen = {"n": 0}

        def _on_trajectory(self, agent_id, payload):
            _seen["n"] += 1
            if _seen["n"] in (10, 20, 30):
                return None
            return _inner(self, agent_id, payload)

        _server.TrainingServer._on_trajectory = _on_trajectory
    else:
        raise SystemExit(f"sitecustomize: unknown BENCH_PLANT {PLANT!r}")

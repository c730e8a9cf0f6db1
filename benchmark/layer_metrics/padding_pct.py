"""Share of the staged timesteps that are padding: 100 x (1 - valid / padded)
over the batches assembled in the traced window, from the ``valid`` and
``padded`` arguments of the program's ``rl:batch.stack`` spans (host numbers
from the padded trajectories' own lengths)."""

from benchmark import program_trace


def read(run):
    t = program_trace.of(run)
    if not t:
        return None
    stacks = [s["args"] for s in t["spans"].get("rl:batch.stack", [])
              if s["inside"] and "padded" in s["args"]]
    padded = sum(a["padded"] for a in stacks)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["valid"] for a in stacks) / padded)

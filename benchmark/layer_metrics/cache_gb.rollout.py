"""GB of decode cache in the fused scan's carry, all lanes: the host's gauge
``relayrl_actor_cache_bytes`` summed over its kinds (``rows`` at their
positions, ``state`` without; a program that does not split it has one
entry). ``run.notes["cache_gb_by_kind"]`` keeps the split. None where the
scan steps from the observation window (0 bytes) or the program has no such
gauge."""

from benchmark import actor_gauges

GAUGE = "relayrl_actor_cache_bytes"


def read(run):
    nbytes = actor_gauges.read(GAUGE)
    if not nbytes:
        return None
    by_kind = {kind: actor_gauges.read(GAUGE, kind=kind)
               for kind in ("rows", "state")}
    run.notes["cache_gb_by_kind"] = {
        kind: held / 1e9 for kind, held in by_kind.items()
        if held is not None}
    return nbytes / 1e9

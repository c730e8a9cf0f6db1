"""Mean size of the model frames the actor children received in the
window, MB (wire v2: keyframes and deltas as sent)."""


def read(run):
    size = run.counters.get("publish_bytes_mean")
    return None if not size else size / 1e6

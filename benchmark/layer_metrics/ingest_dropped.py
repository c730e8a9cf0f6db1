"""Payloads lost at ingest in the window (``server.stats["dropped"]``)."""


def read(run):
    if "dropped" not in run.stats:
        return None
    return float(run.stats["dropped"])

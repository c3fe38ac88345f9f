"""Blocking device-to-host reads per complete episode (``repro/sync`` spans
inside the episode span; ``bench/spans.py``)."""
from bench import spans


def read(ctx):
    a = spans.read(ctx)
    return None if a is None else a.counts.get("sync", 0) / a.episodes

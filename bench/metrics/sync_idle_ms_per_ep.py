"""Device idle per complete episode while the host waited on a blocking
device-to-host read (``repro/sync`` spans; ``bench/spans.py``)."""
from bench import spans


def read(ctx):
    a = spans.read(ctx)
    return None if a is None else a.per_episode_ms("sync")

"""Device idle per complete episode while the host did the program's I/O:
trajectory spill, checkpoint, CFD<->DRL interface (``repro/io.*`` spans;
``bench/spans.py``)."""
from bench import spans


def read(ctx):
    a = spans.read(ctx)
    if a is None:
        return None
    return a.per_episode_ms(*(k for k in a.idle_s if k.startswith("io.")))

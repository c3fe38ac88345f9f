"""Share of the device idle inside complete episodes that no ``repro/``
span inside the episode covers (``bench/spans.py``): what the program's
spans leave unexplained."""
from bench import spans


def read(ctx):
    a = spans.read(ctx)
    if a is None:
        return None
    total = sum(a.idle_s.values())
    return 100.0 * a.idle_s[spans.UNSPANNED] / total if total > 0 else 0.0

"""Device idle per complete episode while the host placed the env batch and
dispatched the rollout, postprocess and PPO update (``repro/collect`` and
``repro/update`` spans; ``bench/spans.py``)."""
from bench import spans


def read(ctx):
    a = spans.read(ctx)
    return None if a is None else a.per_episode_ms("collect", "update")

"""Device time of the learner programs per episode: ``postprocess`` (values,
GAE) and ``update`` (the PPO epochs), averaged over chips."""


def read(ctx):
    t = ctx["trace"]
    s = t.program_s("postprocess") + t.program_s("update")
    return 1e3 * s / ctx["episodes"] if s > 0 else None

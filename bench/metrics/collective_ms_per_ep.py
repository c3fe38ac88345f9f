"""Device time of collective operations per episode, averaged over chips."""


def read(ctx):
    t = ctx["trace"]
    if not t.has_collectives():
        return None
    return 1e3 * t.collective_s() / ctx["episodes"]

"""Device time of the rollout program (``collect_traj``: env step, solver,
Poisson sweeps, policy sampling) per episode, averaged over chips."""


def read(ctx):
    s = ctx["trace"].program_s("collect_traj")
    return 1e3 * s / ctx["episodes"] if s > 0 else None

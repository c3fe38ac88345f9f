"""The rollout's least time on one chip (the larger of its counted FLOPs over
the bf16 peak and its least bytes over HBM bandwidth, ``bench/work``) over
its measured device time per episode."""
from bench.work import least_seconds


def read(ctx):
    s = ctx["trace"].program_s("collect_traj")
    if s <= 0:
        return None
    w = ctx["work"]["rollout"]
    per_chip = {k: v / ctx["chips"] for k, v in w.items()}
    return 100.0 * least_seconds(per_chip, ctx["peaks"]) / (s / ctx["episodes"])

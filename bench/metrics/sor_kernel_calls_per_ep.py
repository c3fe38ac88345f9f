"""Calls of the pressure-solve kernels per complete episode, averaged over
chips: ``poisson_rb_sor_batched`` (every env of a chip in one call) or
``poisson_rb_sor_packed`` (one env per grid step), whichever the plan
runs; one per dt when every solve of the rollout runs in them
(``bench/kernel_ops.py``)."""
from bench import kernel_ops

KERNELS = ("poisson_rb_sor_batched", "poisson_rb_sor_packed")


def read(ctx):
    r = kernel_ops.runs(ctx["trace"], KERNELS.__contains__)
    return None if r is None else r.calls / r.episodes

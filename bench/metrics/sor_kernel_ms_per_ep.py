"""Device time of the batched pressure-solve kernel
(``poisson_rb_sor_batched``) per complete episode, averaged over chips
(``bench/kernel_ops.py``)."""
from bench import kernel_ops
from bench.metrics.sor_kernel_calls_per_ep import KERNEL


def read(ctx):
    r = kernel_ops.runs(ctx["trace"], KERNEL)
    return None if r is None else 1e3 * r.seconds / r.episodes

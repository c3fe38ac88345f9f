"""Device time of the learner's collective operations (all-reduce,
all-gather, ...) inside ``jit_update`` runs within complete episodes, per
complete episode, averaged over chips (``bench/kernel_ops.py``): what the
data-parallel update exchanges across chips."""
from bench import kernel_ops, trace_reduce


def _collective(name: str) -> bool:
    return bool(trace_reduce.COLLECTIVE.search(name))


def read(ctx):
    r = kernel_ops.runs(ctx["trace"], _collective, program="update")
    return None if r is None else 1e3 * r.seconds / r.episodes

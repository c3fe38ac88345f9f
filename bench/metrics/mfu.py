"""The whole episode's counted FLOPs (solver, policy, values and GAE, PPO
forward and backward, optimizer; ``bench/work``) times the traced episodes,
over the traced window times the chips times the bf16 peak."""


def read(ctx):
    if ctx["window_s"] <= 0 or ctx["trace"].busy_s() <= 0:
        return None
    flops = ctx["work"]["episode"]["flops"] * ctx["episodes"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peaks"]["flops_bf16"])

"""Plain reference of the learner: generalised advantage estimation and the
clipped-surrogate PPO update (Schulman et al. 2017) with AdamW and clipping
of the global gradient norm, following the trainer's documented key schedule
for the minibatch shuffles."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import policy as pol

B1, B2, EPS = 0.9, 0.999, 1e-8


def gae(reward, values, last_value, valid, gamma, lam):
    """(T,) rewards and values -> (advantages, returns); an invalid step
    zeroes its advantage and cuts the recursion."""
    nxt = jnp.concatenate([values[1:], last_value[None]])
    delta = reward + gamma * nxt - values

    def back(carry, dm):
        d, m = dm
        a = m * (d + gamma * lam * carry)
        return a, a

    _, adv = jax.lax.scan(back, jnp.zeros((), reward.dtype), (delta, valid),
                          reverse=True)
    return adv, adv + values


def loss_fn(params, rows, ppo):
    feats = pol.features(params, rows["obs"], rows["xy"], rows["mask"])
    mean, log_std = pol.mean_std(params, feats)
    logp = pol.log_prob(rows["act"], mean, log_std)
    ratio = jnp.exp(logp - rows["logp_old"])
    v = pol.value(params, feats)
    m = rows["valid"]
    n = jnp.maximum(jnp.sum(m), 1.0)

    def avg(x):
        return jnp.sum(x * m) / n

    adv = rows["adv"]
    if ppo["normalize_adv"]:
        adv = (adv - avg(adv)) / (jnp.sqrt(avg((adv - avg(adv)) ** 2)) + 1e-8)
    eps = ppo["clip_eps"]
    surr = jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - eps, 1 + eps) * adv)
    out = {"policy_loss": -avg(surr),
           "value_loss": 0.5 * avg((v - rows["ret"]) ** 2),
           "approx_kl": avg(rows["logp_old"] - logp),
           "clip_frac": avg((jnp.abs(ratio - 1) > eps).astype(v.dtype)),
           "entropy": pol.entropy(log_std)}
    loss = (out["policy_loss"] + ppo["value_coef"] * out["value_loss"]
            - ppo["entropy_coef"] * out["entropy"])
    return loss, out


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adam_init(params):
    return {"m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params)}


def adam_step(ppo, params, state, grads, step):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, ppo["max_grad_norm"] / (gn + 1e-9))
    grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    t = step.astype(jnp.float32) + 1.0
    m = jax.tree.map(lambda a, g: B1 * a + (1 - B1) * g, state["m"], grads)
    v = jax.tree.map(lambda a, g: B2 * a + (1 - B2) * g * g, state["v"], grads)
    c1 = (1 - B1 ** t)
    c2 = (1 - B2 ** t)

    def upd(p, a, b):
        mh = a / c1.astype(a.dtype)
        vh = b / c2.astype(b.dtype)
        return p - ppo["lr"] * mh / (jnp.sqrt(vh) + EPS)

    return jax.tree.map(upd, params, m, v), {"m": m, "v": v}


@functools.partial(jax.jit, static_argnames=("ppo_items",))
def ppo_update(params, opt, rows, key, step, ppo_items):
    """``epochs`` passes of ``minibatches`` shuffled splits; returns the new
    params, optimizer state, step and the metrics averaged over minibatches
    (``grad_norm`` before clipping, 0 for a rejected update)."""
    ppo = dict(ppo_items)
    n = rows["obs"].shape[0]
    mb = n // ppo["minibatches"]

    def epoch(carry, ek):
        shuf = jax.tree.map(lambda x: x[jax.random.permutation(ek, n)], rows)

        def mini(carry, i):
            params, opt, step = carry
            sl = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb), shuf)
            (_, out), grads = jax.value_and_grad(
                lambda p: loss_fn(p, sl, ppo), has_aux=True)(params)
            gn = global_norm(grads)
            ok = jnp.isfinite(gn)
            new_p, new_o = adam_step(ppo, params, opt, grads, step)
            keep = lambda a, b: jnp.where(ok, a, b)          # noqa: E731
            out = dict(out, grad_norm=jnp.where(ok, gn, 0.0))
            return ((jax.tree.map(keep, new_p, params),
                     jax.tree.map(keep, new_o, opt), step + 1),
                    jax.tree.map(lambda x: x.astype(jnp.float32), out))

        return jax.lax.scan(mini, carry, jnp.arange(ppo["minibatches"]))

    (params, opt, step), logs = jax.lax.scan(
        epoch, (params, opt, step), jax.random.split(key, ppo["epochs"]))
    return params, opt, step, jax.tree.map(jnp.mean, logs)

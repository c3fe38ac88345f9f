"""Proximal Policy Optimization (clipped surrogate, eq. 10 of the paper)."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.drl import networks
from repro.optim.optimizers import adamw, global_norm
from repro.testing import faults


@dataclass(frozen=True)
class PPOConfig:
    lr: float = 3e-4
    clip_eps: float = 0.2          # epsilon in eq. (10)
    gamma: float = 0.99
    lam: float = 0.95
    epochs: int = 10
    minibatches: int = 4
    value_coef: float = 0.5
    entropy_coef: float = 0.003
    max_grad_norm: float = 0.5
    normalize_adv: bool = True
    skip_nonfinite_grads: bool = True   # reject (don't apply) NaN/Inf updates


class Batch(NamedTuple):
    """The trailing probe-aux fields default to None (absent) so 5-field
    constructions — and pytrees serialized before the attention policy —
    keep their structure; when present they are per-sample rows that shuffle
    and slice with the rest of the batch."""
    obs: jnp.ndarray        # (N, obs_dim)
    act: jnp.ndarray        # (N, act_dim)
    logp_old: jnp.ndarray   # (N,)
    adv: jnp.ndarray        # (N,)
    ret: jnp.ndarray        # (N,)
    probe_xy: jnp.ndarray = None    # (N, obs_dim, 2)
    probe_mask: jnp.ndarray = None  # (N, obs_dim)
    valid: jnp.ndarray = None       # (N,) sentinel mask: 1 = healthy sample


def make_optimizer(cfg: PPOConfig):
    return adamw(cfg.lr, max_grad_norm=cfg.max_grad_norm)


@jax.named_scope("ppo_loss")
def ppo_loss(cfg: PPOConfig, params, batch: Batch):
    """Clipped-surrogate loss.  When the batch carries a sentinel validity
    mask, the loss is computed BOTH with the historical unmasked reductions
    and with masked ``sum(x*m)/sum(m)`` ones, and ``jnp.where(all_valid,
    healthy, degraded)`` selects per batch.  The dual path is what keeps
    all-healthy batches bitwise-identical to the unguarded program: even an
    all-ones mask changes XLA's reduction fusion enough to drift by an ulp,
    while ``where(True, x, _)`` passes the plain-path bits through exactly
    (forward and backward — the VJP of ``where`` is ``where`` of the VJPs).
    With ``valid=None`` only the historical program is emitted."""
    aux = (None if batch.probe_mask is None
           else {"xy": batch.probe_xy, "mask": batch.probe_mask})
    logp = networks.log_prob(params, batch.obs, batch.act, aux)
    ratio = jnp.exp(logp - batch.logp_old)                  # r_t(theta)
    v = networks.value(params, batch.obs, aux)

    def parts(mean_fn, std_fn):
        adv = batch.adv
        if cfg.normalize_adv:
            adv = (adv - mean_fn(batch.adv)) / (std_fn(batch.adv) + 1e-8)
        unclipped = ratio * adv
        clipped = jnp.clip(ratio, 1 - cfg.clip_eps, 1 + cfg.clip_eps) * adv
        return (-mean_fn(jnp.minimum(unclipped, clipped)),    # eq. (10)
                0.5 * mean_fn((v - batch.ret) ** 2),
                mean_fn(batch.logp_old - logp),
                mean_fn((jnp.abs(ratio - 1)
                         > cfg.clip_eps).astype(jnp.float32)))

    if batch.valid is None:
        policy_loss, value_loss, approx_kl, clip_frac = parts(jnp.mean,
                                                              jnp.std)
    else:
        m = batch.valid
        n = jnp.maximum(jnp.sum(m), 1.0)
        mmean = lambda x: jnp.sum(x * m) / n                # noqa: E731
        mstd = lambda x: jnp.sqrt(mmean((x - mmean(x)) ** 2))  # noqa: E731
        all_ok = jnp.all(m > 0.5)
        policy_loss, value_loss, approx_kl, clip_frac = (
            jnp.where(all_ok, h, d)
            for h, d in zip(parts(jnp.mean, jnp.std), parts(mmean, mstd)))
    ent = networks.entropy(params)
    loss = (policy_loss + cfg.value_coef * value_loss
            - cfg.entropy_coef * ent)
    metrics = {"policy_loss": policy_loss, "value_loss": value_loss,
               "entropy": ent, "approx_kl": approx_kl,
               "clip_frac": clip_frac}
    return loss, metrics


def ppo_update(cfg: PPOConfig, optimizer, params, opt_state, batch: Batch,
               key, step) -> Tuple[Any, Any, Dict[str, jnp.ndarray]]:
    """Full PPO update: ``epochs`` passes of ``minibatches`` shuffled splits."""
    n = batch.obs.shape[0]
    mb = n // cfg.minibatches

    def epoch(carry, ek):
        params, opt_state, step = carry
        perm = jax.random.permutation(ek, n)
        shuffled = jax.tree.map(lambda x: x[perm], batch)

        def mini(carry, i):
            params, opt_state, step = carry
            sl = jax.tree.map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb), shuffled)
            with jax.named_scope("ppo_grad"):
                (loss, metrics), grads = jax.value_and_grad(
                    lambda p: ppo_loss(cfg, p, sl), has_aux=True)(params)
            fz = faults.active("grad_nan")
            if fz is not None:   # trace-time gate: absent in production traces
                hit = step == int(fz.get("step", 0))
                bad = jnp.where(hit, jnp.float32(jnp.nan), jnp.float32(0.0))
                grads = jax.tree.map(lambda g: g + bad, grads)
            with jax.named_scope("optimizer"):
                if cfg.skip_nonfinite_grads:
                    # reject the whole update when the gradient is non-finite:
                    # params/opt_state keep their pre-update values and the skip
                    # is counted.  ``where(True, new, old)`` passes ``new``
                    # through exactly, so finite updates stay bitwise-identical
                    # to the unguarded program.  ``step`` advances either way —
                    # it indexes the schedule, not the applied-update count.
                    gnorm = global_norm(grads)
                    ok = jnp.isfinite(gnorm)
                    new_p, new_o = optimizer.update(grads, opt_state, params,
                                                    step)
                    sel = lambda n_, o_: jnp.where(ok, n_, o_)    # noqa: E731
                    params = jax.tree.map(sel, new_p, params)
                    opt_state = jax.tree.map(sel, new_o, opt_state)
                    # grad_norm reports APPLIED updates (0 when skipped): the
                    # rejected gradient is a handled fault, counted in
                    # grad_skips — it must not read as a live anomaly to the
                    # training watchdog
                    metrics = dict(metrics,
                                   grad_norm=jnp.where(ok, gnorm, 0.0),
                                   grad_skips=1.0 - ok.astype(jnp.float32))
                else:
                    params, opt_state = optimizer.update(grads, opt_state,
                                                         params, step)
            return (params, opt_state, step + 1), metrics

        (params, opt_state, step), metrics = jax.lax.scan(
            mini, (params, opt_state, step), jnp.arange(cfg.minibatches))
        return (params, opt_state, step), metrics

    keys = jax.random.split(key, cfg.epochs)
    (params, opt_state, step), metrics = jax.lax.scan(
        epoch, (params, opt_state, step), keys)
    skips = metrics.pop("grad_skips", None)
    metrics = jax.tree.map(lambda m: jnp.mean(m), metrics)
    if skips is not None:
        metrics["grad_skips"] = jnp.sum(skips)   # count, not a mean
    return params, opt_state, step, metrics

"""Plain reference of the actor-critic policies and their initialisation.

The MLP is the paper's 2x512 tanh network (Rabault et al. 2019) with a
Gaussian head of state-independent log-std; the attention policy is a pre-LN
transformer encoder over (x, y, p) probe tokens with grouped-query attention,
masked mean pooling and the same heads.  Weights are drawn from the seed with
the trainer's published initialisation (truncated normal, fan-in scaled),
following its key schedule, so the reference needs nothing from the program.
Every matrix product runs at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LOG_2PI = math.log(2 * math.pi)


def mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _dense(key, shape):
    std = 1.0 / math.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])
    return jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std


def _mlp(key, sizes):
    return [{"w": _dense(jax.random.fold_in(key, i), (a, b)),
             "b": jnp.zeros((b,), jnp.float32)}
            for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:]))]


def init(spec: dict, obs_dim: int, act_dim: int, key) -> dict:
    """``spec`` is the configuration's policy block."""
    ka, kc = jax.random.split(key)
    log_std = jnp.full((act_dim,), spec["init_log_std"], jnp.float32)
    if spec["kind"] == "mlp":
        sizes = [obs_dim] + [spec["hidden"]] * spec["depth"]
        return {"actor": _mlp(ka, sizes + [act_dim]),
                "critic": _mlp(kc, sizes + [1]), "log_std": log_std}
    d, h, hkv = spec["d_model"], spec["heads"], spec["kv_heads"]
    dh = d // h
    ke = jax.random.fold_in(ka, 1000)
    blocks = []
    for i in range(spec["layers"]):
        kq, kk, kv, ko, k1, k2 = jax.random.split(jax.random.fold_in(ke, i), 6)
        blocks.append({
            "ln1": {"g": jnp.ones(d), "b": jnp.zeros(d)},
            "wq": _dense(kq, (d, h * dh)).reshape(d, h, dh),
            "wk": _dense(kk, (d, hkv * dh)).reshape(d, hkv, dh),
            "wv": _dense(kv, (d, hkv * dh)).reshape(d, hkv, dh),
            "wo": _dense(ko, (h * dh, d)),
            "ln2": {"g": jnp.ones(d), "b": jnp.zeros(d)},
            "mlp": [{"w": _dense(k1, (d, 4 * d)), "b": jnp.zeros(4 * d)},
                    {"w": _dense(k2, (4 * d, d)), "b": jnp.zeros(d)}]})
    return {"embed": {"w": _dense(jax.random.fold_in(ke, 999), (3, d)),
                      "b": jnp.zeros(d)},
            "blocks": blocks,
            "ln_f": {"g": jnp.ones(d), "b": jnp.zeros(d)},
            "actor": _mlp(ka, [d, d, act_dim]), "critic": _mlp(kc, [d, d, 1]),
            "log_std": log_std}


def _apply_mlp(layers, x):
    for i, lyr in enumerate(layers):
        x = mm(x, lyr["w"]) + lyr["b"]
        if i < len(layers) - 1:
            x = jnp.tanh(x)
    return x


def _ln(x, p):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["g"] + p["b"]


def _encode(params, obs, xy, mask):
    """(B, P) probe values -> (B, d) pooled features."""
    tok = jnp.concatenate([xy, obs[..., None]], axis=-1) * mask[..., None]
    h = mm(tok, params["embed"]["w"]) + params["embed"]["b"]
    keep = mask[:, None, None, :] > 0
    for blk in params["blocks"]:
        x = _ln(h, blk["ln1"])
        q = jnp.einsum("bpd,dhk->bhpk", x, blk["wq"], precision=HI)
        k = jnp.einsum("bpd,dhk->bhpk", x, blk["wk"], precision=HI)
        v = jnp.einsum("bpd,dhk->bhpk", x, blk["wv"], precision=HI)
        rep = q.shape[1] // k.shape[1]      # query head i reads kv head i//rep
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        s = jnp.einsum("bhpk,bhqk->bhpq", q, k, precision=HI)
        s = s / math.sqrt(q.shape[-1])
        s = jnp.where(keep, s, -1e30)
        w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        att = jnp.einsum("bhpq,bhqk->bphk", w, v, precision=HI)
        h = h + mm(att.reshape(att.shape[0], att.shape[1], -1), blk["wo"])
        x = _ln(h, blk["ln2"])
        m1, m2 = blk["mlp"]
        h = h + mm(jnp.tanh(mm(x, m1["w"]) + m1["b"]), m2["w"]) + m2["b"]
    h = _ln(h, params["ln_f"])
    m = mask[..., None]
    return jnp.sum(h * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)


def features(params, obs, xy, mask):
    """Rows of ``obs`` (B, P) with their probe coordinates (B, P, 2) and
    live-slot mask (B, P)."""
    if "blocks" in params:
        return _encode(params, obs, xy, mask)
    return obs * mask


def mean_std(params, feats):
    return jnp.tanh(_apply_mlp(params["actor"], feats)), params["log_std"]


def value(params, feats):
    return _apply_mlp(params["critic"], feats)[..., 0]


def log_prob(act, mean, log_std):
    var = jnp.exp(2 * log_std)
    return jnp.sum(-0.5 * ((act - mean) ** 2 / var + 2 * log_std + LOG_2PI),
                   axis=-1)


def entropy(log_std):
    return jnp.sum(0.5 * (1 + LOG_2PI) + log_std)

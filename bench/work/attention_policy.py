"""The set-encoder policy: (x, y, p) probe tokens embedded to ``d_model``,
``layers`` pre-LN blocks of grouped-query attention (``heads`` query heads,
``kv_heads`` key/value heads) and a 4x tanh MLP, masked mean pooling, then
actor and critic heads of width ``d_model``.  The encoder is required once
per sample even where actor and critic both read it.  Counted per sample:
two FLOPs per multiply-add of every product, attention over all P tokens
(padded slots are masked, not skipped, by the algorithm as published);
layer norms, softmax and activations are not counted."""
from __future__ import annotations


def _encoder(spec: dict, sh: dict) -> int:
    d, h, kv, p = spec["d_model"], spec["heads"], spec["kv_heads"], sh["obs_dim"]
    dh = d // h
    per_layer = (2 * p * d * (h * dh + 2 * kv * dh)    # q, k, v
                 + 2 * 2 * h * p * p * dh              # scores and values
                 + 2 * p * h * dh * d                  # output projection
                 + 2 * 2 * p * d * 4 * d)              # MLP
    return 2 * p * 3 * d + spec["layers"] * per_layer


def _head(d: int, out: int) -> int:
    return 2 * d * d + d + 2 * d * out + out


def forward_flops(spec: dict, sh: dict, heads=("actor", "critic")) -> int:
    out = {"actor": sh["act_dim"], "critic": 1}
    return _encoder(spec, sh) + sum(_head(spec["d_model"], out[h])
                                    for h in heads)


def param_count(spec: dict, sh: dict) -> int:
    d, h, kv = spec["d_model"], spec["heads"], spec["kv_heads"]
    dh = d // h
    block = (2 * 2 * d + d * (h + 2 * kv) * dh + h * dh * d
             + d * 4 * d + 4 * d + 4 * d * d + d)
    heads = sum(d * d + d + d * o + o for o in (sh["act_dim"], 1))
    return 3 * d + d + spec["layers"] * block + 2 * d + heads + sh["act_dim"]


def param_bytes(spec: dict, sh: dict) -> int:
    return 4 * param_count(spec, sh)

"""The paper's MLP actor-critic (Rabault et al. 2019): two tanh layers of
``hidden`` units per head.  A forward pass of one sample costs two FLOPs per
weight (multiply, add) plus one per bias; the tanh and the Gaussian head's
few operations are not counted."""
from __future__ import annotations


def _sizes(spec: dict, sh: dict, out: int) -> list:
    return [sh["obs_dim"]] + [spec["hidden"]] * spec["depth"] + [out]


def _head(sizes) -> int:
    return sum(2 * a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops(spec: dict, sh: dict, heads=("actor", "critic")) -> int:
    out = {"actor": sh["act_dim"], "critic": 1}
    return sum(_head(_sizes(spec, sh, out[h])) for h in heads)


def param_count(spec: dict, sh: dict) -> int:
    return sum(sum(a * b + b for a, b in zip(s[:-1], s[1:]))
               for s in (_sizes(spec, sh, sh["act_dim"]),
                         _sizes(spec, sh, 1))) + sh["act_dim"]


def param_bytes(spec: dict, sh: dict) -> int:
    return 4 * param_count(spec, sh)

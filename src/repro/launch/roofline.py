"""Roofline terms from compiled dry-run artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOPs
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_bytes_per_device / ICI_link_bw

The SPMD-partitioned HLO is per-device, so analyzer outputs plug in directly.
MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE; 2·N·tokens for single-token
decode) anchors the "useful compute" ratio.

The hardware constants the terms divide by are a :class:`HardwareSpec`, NOT
module constants: every roofline is relative to a named device preset
(``tpu_v5e``, ``cpu_generic``, ...), selected explicitly, via the
``$REPRO_HW_SPEC`` environment variable, or detected from the ``device_kind``
JAX reports for the first device (:data:`DEVICE_KINDS`).  A device kind that
is not in that table raises instead of silently pricing the workload at
another chip's numbers.
"""
from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass
from typing import Dict, Optional, Union

import jax
import numpy as np

from repro.configs.base import InputShape, ModelConfig


@dataclass(frozen=True)
class HardwareSpec:
    """Peak rates a roofline prices against — one device (chip or core).

    ``ici_bw`` is the per-link interconnect bandwidth the collective term
    divides by; single-device presets keep a nominal loopback figure so the
    term stays defined (it is zero whenever coll_bytes is zero).
    """
    name: str
    peak_flops: float            # FLOP/s per device
    hbm_bw: float                # main-memory bytes/s per device
    ici_bw: float                # interconnect bytes/s per link
    description: str = ""
    source: str = ""             # where the peak numbers come from

    def to_dict(self) -> Dict:
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bw": self.hbm_bw, "ici_bw": self.ici_bw}


HARDWARE_PRESETS: Dict[str, HardwareSpec] = {
    "tpu_v5e": HardwareSpec(
        name="tpu_v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        description="TPU v5e chip: bf16 peak, HBM2e, ICI per link "
                    "(~per direction)",
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip ICI "
               "(4 links)"),
    "cpu_generic": HardwareSpec(
        name="cpu_generic", peak_flops=5e10, hbm_bw=2e10, ici_bw=1e10,
        description="one generic x86 core: ~50 GFLOP/s sustained f32 FMA, "
                    "~20 GB/s sustained DRAM, loopback interconnect"),
}

# ``jax.Device.device_kind`` -> preset, for detection on the running host
DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
}

# environment override consulted when no spec is passed explicitly
HW_SPEC_ENV = "REPRO_HW_SPEC"


def hardware_spec(name: Union[None, str, HardwareSpec] = None
                  ) -> HardwareSpec:
    """Resolve the hardware a roofline prices against.

    Precedence: explicit ``name`` (a preset name or a HardwareSpec, passed
    through) > the ``$REPRO_HW_SPEC`` preset name > detection from the
    first device's ``device_kind`` through :data:`DEVICE_KINDS`.  Anything
    unrecognized raises a ValueError — a roofline against silently-wrong
    peak numbers is worse than no roofline.  Detection never picks
    ``cpu_generic``: a host CPU is priced only when the caller names it.
    """
    if isinstance(name, HardwareSpec):
        return name
    if name is None:
        name = os.environ.get(HW_SPEC_ENV) or None
    if name is not None:
        try:
            return HARDWARE_PRESETS[name]
        except KeyError:
            raise ValueError(
                f"unknown hardware spec {name!r}; choose a preset from "
                f"{sorted(HARDWARE_PRESETS)} (or pass a HardwareSpec with "
                f"your device's peak_flops/hbm_bw/ici_bw)") from None
    kind = jax.devices()[0].device_kind
    detected = DEVICE_KINDS.get(kind)
    if detected is None:
        raise ValueError(
            f"no hardware preset for device_kind {kind!r} (known kinds: "
            f"{sorted(DEVICE_KINDS)}); pass one of {sorted(HARDWARE_PRESETS)} "
            f"explicitly (hw= / ${HW_SPEC_ENV}) or a HardwareSpec with your "
            f"device's peak numbers")
    return HARDWARE_PRESETS[detected]


def param_count(cfg: ModelConfig, params_shape) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params_shape)))


def active_param_count(cfg: ModelConfig, params_shape) -> int:
    """MoE: only top_k (+shared) experts per token are active."""
    total = 0
    flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        n = int(np.prod(leaf.shape))
        if cfg.moe is not None and any(
                path.endswith(s) for s in ("we1", "we2", "we3")):
            n = n * cfg.moe.top_k // cfg.moe.num_experts
        total += n
    return total


def _encoder_param_count(params_shape) -> int:
    total = 0
    flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        if path.startswith("encoder/"):
            total += int(np.prod(leaf.shape))
    return total


def model_flops(cfg: ModelConfig, shape: InputShape, params_shape) -> float:
    n_active = active_param_count(cfg, params_shape)
    n_enc = _encoder_param_count(params_shape) if cfg.is_encdec else 0
    tokens = shape.global_batch * (1 if shape.kind == "decode"
                                   else shape.seq_len)
    mult = 6.0 if shape.kind == "train" else 2.0
    fl = mult * (n_active - n_enc) * tokens
    if n_enc and shape.kind != "decode":
        # encoder runs over the (downsampled) frontend token stream
        from repro.models import frontend as fe_mod
        t_enc = shape.global_batch * fe_mod.num_frontend_tokens(
            cfg, shape.seq_len)
        fl += mult * n_enc * t_enc
    return fl


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    model_flops: float
    coll_by_kind: Dict[str, float]
    # the device the terms price against; None resolves through
    # hardware_spec() (explicit > $REPRO_HW_SPEC > device_kind detection)
    hw: Optional[HardwareSpec] = None

    def __post_init__(self):
        if self.hw is None:
            self.hw = hardware_spec()

    @property
    def compute_s(self) -> float:
        return self.flops_per_dev / self.hw.peak_flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_dev / self.hw.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_dev / self.hw.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (global HLO flops) — remat/masking/dispatch waste."""
        total = self.flops_per_dev * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def mfu_bound(self) -> float:
        """Upper bound on model-FLOPs utilization implied by the terms."""
        ideal = self.model_flops / (self.n_devices * self.hw.peak_flops)
        return ideal / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "n_devices": self.n_devices,
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "coll_by_kind": self.coll_by_kind,
            "model_flops": self.model_flops,
            "hw": self.hw.to_dict(),
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_ratio": self.useful_ratio, "mfu_bound": self.mfu_bound,
        }


def build(arch: str, shape_name: str, mesh_name: str, n_devices: int,
          analyzed: Dict[str, float], model_fl: float,
          hw: Union[None, str, HardwareSpec] = None) -> Roofline:
    coll_by_kind = {k[len("coll_"):]: v for k, v in analyzed.items()
                    if k.startswith("coll_") and k != "coll_bytes"}
    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, n_devices=n_devices,
        flops_per_dev=analyzed.get("flops", 0.0),
        bytes_per_dev=analyzed.get("bytes", 0.0),
        coll_bytes_per_dev=analyzed.get("coll_bytes", 0.0),
        model_flops=model_fl, coll_by_kind=coll_by_kind,
        hw=hardware_spec(hw))

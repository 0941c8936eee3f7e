"""Three-term roofline model from the compiled dry-run artifact.

    compute    = HLO_FLOPs / peak_FLOPs_per_chip
    memory     = HLO_bytes / HBM_bw_per_chip
    collective = collective_bytes / (links * link_bw)

All inputs are per-chip (cost_analysis and the parsed HLO are post-SPMD).
Hardware constants come from a preset table (``PRESETS``) selected
explicitly or by backend detection (``detect_preset``, TPUs keyed by
``device_kind``); the default stays TPU v5e — 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI — so existing callers are unchanged.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HWPreset:
    """Per-chip hardware ceilings for one accelerator target."""

    name: str
    peak_flops: float       # FLOP/s (dense matmul, bf16 or vendor peak)
    hbm_bw: float           # bytes/s main-memory bandwidth
    ici_bw: float           # bytes/s per interconnect link
    ici_links: int = 1      # links counted as serializing collectives


PRESETS = {
    # TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~4 usable ICI links/chip
    # but conservatively count 1 link serializing all collective traffic
    "tpu-v5e": HWPreset("tpu-v5e", 197e12, 819e9, 50e9, 1),
    # A100-class GPU: 312 TFLOP/s bf16, 2.04 TB/s HBM2e, 600 GB/s NVLink
    "gpu": HWPreset("gpu", 312e12, 2.04e12, 600e9, 1),
    # server-class CPU socket: ~1 TFLOP/s f32, ~100 GB/s DDR, ~10 GB/s
    # inter-socket — only useful for relative tile ranking, not absolute
    # time prediction
    "cpu": HWPreset("cpu", 1e12, 100e9, 10e9, 1),
}

# module-level constants kept for back-compat (dryrun.py and older tests
# read them); they mirror the default preset
_DEFAULT = PRESETS["tpu-v5e"]
PEAK_FLOPS = _DEFAULT.peak_flops
HBM_BW = _DEFAULT.hbm_bw
ICI_BW = _DEFAULT.ici_bw
ICI_LINKS = _DEFAULT.ici_links


# TPU peaks keyed by the ``device_kind`` JAX reports for the chip
TPU_KINDS = {"TPU v5 lite": "tpu-v5e"}


def preset_for(backend: str, device_kind: str) -> HWPreset:
    """The preset of a backend and device kind: a TPU by its kind (an
    unknown TPU kind is an error, not a default), ``gpu``/``cuda``/
    ``rocm`` -> gpu, ``cpu`` -> cpu."""
    if backend == "tpu":
        if device_kind not in TPU_KINDS:
            raise ValueError(f"no roofline preset for TPU kind "
                             f"{device_kind!r} (known: {sorted(TPU_KINDS)})")
        return PRESETS[TPU_KINDS[device_kind]]
    if backend in ("gpu", "cuda", "rocm"):
        return PRESETS["gpu"]
    if backend == "cpu":
        return PRESETS["cpu"]
    raise ValueError(f"no roofline preset for backend {backend!r}")


def detect_preset() -> HWPreset:
    """The preset of the live JAX backend's first device."""
    import jax
    return preset_for(jax.default_backend(), jax.devices()[0].device_kind)


def resolve_preset(name: str | None) -> HWPreset:
    """Preset by name; ``None`` or ``"auto"`` detects from the backend."""
    if name is None or name == "auto":
        return detect_preset()
    return PRESETS[name]


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    collective_bytes: float
    hw: HWPreset = _DEFAULT

    @property
    def t_compute(self):
        return self.flops / self.hw.peak_flops

    @property
    def t_memory(self):
        return self.hbm_bytes / self.hw.hbm_bw

    @property
    def t_collective(self):
        return self.collective_bytes / (self.hw.ici_bw * self.hw.ici_links)

    @property
    def dominant(self):
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self):
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self):
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "hw": self.hw.name,
        }


def model_flops(cfg, shape, params_active: float) -> float:
    """6·N·D reference FLOPs (N = active params, D = tokens) — global."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * params_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * params_active * tokens
    # decode: one token per sequence
    return 2.0 * params_active * shape.global_batch


def active_params(cfg, total_params: float) -> float:
    """Active (per-token) parameter count for MoE archs."""
    if cfg.moe is None:
        return total_params
    m = cfg.moe
    dff = m.d_ff_expert or cfg.d_ff
    per_expert = 3 * cfg.d_model * dff
    n_layers_moe = sum(cfg.moe_pattern) * (cfg.num_layers // len(cfg.moe_pattern))
    inactive = per_expert * (m.num_experts - m.top_k) * n_layers_moe
    return total_params - inactive

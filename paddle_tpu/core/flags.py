"""Global flag registry — env-overridable runtime knobs.

Mirrors the reference's three-tier flag system (gflags `PD_DEFINE_EXPORTED_*` in
paddle/phi/core/flags.cc, settable via env `FLAGS_x` or `paddle.set_flags`).
Flags are defined here in one registry, overridable from the environment at import
time (`FLAGS_check_nan_inf=1 python train.py`) or from code via `set_flags`.
"""

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict


@dataclass
class _Flag:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str
    value: Any = None


_REGISTRY: Dict[str, _Flag] = {}


def _parse_bool(s):
    return str(s).lower() in ("1", "true", "yes", "on")


def define_flag(name, default, help="", parser=None):
    if parser is None:
        if isinstance(default, bool):
            parser = _parse_bool
        elif isinstance(default, int):
            parser = int
        elif isinstance(default, float):
            parser = float
        else:
            parser = str
    value = default
    env = os.environ.get(name)
    if env is not None:
        value = parser(env)
    _REGISTRY[name] = _Flag(name, default, parser, help, value)
    return value


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if k not in _REGISTRY:
            raise KeyError(f"Unknown flag {k!r}. Known: {sorted(_REGISTRY)}")
        f = _REGISTRY[k]
        f.value = f.parser(v) if isinstance(v, str) else v


def get_flags(flags=None):
    if flags is None:
        return {k: f.value for k, f in _REGISTRY.items()}
    if isinstance(flags, str):
        flags = [flags]
    return {k: _REGISTRY[k].value for k in flags}


def flag(name):
    return _REGISTRY[name].value


# ---- Core flags (parity with the reference's commonly used FLAGS_*) --------
define_flag("FLAGS_check_nan_inf", False, "Scan op outputs/grads for NaN/Inf each step")
define_flag("FLAGS_deterministic", False, "Force deterministic ops where possible")
define_flag("FLAGS_allocator_strategy", "xla_bfc", "Informational: XLA owns allocation on TPU")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.9, "Mapped to XLA mem fraction knob")
define_flag("FLAGS_use_pallas_kernels", True, "Use Pallas fusion kernels when on TPU")
define_flag("FLAGS_fused_decode", True, "Use the fused decode-step path (fused_multi_transformer analog) in generate()")
define_flag("FLAGS_vmem_mib", 0, "Override the device VMEM capacity (MiB) used for Pallas kernel budgets; 0 = derive from device_kind")
define_flag("FLAGS_pallas_interpret", False, "Off-TPU, run Pallas kernels in interpret mode instead of the XLA fallback (CPU-CI kernel parity)")
define_flag("FLAGS_log_level", "INFO", "paddle_tpu logger level")
define_flag("FLAGS_profile_dir", "", "If set, jax.profiler traces are written here")
define_flag("FLAGS_benchmark", False, "Print per-step timing")

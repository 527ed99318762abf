"""Training metrics: TGS (paper's metric), MFU, step time, compiles per step."""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field
from typing import Optional

import jax

# Published dense bf16 peak per chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).  A kind
# not listed here is an error, never a default.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device) -> Optional[float]:
    """bf16 peak FLOP/s of one `device`; None on the CPU, which has no MFU."""
    if device.platform == "cpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(f"no published peak for device kind "
                         f"{device.device_kind!r}; add it to "
                         f"PEAK_BF16_FLOPS") from None


# JAX records the first event once per program it compiles *or* loads from
# the persistent compilation cache, and the second once per cache load.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_counts = {_COMPILE_EVENT: 0, _CACHE_HIT_EVENT: 0}
_listening = False


def _count(event: str, *_, **__) -> None:
    if event in _counts:
        _counts[event] += 1


def compile_stats() -> tuple:
    """(programs compiled or loaded from the persistent cache, how many of
    them were loaded from it) since the first call."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_count)
        jax.monitoring.register_event_listener(_count)
        _listening = True
    return _counts[_COMPILE_EVENT], _counts[_CACHE_HIT_EVENT]


@dataclass
class Meter:
    n_chips: int
    tokens_per_step: int
    n_active_params: int
    peak_flops: Optional[float] = None   # per chip; None: report no MFU
    history: list = field(default_factory=list)
    _t0: Optional[float] = None
    _programs0: int = 0

    def start(self):
        self._programs0 = compile_stats()[0]
        self._t0 = time.perf_counter()

    def stop(self, step: int, loss: float) -> dict:
        dt = time.perf_counter() - self._t0
        tgs = self.tokens_per_step / dt / self.n_chips  # tokens/chip/s (§7)
        mfu = (None if self.peak_flops is None else
               6 * self.n_active_params * self.tokens_per_step / dt
               / (self.n_chips * self.peak_flops))
        rec = {"step": step, "loss": float(loss), "dt": dt,
               "tgs": tgs, "mfu": mfu,
               "compiles": compile_stats()[0] - self._programs0}
        self.history.append(rec)
        return rec

    def dump(self, path: str):
        with open(path, "w") as f:
            json.dump(self.history, f, indent=1)

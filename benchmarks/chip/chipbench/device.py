"""The device side of a run: find the chips, keep the compile cache at a
fixed path, count compilations, read the memory peak, derive keys from a
seed."""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

from chipbench.spec import BENCH_DIR

# Fixed, inside the checkout: the path is part of the cache's key.
CACHE_DIR = BENCH_DIR / ".cache" / "jax"

# Events JAX records for every trace of a Python function to a jaxpr and
# for every backend compilation, a persistent-cache hit included.
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require(chips: int, platform: str = "tpu"):
    """The devices a cell runs on; raises :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoChip(f"no {platform}: JAX found {devices[0].platform!r} "
                     "devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at one fixed path in the
    checkout, for every program however short its compile."""
    import jax

    path = CACHE_DIR
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileCounter:
    """Counts traces and compilations while active (a window must have
    none: every shape it uses was warmed up in set-up)."""

    def __init__(self):
        self.count = 0
        self.active = False

    def _listener(self, event, duration, **_):
        if self.active and event in _COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listener)
        self.active = True
        return self

    def __exit__(self, *exc):
        import jax

        self.active = False
        jax.monitoring.unregister_event_duration_listener(self._listener)
        return False


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def describe(devices, memory_peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}


@dataclasses.dataclass(frozen=True)
class Seeds:
    """Independent streams from one ``--seed`` (any whole number, also one
    wider than 32 bits): ``key(i)`` for JAX, ``rng(i)`` for NumPy."""

    seed: int

    def words(self, stream: int) -> np.ndarray:
        return np.random.SeedSequence([self.seed, stream]).generate_state(2)

    def key(self, stream: int):
        import jax

        w = self.words(stream)
        return jax.random.fold_in(jax.random.PRNGKey(int(w[0] >> 1)),
                                  int(w[1] >> 1))

    def rng(self, stream: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence([self.seed,
                                                             stream]))


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)

"""The accelerator check, the persistent compile cache, the compile clock
and the table of peaks. Copied from the repository's `chip_smoke.py`, so
that a change to the program cannot move them."""

from __future__ import annotations

import json
import os

import jax

# The event JAX records around each backend compile (or cache lookup).
BACKEND_COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     'peaks.json')


class NoAccelerator(RuntimeError):
    pass


def require_tpu(chips: int, devices=None) -> list:
    """The first `chips` TPU devices; raises when JAX finds another
    platform or fewer chips. There is no CPU fallback."""
    devices = jax.devices() if devices is None else devices
    platform = devices[0].platform if devices else 'none'
    if platform != 'tpu':
        raise NoAccelerator(f'needs a TPU, found platform {platform!r}')
    if len(devices) < chips:
        raise NoAccelerator(f'needs {chips} chips, found {len(devices)}')
    return list(devices[:chips])


class CompileClock:
    """Counts and sums JAX's backend-compile events (a persistent-cache
    hit records only its retrieval time)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def setup_compile_cache(root: str) -> str:
    """JAX's persistent compile cache: the directory JAX_COMPILATION_CACHE_DIR
    names (JAX reads it itself), else `<root>/.jax_cache`, one fixed path
    inside the checkout, so a later run there finds what this one wrote."""
    cache = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not cache:
        cache = os.path.join(root, '.jax_cache')
        jax.config.update('jax_compilation_cache_dir', cache)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    return cache


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """Published peaks of one chip of `device_kind`; an unknown kind is an
    error, not a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table['devices']:
        raise KeyError(f'no peaks for device kind {device_kind!r}; known: '
                       f"{sorted(table['devices'])}")
    return table['devices'][device_kind]


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of `devices`, where the backend
    reports it."""
    peaks_ = [(d.memory_stats() or {}).get('peak_bytes_in_use')
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None

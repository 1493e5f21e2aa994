"""Production + fake mesh construction.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (device counts lock on first backend initialization).
"""
from __future__ import annotations

import os
from typing import Sequence, Tuple

import jax

#: The XLA flag that splits the host CPU into N fake devices — the CI/dev
#: substrate for every multi-device test and benchmark in this repo.
FAKE_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def _auto_mesh(shape: Sequence[int], axes: Sequence[str],
               devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: sharding stays a layout
    hint that the compiler propagates, so slicing a sharded result and
    ``with_sharding_constraint`` work as on an unannotated program."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The target deployment mesh.

    single-pod:  (16, 16)    = ("data", "model")         — 256 chips
    multi-pod:   (2, 16, 16) = ("pod", "data", "model")  — 512 chips
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"production mesh {dict(zip(axes, shape))} needs {need} devices "
            f"but this runtime has {have}. For local/CI development use "
            f"fake_mesh(n) with XLA_FLAGS={FAKE_DEVICES_FLAG}={need} "
            f"(or smoke_mesh() for whatever devices exist).")
    return _auto_mesh(shape, axes)


def smoke_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist, as a 1D 'data' mesh (CPU tests)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def _balanced_grid(n: int) -> Tuple[int, int]:
    """``n`` as the most-square ``(rows, cols)`` factorization, rows ≤ cols
    — 1→(1,1), 2→(1,2), 4→(2,2), 8→(2,4)."""
    best = (1, n)
    r = 1
    while r * r <= n:
        if n % r == 0:
            best = (r, n // r)
        r += 1
    return best


def fake_mesh(n: int, axes: Sequence[str] = ("data", "model")
              ) -> jax.sharding.Mesh:
    """An ``n``-device 2-D mesh over fake host devices — the CI substrate
    for the distributed suite and the sharded scaling benchmarks.

    Requires the process to have been started with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (N ≥ ``n``):
    the flag must be set *before* jax initializes its backend, so this
    function can only check, not fix, a missing flag — hence the loud error
    instead of a silent 1-device mesh.
    """
    axes = tuple(axes)
    if len(axes) != 2:
        raise ValueError(f"fake_mesh needs exactly 2 axis names, got {axes}")
    have = len(jax.devices())
    if have < n:
        flags = os.environ.get("XLA_FLAGS", "")
        raise ValueError(
            f"fake_mesh({n}) needs {n} devices but jax sees {have}. Start "
            f"the process with XLA_FLAGS='{FAKE_DEVICES_FLAG}={n}' (before "
            f"jax initializes; current XLA_FLAGS={flags!r}).")
    rows, cols = _balanced_grid(n)
    return _auto_mesh((rows, cols), axes, devices=jax.devices()[:n])

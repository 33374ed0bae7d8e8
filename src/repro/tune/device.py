"""Device introspection for the autotuner and the codegen fusion gate.

The gather-fused kernels keep their whole ungathered source block resident
in VMEM and their gather/slot maps scalar-prefetched into SMEM, so the gate
that picks fusion must know both capacities of the device that runs the
kernel. There is no public memory query in JAX, so the sizes come from a
table keyed by ``Device.device_kind``. Each entry is what the TPU compiler
reports as the capacity when a kernel over-allocates that memory space,
compiled for a described topology of that generation
(``jax.experimental.topologies``). A TPU kind missing from the table is an
error: a guessed size would hand the compiler kernels it refuses.

On the CPU (tests and interpret mode) the gate models a v5e, the chip the
repository targets, so interpret-mode runs take the fusion decisions the
compiled kernels take there.

This module deliberately imports nothing from ``repro`` so that
``core/codegen.py`` can use it without an import cycle (the tuner imports
codegen, codegen imports only this leaf).
"""
from __future__ import annotations

import functools
import os

import jax

_MIB = 1024 * 1024

# device_kind -> (VMEM bytes, SMEM bytes) per core.
_MEMORY_BY_KIND = {
    "TPU v4": (16 * _MIB, 1 * _MIB),
    "TPU v5": (64 * _MIB, 1 * _MIB),
    "TPU v5 lite": (128 * _MIB, 1 * _MIB),
    "TPU v6 lite": (128 * _MIB, 1 * _MIB),
}
_CPU_MODEL_KIND = "TPU v5 lite"

# Fractions of each memory the fused-gather kernels may claim: the resident
# source block (lane-padded, double-buffered) in VMEM, and every
# scalar-prefetched operand in SMEM. The rest stays free for the kernel's
# own blocks and the compiler's bookkeeping.
_FUSED_GATHER_VMEM_FRACTION = 0.25
_FUSED_GATHER_SMEM_FRACTION = 0.5

VMEM_ENV = "REPRO_VMEM_BYTES"
BUDGET_ENV = "REPRO_FUSED_GATHER_BUDGET_BYTES"


@functools.lru_cache(maxsize=None)
def device_kind() -> str:
    """Stable, key-safe identifier of the default device, e.g.
    ``cpu`` or ``tpu:TPU v5 lite``. Part of every tuning-cache key so
    decisions measured on one part are never replayed on another. Raises
    for a TPU kind whose memory sizes are not in the table."""
    dev = jax.devices()[0]
    if dev.platform == "tpu" and dev.device_kind not in _MEMORY_BY_KIND:
        raise RuntimeError(
            f"unknown TPU kind {dev.device_kind!r}: add its VMEM/SMEM sizes "
            f"to tune/device.py (known: {sorted(_MEMORY_BY_KIND)})")
    kind = str(dev.device_kind).strip().replace("|", "/")
    return dev.platform if kind == dev.platform else f"{dev.platform}:{kind}"


def _memory() -> tuple:
    kind = device_kind()
    if kind.startswith("tpu:"):
        return _MEMORY_BY_KIND[kind[4:]]
    return _MEMORY_BY_KIND[_CPU_MODEL_KIND]


def vmem_bytes() -> int:
    """VMEM per core of the default device (env-overridable)."""
    env = os.environ.get(VMEM_ENV)
    if env:
        return int(env)
    return _memory()[0]


def smem_bytes() -> int:
    """SMEM per core of the default device."""
    return _memory()[1]


def fused_gather_budget_bytes() -> int:
    """VMEM bytes the fused-gather kernels may keep resident for their
    source block, derived from the device's VMEM.
    ``REPRO_FUSED_GATHER_BUDGET_BYTES`` overrides the derived value."""
    env = os.environ.get(BUDGET_ENV)
    if env:
        return int(env)
    return int(vmem_bytes() * _FUSED_GATHER_VMEM_FRACTION)


def resident_vmem_bytes(rows: int, width: int, itemsize: int) -> int:
    """VMEM a resident ``[rows, width]`` block takes: rows padded to the
    8-row sublane tile, width to the 128-lane tile, two pipeline buffers."""
    return 2 * (-(-rows // 8) * 8) * (-(-width // 128) * 128) * itemsize


def fused_gather_fits(rows: int, width: int, itemsize: int, slots: int,
                      tile: int) -> bool:
    """Whether a gather-fused kernel over a ``[rows, width]`` source fits
    the device: the source block against the VMEM budget, and the
    scalar-prefetched operands — a ``slots``-entry int32 gather map plus
    the per-tile table (two int32 per ``tile``-slot tile) — against SMEM.
    The in-kernel row gather moves 32-bit rows only: the chip's compiler
    refuses single-row slices of packed (16-bit) arrays."""
    prefetch = 4 * (slots + 2 * -(-slots // max(1, tile)))
    return (itemsize == 4
            and resident_vmem_bytes(rows, width, itemsize)
            <= fused_gather_budget_bytes()
            and prefetch <= int(smem_bytes() * _FUSED_GATHER_SMEM_FRACTION))

"""Single source of kernel tiling constants.

Every Pallas kernel in this package tiles its inputs into N_BLOCK-point
blocks and S_BLOCK-slot blocks.  Per-point and per-slot vectors travel as
``(1, N)`` / ``(1, S)`` rows cut into ``(1, block)`` blocks: on the TPU a
block's last two dims must be a multiple of (8, 128) or the full extent,
and 1-D blocks smaller than 1024 mismatch XLA's layout.  The per-kernel
defaults used to be duplicated literals in each kernel module; they now
live here so a TPU tuning pass edits one table (or installs a runtime
override) instead of chasing copies.

``ROW_ALIGN`` is the row-count alignment for stacked stat-row matrices
fed to the MXU (pad ``R`` up to a multiple of 8 so the ``(R, N)`` operand
tiles cleanly).

Overrides are process-wide and must be installed *before* the first call
of the kernel they target: the jitted wrappers resolve block sizes at
trace time, so a kernel that has already traced keeps its old blocks
until its jit cache is dropped.  This is a process-start tuning knob
(e.g. a TPU sweep harness), not a per-call parameter — per-call control
is the ``block``/``n_block``/``s_block`` arguments the wrappers already
take.

Stdlib-only on purpose: this module sits inside the EDG001-checked
import closure of ``repro.kernels``.
"""

from __future__ import annotations

ROW_ALIGN = 8

# kernel name -> (N_BLOCK, S_BLOCK)
_DEFAULT_BLOCKS: dict[str, tuple[int, int]] = {
    "stratified_stats": (512, 512),
    "edge_reduce": (512, 512),
    "sample_mask": (1024, 512),
    "edge_megakernel": (512, 512),
    # geohash is 1-D (no stratum axis); S_BLOCK is unused but kept for
    # table uniformity.
    "geohash": (2048, 1),
}

_overrides: dict[str, tuple[int, int]] = {}


def kernel_blocks(kernel: str) -> tuple[int, int]:
    """Return ``(n_block, s_block)`` for ``kernel`` (override-aware)."""
    if kernel in _overrides:
        return _overrides[kernel]
    try:
        return _DEFAULT_BLOCKS[kernel]
    except KeyError:
        raise KeyError(
            f"unknown kernel {kernel!r}; known: {sorted(_DEFAULT_BLOCKS)}"
        ) from None


def set_block_override(
    kernel: str, *, n_block: int | None = None, s_block: int | None = None
) -> None:
    """Install a process-wide block-size override for one kernel.

    Must run before the kernel's first trace (see module docstring).
    Blocks should stay multiples of the TPU lane width (128); that is
    the caller's responsibility — this table does not validate against
    a particular generation's tile shapes.
    """
    cur_n, cur_s = kernel_blocks(kernel)
    _overrides[kernel] = (
        int(n_block) if n_block is not None else cur_n,
        int(s_block) if s_block is not None else cur_s,
    )


def clear_block_overrides() -> None:
    """Drop all overrides (tests / tuning sweeps)."""
    _overrides.clear()

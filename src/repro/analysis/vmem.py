"""Static VMEM budgets for the three Pallas kernel families.

The kernel block/tile constants are justified by the DESIGN.md Section 2.5
math; this module *evaluates* that math for a candidate configuration
against a per-platform budget, so an oversized block fails at lint time
with the arithmetic in the message instead of at Mosaic compile time (or,
worse, only on hardware).

Footprints model the per-grid-step VMEM residency of each kernel (B keys
of w bytes per block):

network pass         in+out blocks double buffered (4*B*w), six live
(block sort, VMEM    key temporaries per compare-exchange step — the
 merge)              block, two rolled copies, partner, min, max
                     (6*B*w) — and two int32 index planes (2*B*4)
HBM strided pass     a pair of cols-key tiles, in+out double buffered
                     (8*cols*w), plus min and max (2*cols*w)
probe histogram      (T,) key tile double buffered (2*T*w), the (M, 128)
                     probe block and (M, 128) int32 accumulator, each
                     double buffered, and two (M, 128) temporaries

All sizes are rounded up to the platform's native tile (8x128 lanes on
TPU) before costing, the way Mosaic lays them out.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from repro.kernels.bitonic_sort import ops as bitonic_ops
from repro.kernels.histogram import ops as histogram_ops
from repro.kernels.merge import kernel as merge_kernel

__all__ = [
    "VmemBudgetError",
    "KernelFootprint",
    "vmem_budget_bytes",
    "block_sort_footprint",
    "merge_block_footprint",
    "hbm_pass_footprint",
    "histogram_footprint",
    "check_kernel_budgets",
    "default_footprints",
]

#: Usable VMEM per core. TPU cores expose ~16 MiB; we budget against a
#: reserve so the kernel coexists with surrounding buffers (semaphores,
#: scalar prefetch, the compiler's own scratch).
PLATFORM_VMEM_BYTES = {"tpu": 16 * 1024 * 1024}
RESERVE_FRACTION = 0.25          # leave 25% for the compiler and neighbors
TILE_SUBLANES, TILE_LANES = 8, 128   # f32 native tile


class VmemBudgetError(AssertionError):
    """A kernel configuration exceeds the platform VMEM budget."""


def vmem_budget_bytes(platform: str = "tpu") -> int:
    total = PLATFORM_VMEM_BYTES[platform]
    return int(total * (1 - RESERVE_FRACTION))


def _tiled(n: int) -> int:
    """Elements of a 1-D block after padding to the native (8,128) tile."""
    tile = TILE_SUBLANES * TILE_LANES
    return -(-n // tile) * tile


@dataclasses.dataclass(frozen=True)
class KernelFootprint:
    family: str                 # "bitonic_sort" | "merge" | "histogram"
    config: str                 # human-readable parameter string
    vmem_bytes: int             # modeled per-grid-step residency
    formula: str                # the arithmetic, for the failure message

    def check(self, platform: str = "tpu") -> "KernelFootprint":
        budget = vmem_budget_bytes(platform)
        if self.vmem_bytes > budget:
            raise VmemBudgetError(
                f"{self.family}[{self.config}] needs "
                f"{self.vmem_bytes} B of VMEM ({self.formula}) but the "
                f"{platform} budget is {budget} B "
                f"({PLATFORM_VMEM_BYTES[platform]} B minus "
                f"{RESERVE_FRACTION:.0%} reserve)")
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _network_footprint(family: str, block: int,
                       itemsize: int) -> KernelFootprint:
    b = _tiled(block)
    return KernelFootprint(
        family=family, config=f"block={block},w={itemsize}",
        vmem_bytes=10 * b * itemsize + 2 * b * 4,
        formula=f"10*{b}*{itemsize} + 2*{b}*4")


def block_sort_footprint(block: int, itemsize: int = 4) -> KernelFootprint:
    """The bitonic block sort: one network pass over B-key blocks."""
    return _network_footprint("bitonic_sort", block, itemsize)


def merge_block_footprint(block: int, itemsize: int = 4) -> KernelFootprint:
    """The VMEM merge stages: one network pass over B-key blocks."""
    return _network_footprint("merge", block, itemsize)


def hbm_pass_footprint(cols: int, itemsize: int = 4) -> KernelFootprint:
    """Strided HBM pass: a pair of cols-key tiles in and out, double
    buffered, plus min and max."""
    c = _tiled(cols)
    return KernelFootprint(
        family="merge", config=f"hbm_pass,cols={cols},w={itemsize}",
        vmem_bytes=10 * c * itemsize, formula=f"10*{c}*{itemsize}")


def histogram_footprint(tile: int, m: int, itemsize: int = 4,
                        ) -> KernelFootprint:
    """Probe-rank step: key tile + (M, 128) probes, accumulator and two
    temporaries."""
    t_t = _tiled(tile)
    plane = -(-m // TILE_SUBLANES) * TILE_SUBLANES * TILE_LANES
    nbytes = (2 * t_t * itemsize         # key tile, double buffered
              + 2 * plane * itemsize     # probe block, double buffered
              + 2 * plane * 4            # int32 accumulator, double buffered
              + 2 * plane * 4)           # compare + sum temporaries
    return KernelFootprint(
        family="histogram", config=f"tile={tile},m={m},w={itemsize}",
        vmem_bytes=nbytes,
        formula=f"2*{t_t}*{itemsize} + 2*{plane}*{itemsize} + 4*{plane}*4")


def default_footprints(p: int = 256, itemsize: int = 4,
                       ) -> Tuple[KernelFootprint, ...]:
    """The shipped kernel configurations, costed at their constants.

    ``p`` sizes the histogram probe vector: HSS probes O(p) splitter
    candidates per round (sample cap), so we cost the histogram at the
    largest M the lint matrix ships.
    """
    return (
        block_sort_footprint(bitonic_ops.DEFAULT_BLOCK, itemsize),
        merge_block_footprint(bitonic_ops.MAX_RUN, itemsize),
        hbm_pass_footprint(merge_kernel.DEFAULT_COLS, itemsize),
        histogram_footprint(histogram_ops.DEFAULT_TILE, int(p), itemsize),
    )


def check_kernel_budgets(platform: str = "tpu", p: int = 256,
                         itemsizes: Tuple[int, ...] = (4, 8),
                         ) -> Tuple[KernelFootprint, ...]:
    """Cost every shipped configuration at every key width; raise
    :class:`VmemBudgetError` on the first overflow."""
    checked = []
    for w in itemsizes:
        for fp in default_footprints(p=p, itemsize=w):
            checked.append(fp.check(platform))
    return tuple(checked)

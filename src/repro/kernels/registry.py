"""Kernel entry-point registry: declared contracts for static checking.

Every public Pallas entry point registers itself here with a
:class:`KernelContract` describing the operands the static checker
(repro/analysis/kernel_contracts.py) must be able to see without running
the kernel:

  scalar_prefetch  operand names that ride the PrefetchScalarGridSpec's
                   int32 scalar-prefetch path (grid-visible: page tables,
                   lengths, block selections)
  smem_sidecars    operand names of the per-page f32 scale sidecars that
                   land whole in SMEM (quantized PageLayouts; scalar
                   prefetch itself is int32-only)
  paged_operand    the page-table kwarg name, or None for entry points
                   that only read contiguous caches
  supports_quant   the entry point accepts k/v scale sidecars

The decorator attaches the contract to the function
(``fn.__kernel_contract__``) and records it in :data:`REGISTRY`, so the
checker can sweep "every registered kernel entry point" instead of a
hand-maintained list that silently rots. Importing this module is free of
kernel imports; :func:`load_all` pulls in the kernel modules (which import
*us*) and returns the populated registry.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, Tuple

#: modules whose import registers entry points (kept explicit so a new
#: kernel file that forgets to register is caught by test_analysis.py's
#: registry-coverage check, not silently skipped)
KERNEL_MODULES = (
    "repro.kernels.fused_decode",
    "repro.kernels.gather_attention",
    "repro.kernels.approx_scores",
    "repro.kernels.approx_scores_fm",
    "repro.kernels.flash_attention",
    "repro.kernels.pool_write",
)


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Statically-checkable facts about one Pallas entry point."""
    name: str
    module: str
    scalar_prefetch: Tuple[str, ...] = ()
    smem_sidecars: Tuple[str, ...] = ()
    paged_operand: str = ""
    supports_quant: bool = False
    grid: str = ""

    @property
    def uses_prefetch_grid(self) -> bool:
        return bool(self.scalar_prefetch)


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    fn: Callable[..., object]
    contract: KernelContract


REGISTRY: Dict[str, KernelEntry] = {}


def kernel_entry(*, scalar_prefetch: Tuple[str, ...] = (),
                 smem_sidecars: Tuple[str, ...] = (),
                 paged_operand: str = "",
                 grid: str = "") -> Callable[[Callable[..., object]],
                                             Callable[..., object]]:
    """Register a Pallas entry point with its declared contract."""
    def deco(fn: Callable[..., object]) -> Callable[..., object]:
        contract = KernelContract(
            name=fn.__name__, module=fn.__module__,
            scalar_prefetch=tuple(scalar_prefetch),
            smem_sidecars=tuple(smem_sidecars),
            paged_operand=paged_operand,
            supports_quant=bool(smem_sidecars),
            grid=grid)
        REGISTRY[fn.__name__] = KernelEntry(fn=fn, contract=contract)
        fn.__kernel_contract__ = contract  # type: ignore[attr-defined]
        return fn
    return deco


def load_all() -> Dict[str, KernelEntry]:
    """Import every kernel module and return the populated registry."""
    for mod in KERNEL_MODULES:
        importlib.import_module(mod)
    return dict(REGISTRY)

"""Operations and bytes of each kernel's calls, from shapes: one module per kernel.

A module ``bench/work/<kernel>.py`` states ``PATTERN``, the expression
that finds the kernel's operations by their instruction names in a device
trace, and ``calls(ctx)``, the ``(flops, bytes)`` of each call the traced
window made, rebuilt from what the engine counted there. The trace
reducer and the roofline readers find the modules by file name, so a new
kernel is a new file here and a reader ``bench/metrics/<kernel>_roofline.py``.
"""
from __future__ import annotations

import functools
import importlib
import pkgutil
from typing import Dict

__all__ = ["kernel", "kernels"]


def kernel(name: str):
    """The work module of kernel ``name``."""
    return importlib.import_module(f"{__name__}.{name}")


@functools.lru_cache(maxsize=None)
def kernels() -> Dict[str, object]:
    """Every kernel with a work module, by name."""
    return {m.name: kernel(m.name) for m in pkgutil.iter_modules(__path__)}

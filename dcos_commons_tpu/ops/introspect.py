"""Which kernels a program was built with, read from its text.

The ops dispatch between a Pallas (Mosaic) kernel and a jnp reference
by platform and shape (ops/attention.py, ops/rmsnorm.py).  Both are
correct, so a program that quietly took the reference branch looks
healthy and runs slow.  A lowered program says which it took: every
Mosaic kernel is a ``stablehlo.custom_call @tpu_custom_call`` carrying
the ``name`` its ``pallas_call`` was given, and the operand types at
the call site are what ONE device runs the kernel over (inside a
shard_map body they are the shard's).  Workers print this at start-up;
chip_smoke.py fails when an expected kernel is missing or runs over
the global batch.
"""

from __future__ import annotations

import re
from typing import Dict

_CALL = "@tpu_custom_call"
_NAME = re.compile(r'kernel_name = "([^"]+)"')
# the call's function type trails the attribute dict: ") : (operands) ->"
_OPERANDS = re.compile(r"\}\s*:\s*\(([^()]*)\)\s*->")


def mosaic_calls(lowered_text: str) -> Dict[str, dict]:
    """``{kernel name: {"sites": n, "operands": [...]}}`` for every
    Mosaic call in ``jax.jit(f).lower(...).as_text()``.  ``operands``
    lists the distinct operand signatures seen, each as the call
    site's comma-separated ``tensor<...>`` types."""
    calls: Dict[str, dict] = {}
    for line in lowered_text.splitlines():
        if _CALL not in line:
            continue
        name = _NAME.search(line)
        operands = _OPERANDS.search(line)
        entry = calls.setdefault(
            name.group(1) if name else "unnamed",
            {"sites": 0, "operands": []},
        )
        entry["sites"] += 1
        signature = operands.group(1).strip() if operands else ""
        if signature not in entry["operands"]:
            entry["operands"].append(signature)
    return calls

"""Operations and bytes a step needs, computed from shapes.

These are the least work the algorithm asks of the chip, not what the
current program executes: a roofline share built on them cannot pass 100 %
unless a count is wrong.

What is the same for every block is here: the bytes of a weight tree as it
is stored, and the size of a KV cache's entry. What depends on the block
(which weights a decode step streams, what attention costs in a prefill
chunk) is its family's: `benchmarks/families/<family>.py` defines
`decode_step_bytes`, `decode_matmul_weight_bytes` and
`prefill_chunk_flops`, and a reader takes them from the run's family with
`of`. A count a family does not define is never borrowed from another:
its reader returns nothing for that cell.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

from benchmarks.harness.weights import Leaf, scale_shape

KV_ITEMSIZE = {"bfloat16": 2, "int8": 1}


def weight_bytes(table: Dict[str, Leaf]) -> Dict[str, int]:
    """Bytes of every leaf of a family's table as stored: int8 values +
    float32 scales for a quantized leaf, float32 for a bias, bfloat16
    otherwise."""
    out = {}
    for path, leaf in table.items():
        n = math.prod(leaf.shape)
        if leaf.kind == "int8":
            out[path] = n + 4 * math.prod(scale_shape(leaf))
        else:
            out[path] = (4 if leaf.kind == "bias" else 2) * n
    return out


def of(run: Dict[str, Any], name: str) -> Optional[Callable]:
    """The run's family's count of that name, or nothing."""
    return getattr(run.get("family"), name, None)

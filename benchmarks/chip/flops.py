"""Operations and bytes the benchmark counts, from shapes alone.

Model FLOPs follow the usual convention: a matrix multiplication of an
(m, k) by a (k, n) operand is 2*m*k*n operations. ``P`` counts the
parameters that enter a matrix multiplication per token and the
attention's own products are counted apart; both counts belong to the
model's family and are the functions ``matmul_params(m)`` and
``attention_flops_fwd(m, length)`` of the cell's reference module.
Recomputed work and padding are not counted.
"""
from __future__ import annotations

import json
import re
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

_DTYPE_BYTES = {"bf16": 2, "f32": 4}


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of one chip of ``device_kind``. A device that
    is not in the table is an error, not a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def rollout_flops(counts, m: dict, length: int) -> int:
    """Forward operations to feed and generate one sequence of
    ``length`` tokens (prompt plus response) through the model of sizes
    ``m``, by the family's ``counts`` (the cell's reference module)."""
    return (2 * counts.matmul_params(m) * length
            + counts.attention_flops_fwd(m, length))


def train_flops(counts, m: dict, length: int) -> int:
    """Forward and backward operations of the actor update on one
    sequence of ``length`` trained tokens (backward is twice forward)."""
    return (6 * counts.matmul_params(m) * length
            + 3 * counts.attention_flops_fwd(m, length))


def fused_rl_loss_cost(kind: str, n: int, v: int, dtype: str = "bf16"):
    """(operations, bytes) one call of the fused RL-loss kernel needs on
    (n, v) logits of ``dtype``.

    Forward (``kind="fwd"``) streams the logits once: per element a max,
    a subtract, an exp, a sum, the entropy product and sum, and the
    target compare-select (7 operations); it reads four (n,) f32 row
    vectors and writes six. Backward (``"bwd"``) reads the logits and
    writes their gradient in the same dtype: per element a subtract, an
    exp, the target compare, and the three terms of the gradient
    (6 operations); it reads five (n,) row vectors."""
    b = _DTYPE_BYTES[dtype]
    if kind == "fwd":
        return 7 * n * v, n * v * b + 10 * n * 4
    if kind == "bwd":
        return 6 * n * v, 2 * n * v * b + 5 * n * 4
    raise ValueError(f"unknown kernel direction {kind!r}")


_SHAPE = re.compile(r"custom-call\((\w+)\[(\d+),(\d+)\]")


def call_shape(op_text: str):
    """(dtype, n, v) of the logits operand of a kernel call, read from the
    HLO text the device trace gives the op: its first operand."""
    m = _SHAPE.search(op_text)
    if m is None:
        raise ValueError(f"no 2-D first operand in {op_text[:120]!r}")
    return m.group(1), int(m.group(2)), int(m.group(3))

"""The least operations and bytes of one pass of a fixed-effect fit, from
its shapes alone: what the algorithm needs, nothing an implementation adds.

A pass is one optimizer iteration counted as one product ``X v`` (gather
``k`` table entries a row and add them) and one product ``X^T d`` (add each
row's ``d`` into its ``k`` columns). Line-search trials, the L-BFGS two-loop
recursion and TRON's further Hessian-vector products are the optimizer's own
and are not counted: a faster optimizer then shows as a higher share, and the
share can only be low, never above the roofline.
"""

from __future__ import annotations


def pass_flops(rows: int, k: int) -> float:
    """2 per nonzero for ``X v`` (multiply by the implicit one, add) and 2
    for ``X^T d``."""
    nnz = rows * k
    return 4.0 * nnz


def pass_bytes(rows: int, k: int) -> float:
    """Each index read once per product (4 B, int32), each gathered element
    4 B (``X v``) and each emitted element 4 B (``X^T d``), float32."""
    nnz = rows * k
    return 2.0 * nnz * 4 + 2.0 * nnz * 4


def pass_roofline_seconds(rows: int, k: int, peaks: dict):
    """-> (least seconds for one pass on one chip, which peak binds)."""
    t_flops = pass_flops(rows, k) / peaks["flops_per_s"]
    t_bytes = pass_bytes(rows, k) / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops > t_bytes else (t_bytes, "bytes")

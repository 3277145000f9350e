"""Operations and bytes, counted from shapes.

Two yardsticks:

* a kernel's work is the work of the site's plain GEMM: ``rows x K x N``
  multiply-adds (2 operations each) on int8 weights and int8 activations
  with int32 outputs, whatever the kernel does to protect it;
* a model's operations per token are the GEMM operations of every
  projection (2 per weight) plus the attention or scan operations that the
  architecture module counts.
"""
from __future__ import annotations

import re

import jax


def param_counts(shapes) -> dict:
    """Parameter counts from a shape tree (``jax.eval_shape`` of an init):
    total, embedding and head rows (``tok``/``head``/``pos`` leaves), and
    the rest."""
    total = emb = 0
    for kp, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        path = jax.tree_util.keystr(kp)
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        if re.search(r"'tok'|'head'|'pos'", path):
            emb += n
    return {"total": total, "embedding": emb, "nonemb": total - emb}


def gemm_ops(rows: int, K: int, N: int) -> float:
    return 2.0 * rows * K * N


def kernel_bytes(rows: int, K: int, N: int) -> float:
    """int8 weights and activations read, int32 outputs written."""
    return float(K * N + rows * K + 4 * rows * N)


def least_time(ops: float, nbytes: float, peak_ops: float,
               hbm_bytes_per_s: float) -> float:
    """The least time the chip needs: the larger of the compute bound and
    the memory bound."""
    return max(ops / peak_ops, nbytes / hbm_bytes_per_s)


def kernel_least_time(sites, rows: int, peak_ops: float,
                      hbm_bytes_per_s: float) -> float:
    """Least time of one program call that runs every site in ``sites``
    (dicts with ``K``, ``N`` and ``layers``) on ``rows`` rows."""
    return sum(s["layers"] * least_time(gemm_ops(rows, s["K"], s["N"]),
                                        kernel_bytes(rows, s["K"], s["N"]),
                                        peak_ops, hbm_bytes_per_s)
               for s in sites)


def gemm_ops_per_token(sites) -> float:
    """GEMM operations of one token through every site in ``sites``."""
    return sum(s["layers"] * gemm_ops(1, s["K"], s["N"]) for s in sites)

"""The plain reference and the comparison that decides ``correct``.

The reference computes the top-k eigenpairs of the exact matrix a request
was served with, in float64 on the host, with ARPACK (``scipy.sparse
.linalg.eigsh``).  It imports nothing of the system under test.

Numbers compared, each in units of the matrix's Frobenius norm and each
the worst over every sampled answer:

    eig_err    max |lambda_i - lambda_ref_i|           limit: the cell's
                                                        config, set from
                                                        readings
    residual   max ||A v_i - lambda_i v_i||_2           limit: the served
                                                        residual guarantee
    norm_err   max | ||v_i||_2 - 1 |  (not in ||A||_F)  limit: the served
                                                        unit-norm guarantee
    missing    answers sampled that never came          limit: 0

The control is the same reference computed one precision below the
float32 the configurations state: the matrix rounded to bfloat16, and
the answer rounded to bfloat16.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

NUMBERS = ("eig_err", "residual", "norm_err", "missing")


@dataclasses.dataclass
class Sample:
    """One answer drawn for the check, with the matrix it was served for.

    ``a`` is the ``(n, n)`` float32 matrix as the client sent it; ``lam``
    and ``vecs`` are the answer (``None`` when it never came).
    """

    a: np.ndarray
    k: int
    largest: bool
    lam: Optional[np.ndarray]
    vecs: Optional[np.ndarray]
    key: object = None  # samples of one matrix share a key: one reference

    def matrix(self) -> np.ndarray:
        return np.asarray(self.a, np.float64)


def round_bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (to nearest even), returned as float64."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def reference_topk(a: np.ndarray, k: int, largest: bool):
    """Top-k eigenpairs of a float64 symmetric ``a``: ``(lam ascending
    (k,), vecs (k, n) rows)``."""
    from scipy.sparse.linalg import eigsh

    n = a.shape[0]
    ncv = min(n, max(2 * k + 1, 64))
    lam, v = eigsh(a, k=k, which="LA" if largest else "SA", tol=0.0,
                   ncv=ncv, v0=np.ones(n) / np.sqrt(n))
    order = np.argsort(lam)
    return lam[order], v[:, order].T


def numbers(a: np.ndarray, lam_ref: np.ndarray, lam, vecs) -> dict:
    """The compared numbers of one answer against the reference."""
    scale = max(float(np.linalg.norm(a)), 1e-300)
    lam = np.asarray(lam, np.float64)
    vecs = np.asarray(vecs, np.float64)
    if lam.shape != lam_ref.shape or vecs.shape != (len(lam_ref), a.shape[0]):
        return {"eig_err": np.inf, "residual": np.inf, "norm_err": np.inf}
    res = vecs @ a - lam[:, None] * vecs
    out = {
        "eig_err": float(np.max(np.abs(np.sort(lam) - lam_ref))) / scale,
        "residual": float(np.max(np.linalg.norm(res, axis=1))) / scale,
        "norm_err": float(np.max(np.abs(np.linalg.norm(vecs, axis=1) - 1.0))),
    }
    return {key: (val if np.isfinite(val) else np.inf)
            for key, val in out.items()}


def check(samples: list, limits: dict, control: bool = False) -> dict:
    """Compare every sample with the reference.

    Returns ``{"correct": bool, "numbers": {name: {"value", "limit"}}}``.
    With ``control=True`` each answer is replaced by the bfloat16 control's
    answer for the same matrix, so the numbers are the control's readings.
    """
    worst = {"eig_err": 0.0, "residual": 0.0, "norm_err": 0.0, "missing": 0}
    refs: dict = {}
    for s in samples:
        a = s.matrix()
        cache_key = (s.key, s.k, s.largest) if s.key is not None else None
        if cache_key is not None and cache_key in refs:
            lam_ref = refs[cache_key]
        else:
            lam_ref, _ = reference_topk(a, s.k, s.largest)
            if cache_key is not None:
                refs[cache_key] = lam_ref
        if control:
            lam_c, vec_c = reference_topk(round_bf16(a), s.k, s.largest)
            lam, vecs = round_bf16(lam_c), round_bf16(vec_c)
        elif s.lam is None:
            worst["missing"] += 1
            continue
        else:
            lam, vecs = s.lam, s.vecs
        for key, val in numbers(a, lam_ref, lam, vecs).items():
            worst[key] = max(worst[key], val)
    out = {}
    for key in NUMBERS:
        limit = 0 if key == "missing" else float(limits[key])
        out[key] = {"value": worst[key], "limit": limit}
    correct = bool(samples) and all(
        v["value"] <= v["limit"] for v in out.values())
    return {"correct": correct, "numbers": out}

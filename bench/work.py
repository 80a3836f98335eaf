"""The least work of each layer of one CV problem, from its shapes alone.

These counts are the algorithm's, not any implementation's: they read the
same whether the Pallas kernels, XLA's own routines or a later rewrite do
the work, so a share of the roofline built on them cannot pass 100% by a
change of implementation.  ``P`` is the number of entries of a
tile-packed lower triangle: ``nt(nt+1)/2`` tiles of ``block²``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def packed_size(h: int, block: int) -> int:
    nt = -(-h // block)
    return nt * (nt + 1) // 2 * block * block


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_s(self, peak: dict) -> float:
        """Least seconds on a chip: the larger of the compute and the
        memory bound."""
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def anchor_factorization(*, h: int, k: int, g: int, block: int) -> Work:
    """g·k Cholesky factorizations of h×h: h³/3 flops each; each reads its
    matrix's lower triangle once and writes its factor once, in float32."""
    p = packed_size(h, block)
    return Work(flops=g * k * h ** 3 / 3, bytes=g * k * 2 * p * 4)


def lambda_stage(*, h: int, k: int, q: int, degree: int, block: int,
                 itemsize: int = 4, solves: int = 1) -> Work:
    """The interpolated solves of k folds at q λ.

    Each solve is a forward and a back substitution through L(λ); the
    least bytes read Θ ((degree+1)·P entries) once per fold per
    substitution sweep, and move each right-hand side and solution (q·h per
    sweep, read and written, float32).  Flops: Horner evaluation of P
    entries (2·degree each) and two triangular solves (h² each) at every λ.
    """
    p = packed_size(h, block)
    sweeps = 2 * solves
    theta_bytes = k * sweeps * (degree + 1) * p * itemsize
    vec_bytes = k * sweeps * q * h * 4 * 2
    flops = k * q * solves * (2 * degree * p + 2 * h * h)
    return Work(flops=flops, bytes=theta_bytes + vec_bytes)


def peak_for(kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    """The published peaks of a device kind; an unknown kind raises."""
    table = json.loads(path.read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[kind]

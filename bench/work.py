"""The work a fit requires, from its shapes alone.

These count what any implementation has to do, not what the repo's kernels
happen to do: the Gram pass reads H and T once and writes G and R once, and
computes the symmetric half of H^T H.  So every implementation is judged
against the same work.
"""

from __future__ import annotations

import json
from pathlib import Path

DTYPE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def gram_flops(m: int, N: int, L: int, D: int) -> int:
    """H^T H (symmetric half: L (L + 1) / 2 entries) and H^T T."""
    return m * N * L * (L + 1) + 2 * m * N * L * D


def gram_bytes(m: int, N: int, L: int, D: int, precision: str) -> int:
    """H and T read once at the stream's dtype; G and R written in fp32."""
    return m * N * (L + D) * DTYPE_BYTES[precision] + m * (L * L + L * D) * 4


def hidden_flops(m: int, N: int, d_in: int, L: int) -> int:
    return 2 * m * N * d_in * L


def admm_flops_per_iter(m: int, L: int, r: int) -> int:
    """G U and U^T (G U), which any U-solve needs once per iteration."""
    return 4 * m * L * L * r


def fit_flops(m: int, N: int, d_in: int, L: int, D: int, r: int,
              iters: int) -> int:
    """One fit from raw features; leaves out the eigh and any inner solver
    steps, so that swapping solvers does not move it."""
    return (hidden_flops(m, N, d_in, L) + gram_flops(m, N, L, D)
            + iters * admm_flops_per_iter(m, L, r))


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, not a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least time the chip could take, the bound that sets it)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")

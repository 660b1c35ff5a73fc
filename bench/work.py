"""Work counts and peaks: the least work a step must do, from the shapes a
configuration states, whatever implements it; and the chip's peaks.

A roofline or mfu share is (least time the chip could take) / (measured
time), the least time being the larger of operations over the peak rate
and bytes over the peak bandwidth. The counts never look at how the
program lays out its store or schedules its kernels, so a layout change
cannot move them.
"""

from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The chip's peaks; a device missing from the table is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add its published numbers")
    return table[device_kind]


def levels(cfg: dict) -> int:
    """Quantization levels of a stored value (MTMC: 3 * CL + 1)."""
    if cfg["encoding"] != "mtmc":
        raise ValueError(f"no level count for encoding {cfg['encoding']!r}")
    return 3 * cfg["cl"] + 1


def shortlist_work(cfg: dict, batch: int) -> tuple[float, float]:
    """Phase 1 of one batch: (ops, bytes). Every query meets every row in
    every dimension once (a multiply and an add), and every row is read
    once at its stored bit width."""
    n, d = cfg["rows"], cfg["dim"]
    ops = 2.0 * batch * n * d
    nbytes = n * d * math.ceil(math.log2(levels(cfg))) / 8.0
    return ops, nbytes


def rescore_bytes(cfg: dict, batch: int, k: int) -> float:
    """Phase 2 of one batch: the k rescored rows of every query, read as
    their programmed cells (4 states, 2 bits a cell)."""
    mc = cfg["mcam"]
    seg = math.ceil(cfg["dim"] / mc["string_len"])
    cells = seg * cfg["cl"] * mc["string_len"]
    return batch * k * cells * 2 / 8.0


def search_work(cfg: dict, batch: int, k: int, mode: str
                ) -> tuple[float, float]:
    """Least (ops, bytes) of one search batch in `mode`."""
    ops, nbytes = shortlist_work(cfg, batch)
    if mode == "two_phase":
        nbytes += rescore_bytes(cfg, batch, k)
    elif mode != "ideal":
        raise ValueError(f"no least work for mode {mode!r}")
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    return max(ops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

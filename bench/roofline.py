"""Operations and bytes of the survivor DP, and its roofline.

The count is of the algorithm, not of its tiling, so a better tiling or
a compaction of survivors shows as a gain and leaves the count alone.

Operations. The survivor DP evaluates SP-DTW exactly on the pairs the
cascade hands it: the cascade's ``pairs_dp`` less the seed pairs it
already evaluated (``seed_k`` per query). Each such pair visits every
cell of the learned support once (``n_cells``). Per cell, forming the
local cost w * sum_c (x_c - y_c)^2 takes a subtraction, a multiplication
and an addition per channel (3d; the weight is 1 with gamma = 0, and a
weighted grid adds one multiply that the count leaves out), and the
recurrence D = cost + min(up, diag, left) takes two minima and one
addition (3). So 3d + 3 per cell: 6 at d = 1.

Bytes. The least traffic the sweep needs: every query and every corpus
series read once, in float32, and one float32 result written per pair
of the Gram block (queries x corpus), the weight grid read once.

Time. The least time the chip could take is the larger of operations
over the VPU's float32 element-op rate (the DP is min-plus: it cannot
use the matrix unit) and bytes over the HBM bandwidth.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def ops_per_cell(d: int) -> int:
    """Element operations per support cell per pair (see module doc)."""
    return 3 * d + 3


def survivor_ops(pairs_dp: int, n_queries: int, seed_k: int, n_cells: int,
                 d: int = 1) -> int:
    """Element operations of the survivor DP over the window."""
    survivors = max(int(pairs_dp) - int(n_queries) * int(seed_k), 0)
    return survivors * int(n_cells) * ops_per_cell(d)


def survivor_bytes(n_batches: int, batch: int, n_corpus: int, T: int,
                   d: int = 1) -> int:
    """Least HBM bytes of the survivor DP over the window."""
    per_batch = 4 * (batch * T * d + n_corpus * T * d + batch * n_corpus
                     + T * T)
    return int(n_batches) * per_batch


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's entry for ``device_kind``; an unknown kind is an
    error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def roofline_share(ops: int, nbytes: int, seconds: float,
                   device_kind: str) -> Optional[Dict[str, float]]:
    """{share (%), bound ('vpu' or 'hbm')}; None without work or time."""
    if ops <= 0 or seconds <= 0:
        return None
    pk = peaks(device_kind)
    t_ops = ops / pk["vpu_f32_elementops_per_s"]["value"]
    t_mem = nbytes / pk["hbm_bytes_per_s"]["value"]
    return {"share": 100.0 * max(t_ops, t_mem) / seconds,
            "bound": "vpu" if t_ops >= t_mem else "hbm"}

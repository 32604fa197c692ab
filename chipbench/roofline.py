"""Bytes that a lane program must move, and the device's peak HBM
bandwidth, for the memory bound of the lane programs.

A device lane dispatch (``solver_stats["lane_dispatches"]``, one row
per shape: kind, k, L, S_pad, NB, SB, rung, Kp) gathers ``rung`` lanes
of the store's mirror and runs the DP or k-best scan over them.  The
bytes it must move are its operands read once and its results written
once: each lane's op rows (``t_op``, ``e_op`` float64 and ``valid``
bool, ``[L, S_pad]``), block indices (``block_of`` ``[L-1]``, ``rsel``
and ``csel`` ``[L-1, S_pad]``, int32) and transition blocks (``t_blk``,
``e_blk``, ``[NB, SB, SB]`` float64); the lane indices (int64) and
weight rows (float64: two ``[rung, Kp]`` rows for the DP, one for the
k-best); the paths (int64: ``[rung, Kp, L]`` for the DP, ``[rung, Kp,
k, L]`` and the ``[rung, Kp]`` counts for the k-best).  Intermediate
back pointers are not counted: they may stay on the chip.

This is the memory bound only.  The scans' min-plus work runs in
emulated float64, which has no published peak, so there is no compute
roof to set beside it.
"""

from __future__ import annotations

F64 = I64 = 8
I32 = 4
BOOL = 1

#: peak HBM bandwidth in bytes/s by ``jax.Device.device_kind`` —
#: "TPU v5 lite" is the TPU v5e: 819 GB/s (Google Cloud, "TPU v5e",
#: system architecture)
PEAK_HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    """The peak of a device kind; raises KeyError for a kind the table
    does not know, rather than guess."""
    try:
        return PEAK_HBM_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(f"no peak HBM bandwidth known for device kind "
                       f"{device_kind!r}; add it to "
                       f"chipbench/roofline.PEAK_HBM_BYTES_PER_S") from None


def lane_bytes(L: int, S_pad: int, NB: int, SB: int) -> int:
    """Bytes of one lane's operands."""
    ops = L * S_pad * (2 * F64 + BOOL)
    index = (L - 1) * I32 + 2 * (L - 1) * S_pad * I32
    blocks = 2 * NB * SB * SB * F64
    return ops + index + blocks


def dispatch_bytes(kind: str, k: int, L: int, S_pad: int, NB: int,
                   SB: int, rung: int, Kp: int) -> int:
    """Bytes one lane dispatch of this shape must move."""
    lanes = rung * (lane_bytes(L, S_pad, NB, SB) + I64)
    if kind == "dp":
        return lanes + 2 * rung * Kp * F64 + rung * Kp * L * I64
    if kind == "kbest":
        return lanes + rung * Kp * F64 + rung * Kp * (k * L + 1) * I64
    raise ValueError(f"unknown lane dispatch kind {kind!r}")


def schedules_bytes(schedules) -> int | None:
    """Bytes the device lane dispatches of ``schedules`` must move, or
    None where no schedule counted its dispatches."""
    rows = [row for s in schedules
            for row in s.solver_stats.get("lane_dispatches", [])]
    if not rows:
        return None
    fields = ("kind", "k", "L", "S_pad", "NB", "SB", "rung", "Kp")
    return sum(row["n"] * dispatch_bytes(**{f: row[f] for f in fields})
               for row in rows)

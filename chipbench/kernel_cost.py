"""Operations and bytes of the device programs the cells time, from
their shapes.

Kept with the benchmark so that a change to a program cannot change
what its roofline share is measured against.
"""
from __future__ import annotations


def offload_aggregate_bytes(n_records: int) -> int:
    """The least HBM traffic of one whole-window offload
    (``offload_aggregate``: fold, Pallas ``window_agg``, lane combine):
    every f32 record of the window read once. One add per record, so
    memory bounds it; what the fold writes and the kernel reads back
    lies in VMEM on the chip and is not owed to HBM."""
    return 4 * n_records

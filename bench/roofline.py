"""HBM bytes that one p(l)-CG iteration has to stream, from its shapes.

The count is the algorithm's (paper arXiv:1801.04728 Alg. 3 with the
sliding windows of the scan engine), not any one implementation's: a
later PR that fuses, splits or reorders the body moves the time, never
the count.  It leaves out the O(l^2) scalar recurrences and the
solution / search-direction updates, so it is a lower bound of the
streaming traffic and a share against it stays at or under 100% as long
as every window is read from HBM once per iteration.  An implementation
that kept the whole working set on chip across iterations would beat it
and read above 100%.
"""
from __future__ import annotations


def words_per_iter(l: int, n_local: int) -> int:
    """Words one iteration streams per right-hand side on one device."""
    v_window = 2 * l + 1      # the basis window V (v_{i-2l} .. v_i): read once
    z_window = l + 1          # the auxiliary window Z (z_{i-l} .. z_i): read once
    v_store = 2 * l + 1       # the updated V window written back
    z_store = l + 1           # the updated Z window written back
    spmv_in = 1               # the SPMV reads its input z_i
    spmv_out = 1              # ... and writes t = A z_i
    t_read = 1                # the window recurrence reads t back
    per_point = (v_window + z_window + v_store + z_store
                 + spmv_in + spmv_out + t_read)           # = 6l + 7
    return per_point * n_local


def bytes_per_iter(l: int, n_local: int, word_bytes: int,
                   lanes: int = 1) -> int:
    """HBM bytes of one iteration on one device: ``(6l+7) * n_local``
    words at the storage width, for every right-hand side in the batch."""
    return words_per_iter(l, n_local) * word_bytes * lanes

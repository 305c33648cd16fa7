"""Operations and bytes of one call of each round kernel, from shapes
and the program's counts. Kept with the benchmark so that a change to
a kernel cannot change what its roofline share is measured against.

``gather_blocks`` copies each block a round needs from the HBM block
store into VMEM and writes it to its output in HBM: per paying cold
DMA (``io - dedup_saved``: a tier-0 hit or a join needs no copy) the
tile is read once and written once.

``rank_tiles`` reads, per query row of the batch, the query, its F
picked ids, its F*eps broadcast tile rows and their ids, and writes the
rows' distances and the expansion order; it computes a squared
difference per element (3 operations: subtract, multiply, add) and
picks ``n_expand`` minima over the F*eps keys (4 operations per key and
pick: mask, compare, select, reduce).
"""
from __future__ import annotations

import math


def n_expand(fetch_width: int, eps: int, sigma: float) -> int:
    """Expansion slots per query and round, as the block search sizes
    them: the F targets plus the top ``ceil((eps - 1) * sigma)``
    residents of each fetched block."""
    return fetch_width * (1 + max(math.ceil((eps - 1) * sigma), 0))


def gather_blocks_bytes(dmas: int, eps: int, dim: int,
                        itemsize: int) -> int:
    """HBM bytes of ``dmas`` paying block copies (read + write)."""
    return 2 * dmas * eps * dim * itemsize


def rank_tiles_work(q: int, fetch_width: int, eps: int, dim: int,
                    expand: int, itemsize: int = 4):
    """(operations, HBM bytes) of one ``rank_tiles`` call over a batch
    of ``q`` rows."""
    fe = fetch_width * eps
    ops = 3 * q * fe * dim + 4 * q * fe * expand
    read = (q * dim * 4 + q * fetch_width * 4 + q * fe * dim * itemsize
            + q * fe * 4)
    write = q * fe * 4 + q * expand * 4
    return ops, read + write

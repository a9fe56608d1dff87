"""The one generator of a training cell's traffic.

A traffic mix is a data file (``traffic/<mix>.json``) that this module
reads; a new mix is a new file, never new code. ``batch_rows`` is the
rows a step reads: ``null`` is full batch, every step reads every row;
a number draws one permutation of the rows from the seed and gives
each step the next ``batch_rows`` of it, from its start again once its
whole batches are used up.
"""

from __future__ import annotations

import numpy as np


class Feed:
    """Which rows each step of a run reads, from the mix and the seed."""

    def __init__(self, traffic: dict, rows: int, seed: int):
        batch = traffic.get("batch_rows")
        self.batches = None
        if batch is None:
            self.batch_rows = rows
            return
        batch = int(batch)
        if not 0 < batch <= rows:
            raise ValueError(f"batch_rows {batch} outside 1..{rows}")
        perm = np.random.default_rng(seed).permutation(rows)
        whole = rows // batch
        self.batch_rows = batch
        self.batches = perm[: whole * batch].reshape(whole, batch).astype(np.int32)

    @property
    def full(self) -> bool:
        return self.batches is None

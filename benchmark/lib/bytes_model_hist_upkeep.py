"""The bytes one upkeep of the selector grid has to move, from the cell's
shapes: each sample of a cycle's writes lands in a cell of its own, whose
value (4 B), tick (4 B) and flag (1 B) are read and written once, and the
batch's own columns come in once (series, cell, tick and the value's bits,
4 B each), padded to the program's bucket. The same work reads the same
bytes whatever implements the scatter: a program that passes over whole
planes to write 8,000 cells moves this many useful bytes in more time,
and its share of the roofline says so.
"""

CELL_BYTES = 4 + 4 + 1      # float32 value, int32 tick, bool flag
COLUMN_BYTES = 4 * 4        # series, cell, tick, value bits


def hist_upkeep_bytes(shapes: dict) -> int:
    return (2 * shapes["upkeep_samples"] * CELL_BYTES
            + shapes["append_bucket"] * COLUMN_BYTES)

"""The bytes a PromQL panel's device work has to move, from the cell's
shapes (see `bytes_model.py`: the same work reads the same bytes
whatever program implements it). A selector grid holds a float32 value,
an int32 tick and a validity byte a cell.
"""

F32 = 4
TICK = 4
MASK = 1


def histogram_quantile_bytes(shapes: dict) -> int:
    """`histogram_quantile(phi, sum by (le) (rate(m[w])))` over a
    range: read value, tick and validity of every series over the cells
    the windows touch (the panel's range and the first window), write
    one f32 a step."""
    read = shapes["series"] * shapes["span_cells"] * (F32 + TICK + MASK)
    return read + shapes["steps"] * F32

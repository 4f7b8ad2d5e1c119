"""The bytes one upkeep of the range grid has to move, from the cell's
shapes: each cell a body's rows touch is read and written once in every
plane of the entry (4 B each way), and the body's own columns come in
once, padded to the program's bucket. The same work reads the same
bytes whatever implements the scatter: a program that copies whole
planes to update 400 cells moves this many useful bytes in more time,
and its share of the roofline says so.
"""

B32 = 4


def upkeep_bytes(shapes: dict) -> int:
    cells = shapes["batch_lines"]          # one row a cell at the source's
    planes = shapes["grid_planes"]         # interval: a row is a cell
    columns = shapes["append_columns"]
    return (2 * cells * planes * B32
            + shapes["append_bucket"] * columns * B32)

"""The bytes a query's device work has to move, from the cell's shapes.
The same work reads the same bytes whatever program implements it: a
later PR that fuses, tiles or replaces a program moves the roofline
share only through its device time. float32 values and a validity
byte per cell, as the configurations state (`f32 cell states`).
"""

F32 = 4
MASK = 1


def range_query_bytes(shapes: dict) -> int:
    """A RANGE panel: read the queried span of each selected series'
    cell plane (value + validity) for each field, write one f32 and a
    validity byte per (series, bucket, field)."""
    read = (shapes["hosts_selected"] * shapes["span_cells"]
            * shapes["fields"] * (F32 + MASK))
    write = (shapes["hosts_selected"] * shapes["buckets"]
             * shapes["fields"] * (F32 + MASK))
    return read + write


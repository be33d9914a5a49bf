"""slab_rows.decode: the fewest slab rows any decode replica holds (compute
node): the gauge ``slab_rows`` of each replica's totals, the byte budget's
outcome (a slot per resident session and the scratch row).  Nothing to
read from a program without the gauge."""
from bench import readers


def read(win):
    if win.traffic["kind"] != "decode":
        return None
    rows = [n["totals"]["slab_rows"] for n in readers.nodes(win)
            if n.get("totals") and n["totals"].get("slab_rows")]
    return min(rows) if rows else None

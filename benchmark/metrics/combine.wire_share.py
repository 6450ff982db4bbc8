"""combine.wire_share: the share of the staged pairs that the map-side
combine put on the wire, sum(rows_wire) / sum(rows_in) over the window's
combining exchanges (each wave of a waved read, or a single-shot read).

The read sets both on its ``shuffle.exchange.wait`` span where it reads
the exchange's overflow flag back: ``rows_in`` the pairs staged,
``rows_wire`` the rows the map-side combines sent, which equals the rows
received summed over chips. 1 means the combine merged nothing; lower
means fewer rows crossed the all-to-all. Silent where no span carries the
counts (plain and ordered reads, a program without them)."""

import progtrace


def read(ctx):
    pt = progtrace.for_run(ctx)
    if pt is None:
        return None
    spans = [s for s in pt.spans
             if "rows_in" in s.stats and "rows_wire" in s.stats]
    rows_in = sum(int(s.stats["rows_in"]) for s in spans)
    if not rows_in:
        return None
    return sum(int(s.stats["rows_wire"]) for s in spans) / rows_in

"""Host time per partition job with no scan call in flight: the mean of
``partition_file``'s ``host_serial_s`` counter over the window's jobs, as
the program writes it on its ``repro.partition_file`` annotation
(``bench.scopes``)."""
from bench.scopes import counters


def read(ctx):
    found = counters(ctx)
    if not found:
        return None
    return sum(c["host_serial_s"] for c in found) / len(found) * 1e3

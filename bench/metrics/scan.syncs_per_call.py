"""Device-to-host reads per ring-scan call: ``partition_file``'s
``host_syncs`` over its ``scan_calls``, summed over the window's jobs, as
the program writes them on its ``repro.partition_file`` annotation
(``bench.scopes``)."""
from bench.scopes import counters


def read(ctx):
    found = counters(ctx)
    calls = sum(c.get("scan_calls", 0) for c in found or ())
    if not calls:
        return None
    return sum(c["host_syncs"] for c in found) / calls

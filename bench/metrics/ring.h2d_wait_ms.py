"""Host time the ring driver spent blocked in refills, per scan call:
``h2d_wait_s / scan_calls`` from ``partition_file``'s stats, over the
window's jobs."""


def read(ctx):
    calls = sum(r["stats"]["scan_calls"] for r in ctx["results"])
    if not calls:
        return None
    return sum(r["stats"]["h2d_wait_s"] for r in ctx["results"]) / calls * 1e3

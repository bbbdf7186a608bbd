"""Device time per ADWISE ring-scan step outside the step's phases: the
self time of the ``_run_scan_ring`` ops under no ``adwise.*`` scope (the
scan loop's own carry copies, broadcasts and updates) over the scan steps
it ran (``bench.scopes``). With the four phase metrics it adds up to the
program's device time per step."""
from bench.scopes import scan_us_per_step


def read(ctx):
    return scan_us_per_step(ctx, None)

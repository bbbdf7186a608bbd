"""Device time per ADWISE ring-scan step: the summed device time of the
``_run_scan_ring`` program in the traced window over the scan steps it ran
(calls x steps per call, from ``partition_file``'s stats)."""
from bench.trace import module_time


def read(ctx):
    seconds, runs = module_time(ctx["trace"], r"_run_scan_ring")
    steps = sum(r["stats"]["scan_calls"] * r["stats"]["scan_steps_per_call"]
                for r in ctx["results"])
    if not runs or not steps:
        return None
    return seconds / steps * 1e6

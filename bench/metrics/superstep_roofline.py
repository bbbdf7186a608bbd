"""Share of the superstep's roofline: the least time one PageRank
superstep can take at the chip's peak HBM bandwidth (8 bytes per edge and
8 per vertex, ``bench.roofline``) over its device time per superstep."""
from bench.roofline import pagerank_superstep_bytes, peak
from bench.trace import module_time

PROGRAM = r"jit_step"


def read(ctx):
    seconds, runs = module_time(ctx["trace"], PROGRAM)
    steps = sum(r["work"] for r in ctx["results"])
    if not runs or not steps or seconds <= 0:
        return None
    g = ctx["config"]["graph"]
    least = pagerank_superstep_bytes(g["num_edges"], g["num_vertices"]) / peak(
        ctx["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least / (seconds / steps)

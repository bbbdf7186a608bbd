"""Device bytes per vertex of one scan instance's V-sized tables (for
ADWISE: the replica table, its version stamps and the degrees):
``partition_file``'s ``vertex_table_bytes`` counter over the
configuration's ``num_vertices + 1`` rows, as the program writes it on its
``repro.partition_file`` annotation (``bench.scopes``). A program that
writes no such counter reads as nothing."""
from bench.scopes import counters


def read(ctx):
    found = [c["vertex_table_bytes"] for c in counters(ctx) or ()
             if "vertex_table_bytes" in c]
    if not found:
        return None
    rows = int(ctx["config"]["graph"]["num_vertices"]) + 1
    return sum(found) / len(found) / rows

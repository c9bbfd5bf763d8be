"""The least bytes any TPC-H Q1 over one batch can move: every row of
the seven columns the query reads, once, and the result rows out. The
sort, the compaction and the carried products are this program's way of
doing it, not the query's need, so a share of the roofline computed from
this count cannot pass 100%."""

from ..wirefmt import width_of


def count(config, traffic, rows):
    q = config["query"]
    table = config["tables"][traffic["tables"][traffic["rows_in"]]["table"]]
    widths = {c["name"]: width_of(c["type"]) for c in table["columns"]}
    scanned = rows * sum(widths[name] for name in q["reads"])
    result = q["result_rows"] * sum(width_of(t) for t in q["result_types"])
    return scanned + result

"""The least bytes any TPC-H Q3 over one partition can move: every row of
the columns the query reads of ``lineitem``, ``orders`` and ``customer``,
once, and the result rows out. Probes, sorts, compactions and carried
payload are this program's way of doing it, not the query's need, so a
share of the roofline computed from this count cannot pass 100%.
``rows`` are the rows of the traffic's ``rows_in`` table; the other
tables have the configuration's."""

from ..wirefmt import width_of


def count(config, traffic, rows):
    q = config["query"]
    counted = traffic["tables"][traffic["rows_in"]]["table"]
    scanned = 0
    for name, reads in q["reads"].items():
        table = config["tables"][name]
        widths = {c["name"]: width_of(c["type"]) for c in table["columns"]}
        n = rows if name == counted else int(table["rows"])
        scanned += n * sum(widths[c] for c in reads)
    result = q["result_rows"] * sum(width_of(t) for t in q["result_types"])
    return scanned + result

"""The least bytes the HOTTEST chip of four has to move for TPC-H Q18's
aggregate behind the hash exchange, whatever implements the stage: its
quarter of the batch read once (the columns the subquery reads), the
rows it receives written once and read once, and its groups written
once. The hottest chip receives at least the mean, a quarter of the
rows, and holds at least a seventh as many groups as rows (an order has
at most seven lines, 4.2.3). Counts passes, the pid sort, the groupby's
sort passes, its searches and gathers, padding to a capacity and the
gather of every column whole are this program's way of doing it, not
the stage's need, so a share of the roofline computed from this count
cannot pass 100%. It is read against the device that is busy longest.
``rows`` are the rows of the traffic's ``rows_in`` table."""

from ..wirefmt import width_of


def count(config, traffic, rows):
    q = config["query"]
    table = config["tables"][traffic["tables"][traffic["rows_in"]]["table"]]
    widths = {c["name"]: width_of(c["type"]) for c in table["columns"]}
    row = sum(widths[c] for c in q["reads"])
    chips = int(traffic["mesh"])
    mine = -(-rows // chips)
    groups = -(-mine // int(q["max_lines_an_order"]))
    return mine * row * 3 + groups * sum(width_of(t) for t in q["result_types"])

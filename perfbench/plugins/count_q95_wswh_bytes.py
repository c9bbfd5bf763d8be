"""The least bytes any implementation of TPC-DS q95's ``ws_wh`` stage
over one partition of ``web_sales`` has to move: the two columns the CTE
reads, once, and the answer written once. The answer holds one row an
order, and an order has at most ``max_lines_an_order`` lines, so at
least a sixteenth as many rows as the input. The candidate pairs (12.5
for every input row), the probe's table, the materialise's gathers and
the groupby's sort are this program's way of doing it, not the stage's
need: each order's count of cross-warehouse pairs follows from its
lines' warehouses alone. So a share of the roofline computed from this
count cannot pass 100%, and reads the same work whatever implements the
join. ``rows`` are the rows of the traffic's ``rows_in`` table."""

from ..wirefmt import width_of


def count(config, traffic, rows):
    q = config["query"]
    table = config["tables"][traffic["tables"][traffic["rows_in"]]["table"]]
    widths = {c["name"]: width_of(c["type"]) for c in table["columns"]}
    answer = -(-rows // int(q["max_lines_an_order"]))
    return (rows * sum(widths[c] for c in q["reads"])
            + answer * sum(width_of(t) for t in q["result_types"]))

"""ivf.rows_read_per_query (rows): corpus rows the ivf plan gathered and
scored (its ``bytes_read`` over the row's bytes, padded queries and
padded list slots included) per query answered."""


def read(run):
    done = [r for r in run.records if r["done"] is not None]
    rows = sum(r["stats"].get("bytes_read", 0) for r in done) / run.row_bytes
    queries = sum(r["size"] for r in done)
    return rows / queries if queries else None

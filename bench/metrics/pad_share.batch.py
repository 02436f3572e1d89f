"""pad_share.batch (%): query rows the Searcher padded to reach its
compiled bucket, over all rows it ran (``padded_q`` of its stats)."""


def read(run):
    done = [r for r in run.records if r["done"] is not None]
    padded = sum(r["stats"].get("padded_q", 0) for r in done)
    total = sum(r["size"] for r in done) + padded
    return 100.0 * padded / total if total else None

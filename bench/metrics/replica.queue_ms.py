"""replica.queue_ms (ms): how long a request waited in its replica's
queue before the replica took it up, over the window's requests: the
mean of the ``replica<r>/queue_wait`` phase of the request rows the
program's router (``ReplicaSet``) logged.  A cell with no router reads
nothing."""


def read(run):
    rows = run.replica_log
    if not rows:
        return None
    waits = [row[f"replica{row['replica']}/queue_wait_s"] for row in rows]
    return 1e3 * sum(waits) / len(waits)

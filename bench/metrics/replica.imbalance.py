"""replica.imbalance (ratio): the queries the busiest replica served over
the mean over all replicas, in the window; 1 is an even spread.  Read
from the request rows the program's router (``ReplicaSet``) logged, one
per request served, with its ``replica`` and ``queries``.  A cell with
no router reads nothing."""


def read(run):
    rows = run.replica_log
    if not rows:
        return None
    served = [0] * run.replicas
    for row in rows:
        served[row["replica"]] += row["queries"]
    return max(served) * run.replicas / sum(served)

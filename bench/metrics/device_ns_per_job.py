"""Device-busy nanoseconds per simulated job-replication.

The busy ns of every chip inside the traced calls, summed over the
chips, over the job-replications those calls simulated: the device
scan's cost per job, free of the host's share.
"""


def read(record: dict, trace: dict | None) -> float | None:
    if not trace or not trace["busy_ns"] or not record["traced_jobs"]:
        return None
    busy = sum(trace["busy_ns"].values())
    return busy / record["traced_jobs"] if busy > 0 else None

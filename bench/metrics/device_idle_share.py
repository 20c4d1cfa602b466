"""Share of the timed calls' time in which no operation ran on a chip.

100 x (1 - busy / window), busy being the union of a chip's operation
intervals inside the calls on the profiler's clock, averaged over the
chips of the cell.  It is the host's share of a call: dispatch, input
checks, the eq.-2 partition, transfers and result assembly.
"""


def read(record: dict, trace: dict | None) -> float | None:
    if not trace or not trace["window_ns"] or not trace["busy_ns"]:
        return None
    busy = sum(trace["busy_ns"].values()) / len(trace["busy_ns"])
    return 100.0 * (1.0 - busy / trace["window_ns"])

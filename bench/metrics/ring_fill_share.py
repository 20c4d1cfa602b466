"""Share of the BS helper-wait ring the scans filled, in %.

100 x the program's ``bs_ring_peak`` counter (the most jobs of one class
that a BS-FCFS scan held in its helper-wait ring at once, over every
replication) over ``bs_ring_cap`` (the ring's size, ``queue_cap`` slots
per class), read from the program's own counters (``repro.core.spans``)
after the window.  The counters are the process's, so the warm-up call,
a call of the same mix, counts too, as set-up does in ``peak_hbm_mib``.
Silent for a program without the counters, or a cell that runs no BS
scan.
"""

import importlib


def read(record: dict, trace: dict | None) -> float | None:
    try:
        spans = importlib.import_module("repro.core.spans")
    except ImportError:
        return None
    c = spans.counters()
    if "bs_ring_peak" not in c or not c.get("bs_ring_cap"):
        return None
    return 100.0 * c["bs_ring_peak"] / c["bs_ring_cap"]

"""Share of the SRPT slot table the scans filled, in %.

100 x the program's ``srpt_peak`` counter (the largest number of jobs
in the system over every replication's scan) over ``srpt_q`` (the slot
table's size Q that each event sorts), read from the program's own
counters (``repro.core.spans``) after the window.  The counters are the
process's, so the warm-up call, a call of the same mix, counts too, as
set-up does in ``peak_hbm_mib``.  Silent for a program without the
counters, or a cell that runs no SRPT scan.
"""

import importlib


def read(record: dict, trace: dict | None) -> float | None:
    try:
        spans = importlib.import_module("repro.core.spans")
    except ImportError:
        return None
    c = spans.counters()
    if "srpt_peak" not in c or not c.get("srpt_q"):
        return None
    return 100.0 * c["srpt_peak"] / c["srpt_q"]

"""Peak device memory of the fullest chip after the window, in MiB.

``memory_stats()["peak_bytes_in_use"]`` as the runtime reports it: the
high-water mark of the process on that chip, set-up included.
"""


def read(record: dict, trace: dict | None) -> float | None:
    peak = record.get("memory_peak_bytes")
    return None if not peak else peak / 2**20

"""Set-up seconds: from the start of the process to the first timed call.

It covers the imports, bringing up the chips, making the first inputs,
and the warm-up call (compilation, or loading the compiled program from
the persistent cache, and one full run of it).
"""


def read(record: dict, trace: dict | None) -> float | None:
    return record["setup_s"]

"""1 - (union of device-op intervals / traced window), on the worst chip.

Read from the profiler trace (``trace.reduce``); None without a trace.
"""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    return max(d["idle_frac"] for d in tr["devices"])

"""Share of the traced window in which a collective runs on a chip with no
compute beside it, on the worst chip (the exposed-exchange arithmetic of
``obs/profile.attribute_events``, copied into ``perfbench/trace.py``).

None where the trace holds no collective: an unsharded run has no
exchange, which is not a perfectly hidden one.
"""


def read(run):
    tr = run.get("trace")
    if not tr or not any(d["comm_s"] > 0 for d in tr["devices"]):
        return None
    return max(d["exposed_comm_s"] for d in tr["devices"]) / tr["window_s"]

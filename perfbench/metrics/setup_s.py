"""Process start to the first timed chunk (host clock).

Backend start, the program's build and seeded init, the compile or cache
load of the chunk and diagnostics programs, the warm chunk and the digest
the correctness check keeps of it.
"""


def read(run):
    return run["setup_s"]

"""Host clock around ``cli.build`` (stepper build and jitted seeded init)
up to ``block_until_ready`` of the initial state."""


def read(run):
    return run["init_s"]

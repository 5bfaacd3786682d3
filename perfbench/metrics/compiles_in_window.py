"""compile + executable cache: persistent-cache misses plus in-process
executable-cache misses inside the measured window. Should read 0."""


def read(run):
    return run["window"].get("compiles")

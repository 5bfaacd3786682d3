"""end to end: process start to the first query of the window: imports,
data generation, compile or cache read, first upload, warm-up."""


def read(run):
    return run["window"]["setup_s"]

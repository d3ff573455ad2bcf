"""``setup_s``: process start to the first timed search (host clock):
device init, graph generation, compile or cache load, warm-up search."""


def read(run):
    return run.setup["setup_s"]

"""setup_s: seconds from process start to the first timed call (host
clock): data from the seed, building the engine, and one warm call at the
cell's shapes, compilation or cache load included."""


def read(record):
    return record["setup_s"]

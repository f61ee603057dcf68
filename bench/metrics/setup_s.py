"""End to end: process start to the window's start — JAX and chip start-up,
data, index build or load, warm-up of every shape (host clock)."""


def read(rec):
    return rec["setup_s"]

"""setup_s: process start to the window's start (host clock): loading,
weights from the seed, compilation or the compile cache, warm-up."""


def read(run):
    return run.setup_s

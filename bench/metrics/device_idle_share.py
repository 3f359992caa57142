"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over chips."""


def read(run):
    from bench import trace
    return 100.0 * trace.idle_share(run.trace)

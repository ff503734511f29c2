"""All pods whose bind the watch saw before the close of the window over
the window's length, the cycle in progress cut off: the plain count, which
moves in steps of one bind batch."""

META = {'name': 'window_pods_per_s', 'unit': 'pods/s', 'better': 'higher', 'source': 'host_clock', 'layer': 'scheduler loop', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    n = sum(1 for t in run.binds_in_window() if t < run.t_close)
    return n / run.seconds if run.created else None

"""Pods bound per second: all pods whose bind the benchmark's watch saw
from the open of the window to the end of the cycle that its close fell
in, over all of that time, staging included. The closed loop finishes the
cycle it is in when the window closes (kinds/waves.py), so nothing is cut
off and no second drops out: a scheduler that stalls at the tail makes
the time longer and the rate lower. The count over the fixed window alone
moves in steps of one 2048-pod bind batch; that is `window_pods_per_s`."""

META = {'name': 'pods_per_s', 'unit': 'pods/s', 'better': 'higher', 'source': 'host_clock'}
KIND = 'end_to_end'


def read(run):
    seen = run.binds_in_window()
    return len(seen) / (run.t_end - run.t_open) if seen else None

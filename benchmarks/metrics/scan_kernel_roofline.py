"""Least time the chip could take for the traced launches (benchlib/roofline.py:
operations and bytes from shapes; the vector unit's peak and the HBM's
from peaks.json, not the MXU's: the kernel is elementwise) over the scan
kernel's device time. The least time knows no per-pod latency, which is
what a sequential scan pays most: read the share as a ratio between two
PRs, next to kernel_us_per_pod. `run.notes` says which bound binds."""

META = {'name': 'scan_kernel_roofline', 'unit': '%', 'better': 'higher', 'source': 'device_trace', 'layer': 'kernel', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import roofline

    t = run.trace
    launches = run.traced_launches()
    if not t or not t['kernel_s'] or not launches:
        return None
    peak = roofline.peaks(run.device['kind'])
    least, bound = 0.0, set()
    for n, terms in launches:
        ls = roofline.least_seconds(
            roofline.launch_work(n, run.n_nodes, terms), peak)
        least += ls['seconds']
        bound.add(ls['bound'])
    run.notes['scan_kernel_roofline_bound'] = sorted(bound)
    return 100.0 * least / t['kernel_s']

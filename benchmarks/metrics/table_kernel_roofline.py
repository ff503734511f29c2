"""Least time the chip could take for the traced launches, with the work
counted from each launch's own mix of pod specs (benchlib/tableroofline.py:
pods, pods that carry a term, count rows touched, node lanes; peaks as
scan_kernel_roofline's), over the scan kernel's device time. Nothing where
the program's dispatch spans do not say what a launch carried."""

META = {'name': 'table_kernel_roofline', 'unit': '%', 'better': 'higher', 'source': 'device_trace', 'layer': 'kernel', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import roofline, tableroofline

    t = run.trace
    if not t or not t['kernel_s']:
        return None
    a, b = t['t_start'], t['t_stop']
    launches = [at for _, t0, _, at in run.window_spans('dispatch')
                if a <= t0 < b and at and 'templates' in at]
    if not launches:
        return None
    peak = roofline.peaks(run.device['kind'])
    least, bound = 0.0, set()
    for at in launches:
        ls = roofline.least_seconds(tableroofline.launch_work(
            at.get('n', 0), at.get('term_pods', 0), at.get('rows', 0),
            run.n_nodes), peak)
        least += ls['seconds']
        bound.add(ls['bound'])
    run.notes['table_kernel_roofline_bound'] = sorted(bound)
    return 100.0 * least / t['kernel_s']

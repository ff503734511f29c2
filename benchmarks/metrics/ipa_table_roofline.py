"""Least time the chip could take for the traced launches, with the work
counted from each launch's own mix (benchlib/iparoofline.py: pods, term
pods, count rows, pods that read an InterPodAffinity score and the score
rows they read or write; peaks as scan_kernel_roofline's), over the scan
kernel's device time. Nothing where the program's dispatch spans do not
say what a launch scored."""

META = {'name': 'ipa_table_roofline', 'unit': '%', 'better': 'higher', 'source': 'device_trace', 'layer': 'kernel', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import iparoofline, roofline

    t = run.trace
    if not t or not t['kernel_s']:
        return None
    a, b = t['t_start'], t['t_stop']
    launches = [at for _, t0, _, at in run.window_spans('dispatch')
                if a <= t0 < b and at and 'score_rows' in at]
    if not launches:
        return None
    peak = roofline.peaks(run.device['kind'])
    least, bound = 0.0, set()
    for at in launches:
        ls = roofline.least_seconds(iparoofline.launch_work(
            at.get('n', 0), at.get('term_pods', 0), at.get('rows', 0),
            at.get('score_pods', 0), at.get('score_rows', 0), run.n_nodes),
            peak)
        least += ls['seconds']
        bound.add(ls['bound'])
    run.notes['ipa_table_roofline_bound'] = sorted(bound)
    return 100.0 * least / t['kernel_s']

"""Median over the window's `preemption-wait` spans (one a node's
preemption: from the victims' registration to the delete echo of the last
of them, when the node's preemptors go back to the queue) of their length.
The notes say how many, and the victims and preemptors they held. Nothing
where no preemption finished inside the window, or the program has no
such span."""

META = {'name': 'victim_wait_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib.stats import percentile

    sp = [(d, a or {}) for n, _, d, a in run.window_spans('preemption-wait')
          if n == 'preemption-wait']
    if not sp:
        return None
    run.notes['preemption_waits'] = {
        'waits': len(sp),
        'victims': sum(a.get('victims') or 0 for _, a in sp),
        'preemptors': sum(a.get('preemptors') or 0 for _, a in sp)}
    return percentile([d for d, _ in sp], 50)

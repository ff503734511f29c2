"""Seconds inside the program's `whatif` spans of the window (one a
device-planned preemptor: its victim tensors prepared on the host, the
fused what-if launch, the wait for it and the pick) over their number, in
ms. The notes split the mean into the span's `prep_s` (host preparation)
and `wait_s` (launch to results on the host) where the span carries them.
Nothing where no preemptor was planned on the device inside the window."""

META = {'name': 'whatif_launch_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    sp = [(d, a or {}) for n, _, d, a in run.window_spans('whatif')
          if n == 'whatif']
    if not sp:
        return None
    parts = {}
    for key in ('prep_s', 'wait_s'):
        got = [a[key] for _, a in sp if a.get(key) is not None]
        if got:
            parts[key.replace('_s', '_ms')] = 1e3 * sum(got) / len(got)
    run.notes['whatif_launches'] = {'launches': len(sp), **parts}
    return 1e3 * sum(d for d, _ in sp) / len(sp)

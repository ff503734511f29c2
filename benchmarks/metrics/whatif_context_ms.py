"""Seconds inside the program's `whatif-context` spans of the window (one
a build of a what-if view of the cluster: a host snapshot of the encoding
uploaded and a scratch session's prologue, or a copy of the live
session's carry, as the span's `how` says) over their number, in ms. The
notes say how many builds, and of which kind. Nothing where no view was
built inside the window, or the program has no such span."""

META = {'name': 'whatif_context_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    sp = [(d, a or {}) for n, _, d, a in run.window_spans('whatif-context')
          if n == 'whatif-context']
    if not sp:
        return None
    how = {}
    for _, a in sp:
        how[a.get('how', '?')] = how.get(a.get('how', '?'), 0) + 1
    run.notes['whatif_contexts'] = {'builds': len(sp), 'how': how}
    return 1e3 * sum(d for d, _ in sp) / len(sp)

"""Seconds inside the program's `session-build` spans of the window (a
session torn down by a node event, built again from the encoding that took
the event: prologue, statics, upload) over their number, in ms. The span's
`reason` says what its predecessor was torn down for; the notes count the
builds by it. Nothing where no session was built inside the window."""

META = {'name': 'session_rebuild_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    sp = [(d, a or {}) for n, _, d, a in run.window_spans('session')
          if n == 'session-build']
    if not sp:
        return None
    by_reason = {}
    for _, a in sp:
        r = a.get('reason') or 'initial'
        by_reason[r] = by_reason.get(r, 0) + 1
    run.notes['session_builds'] = {'builds': len(sp), 'by_reason': by_reason}
    return 1e3 * sum(d for d, _ in sp) / len(sp)

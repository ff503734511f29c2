"""Pods the program's dispatch spans carried in the window over the number
of those spans (one per device launch)."""

META = {'name': 'pods_per_launch', 'unit': 'pods/launch', 'better': 'higher', 'source': 'program_span', 'layer': 'scheduler loop', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    sp = run.window_spans('dispatch')
    if not sp:
        return None
    return sum((a or {}).get('n', 0) for _, _, _, a in sp) / len(sp)

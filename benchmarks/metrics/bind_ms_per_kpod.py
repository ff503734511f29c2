"""Seconds inside the program's `bind` and `assume` spans in the window per
thousand pods bound in it."""

META = {'name': 'bind_ms_per_kpod', 'unit': 'ms/kpod', 'better': 'lower', 'source': 'program_span', 'layer': 'binder and cache', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    sp = run.window_spans('bind') + run.window_spans('assume')
    n = len(run.binds_in_window())
    if not sp or not n:
        return None
    return 1e3 * sum(d for _, _, d, _ in sp) / (n / 1e3)

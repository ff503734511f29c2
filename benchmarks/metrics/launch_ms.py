"""Device time of the scan kernel's trace events over their number, in the
traced part of the window."""

META = {'name': 'launch_ms', 'unit': 'ms', 'better': 'lower', 'source': 'device_trace', 'layer': 'sessions', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    t = run.trace
    if not t or not t['kernel_events']:
        return None
    return 1e3 * t['kernel_s'] / t['kernel_events']

"""Device time of the scan kernel in the traced part of the window over the
pods its launches carried (dispatch spans that started inside it)."""

META = {'name': 'kernel_us_per_pod', 'unit': 'us/pod', 'better': 'lower', 'source': 'device_trace', 'layer': 'kernel', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    t = run.trace
    pods = sum(n for n, _ in run.traced_launches())
    if not t or not t['kernel_s'] or not pods:
        return None
    return 1e6 * t['kernel_s'] / pods

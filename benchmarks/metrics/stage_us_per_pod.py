"""Benchmark clock around each wave's create loop (scheduler paused) over
the pods it created."""

META = {'name': 'stage_us_per_pod', 'unit': 'us/pod', 'better': 'lower', 'source': 'host_clock', 'layer': 'control plane', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    waves = run.kind_out.get('waves') or []
    pods = sum(w['pods'] for w in waves)
    if not pods:
        return None
    return 1e6 * sum(w['t_create1'] - w['t_create0'] for w in waves) / pods

"""scheduler_tpu_balanced_quirk_states{what="listed"} at the close of
the window: the node states at which float64's BalancedAllocation reads
one less than the exact floor, as the live table session lists them for
its kernel (room: `capacity`, 1024). Nothing on a program without the
gauge."""

META = {'name': 'quirk_states', 'unit': 'count', 'better': 'lower', 'source': 'program_counter', 'layer': 'kernel', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    gauge = run.counters1['registry'].get(
        'scheduler_tpu_balanced_quirk_states', {})
    if 'listed' not in gauge:
        return None
    return float(gauge['listed'])

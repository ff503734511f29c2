"""scheduler_tpu_inexact_builds_total over set-up and the window, every
`what`: device sessions built with BalancedAllocation (`balanced`) or
the zone-spread product (`spread`) in float32, or demoted to the jnp
hoisted session (`demoted`). Has to read 0 where the table kernel holds
the cluster exactly. Nothing on a program without the counter."""

META = {'name': 'inexact_builds', 'unit': 'count', 'better': 'lower', 'source': 'program_counter', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    by_what = run.counters1['registry'].get(
        'scheduler_tpu_inexact_builds_total')
    if by_what is None:
        return None
    return float(sum(by_what.values()))

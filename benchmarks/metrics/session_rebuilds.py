"""scheduler_session_rebuilds_total, all reasons, over the window."""

META = {'name': 'session_rebuilds', 'unit': 'count', 'better': 'lower', 'source': 'program_counter', 'layer': 'scoring backend', 'moves': 'bind_p95_s'}
KIND = 'per_layer'


def read(run):
    now, base = run.counters1['session_rebuilds'], run.counters0['session_rebuilds']
    return float(sum(now.values()) - sum(base.values()))

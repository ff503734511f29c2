"""scheduler_session_delta_applies_total{kind="pod-remove"} over the window
— deletes of bound pods that the live session took as carry deltas
(`PallasSession.apply_deltas` -> `_delta_scan`), no rebuild — over the cycles
of the `churn-waves` kind. About the bound pods a cycle deletes where nothing
tears the session down first; 0 where a node event does (the queued deltas
die with the session). Nothing where the kind ran no cycle or the program
keeps no such counter."""

META = {'name': 'delta_pods_per_cycle', 'unit': 'pods', 'better': 'higher', 'source': 'program_counter', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'

COUNTER = 'scheduler_session_delta_applies_total'


def read(run):
    cycles = len(run.kind_out.get('waves') or [])
    now = run.counters1.get('registry', {}).get(COUNTER)
    if not cycles or now is None:
        return None
    was = run.counters0.get('registry', {}).get(COUNTER, {})
    return float(now.get('pod-remove', 0) - was.get('pod-remove', 0)) / cycles

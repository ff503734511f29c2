"""Of the preemptors the program planned inside the window
(scheduler_preemption_planner_total over every `path`), the share planned
by the device what-if rung (`path` device): 1.0 where the device rung
planned them all. The notes keep every rung's count and what
scheduler_whatif_fallbacks_total added, by reason. Nothing where no
preemptor was planned, or the program keeps no such counter."""

META = {'name': 'device_plan_share', 'unit': 'fraction', 'better': 'higher', 'source': 'program_counter', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'
COUNTER = 'scheduler_preemption_planner_total'
FALLBACKS = 'scheduler_whatif_fallbacks_total'


def _moved(run, name):
    was = run.counters0.get('registry', {}).get(name, {})
    now = run.counters1.get('registry', {}).get(name, {})
    return {k: v - was.get(k, 0) for k, v in now.items()
            if v != was.get(k, 0)}


def read(run):
    paths = _moved(run, COUNTER)
    total = sum(paths.values())
    if not total:
        return None
    run.notes['preemption_planner'] = {'paths': paths,
                                       'whatif_fallbacks': _moved(run, FALLBACKS)}
    return paths.get('device', 0) / total

"""Of the preemptors the program planned in a what-if launch inside the
window (scheduler_whatif_planned_total over every `path` and `reason`),
the share planned in a wave launch (`path` wave: one launch for a run of
preemptors of one view, template and priority, the pick and the claim on
the device): 1.0 where every one was. The notes keep the count of each
(path, reason), the reasons of `single` saying why a preemptor launched
alone. Nothing where no preemptor was planned on the device, or the
program keeps no such counter."""

META = {'name': 'whatif_wave_share', 'unit': 'fraction', 'better': 'higher', 'source': 'program_counter', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'
COUNTER = 'scheduler_whatif_planned_total'


def read(run):
    was = run.counters0.get('registry', {}).get(COUNTER, {})
    now = run.counters1.get('registry', {}).get(COUNTER, {})
    moved = {k: v - was.get(k, 0) for k, v in now.items()
             if v != was.get(k, 0)}
    total = sum(moved.values())
    if not total:
        return None
    run.notes['whatif_planned'] = moved
    return sum(v for k, v in moved.items()
               if k.split('/')[0] == 'wave') / total

"""scheduler_session_rebuilds_total over the window under the reasons a
delete or a node change causes — `node-add`, `node-remove` (a node event the
live session could not take as a lane delta), `pod-remove` (a delete it
could not take as a carry delta), `delta-apply-failed` — over the cycles of
the `churn-waves` kind. Has to read 0 where pods are deleted and no node
changes: every delete is a carry delta. Nothing where the kind ran no cycle
or the program keeps no such counter."""

META = {'name': 'churn_rebuilds_per_cycle', 'unit': 'count', 'better': 'lower', 'source': 'program_counter', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'

COUNTER = 'scheduler_session_rebuilds_total'
REASONS = ('node-add', 'node-remove', 'pod-remove', 'delta-apply-failed')


def _count(by_label):
    return sum(v for k, v in by_label.items() if k.split('/')[0] in REASONS)


def read(run):
    cycles = len(run.kind_out.get('waves') or [])
    now = run.counters1.get('registry', {}).get(COUNTER)
    if not cycles or now is None:
        return None
    was = run.counters0.get('registry', {}).get(COUNTER, {})
    by_reason = {k: v - was.get(k, 0) for k, v in now.items()
                 if v != was.get(k, 0)}
    run.notes['churn_rebuilds'] = {'cycles': cycles, 'by_reason': by_reason}
    return float(_count(now) - _count(was)) / cycles

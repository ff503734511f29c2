"""Binds the watch saw between each wave's resume and its end (the unbound
count back at the standing backlog) over that time: the loop's rate with
staging left out. A wave that did not drain before the settle deadline is
left out (and makes the run not correct)."""

META = {'name': 'drain_pods_per_s', 'unit': 'pods/s', 'better': 'higher', 'source': 'host_clock', 'layer': 'scheduler loop', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    waves = [w for w in run.kind_out.get('waves') or [] if w['drained']]
    t = sum(w['t_done'] - w['t_resume'] for w in waves)
    n = sum(w['bound_after'] - w['bound_at_resume'] for w in waves)
    return n / t if t > 0 else None

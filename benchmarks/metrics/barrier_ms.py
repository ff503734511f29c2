"""Benchmark clock from the pause of a cycle of the `churn-waves` kind to
the instant every pod the scheduler had popped is bound on the benchmark's
watch (`Cluster.barrier`), median over the cycles, in ms: how long the
batches in flight take to land once the scheduler is paused. Nothing where
the kind waits at no barrier."""

META = {'name': 'barrier_ms', 'unit': 'ms', 'better': 'lower', 'source': 'host_clock', 'layer': 'scheduler loop', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    waits = sorted(w['t_mutate0'] - w['t_barrier0']
                   for w in run.kind_out.get('waves') or []
                   if 't_barrier0' in w and 't_mutate0' in w)
    if not waits:
        return None
    mid = len(waits) // 2
    return 1e3 * (waits[mid] if len(waits) % 2
                  else (waits[mid - 1] + waits[mid]) / 2)

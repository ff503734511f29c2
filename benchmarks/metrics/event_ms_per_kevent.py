"""Benchmark clock from the first event of a cycle's change of the cluster
to its first create, summed over the cycles of the `churn-waves` kind, over
the events issued (pods deleted, nodes removed, nodes added), in ms per 1000
events: what the API server, the informers, the scheduler's cache, its queue
and the scoring backend's listeners take to absorb the deletes and node
calls, the waits for the cache to follow (`Cluster.settle`) included.
Nothing where no cycle changed the cluster."""

META = {'name': 'event_ms_per_kevent', 'unit': 'ms/kevent', 'better': 'lower', 'source': 'host_clock', 'layer': 'control plane', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    waves = [w for w in run.kind_out.get('waves') or [] if 't_mutate0' in w]
    events = sum(w.get('deleted', 0) + w.get('nodes_removed', 0)
                 + w.get('nodes_added', 0) for w in waves)
    if not events:
        return None
    return 1e6 * sum(w['t_create0'] - w['t_mutate0'] for w in waves) / events

"""99th percentile of the samples bind_p95_s is taken from."""

META = {'name': 'bind_p99_s.tail', 'unit': 's', 'better': 'lower', 'source': 'host_clock', 'layer': 'tail', 'moves': 'bind_p95_s'}
KIND = 'per_layer'


def read(run):
    from benchlib.stats import percentile

    return percentile(run.latencies, 99) if run.latencies else None

"""95th percentile of the samples bind_p50_s is the median of, where it is
no end-to-end metric: in `default-5000n.arrivals` two sets of six runs of
one program spread by 0.6 to 1.2 of their median (PERF.md section 2), so no
bound the contract allows could hold it."""

META = {'name': 'bind_p95_s.tail', 'unit': 's', 'better': 'lower', 'source': 'host_clock', 'layer': 'tail', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib.stats import percentile

    return percentile(run.latencies, 95) if run.latencies else None

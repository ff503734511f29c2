"""95th percentile of the same samples as bind_p50_s; a pod that never
bound counts with the whole wait."""

META = {'name': 'bind_p95_s', 'unit': 's', 'better': 'lower', 'source': 'host_clock'}
KIND = 'end_to_end'


def read(run):
    from benchlib.stats import percentile

    return percentile(run.latencies, 95) if run.latencies else None

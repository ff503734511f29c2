"""Median over ALL pods due in the window of (bind seen on the watch - the
instant the pod was due to be created)."""

META = {'name': 'bind_p50_s', 'unit': 's', 'better': 'lower', 'source': 'host_clock'}
KIND = 'end_to_end'


def read(run):
    from benchlib.stats import percentile

    return percentile(run.latencies, 50) if run.latencies else None

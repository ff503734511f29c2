"""metrics/gen_late_p95_s.py's number (how late the benchmark's one
generator thread ran, 95th percentile over all pods), for a cell that
reports no bind_p95_s: it has to stay a small part of bind_p50_s."""

META = {'name': 'gen_late_p95_s.arrivals', 'unit': 's', 'better': 'lower', 'source': 'host_clock', 'layer': 'generator', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib.stats import percentile

    d = [run.issued[i] - run.ready[i] for i in run.created if i in run.ready]
    return percentile(d, 95) if d else None

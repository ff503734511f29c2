"""95th percentile over ALL pods of the window of (create call really
started - the instant the pod's own client was free to make it): what the
benchmark's one generator thread cost the clients it plays. A single pod's
client is free when the pod is due; a scale-up's controller creates its
pods one call after another, so it is free for the next when the call
before has returned (that wait is the API server's, not the generator's).
Has to stay a small part of bind_p50_s."""

META = {'name': 'gen_late_p95_s', 'unit': 's', 'better': 'lower', 'source': 'host_clock', 'layer': 'generator', 'moves': 'bind_p95_s'}
KIND = 'per_layer'


def read(run):
    from benchlib.stats import percentile

    d = [run.issued[i] - run.ready[i] for i in run.created if i in run.ready]
    return percentile(d, 95) if d else None

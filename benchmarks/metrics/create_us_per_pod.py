"""Benchmark clock around each pods.create call of the open loop, next to
a running scheduler."""

META = {'name': 'create_us_per_pod', 'unit': 'us/pod', 'better': 'lower', 'source': 'host_clock', 'layer': 'control plane', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    d = [run.create_done[i] - run.issued[i] for i in run.created
         if i in run.create_done]
    return 1e6 * sum(d) / len(d) if d else None

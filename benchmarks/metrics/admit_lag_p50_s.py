"""Median over the window's pods of (create call issued -> the end of the
pod's `informer ADDED pods` span): API server, store, watch fan-out, the
informer's decode and hop, queue.add. The pod is then in the scheduling
queue. One segment of benchlib/podpath.py's tiling of bind_p50_s."""

META = {'name': 'admit_lag_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'control plane', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    return podpath.segment_p50(run, 'admit_lag')

"""Median over the window's pods of (queue admission -> the start of the
`pop` span of the batch that bound the pod): how long a pod that could
be scheduled stood in the queue. A pod popped by the blocking pop that
opens its batch can read a few microseconds below zero: its informer
span was still closing. One segment of benchlib/podpath.py's tiling."""

META = {'name': 'queue_wait_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'scheduler loop', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    return podpath.segment_p50(run, 'queue_wait')

"""Median over the window's pods of (its batch's `pop` start -> its batch's
`harvest` end): gather, prep, encode, dispatch, the completion FIFO, the
device wait and the decode. One segment of benchlib/podpath.py's tiling."""

META = {'name': 'decide_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'scoring backend', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    return podpath.segment_p50(run, 'decide')

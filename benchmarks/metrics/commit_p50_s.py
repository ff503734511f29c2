"""Median over the window's pods of (its batch's `harvest` end -> the bind
seen on the benchmark's watch): assume, reserve-permit, the wait for a
binder thread, the bind POST, store and fan-out. One segment of
benchlib/podpath.py's tiling."""

META = {'name': 'commit_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'binder and cache', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    return podpath.segment_p50(run, 'commit')

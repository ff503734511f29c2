"""Median over the window's bound preemptors of (the start of its wave's
`evict` span -> the end of its node's `preemption-wait`): its victims'
deletes in their turn on the binder thread and their echoes. One
segment of benchlib/preemptpath.py's tiling; nothing on a program
without the preemption path's spans."""

META = {'name': 'preemptor_evict_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import preemptpath

    return preemptpath.segment_p50(run, 'evict')

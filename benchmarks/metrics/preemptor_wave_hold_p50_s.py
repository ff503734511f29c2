"""Median over the window's bound preemptors of (the end of its own
`whatif` span -> the start of its wave's `evict` span): the preemptors
planned after it, the wave's registration and the binder's queue. One
segment of benchlib/preemptpath.py's tiling; nothing on a program
without the preemption path's spans."""

META = {'name': 'preemptor_wave_hold_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import preemptpath

    return preemptpath.segment_p50(run, 'wave_hold')

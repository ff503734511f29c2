"""Median over the window's bound preemptors of (its failed launch's
`harvest` end -> the start of its own `whatif` span): the wave's books
and the preemptors planned before it. One segment of
benchlib/preemptpath.py's tiling; nothing on a program without the
preemption path's spans."""

META = {'name': 'preemptor_plan_wait_p50_s', 'unit': 's', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import preemptpath

    return preemptpath.segment_p50(run, 'plan_wait')

"""Per failure wave of the window, in ms: its `preemption-wave` span's
`snapshot_s` and `eligibility_s` steps (the cache's snapshot, the PDB
list, the wave's anti-affinity terms, the per-pod rung checks) plus the
`preemption-books` span inside it (the planner's books); the mean over
the waves. The notes keep the waves' summed steps
(`preemption_waves`). Nothing on a program without those spans."""

META = {'name': 'preempt_books_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import preemptpath

    return preemptpath.books_ms(run)

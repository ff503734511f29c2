"""Seconds inside the program's `preemption-plan` spans of the window (one
a failure wave: the wave's books, then every preemptor planned on its
rung) over the preemptors they planned (the span's `n`), in ms: planning
time per preemptor, every rung and the books included. The notes say how
many waves and preemptors, and the rung mix where the span says it.
Nothing where no wave was planned inside the window."""

META = {'name': 'preempt_plan_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'preemption', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    sp = [(d, a or {}) for n, _, d, a in run.window_spans('planner')
          if n == 'preemption-plan']
    pods = sum(a.get('n') or 0 for _, a in sp)
    if not pods:
        return None
    mix = {}
    for _, a in sp:
        for path in ('device', 'fast', 'oracle'):
            mix[path] = mix.get(path, 0) + (a.get(path) or 0)
    run.notes['preemption_plan'] = {'waves': len(sp), 'preemptors': pods,
                                    'paths': mix}
    return 1e3 * sum(d for d, _ in sp) / pods

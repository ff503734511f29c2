"""Seconds inside the program's `template-admit` spans of the window
(a live session taking new pod specs in: prologue on the new specs, row
writes, no rebuild) over their number, in ms. Nothing where no spec was
admitted inside the window."""

META = {'name': 'template_admit_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    sp = run.window_spans('template-admit')
    if not sp:
        return None
    run.notes['template_admits'] = {
        'admissions': len(sp),
        'specs': sum((a or {}).get('n', 0) for _, _, _, a in sp),
        'rows': sum((a or {}).get('rows', 0) for _, _, _, a in sp)}
    return 1e3 * sum(d for _, _, d, _ in sp) / len(sp)

"""Distinct InterPodAffinity score rows a device launch read or wrote,
mean over the window's dispatch spans that say so (`score_rows`: the
table session counts them per launch, beside `score_pods`, the pods whose
spec reads a score; a program whose spans lack it reports nothing)."""

META = {'name': 'score_rows_per_launch', 'unit': 'rows/launch', 'better': 'lower', 'source': 'program_span', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    spans = [a for _, _, _, a in run.window_spans('dispatch')
             if a and 'score_rows' in a]
    if not spans:
        return None
    run.notes['score_pods_per_launch'] = (
        sum(a.get('score_pods', 0) for a in spans) / len(spans))
    return sum(a['score_rows'] for a in spans) / len(spans)

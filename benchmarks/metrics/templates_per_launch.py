"""Distinct pod specs a device launch carried, mean over the window's
dispatch spans that say so (`templates`: the table session counts them
per launch; a program whose spans lack it reports nothing)."""

META = {'name': 'templates_per_launch', 'unit': 'specs/launch', 'better': 'higher', 'source': 'program_span', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    got = [a['templates'] for _, _, _, a in run.window_spans('dispatch')
           if a and 'templates' in a]
    if not got:
        return None
    return sum(got) / len(got)

"""scheduler_session_rebuilds_total over the window under the reasons a
pod spec causes: a spec the session had not met (`new-template`), more
specs than it holds (`template-overflow`, and the table session's own
`table-*` and `terms-enabled`), a label that grew the vocabulary
(`shape-change`). Has to read 0 where specs are rows of a table."""

META = {'name': 'template_rebuilds', 'unit': 'count', 'better': 'lower', 'source': 'program_counter', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'

REASONS = ('new-template', 'template-overflow', 'shape-change',
           'terms-enabled')


def _count(by_label):
    return sum(v for k, v in by_label.items()
               if k.split('/')[0] in REASONS or k.startswith('table-'))


def read(run):
    return float(_count(run.counters1['session_rebuilds'])
                 - _count(run.counters0['session_rebuilds']))

"""Seconds CPython's cyclic collector held the interpreter between the two
readings of the program's counters (the open of the window and the settled
close), all generations, per thousand pods bound in the window:
`python_gc_seconds_total{generation}` of the program's registry, which its
heap policy feeds from a `gc.callbacks` hook (utils/selfstats.py). Every
collection stops every thread, so this is time no stage span owns.
`notes.gc` has the collections, the seconds and the objects freed by
generation (2 = a full collection). Nothing where no pod was bound or the
program keeps no such counter."""

META = {'name': 'gc_ms_per_kpod', 'unit': 'ms/kpod', 'better': 'lower', 'source': 'program_counter', 'layer': 'interpreter', 'moves': 'pods_per_s'}
KIND = 'per_layer'

SECONDS = 'python_gc_seconds_total'
NOTED = {'collections': 'python_gc_collections_total', 'seconds': SECONDS,
         'collected': 'python_gc_objects_collected_total'}


def _moved(run, counter):
    was = run.counters0.get('registry', {}).get(counter, {})
    now = run.counters1.get('registry', {}).get(counter, {})
    return {g: v - was.get(g, 0) for g, v in sorted(now.items())}


def read(run):
    n = len(run.binds_in_window())
    if not n or SECONDS not in run.counters1.get('registry', {}):
        return None
    noted = run.notes['gc'] = {k: _moved(run, c) for k, c in NOTED.items()}
    return 1e3 * sum(noted['seconds'].values()) / (n / 1e3)

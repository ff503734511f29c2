"""Seconds inside the program's `informer` spans named `ADDED pods` in the
window (watch event in hand -> last handler returned: the informer's
lock, its cache, the scheduler's handler and queue.add) over their
number: the informer delivers each created pod once."""

META = {'name': 'admit_us_per_pod', 'unit': 'us/pod', 'better': 'lower', 'source': 'program_span', 'layer': 'control plane', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    sp = podpath.named_spans(run, 'informer', 'ADDED pods')
    if not sp:
        return None
    return 1e6 * sum(d for d, _ in sp) / len(sp)

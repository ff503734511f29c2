"""Median over the batches popped in the window of (start of the batch's
`pop` span -> end of its `bind` span), the two joined by the spans'
`batch` attribute: one batch's way through the whole pipeline."""

META = {'name': 'batch_turnaround_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'scheduler loop', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    t = podpath.batch_turnaround_p50(run)
    return None if t is None else 1e3 * t

"""Seconds inside the program's `delta-apply` spans of the window (the
queued carry deltas of a cycle's deletes flushed into the live session in
one launch, its host side: entries built and padded, the `_delta_scan`
enqueued) over their number, in ms. The notes say what shaped the launches
where the program says it: deltas, carry entries, and the bucket they were
padded to. Nothing where no delta was applied inside the window."""

META = {'name': 'delta_apply_ms', 'unit': 'ms', 'better': 'lower', 'source': 'program_span', 'layer': 'scoring backend', 'moves': 'pods_per_s'}
KIND = 'per_layer'


def read(run):
    sp = run.window_spans('delta-apply')
    if not sp:
        return None
    attrs = [a or {} for _, _, _, a in sp]
    run.notes['delta_applies'] = {
        'applies': len(sp),
        'deltas': sum(a.get('n') or 0 for a in attrs),
        'entries': sum(a.get('entries') or 0 for a in attrs),
        'buckets': sorted({a['bucket'] for a in attrs if a.get('bucket')})}
    return 1e3 * sum(d for _, _, d, _ in sp) / len(sp)

"""Seconds the creating thread stood waiting inside the program's
`apiserver` spans named `create pods` (wall less the span's `cpu_s`: the
interpreter lock, the server's and the store's locks) per create, over
the creates of the window whose span read the thread's CPU clock (the
program reads it on one span in sixteen). Beside create_us_per_pod, which
times the same call from outside, it says how much of a create is
standing in line."""

META = {'name': 'create_wait_us_per_pod', 'unit': 'us/pod', 'better': 'lower', 'source': 'program_span', 'layer': 'control plane', 'moves': 'bind_p50_s'}
KIND = 'per_layer'


def read(run):
    from benchlib import podpath

    sp = [(d, a) for d, a in podpath.named_spans(
        run, 'apiserver', 'create pods') if 'cpu_s' in a]
    if not sp:
        return None
    # sums first: one reading of a clock that ticks in steps of 10 ms can
    # exceed its span, the sum over many does not
    wait = sum(d for d, _ in sp) - sum(a['cpu_s'] for _, a in sp)
    return 1e6 * max(0.0, wait) / len(sp)

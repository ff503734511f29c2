"""Process start to window open: imports, cluster build, informer sync,
init pods, warm-up (compile or cache load), pod objects built."""

META = {'name': 'setup_s', 'unit': 's', 'better': 'lower', 'source': 'host_clock'}
KIND = 'end_to_end'


def read(run):
    return run.setup_s

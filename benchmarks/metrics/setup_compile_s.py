"""Seconds inside compile requests before the window opened (cache loads
included), summed over threads, by the program's CompileMeter."""

META = {'name': 'setup_compile_s', 'unit': 's', 'better': 'lower', 'source': 'program_counter', 'layer': 'set-up', 'moves': 'setup_s'}
KIND = 'per_layer'


def read(run):
    return run.setup_compile_s

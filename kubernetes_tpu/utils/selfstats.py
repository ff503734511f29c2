"""Process self-telemetry gauges (process_* metrics on /metricsz).

The endurance soak's leak invariants (bounded RSS/fd/thread growth —
testing/invariants.py) read the SAME surface an operator scrapes instead
of poking process internals: `refresh()` samples the process and updates
the gauges, and `configz.metricsz_body()` calls it right before every
exposition so /metricsz is always current without a background sampler
thread.

Sources are Linux-first with portable fallbacks: RSS from
/proc/self/statm (resource.getrusage reports the PEAK, useless for a
growth invariant), fd count from /proc/self/fd, thread count from
threading (enumerate of live Python threads — the pipeline's workers,
binders, watch writers all register there).

The process's HEAP POLICY lives here too (`adopt_heap_policy`): when
CPython's cyclic collector runs is decided once, by the program, and the
`python_gc_*` counters say what it cost.
"""

from __future__ import annotations

import gc
import os
import threading
import time

from .metrics import Counter, Gauge, legacy_registry

process_rss = legacy_registry.register(
    Gauge(
        "process_resident_memory_bytes",
        "Resident set size of this process (from /proc/self/statm; 0 "
        "where /proc is unavailable). The soak's leak invariant bounds "
        "its first-window-to-last-window growth.",
        (),
    )
)
process_open_fds = legacy_registry.register(
    Gauge(
        "process_open_fds",
        "Open file descriptors of this process (from /proc/self/fd; 0 "
        "where /proc is unavailable). Sustained growth under churn = a "
        "leaked socket/stream per wave.",
        (),
    )
)
process_threads = legacy_registry.register(
    Gauge(
        "process_threads",
        "Live Python threads in this process (threading.active_count). "
        "The pipeline workers, binder pool, probe thread, and per-watch "
        "writer threads all count here; growth under churn = a worker "
        "restart or watch path leaking threads.",
        (),
    )
)

_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def rss_bytes() -> int:
    try:
        with open("/proc/self/statm", "r", encoding="ascii") as f:
            return int(f.read().split()[1]) * _PAGE
    except (OSError, ValueError, IndexError):
        return 0


def open_fds() -> int:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return 0


def refresh() -> None:
    """Sample the process into the gauges; called by metricsz_body()
    before every exposition. Cheap (two /proc reads) and must never
    raise into the metrics handler."""
    try:
        process_rss.set(rss_bytes())
        process_open_fds.set(open_fds())
        process_threads.set(threading.active_count())
    except Exception:  # noqa: BLE001 — telemetry is best-effort
        pass


# -- heap policy ----------------------------------------------------------------
#
# The program is a long-lived server whose heap is acyclic API data: a pod
# leaves dicts, lists and dataclass instances behind in the store, the
# informers, the scheduler's cache and queue, and all of it dies by
# reference count or lives on (tests/test_heap_policy.py holds the create ->
# watch -> informer -> bind path to `collected == 0`; on the chip every
# generation of every cell read 0 too, with jax and the backend in the
# process). The interpreter's default (700, 10, 10) is made for short
# scripts: a full collection every 100 young ones that CPython's own rule
# (only once a quarter of the old generation is new) lets through at every
# 25 % of growth, so a window that binds 80 000 pods walked its 6 million
# objects 15-17 times, 7-9 s of 51, to free nothing. So the process says
# once how often the OLD generation is worth walking. Automatic collection
# stays on and nothing is frozen: a cycle (an exception's traceback, a
# closure) is reclaimed young within 700 allocations, at the latest after
# 10.5 million, about two minutes of binding 1500 pods a second.
#
# (young, middle, old): a young collection every `young` net container
# allocations, a middle one every `middle` young ones, a full one every `old`
# middle ones. Chosen on one TPU v5e's host (PERF.md section 6, PR 35),
# collector seconds of a 51 s window of `churn-5000n.scale-downs` and its
# longest stop, the same seed:
#     (700, 10, 10)      11.02 s   1.095 s   the interpreter's default
#     (50000, 20, 100)    3.04 s   0.331 s   one stop as long as the old full
#     (10000, 10, 100)    3.64 s   0.112 s     ones in the open-loop cells
#     (5000, 10, 200)     3.54 s   0.057 s
#     (2000, 10, 500)     3.16 s   0.051 s
#     (700, 10, 1500)     2.19 s   0.025 s   chosen
# The young generations stay the interpreter's: 700 objects still lie in
# the cache they were allocated in (141 ns an object walked, against 242 at
# 10 000 and 303 at 50 000), and a stop stays under 0.06 s.
GC_THRESHOLDS = (700, 10, 1500)

_GENERATIONS = ("0", "1", "2")


def _gc_counter(name: str, help: str) -> Counter:
    counter = legacy_registry.register(Counter(name, help, ("generation",)))
    for g in _GENERATIONS:
        counter.inc(0.0, generation=g)
    return counter


gc_collections = _gc_counter(
    "python_gc_collections_total",
    "Runs of CPython's cyclic collector since the heap policy was adopted, "
    "by the generation collected (2 = a full collection).")
gc_seconds = _gc_counter(
    "python_gc_seconds_total",
    "Seconds the cyclic collector held the interpreter (every thread "
    "stands still), by generation.")
gc_collected = _gc_counter(
    "python_gc_objects_collected_total",
    "Objects the cyclic collector freed, by generation. ~0 on the pod "
    "path: what a pod allocates dies by reference count.")

_adopt_lock = threading.Lock()
_gc_started = 0.0


def _gc_hook(phase: str, info: dict) -> None:
    """gc.callbacks hook: two clock reads a collection. It takes NO lock: a
    collection starts between any two bytecodes, also inside a `with
    counter._lock:` of the thread it runs on, and the collector never runs
    twice at once, so this is the counters' only writer."""
    global _gc_started
    if phase == "start":
        _gc_started = time.perf_counter()
        return
    seconds = time.perf_counter() - _gc_started
    key = (_GENERATIONS[info["generation"]],)
    for counter, amount in ((gc_collections, 1.0), (gc_seconds, seconds),
                            (gc_collected, float(info["collected"]))):
        counter._values[key] = counter._values.get(key, 0.0) + amount


def adopt_heap_policy() -> None:
    """Set the collector's thresholds and hook its counters, once a
    process however often it is called: `APIServer` and `Scheduler` both
    call it where they start, whichever a deployment constructs first."""
    with _adopt_lock:
        if _gc_hook in gc.callbacks:
            return
        gc.set_threshold(*GC_THRESHOLDS)
        gc.callbacks.append(_gc_hook)

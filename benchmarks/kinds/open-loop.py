"""Traffic kind `open-loop`: pods fall due on a schedule made from the
seed, whatever the scheduler is doing.

Two clients, either may be absent:
  users       `rate_pods_per_s` single pods a second, exponential gaps;
              each pod is a user of its own, free to create it the instant
              it is due
  controller  every `burst_every_s` seconds on average one scale-up: all
              `burst_pods` pods of it fall due at the same instant, and the
              controller creates them one call after another, so each call
              waits for the one before it to return: that wait is the API
              server's service time, part of what a scale-up's owner sees
One thread issues both (a second thread made the runs erratic, PERF.md §6),
but never lets one client stand in the other's way for longer than one
call: before every create of a scale-up it first issues every single that
has fallen due. `ready` records when the pod's own client was free to
create it (its due time; for a scale-up's later pods the return of the
controller's previous create, if later), `issued` when the call really
started: issued - ready is the generator's own lateness, what sharing one
thread cost, and it is read beside the latencies. Every pod is timed from
the instant it was due.
Every seed gets the SAME multiset of gaps, burst intervals and burst
sizes in another order (the quantiles of the distribution, shuffled), so
the seed moves the arrangement and never the amount of work. Parameters
from the traffic file: `rate_pods_per_s`, `single_template`,
`burst_every_s`, `burst_pods` (a number or a list of sizes to cycle),
`burst_template`, `groups` (labels' `{group}` cycles over this many
values, one per burst; 0: none).
"""

from __future__ import annotations

import math
import random
import time
from typing import Dict, List, Tuple


def schedule(traffic: Dict, seed: int, seconds: float
             ) -> Tuple[List[float], List[Tuple[float, int, int]]]:
    """(due offsets of the singles, [(due offset, group or -1, pods)] of
    the scale-ups), each sorted by due offset."""
    rng = random.Random(seed)
    singles: List[float] = []
    rate = float(traffic.get("rate_pods_per_s", 0))
    if rate > 0:
        n = int(rate * seconds)
        gaps = [-math.log(1.0 - (k + 0.5) / n) / rate for k in range(n)]
        rng.shuffle(gaps)
        t = 0.0
        for g in gaps:
            t += g
            if t < seconds:
                singles.append(t)
    bursts: List[Tuple[float, int, int]] = []
    every = float(traffic.get("burst_every_s", 0))
    if every > 0:
        m = int(seconds // every)
        gaps = [every * (0.5 + (j + 0.5) / m) for j in range(m)]
        rng.shuffle(gaps)
        sizes = traffic["burst_pods"]
        sizes = [sizes] if isinstance(sizes, int) else list(sizes)
        sizes = [sizes[j % len(sizes)] for j in range(m)]
        rng.shuffle(sizes)
        groups = int(traffic.get("groups", 0))
        turn = list(range(groups))
        rng.shuffle(turn)
        t = -every / 2.0
        for j in range(m):
            t += gaps[j]
            bursts.append((t, turn[j % groups] if groups else -1, sizes[j]))
    return singles, bursts


def prepare(cluster, traffic: Dict, seed: int, seconds: float) -> Dict:
    singles, bursts = schedule(traffic, seed, seconds)
    s_idx = cluster.prebuild(
        [cluster.pod_class(traffic["single_template"])] * len(singles)
    ) if singles else []
    b_due, b_idx = [], []
    for off, g, n in bursts:
        b_due += [off] * n
        b_idx += cluster.prebuild(
            [cluster.pod_class(traffic["burst_template"],
                               g if g >= 0 else None)] * n)
    return {"singles": (singles, s_idx), "bursts": (b_due, b_idx)}


def drive(cluster, plan: Dict, rec, t_open: float, t_close: float) -> Dict:
    now, sleep = time.perf_counter, time.sleep
    (s_due, s_idx), (b_due, b_idx) = plan["singles"], plan["bursts"]
    ns, nb = len(s_idx), len(b_idx)
    s = b = 0
    controller_free = t_open  # return of the controller's previous create
    while s < ns or b < nb:
        t = now()
        due_s = t_open + s_due[s] if s < ns else math.inf
        due_b = t_open + b_due[b] if b < nb else math.inf
        single = due_s <= t
        if single:
            i, due, ready = s_idx[s], due_s, due_s
            s += 1
        elif due_b <= t:
            i, due, ready = b_idx[b], due_b, max(due_b, controller_free)
            b += 1
        else:
            sleep(min(due_s, due_b) - t)
            continue
        rec.due[i], rec.ready[i] = due, ready
        rec.issued[i] = now()
        cluster.create(i)
        rec.create_done[i] = done = now()
        rec.created.append(i)
        if not single:
            controller_free = done
    return {}

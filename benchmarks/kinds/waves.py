"""Traffic kind `waves`: a closed loop of staged backlogs, one client.

Set-up stages a standing backlog of `backlog_pods` pods with the scheduler
paused (0: none). Then, until the window closes: pause the scheduler,
create pods of `template` until `backlog_pods + wave_pods` are unbound
(objects built in set-up: the window pays the API call only), wait until
the queue holds them, resume, and wait until the benchmark's watch has
seen the unbound count fall back to `backlog_pods`. With a standing
backlog the scheduler always finds at least that many pods pending and
drains them in full batches. The cycle that the close of the window falls
in is finished like every other, and its end is the end of what is
measured (`t_end`): a rate is then all binds over all the time, whole
cycles only, and a scheduler that stalls at the tail lengthens the time it
is divided by. Parameters, all from the traffic
file: `backlog_pods`, `wave_pods`, `max_pods` (objects are built for that
many; the window never creates more), `park_s` (sleep after the pause,
for a scheduler that may be blocked on an empty queue), `template`,
`groups` (0: none).
"""

from __future__ import annotations

import time
from typing import Dict


def prepare(cluster, traffic: Dict, seed: int, seconds: float) -> Dict:
    groups = traffic.get("groups", 0)
    # the seed turns the order of the groups, never the amount of work
    idxs = cluster.prebuild([
        cluster.pod_class(traffic["template"],
                          (seed + k) % groups if groups else None)
        for k in range(traffic["max_pods"])])
    backlog = traffic.get("backlog_pods", 0)
    if backlog:
        cluster.sched.pause()
        time.sleep(traffic.get("park_s", 0.0))
        for i in idxs[:backlog]:
            cluster.create(i)
        cluster.stage_end(backlog, time.perf_counter() + 60.0, resume=False)
    return {"idxs": idxs, "next": backlog}


def drive(cluster, plan: Dict, rec, t_open: float, t_close: float) -> Dict:
    now = time.perf_counter
    traffic, idxs, nxt = plan["traffic"], plan["idxs"], plan["next"]
    backlog, wave = traffic.get("backlog_pods", 0), traffic["wave_pods"]
    park = traffic.get("park_s", 0.0)
    # the standing backlog was due when the window opened
    for i in idxs[:nxt]:
        rec.due[i] = rec.issued[i] = t_open
    rec.created.extend(idxs[:nxt])
    out, spans = [], []
    deadline = t_close + plan["settle_s"]
    while nxt < len(idxs):
        w = {"pods": 0}
        cluster.sched.pause()
        if park:
            time.sleep(park)
        queued = cluster.sched.queue.num_active()
        w["t_create0"] = t0 = now()
        unbound = len(cluster.order) - cluster.n_bound()
        stop = min(len(idxs), nxt + max(0, backlog + wave - unbound))
        while nxt < stop:
            i = idxs[nxt]
            rec.due[i] = rec.issued[i] = t0
            cluster.create(i)
            rec.created.append(i)
            nxt += 1
            w["pods"] += 1
        w["t_create1"] = now()
        spans.append(("stage", t0, w["t_create1"] - t0))
        # a batch popped as the pause fell leaves the queue short of this
        # count for good: then a queue that has stopped growing is enough
        cluster.stage_end(queued + w["pods"], deadline, settle_s=0.25)
        w["t_resume"] = now()
        w["bound_at_resume"] = cluster.n_bound()
        w["drained"] = cluster.wait_bound(len(cluster.order) - backlog,
                                          deadline)
        w["t_done"] = now()
        w["bound_after"] = cluster.n_bound()
        out.append(w)
        if w["t_done"] >= t_close:
            break
    return {"waves": out, "spans": spans,
            "t_end": out[-1]["t_done"] if out else t_close}

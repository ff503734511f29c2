"""Traffic kind `churn-waves`: the closed loop of `waves`, with the cluster
changed inside each pause.

A cycle: pause the scheduler; wait at the barrier until every pod it has
popped is bound on the benchmark's watch (`Cluster.barrier`); change the
cluster; create pods of `template` until `backlog_pods + wave_pods` are
unbound; wait until the queue holds them; resume; wait until the unbound
count is back at `backlog_pods`. The cycle the close of the window falls
in is finished, and its end is the end of what is measured, as in
kinds/waves.py. Every change is an event of `Cluster.log`, issued between
the barrier and the resume, which is what keeps the comparison with the
plain reference exact (benchmarks/README.md, "correct").

The change of one cycle, in this order, each step delivered to the
scheduler's cache (`Cluster.settle`) before the next, as an operator
waits for a drain before the node goes and brings the replacement up
before the old node is taken away:

1. `delete_pending_pods` pods that are still pending are deleted.
2. `remove_nodes` nodes are drained: every pod bound to them is deleted.
3. Further bound pods are deleted until `delete_bound_pods` are gone in
   all (step 2's among them), in runs of `delete_run_pods` pods that were
   created one after another: a rolling update ending its old ReplicaSet,
   a Deployment scaled down.
4. `remove_nodes` nodes are added: `fresh_nodes` of them new indices past
   every node there has been, the others the longest-gone of the nodes
   that left `return_after_cycles` (1 or more) cycles ago or earlier, as
   far as there are any. A node that comes back has been away for whole
   cycles of decisions.
5. The drained nodes are removed.

What the seed moves: which nodes, which runs, which pending pods. Never
how many: every seed deletes `delete_bound_pods` + `delete_pending_pods`
pods and removes and adds the same number of nodes in each cycle.

Set-up runs the shapes of the window once (`warm_deletes`, 0: none): that
many bound pods are deleted and as many created and bound before the
standing backlog is staged. A backend that takes pod removals into its
live session by a program of its own compiles that program there, not
inside the window. Give it the number of bound pods a cycle deletes: the
program's backend shapes that program by the count (a power-of-two bucket),
so 256 in set-up do not warm the 1024 of a cycle (PERF.md section 6, PR 33).

Parameters, all from the traffic file: those of `waves` (`backlog_pods`,
`wave_pods`, `max_pods`, `park_s`, `template`, `groups`), `warm_deletes`
and the six named above. The configuration has to keep room: the plain
reference does not try a pod again that found no node.
"""

from __future__ import annotations

import random
import time
from typing import Dict, List


def prepare(cluster, traffic: Dict, seed: int, seconds: float) -> Dict:
    groups = traffic.get("groups", 0)
    idxs = cluster.prebuild([
        cluster.pod_class(traffic["template"],
                          (seed + k) % groups if groups else None)
        for k in range(traffic["max_pods"])])
    backlog = traffic.get("backlog_pods", 0)
    warm = traffic.get("warm_deletes", 0)
    if warm:
        # the window's shapes, run once in set-up: bound pods deleted and
        # as many created and bound, so that the backend has taken a
        # pod's removal into its live session before the window opens
        cluster.sched.pause()
        deadline = time.perf_counter() + 60.0
        cluster.barrier(deadline)
        for i in cluster.live_pods()[0][:warm]:
            cluster.delete(i)
        cluster.settle(deadline)
        cluster.stage(idxs[:warm])
        idxs = idxs[warm:]
    if backlog:
        cluster.sched.pause()
        time.sleep(traffic.get("park_s", 0.0))
        for i in idxs[:backlog]:
            cluster.create(i)
        cluster.stage_end(backlog, time.perf_counter() + 60.0, resume=False)
    return {"idxs": idxs, "next": backlog, "rng": random.Random(seed),
            "gone": [], "cycle": 0,
            "fresh": cluster.config["nodes"]["count"]}


def _runs(pods: List[int], run: int, total: int, rng) -> List[int]:
    """`total` of `pods` (in creation order), as whole runs of `run`
    neighbours drawn without replacement; the last run is cut to fit."""
    starts = list(range(0, len(pods), run))
    out: List[int] = []
    for s in rng.sample(starts, min(len(starts), -(-total // run))):
        out += pods[s:s + run][:total - len(out)]
    return out


def mutate(cluster, plan: Dict, w: Dict, deadline: float) -> bool:
    """One cycle's change of the cluster; False if the scheduler's caches
    did not follow before `deadline`."""
    tr, rng = plan["traffic"], plan["rng"]
    k = tr.get("remove_nodes", 0)
    bound, pending = cluster.live_pods()
    leaving = rng.sample(sorted(cluster.nodes), k)
    on_leaving = {cluster.node_name(n) for n in leaving}
    drained, rest = [], []
    for i in bound:
        (drained if cluster.bound_node[i] in on_leaving else rest).append(i)
    # pending pods first: the bound pods' deletes, which `settle` can see
    # in the scheduler's cache, then follow them on the one pod stream
    doomed = rng.sample(pending, min(len(pending),
                                     tr.get("delete_pending_pods", 0)))
    doomed += drained + _runs(
        rest, tr.get("delete_run_pods", 1),
        max(0, tr.get("delete_bound_pods", 0) - len(drained)), rng)
    for i in doomed:
        cluster.delete(i)
    ok = cluster.settle(deadline)
    joining = list(range(plan["fresh"],
                         plan["fresh"] + min(k, tr.get("fresh_nodes", 0))))
    plan["fresh"] += len(joining)
    back = plan["cycle"] - max(1, tr.get("return_after_cycles", 1))
    while len(joining) < k and plan["gone"] and plan["gone"][0][0] <= back:
        joining.append(plan["gone"].pop(0)[1])
    for n in joining:
        cluster.add_node(n)
    ok = cluster.settle(deadline) and ok
    for n in leaving:
        cluster.remove_node(n)
    ok = cluster.settle(deadline) and ok
    plan["gone"] += [(plan["cycle"], n) for n in leaving]
    plan["cycle"] += 1
    w.update(deleted=len(doomed), pods_drained=len(drained),
             nodes_removed=len(leaving), nodes_added=len(joining))
    return ok


def drive(cluster, plan: Dict, rec, t_open: float, t_close: float) -> Dict:
    now = time.perf_counter
    traffic, idxs, nxt = plan["traffic"], plan["idxs"], plan["next"]
    backlog, wave = traffic.get("backlog_pods", 0), traffic["wave_pods"]
    park = traffic.get("park_s", 0.0)
    for i in idxs[:nxt]:
        rec.due[i] = rec.issued[i] = t_open
    rec.created.extend(idxs[:nxt])
    out, spans, not_reached = [], [], 0
    deadline = t_close + plan["settle_s"]
    while nxt < len(idxs):
        w = {"pods": 0}
        cluster.sched.pause()
        if park:
            time.sleep(park)
        w["t_barrier0"] = tb = now()
        reached = cluster.barrier(deadline)
        w["t_mutate0"] = tm = now()
        reached = mutate(cluster, plan, w, deadline) and reached
        not_reached += not reached
        queued = cluster.sched.queue.num_active()
        w["t_create0"] = t0 = now()
        spans += [("barrier", tb, tm - tb), ("mutate", tm, t0 - tm)]
        stop = min(len(idxs),
                   nxt + max(0, backlog + wave - cluster.n_unbound()))
        while nxt < stop:
            i = idxs[nxt]
            rec.due[i] = rec.issued[i] = t0
            cluster.create(i)
            rec.created.append(i)
            nxt += 1
            w["pods"] += 1
        w["t_create1"] = now()
        spans.append(("stage", t0, w["t_create1"] - t0))
        cluster.stage_end(queued + w["pods"], deadline, settle_s=0.25)
        w["t_resume"] = now()
        w["bound_at_resume"] = cluster.n_bound()
        w["drained"] = cluster.wait_bound(
            len(cluster.order) - cluster.n_deleted_pending - backlog,
            deadline)
        w["t_done"] = now()
        w["bound_after"] = cluster.n_bound()
        out.append(w)
        if w["t_done"] >= t_close:
            break
    return {"waves": out, "spans": spans, "barriers_not_reached": not_reached,
            "t_end": out[-1]["t_done"] if out else t_close}

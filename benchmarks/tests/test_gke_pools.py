"""The gke-pools deployment, rehearsed on a CPU:
rehearsal-gke-pools-96n.rehearsal-scaleup-waves is the cell
gke-pools-5000n.scaleup-waves at a size the interpreter can hold (three
of the seven pools, 26 Deployments with a drawn batch tier), and the
configuration's numbers are checked against the rules they come from."""

import importlib.util
import json
import os
from collections import Counter
from fractions import Fraction

import pytest

from conftest import BENCH
from test_rehearsal import run_cell

CELL = "rehearsal-gke-pools-96n.rehearsal-scaleup-waves"


def _module(directory, name):
    spec = importlib.util.spec_from_file_location(
        f"{directory}_{name}".replace("-", "_"),
        os.path.join(BENCH, directory, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def run():
    p = run_cell(CELL, "--rehearse", trace=1, seconds=4)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_rehearsal_is_correct_on_one_wide_session(run):
    assert run["correct"] is True, run["checks"]
    assert all(c["value"] == 0 for c in run["checks"].values())
    assert run["failed"] == 0 and run["attempted"] > 150
    assert run["detail"]["session_rebuilds"] == {}
    m = run["metrics"]
    assert m["inexact_builds"]["value"] == 0.0
    assert m["quirk_states"]["value"] >= 1.0
    assert m["template_rebuilds"]["value"] == 0.0


def _gke_reserved(vcpu, gib):
    """GKE's reservations (as the configuration's `assumed` states them)
    on a machine of `vcpu` cores and `gib` GiB: (cores, GiB), exact."""
    def tiers(amount, steps):
        out = Fraction(0)
        for width, share in steps:
            out += Fraction(share) * min(width, max(0, amount))
            amount -= width
        return out

    return (tiers(vcpu, ((1, "0.06"), (1, "0.01"), (2, "0.005"),
                         (10 ** 6, "0.0025"))),
            tiers(gib, ((4, "0.25"), (4, "0.20"), (8, "0.10"),
                        (112, "0.06"), (10 ** 6, "0.02"))))


def test_allocatable_is_capacity_less_gkes_reservations():
    for pool in _config("gke-pools-5000n")["nodes"]["pools"]:
        gib = int(pool["memory"][:-2])
        cores, reserved_gib = _gke_reserved(pool["vcpu"], gib)
        cpu_m = 1000 * (pool["vcpu"] - cores)
        # the eviction threshold, and the floor to whole Ki
        ki = (gib - reserved_gib) * 2 ** 20 - 100 * 1024
        assert pool["allocatable_cpu"] == f"{int(cpu_m)}m", pool["name"]
        assert pool["allocatable_memory"] == f"{int(ki)}Ki", pool["name"]


def test_pools_take_their_shares_and_every_zone():
    nodes = _config("gke-pools-5000n")["nodes"]
    cycle = nodes["cycle"]
    counts = Counter(cycle)
    for k, pool in enumerate(nodes["pools"]):
        assert counts[k] == round(pool["share"] * len(cycle))
        zones = {i % nodes["zones"] for i in range(nodes["count"])
                 if cycle[i % len(cycle)] == k}
        assert zones == set(range(nodes["zones"]))


@pytest.mark.parametrize("name", ["gke-pools-5000n", "rehearsal-gke-pools-96n"])
def test_builder_and_reference_agree(name):
    """The pool rule and the draw are written twice, on purpose: the two
    copies give the same nodes and the same requests."""
    config = _config(name)
    builder = _module("builders", "gke-pools")
    ref = _module("references", "gke-pools")
    cluster = ref.GkePoolsCluster.from_config(config)
    for i in range(config["nodes"]["count"]):
        node = builder.build_node(i, config)
        alloc = node.status.allocatable
        assert (ref.reference.milli_cpu(alloc["cpu"]),
                ref.reference.quantity_bytes(alloc["memory"])) == (
            cluster.alloc_cpu[i], cluster.alloc_mem[i])
        assert node.metadata.labels["topology.kubernetes.io/zone"] == \
            f"zone-{cluster.zone[i]}"
    batch = config["pod_templates"]["batch"]
    n = batch["deployments"]
    want = builder.batch_requests(batch["draw"], n)
    assert ref.drawn_requests(batch["draw"], n) == want
    classes = [{**batch, "labels": {"app": f"batch-{g}"}} for g in range(n)]
    for g, c in enumerate(ref.with_requests(classes)):
        pod = builder.build_pod("p", classes[g])
        req = pod.spec.containers[0].resources.requests
        assert (c["cpu"], c["memory"]) == (req["cpu"], req["memory"]) \
            == want[g]


def test_the_tail_passes_the_narrow_forms_wall():
    """Some batch requests pass ~20.3 GiB (21 262 214 Ki, where the
    narrow form's int32 ends), and none passes the largest pool."""
    config = _config("gke-pools-5000n")
    batch = config["pod_templates"]["batch"]
    ref = _module("references", "gke-pools")
    mem = [ref.reference.quantity_bytes(m) for _, m in
           ref.drawn_requests(batch["draw"], batch["deployments"])]
    largest = max(ref.reference.quantity_bytes(p["allocatable_memory"])
                  for p in config["nodes"]["pools"])
    assert max(mem) <= largest
    assert sum(m > 21262214 * 1024 for m in mem) >= 4

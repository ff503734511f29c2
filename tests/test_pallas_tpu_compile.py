"""The table kernel compiled for the REAL chip at the REAL size, without
the chip: Mosaic refuses here what interpret mode lets through (a slice
off the tiling, a 64-bit op, too much scalar or vector memory). Nothing
runs; a compile that passes is not a chip run.

All of these live in this one file, behind one fixture (the worker that
is given the file loads the TPU's compiler, and keeps it)."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kubernetes_tpu.models.encoding import ClusterEncoding
from kubernetes_tpu.models.pod_encoder import PodEncoder
from kubernetes_tpu.ops import pallas_scan

from .test_pallas_table import _nodes, _pod


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("terms", [False, True])
def test_table_kernel_compiles_for_v5e_at_5000_nodes(one_chip, terms,
                                                     monkeypatch):
    """5000 nodes, the table a 120 000-pod reserve asks for (1024 specs,
    1024 count rows), bucket 2048 — the deployments-5000n cell's launch."""
    enc = ClusterEncoding()
    enc.set_cluster(_nodes(5000), [])
    enc.reserve(pods=256, anti_terms=256 if terms else 0)
    pe = PodEncoder(enc)
    pods = [_pod("a", "web", 1), _pod("c", "small", 1)] + (
        [_pod("b", "ha", 1)] if terms else [])
    arrays = [{k: v for k, v in pe.encode(p).items()
               if not k.startswith("_")} for p in pods]
    sess = pallas_scan.PallasSession(
        enc.device_state(), arrays, interpret=True,
        capacity=pallas_scan.table_capacity(120_000), terms=terms)
    # the narrow form, as every cell before the pools compiled it
    assert sess.Tcap == 1024 and sess._cfg.bal_int and not sess._cfg.wide
    # v5e: 128 MiB of VMEM a core (the session asks the device, which
    # is not attached here)
    monkeypatch.setattr(pallas_scan, "_vmem_cap", lambda: 112 << 20)

    def on_chip(x):
        return jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.asarray(x).dtype, sharding=one_chip)

    compiled = pallas_scan._dispatch.lower(
        sess._cfg._replace(interpret=False),
        {k: on_chip(v) for k, v in sess._statics.items()},
        jax.ShapeDtypeStruct((1 + 2048,), jnp.int32, sharding=one_chip),
        {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
         for k, s in sess._carry_struct().items()}).compile()
    assert "tpu_custom_call" in compiled.as_text()
    need = pallas_scan._kernel_vmem_bytes(
        sess._statics, sess._carry_struct(), 2048)
    assert pallas_scan._vmem_request(need) < 112 << 20


def test_wide_table_kernel_compiles_for_v5e_at_5000_nodes(one_chip,
                                                          monkeypatch):
    """The gke-pools-5000n cell's launch: 5000 nodes of seven GKE pools
    (allocatable in Ki), so the resource scores take the wide form, with
    its three-word quirk list; ha's term rows on."""
    from .test_pallas_wide import GKE_POOLS, _pool_nodes

    enc = ClusterEncoding()
    enc.set_cluster(_pool_nodes(5000), [])
    enc.reserve(pods=256, anti_terms=256)
    pe = PodEncoder(enc)
    pods = [_pod("a", "web", 1), _pod("c", "small", 1), _pod("b", "ha", 1)]
    arrays = [{k: v for k, v in pe.encode(p).items()
               if not k.startswith("_")} for p in pods]
    sess = pallas_scan.PallasSession(
        enc.device_state(), arrays, interpret=True,
        capacity=pallas_scan.table_capacity(120_000), terms=True)
    assert sess._cfg.wide and sess._cfg.bal_int and sess.quirk_states > 0
    assert len(sess._cap_pairs()) == len(GKE_POOLS)
    monkeypatch.setattr(pallas_scan, "_vmem_cap", lambda: 112 << 20)

    def on_chip(x):
        return jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.asarray(x).dtype, sharding=one_chip)

    compiled = pallas_scan._dispatch.lower(
        sess._cfg._replace(interpret=False),
        {k: on_chip(v) for k, v in sess._statics.items()},
        jax.ShapeDtypeStruct((1 + 2048,), jnp.int32, sharding=one_chip),
        {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
         for k, s in sess._carry_struct().items()}).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_whatif_launch_compiles_for_v5e_at_5000_nodes(one_chip, monkeypatch):
    """The preemption what-if launch of the bursts cell (5000 nodes, four
    victims a node), as the second preemptor of a wave makes it:
    nominated load, a delta into the inputs the first launch left on
    the device. The chip's compiler must take the inputs as donated
    (aliased to the outputs), or every launch copies them."""
    from kubernetes_tpu.api import types as v1
    from kubernetes_tpu.ops import whatif
    from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
    from kubernetes_tpu.scheduler.internal.nominator import PodNominator
    from kubernetes_tpu.scheduler.preemption_device import (
        DevicePreemptionPlanner,
    )

    from .test_preemption_fast import _mk_backend
    from .test_whatif_resident import _burst

    launches = []
    run = whatif._whatif_run

    def capture(*args, **kw):
        launches.append((args, kw))
        return run(*args, **kw)

    monkeypatch.setattr(whatif, "_whatif_run", capture)
    nodes, pods, wave = _burst(5000, 2)
    planner = DevicePreemptionPlanner(
        Snapshot.from_objects(pods, nodes), PodNominator(),
        _mk_backend(nodes, pods),
        eligibility={v1.pod_key(p): (True, False) for p in wave},
        wave_launch=False)
    assert all(planner.plan(wave))
    args, kw = launches[1]  # its inputs were donated: shapes only
    assert kw["has_nom"]
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = run.lower(*shapes, **kw).compile()
    assert "input_output_alias" in compiled.as_text()


def test_whatif_wave_launch_compiles_for_v5e_at_5000_nodes(one_chip,
                                                           monkeypatch):
    """The bursts cell's wave launch (5000 nodes, four victims a node):
    64 steps of dry run, pick and claim over inputs kept on the device.
    The chip's compiler must take the inputs as donated, and the scan
    must carry them in place."""
    from kubernetes_tpu.api import types as v1
    from kubernetes_tpu.ops import whatif
    from kubernetes_tpu.scheduler.framework.snapshot import Snapshot
    from kubernetes_tpu.scheduler.internal.nominator import PodNominator
    from kubernetes_tpu.scheduler.preemption_device import (
        DevicePreemptionPlanner,
    )

    from .test_preemption_fast import _mk_backend
    from .test_whatif_resident import _burst

    launches = []
    run = whatif._whatif_wave_run

    def capture(*args, **kw):
        launches.append((args, kw))
        return run(*args, **kw)

    monkeypatch.setattr(whatif, "_whatif_wave_run", capture)
    nodes, pods, wave = _burst(5000, 2)
    planner = DevicePreemptionPlanner(
        Snapshot.from_objects(pods, nodes), PodNominator(),
        _mk_backend(nodes, pods),
        eligibility={v1.pod_key(p): (True, False) for p in wave})
    assert all(planner.plan(wave))
    (args, kw), = launches  # its inputs were donated: shapes only
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    compiled = run.lower(*shapes, **kw).compile()
    assert "input_output_alias" in compiled.as_text()

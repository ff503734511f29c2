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
    assert sess.Tcap == 1024 and sess._cfg.bal_int
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

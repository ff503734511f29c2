"""Declarative GSPMD placement: the partition-rule tables of
parallel/partition.py.

Every array the mesh ever sees — cluster encoding, session statics/
tables/carry — gets its PartitionSpec from a regex-on-leaf-path rule
table (match_partition_rules), not per-key wiring. These tests pin the
three contracts that make that safe at 100k nodes:

  * coverage: every leaf of every live tree matches a rule (an
    unmatched leaf is a loud ValueError, not silent replication);
  * placement: the rules reproduce the hand-wired placements they
    replaced (node rows split over the "nodes" axis, everything else
    replicated), so per-host memory stays bounded by shard size;
  * padding: pad_node_axis quantizes the node axis to shard multiples
    with growth headroom, and the all-zero padding rows can never win
    a scheduling cycle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.parallel.partition import (
    CLUSTER_PARTITION_RULES,
    NODE_AXIS,
    SESSION_PARTITION_RULES,
    match_partition_rules,
    session_specs,
    tree_path_to_string,
)
from kubernetes_tpu.parallel.sharded import (
    NODE_DIM0_KEYS,
    ShardedScheduler,
    make_mesh,
    node_capacity_multiple,
    pad_node_axis,
    shard_cluster,
)
from kubernetes_tpu.scheduler.internal.cache import SchedulerCache
from kubernetes_tpu.scheduler.tpu_backend import TPUBackend

from .util import make_node, make_pod


def _mesh_or_skip(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")
    return make_mesh(n_devices=n)


def _backend(n_nodes=6, mesh=None, fill=True):
    cache = SchedulerCache()
    be = TPUBackend(mesh=mesh)
    cache.add_listener(be)
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"node-{i}", cpu="8", memory="32Gi",
            labels={v1.LABEL_HOSTNAME: f"node-{i}"}))
    if fill:
        # every LIVE node carries allocation, so an all-zero padding row
        # would win the least-allocated leg if it ever reached scoring
        for i in range(n_nodes):
            cache.add_pod(make_pod(
                f"fill-{i}", namespace="default", cpu="2", memory="4Gi",
                labels={"app": "fill"}, node_name=f"node-{i}"))
    return cache, be


# ---------------------------------------------------------------- rules


class TestClusterRules:
    def test_rules_cover_every_device_state_leaf(self):
        """The REAL cluster dict (encoding device_state) is fully
        covered, and the specs reproduce the hand-wired placement the
        table replaced: NODE_DIM0_KEYS split on dim 0, rest replicated."""
        _, be = _backend()
        cluster = {k: np.asarray(v) for k, v in be.enc.device_state().items()}
        specs = match_partition_rules(CLUSTER_PARTITION_RULES, cluster)
        assert set(specs) == set(cluster)
        for k, spec in specs.items():
            arr = cluster[k]
            if k in NODE_DIM0_KEYS:
                assert spec == P(NODE_AXIS), (k, spec)
            else:
                assert spec == P(), (k, spec)
                # scalar/1-elem short circuit never sees the node axis
            if arr.ndim == 0 or arr.size <= 1:
                assert spec == P(), (k, spec)

    def test_unmatched_leaf_raises(self):
        """A leaf no rule covers fails construction loudly — new state
        must be placed deliberately, not silently replicated."""
        with pytest.raises(ValueError, match="partition rule not found"):
            match_partition_rules(
                [("^valid$", P(NODE_AXIS))], {"mystery": np.zeros((8, 4))})

    def test_scalar_short_circuit(self):
        """Scalars and 1-element arrays replicate even when a
        node-axis rule matches their path (nothing to split)."""
        specs = match_partition_rules(
            [(".*", P(NODE_AXIS))],
            {"s": np.int32(3), "one": np.zeros((1,)), "v": np.zeros((8,))})
        assert specs["s"] == P()
        assert specs["one"] == P()
        assert specs["v"] == P(NODE_AXIS)

    def test_tree_path_to_string_nested(self):
        tree = {"a": {"b": [np.zeros(2), np.zeros(2)]}}
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        paths = [tree_path_to_string(p) for p, _ in flat]
        assert paths == ["a/b/0", "a/b/1"]


class TestSessionRules:
    def test_rules_cover_every_session_leaf(self, sim_mesh):
        """Every statics/tables/delta/carry leaf of a LIVE
        ShardedPallasSession matches a rule, and every node-sharded
        leaf's shard is bounded to Npl = Nps/nsh rows — the per-host
        memory contract that makes 100k nodes survivable."""
        from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession

        _, be = _backend(n_nodes=19, mesh=sim_mesh)
        pa = {k: va for k, va in be.pe.encode(
            make_pod("probe", namespace="default", cpu="100m",
                     memory="64Mi", labels={"app": "p"})).items()
            if not k.startswith("_")}
        sess = ShardedPallasSession(
            be.enc.device_state(), [pa], be.weights, mesh=sim_mesh)
        nsh = sim_mesh.devices.size
        tree = {"statics": sess._statics, "tables": sess._tables,
                "delta": sess._delta_statics, "carry": sess._carry}
        specs = match_partition_rules(SESSION_PARTITION_RULES, tree)
        flat_specs = jax.tree_util.tree_flatten_with_path(specs)[0]
        flat_arrs = jax.tree_util.tree_flatten_with_path(tree)[0]
        assert len(flat_specs) == len(flat_arrs)
        sharded = 0
        for (path, spec), (_, arr) in zip(flat_specs, flat_arrs):
            name = tree_path_to_string(path)
            if NODE_AXIS in tuple(spec):
                dim = tuple(spec).index(NODE_AXIS)
                assert arr.shape[dim] == sess.Nps, (name, arr.shape)
                got = arr.sharding.shard_shape(arr.shape)[dim]
                assert got == sess.Npl == sess.Nps // nsh, (name, got)
                sharded += 1
            else:
                # replicated leaf: one full copy per device
                assert arr.sharding.is_fully_replicated, name
        # the carry (all 4+ leaves) and the big statics ride the mesh
        assert sharded >= len(sess._carry) + 10
        # the per-group helper agrees with the full-tree match
        assert session_specs("carry", sess._carry) == specs["carry"]

    def test_session_rules_reject_unknown_group(self):
        with pytest.raises(ValueError, match="partition rule not found"):
            match_partition_rules(
                SESSION_PARTITION_RULES, {"mystery": {"x": np.zeros((8, 8))}})


# ----------------------------------------------------------- make_mesh


class TestMakeMesh:
    def test_env_device_count(self, monkeypatch):
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        monkeypatch.setenv("KTPU_MESH_DEVICES", "4")
        mesh = make_mesh()
        assert mesh.devices.size == 4
        assert mesh.axis_names == (NODE_AXIS,)

    def test_env_zero_means_all(self, monkeypatch):
        monkeypatch.setenv("KTPU_MESH_DEVICES", "0")
        assert make_mesh().devices.size == len(jax.devices())

    def test_explicit_count_wins(self, monkeypatch):
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        monkeypatch.setenv("KTPU_MESH_DEVICES", "1")
        assert make_mesh(n_devices=2).devices.size == 2

    def test_sim_mesh_fixture(self, sim_mesh):
        """The conftest recipe (XLA_FLAGS --xla_force_host_platform_
        device_count=8) yields a real 8-way mesh on CPU."""
        assert sim_mesh.devices.size == 8
        assert node_capacity_multiple(sim_mesh) == 8


# ------------------------------------------------------- pad_node_axis


class TestPadNodeAxis:
    def _cluster(self, n):
        _, be = _backend(n_nodes=n, fill=False)
        return {k: np.asarray(v) for k, v in be.enc.device_state().items()}

    def test_quantized_to_shard_multiple(self, monkeypatch):
        monkeypatch.delenv("KTPU_NODE_HEADROOM", raising=False)
        c = self._cluster(6)
        ncap = c["valid"].shape[0]
        out = pad_node_axis(c, 8)
        want = -(-ncap // 8) * 8
        for k in NODE_DIM0_KEYS:
            assert out[k].shape[0] == want, k
        # non-node arrays untouched
        assert out["n_nodes"] is c["n_nodes"]

    def test_headroom_over_pads(self):
        c = self._cluster(6)
        ncap = c["valid"].shape[0]
        out = pad_node_axis(c, 4, headroom=1.0)
        # ceil(ncap * 2) rounded up to the multiple
        want = -(-(ncap * 2) // 4) * 4
        assert out["valid"].shape[0] == want

    def test_already_aligned_is_identity(self):
        c = self._cluster(6)
        ncap = c["valid"].shape[0]
        out = pad_node_axis(c, 1, headroom=0.0)
        assert out is c or out["valid"].shape[0] == ncap

    def test_padding_rows_are_infeasible_zeros(self):
        c = self._cluster(6)
        ncap = c["valid"].shape[0]
        out = pad_node_axis(c, 64)
        assert not np.asarray(out["valid"][ncap:]).any()
        for k in NODE_DIM0_KEYS:
            assert not np.asarray(out[k][ncap:]).any(), k

    def test_env_headroom_applies(self, monkeypatch):
        monkeypatch.setenv("KTPU_NODE_HEADROOM", "0.5")
        c = self._cluster(6)
        ncap = c["valid"].shape[0]
        out = pad_node_axis(c, 2)
        want = -(-int(np.ceil(ncap * 1.5)) // 2) * 2
        assert out["valid"].shape[0] == want


# -------------------------------------------- padding never schedules


class TestPaddingExclusion:
    """Directed: every live node carries allocation, so the all-zero
    padding rows (alloc=0, requested=0) would WIN the least-allocated
    tiebreak if they ever reached scoring — `valid` stays False in the
    pad, so they must be filtered at every shard count."""

    @pytest.mark.parametrize("nsh", [2, 4, 8])
    def test_single_cycle_never_picks_padding(self, nsh):
        mesh = _mesh_or_skip(nsh)
        _, be = _backend(n_nodes=5, fill=True)
        n_live = be.enc.n_nodes
        cluster = be.enc.device_state()
        pod = {k: va for k, va in be.pe.encode(
            make_pod("probe", namespace="default", cpu="100m",
                     memory="64Mi", labels={"app": "p"})).items()
            if not k.startswith("_")}
        out = ShardedScheduler(mesh=mesh).schedule(dict(cluster), pod)
        best = int(out["best_idx"])
        total = np.asarray(out["total"])
        assert total.shape[0] % nsh == 0  # padded to the shard multiple
        assert best < n_live, (best, n_live)
        assert int(out["n_feasible"]) == n_live
        # the padded tail is scored infeasible, not zero-allocated-best
        assert (total[n_live:] < total[best]).all()

    @pytest.mark.parametrize("nsh", [2, 4, 8])
    def test_session_never_picks_padding(self, nsh):
        from kubernetes_tpu.ops.sharded_scan import ShardedPallasSession

        mesh = _mesh_or_skip(nsh)
        _, be = _backend(n_nodes=5, fill=True)
        n_live = be.enc.n_nodes
        pods = [make_pod(f"w-{i}", namespace="default", cpu="100m",
                         memory="64Mi", labels={"app": "w"})
                for i in range(6)]
        arrays = [{k: va for k, va in be.pe.encode(p).items()
                   if not k.startswith("_")} for p in pods]
        sess = ShardedPallasSession(
            be.enc.device_state(), [arrays[0]], be.weights, mesh=mesh)
        assert sess.Nps >= n_live and sess.Nps % nsh == 0
        got = ShardedPallasSession.decisions(sess.schedule(arrays))
        assert all(0 <= d < n_live for d in got), (got, n_live)

    def test_whole_shard_of_padding(self):
        """Headroom large enough that ENTIRE shards are fake nodes —
        the regime after mass node removal. No fake lane may win."""
        mesh = _mesh_or_skip(8)
        _, be = _backend(n_nodes=3, fill=True)
        n_live = be.enc.n_nodes
        cluster = pad_node_axis(
            {k: np.asarray(v) for k, v in be.enc.device_state().items()},
            node_capacity_multiple(mesh), headroom=4.0)
        assert cluster["valid"].shape[0] >= 5 * n_live
        pod = {k: va for k, va in be.pe.encode(
            make_pod("probe", namespace="default", cpu="100m",
                     memory="64Mi", labels={"app": "p"})).items()
            if not k.startswith("_")}
        out = ShardedScheduler(mesh=mesh).schedule(cluster, pod)
        assert int(out["best_idx"]) < n_live
        assert int(out["n_feasible"]) == n_live


# ------------------------------------------------------ shard_map smoke


class TestShardMap:
    def test_psum_over_node_axis(self, sim_mesh):
        """A psum over the node axis is the canonical collective every
        kernel reduction builds on."""
        x = jnp.arange(16.0)

        def f(xs):
            return jax.lax.psum(jnp.sum(xs), NODE_AXIS)

        f_sharded = jax.shard_map(
            f, mesh=sim_mesh, in_specs=(P(NODE_AXIS),), out_specs=P(),
            check_vma=False)
        assert float(f_sharded(x)) == float(jnp.sum(x))

    def test_shard_cluster_places_on_mesh(self, sim_mesh):
        _, be = _backend(n_nodes=6, fill=False)
        c = shard_cluster(
            {k: np.asarray(v) for k, v in be.enc.device_state().items()},
            sim_mesh)
        nsh = sim_mesh.devices.size
        for k in NODE_DIM0_KEYS:
            arr = c[k]
            assert arr.shape[0] % nsh == 0, k
            assert (arr.sharding.shard_shape(arr.shape)[0]
                    == arr.shape[0] // nsh), k
        assert c["n_nodes"].sharding.is_fully_replicated

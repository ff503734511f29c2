"""Declarative GSPMD partitioning: regex-on-leaf-path -> PartitionSpec.

Before this module, every node-sharded array in the mesh path was
hand-wired: `ops/sharded_scan.py` kept a `_NODE_DIM` placement dict that
had to be edited in lock-step with every new static, and
`parallel/sharded.py` kept a parallel `NODE_DIM0_KEYS` frozenset for the
cluster dict. State added since PR 5 (delta statics, what-if scratch
carries, the explain harvest) each needed a matching hand edit — at
100k nodes a forgotten entry silently replicates a [rows, N] array onto
every host.

The declarative form is the `match_partition_rules` pattern from large
LM trainers: flatten the pytree with key paths, join each path into a
`/`-separated name, and take the first regex rule that matches. Scalars
short-circuit to replicated. An unmatched leaf is an ERROR, not a
default — new state must name its placement (one line in a rule table)
or construction fails loudly.

Two rule tables live here:

- `CLUSTER_PARTITION_RULES` — the ClusterEncoding device dict: node rows
  (dim 0 = node axis) sharded, pod/term/vocab state replicated.
- `SESSION_PARTITION_RULES` — the sharded session's grouped tree
  (`statics/`, `tables/`, `carry/`, `delta/`, `xs/`): per-node statics
  and carries split along their node axis, score tables and batch rows
  replicated. The specs reproduce the old `_NODE_DIM` placements
  exactly (pinned by tests/test_mesh_partition.py).
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NODE_AXIS = "nodes"


def tree_path_to_string(path: Tuple, sep: str = "/") -> str:
    """Join a jax key path into a readable `/`-separated name."""
    keys = []
    for key in path:
        if isinstance(key, jax.tree_util.SequenceKey):
            keys.append(str(key.idx))
        elif isinstance(key, jax.tree_util.DictKey):
            keys.append(str(key.key))
        elif isinstance(key, jax.tree_util.GetAttrKey):
            keys.append(str(key.name))
        elif isinstance(key, jax.tree_util.FlattenedIndexKey):
            keys.append(str(key.key))
        else:
            keys.append(str(key))
    return sep.join(keys)


def named_tree_map(f: Callable, tree: Any, *rest, is_leaf=None,
                   sep: str = "/") -> Any:
    """tree_map where `f` receives (path-name, leaf, *rest-leaves)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x, *r: f(tree_path_to_string(path, sep=sep), x, *r),
        tree, *rest, is_leaf=is_leaf)


def match_partition_rules(rules: List[Tuple[str, P]], tree: Any,
                          sep: str = "/") -> Any:
    """PartitionSpec tree for `tree`: first rule whose regex matches the
    leaf's path name wins; 0-d / 1-element leaves are replicated without
    consulting the rules; a leaf no rule covers raises ValueError (new
    state MUST declare its placement)."""

    def get_partition_spec(name, leaf):
        if np.ndim(leaf) == 0 or np.prod(np.shape(leaf)) == 1:
            return P()
        for rule, ps in rules:
            if re.search(rule, name) is not None:
                return ps
        raise ValueError(f"partition rule not found for leaf: {name}")

    return named_tree_map(get_partition_spec, tree, sep=sep)


def make_shard_and_gather_fns(partition_specs: Any, mesh: Mesh):
    """Per-leaf placement/readback fns for a spec tree.

    shard_fns[leaf](x) puts x on the mesh under its NamedSharding;
    gather_fns[leaf](x) pulls the full (unsharded) value back to host
    numpy. Trees mirror `partition_specs`.
    """

    def make_shard_fn(spec: P):
        sharding = NamedSharding(mesh, spec)

        def shard_fn(x):
            return jax.device_put(jnp.asarray(x), sharding)

        return shard_fn

    def make_gather_fn(spec: P):
        def gather_fn(x):
            return jax.device_get(x)

        return gather_fn

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    shard_fns = jax.tree_util.tree_map(make_shard_fn, partition_specs,
                                       is_leaf=is_spec)
    gather_fns = jax.tree_util.tree_map(make_gather_fn, partition_specs,
                                        is_leaf=is_spec)
    return shard_fns, gather_fns


def shard_tree(tree: Any, rules: List[Tuple[str, P]], mesh: Mesh) -> Any:
    """match + place in one call: every leaf of `tree` lands on `mesh`
    under its matched spec."""
    specs = match_partition_rules(rules, tree)
    shard_fns, _ = make_shard_and_gather_fns(specs, mesh)
    return jax.tree_util.tree_map(lambda f, x: f(x), shard_fns, tree)


# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------

# ClusterEncoding device-dict: arrays whose dim 0 is the node axis. The
# name list mirrors ClusterEncoding._NODE_ROW_KEYS; everything else
# (pod rows, term tables, vocab-indexed vectors, scalars) replicates.
_CLUSTER_NODE_KEYS = (
    "valid", "alloc", "requested", "nz_requested", "pod_count",
    "allowed_pods", "unschedulable", "taints", "ports_triple",
    "ports_pair_any", "ports_pair_wild", "npair", "nkey", "pair_of_key",
    "nnum", "nnum_valid", "img_size", "avoid",
)

CLUSTER_PARTITION_RULES: List[Tuple[str, P]] = [
    (r"^(%s)$" % "|".join(_CLUSTER_NODE_KEYS), P(NODE_AXIS)),
    (r".*", P()),
]

# ShardedPallasSession grouped tree. Node-axis positions mirror the
# session layouts: carries and most statics are [rows, N]; the stat /
# IPA blocks are template-major [T, rows, N]; onehot is [K, N, VZ].
SESSION_PARTITION_RULES: List[Tuple[str, P]] = [
    # carries: requested/nzpc/cnt_fn/cnt_sn [rows, N]; ucnt [UR, N];
    # kcnt [UR, nsh] keeps one per-shard partial column per device
    (r"^carry/", P(None, NODE_AXIS)),
    # template-major static blocks, node axis last
    (r"^statics/(stat|ipa_stat|anti_static|anti_konn|aff_static)$",
     P(None, None, NODE_AXIS)),
    # zone one-hots [K, N, VZ]
    (r"^statics/onehot$", P(None, NODE_AXIS, None)),
    # replicated zone-validity rows [TCp, VZ] — vocab space, not nodes
    (r"^statics/zvalid_s_rows$", P()),
    # per-node row statics [rows, N]
    (r"^statics/(alloc|regrow_f|zvalid_node_s|konn_f|konn_s|shasall"
     r"|valid_n|prow_f|prow_s|prow_ipa)$", P(None, NODE_AXIS)),
    # delta statics: src factor rows are per-node, perno flags replicate
    (r"^delta/src_rows$", P(None, NODE_AXIS)),
    (r"^delta/", P()),
    # score/meta tables and batch rows replicate
    (r"^tables/", P()),
    (r"^xs/", P()),
]


def session_specs(group: str, tree: Dict) -> Dict:
    """Spec dict for one session group ('statics'/'tables'/'carry'/
    'delta'/'xs') — usable both at placement time (numpy leaves) and
    inside jit for shard_map in/out specs (tracer leaves)."""
    return match_partition_rules(SESSION_PARTITION_RULES,
                                 {group: tree})[group]


"""The compiled per-class codecs of utils/serde.py against the reflective walk
they replaced.

The walk below is the old `to_dict` / `_from_value`, word for word: it asks
`typing` what every field is on every call, so it is slow and plainly right.
It lives here as the oracle; the program has only the codecs."""

import dataclasses
import sys
import threading
import typing
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, TypeVar, Union, get_args, get_origin

import pytest

from kubernetes_tpu.api import types as v1
from kubernetes_tpu.apiserver import crd
from kubernetes_tpu.apiserver.server import APIServer, _default_resources
from kubernetes_tpu.client.clientset import Clientset
from kubernetes_tpu.utils import knobs, serde

# -- the oracle ---------------------------------------------------------------


def oracle_to_dict(obj: Any) -> Any:
    if obj is None:
        return None
    custom = getattr(obj, "__serde_to_dict__", None)
    if custom is not None and not isinstance(obj, type):
        return custom()
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out: Dict[str, Any] = {}
        for name, key, _tp, is_opt in serde._field_plan(type(obj)):
            v = getattr(obj, name)
            if v is None:
                continue
            if not is_opt and (
                v == "" or v == 0 or v is False or v == [] or v == {}
            ):
                continue
            out[key] = oracle_to_dict(v)
        return out
    if isinstance(obj, dict):
        return {k: oracle_to_dict(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [oracle_to_dict(v) for v in obj]
    return obj


def oracle_from_dict(tp: Any, data: Any) -> Any:
    if data is None:
        return None
    tp = serde._unwrap_optional(tp)
    origin = get_origin(tp)
    if origin in (list, tuple):
        (elem_tp,) = get_args(tp) or (Any,)
        return [oracle_from_dict(elem_tp, v) for v in data]
    if origin is dict:
        args = get_args(tp)
        val_tp = args[1] if len(args) == 2 else Any
        return {k: oracle_from_dict(val_tp, v) for k, v in data.items()}
    if isinstance(tp, type) and hasattr(tp, "__serde_from_dict__"):
        return tp.__serde_from_dict__(data)
    if dataclasses.is_dataclass(tp):
        kwargs = {}
        for name, key, field_tp, _is_opt in serde._field_plan(tp):
            if key in data:
                kwargs[name] = oracle_from_dict(field_tp, data[key])
        return tp(**kwargs)
    if tp in (Any, object) or isinstance(tp, TypeVar):
        return data
    if tp is float and isinstance(data, int):
        return float(data)
    return data


# -- every class the default resources reach ----------------------------------


def _reachable() -> List[type]:
    seen: Dict[type, None] = {}

    def visit(tp: Any) -> None:
        for arg in get_args(tp):
            visit(arg)
        if isinstance(tp, type) and dataclasses.is_dataclass(tp) and tp not in seen:
            seen[tp] = None
            for _name, _key, field_tp, _opt in serde._field_plan(tp):
                visit(field_tp)

    for info in _default_resources():
        visit(info.type)
    return sorted(seen, key=lambda c: (c.__module__, c.__qualname__))


REACHABLE = _reachable()
FREE = {"free": [1, {"k": "v"}], "n": None}


def _fill(tp: Any, shape: str, depth: int = 0) -> Any:
    """A value of hint `tp`. `set`: everything filled, Optional scalars at a
    present zero. `holes`: the same with None among the elements of every
    list and dict, and whole numbers in the float fields. `typed`: as `set`
    with a scalar wherever the hint is open (what an open hint holds passes
    through a decode as it is, by contract)."""
    if serde._is_optional(tp):
        inner = serde._unwrap_optional(tp)
        if inner in (str, int, float, bool):
            return inner()
        if inner is tp:  # Union[int, str, None]
            return 8080
        return _fill(inner, shape, depth)
    origin = get_origin(tp)
    if origin in (list, tuple):
        (elem,) = get_args(tp) or (Any,)
        out = [_fill(elem, shape, depth + 1), _fill(elem, shape, depth + 1)]
        return out + [None] if shape == "holes" else out
    if origin is dict:
        args = get_args(tp)
        out = {"a": _fill(args[1] if len(args) == 2 else Any, shape, depth + 1)}
        if shape == "holes":
            out["hole"] = None
        return out
    if isinstance(tp, type) and hasattr(tp, "__serde_from_dict__"):
        return tp({"kind": "Free", "metadata": {"name": "u"}, "spec": dict(FREE)})
    if isinstance(tp, type) and dataclasses.is_dataclass(tp):
        if depth > 6:  # a class that holds itself
            return tp()
        return tp(**{name: _fill(field_tp, shape, depth + 1)
                     for name, _key, field_tp, _opt in serde._field_plan(tp)})
    if tp is str:
        return "s"
    if tp is bool:
        return True
    if tp is int:
        return 7
    if tp is float:
        return 3 if shape == "holes" else 1.5
    if shape == "typed":
        return "free"
    return [dict(FREE), "x"] if tp is list else dict(FREE)


def _instance(cls: type, shape: str) -> Any:
    return cls() if shape == "default" else _fill(cls, shape)


@pytest.mark.parametrize("shape", ["default", "set", "holes"])
@pytest.mark.parametrize(
    "cls", REACHABLE, ids=[f"{c.__module__.rsplit('.', 1)[-1]}.{c.__name__}" for c in REACHABLE])
def test_codec_matches_the_reflective_walk(cls, shape):
    obj = _instance(cls, shape)
    body = serde.to_dict(obj)
    assert body == oracle_to_dict(obj)
    assert repr(body) == repr(oracle_to_dict(obj))  # 1 == 1.0 == True
    back = serde.from_dict(cls, body)
    want = oracle_from_dict(cls, body)
    assert type(back) is cls
    assert back == want
    assert repr(back) == repr(want)
    if shape != "holes":  # whole numbers came back as floats there
        assert serde.to_dict(back) == body


def test_reachable_classes_cover_the_api():
    assert len(REACHABLE) > 100
    for cls in (v1.Pod, v1.PodSpec, v1.Container, v1.ObjectMeta, v1.Node,
                v1.Affinity, v1.TopologySpreadConstraint):
        assert cls in REACHABLE


# -- the contract, case by case -----------------------------------------------


@dataclass
class Leaf:
    name: str = ""
    weight: float = 0.0
    tags: List[str] = field(default_factory=list)


@dataclass
class Sample:
    api_version: str = ""
    renamed: str = field(default="", metadata={"json": "openAPIV3Thing"})
    count: int = 0
    ratio: float = 0.0
    on: bool = False
    replicas: Optional[int] = None
    note: Optional[str] = None
    leaf: Leaf = field(default_factory=Leaf)
    maybe_leaf: Optional[Leaf] = None
    leaves: List[Leaf] = field(default_factory=list)
    by_name: Dict[str, Leaf] = field(default_factory=dict)
    labels: Dict[str, str] = field(default_factory=dict)
    ports: Optional[Tuple[int, ...]] = None
    nested: List[List[str]] = field(default_factory=list)
    limits: Dict[str, float] = field(default_factory=dict)
    anything: Any = None
    thing: object = None
    int_or_str: Union[int, str, None] = None
    raw: dict = field(default_factory=dict)
    custom: Optional[crd.Unstructured] = None


@dataclass
class Tree:
    name: str = ""
    left: Optional["Tree"] = None
    kids: List["Tree"] = field(default_factory=list)
    by_name: Optional[Dict[str, "Tree"]] = None


def test_unknown_key_is_ignored():
    got = serde.from_dict(Sample, {"count": 3, "noSuchField": {"x": 1}, "api_version": "no"})
    assert got == Sample(count=3)


def test_missing_key_keeps_the_default_and_null_sets_none():
    assert serde.from_dict(Sample, {}).leaf == Leaf()
    got = serde.from_dict(Sample, {"leaf": None, "labels": None, "count": None})
    assert got.leaf is None and got.labels is None and got.count is None
    assert serde.from_dict(Sample, None) is None


def test_json_key_override_both_ways():
    body = serde.to_dict(Sample(renamed="x", api_version="v1"))
    assert body == {"openAPIV3Thing": "x", "apiVersion": "v1", "leaf": {}}
    assert serde.from_dict(Sample, body) == Sample(renamed="x", api_version="v1")
    assert serde.from_dict(Sample, {"renamed": "x"}).renamed == ""


def test_int_becomes_float_where_the_hint_says_float():
    got = serde.from_dict(Sample, {"ratio": 2, "leaf": {"weight": 1}, "limits": {"a": 1, "b": None}})
    assert type(got.ratio) is float and got.ratio == 2.0
    assert type(got.leaf.weight) is float
    assert got.limits == {"a": 1.0, "b": None} and type(got.limits["a"]) is float
    assert type(serde.from_dict(Sample, {"count": 2}).count) is int


def test_empty_tuple_is_kept_and_empty_list_dropped():
    assert serde.to_dict(Sample()) == {"leaf": {}}
    odd = Sample(leaves=(), labels={}, nested=[], api_version="", count=0, ratio=0.0, on=False)
    assert serde.to_dict(odd) == {"leaf": {}, "leaves": []} == oracle_to_dict(odd)
    assert serde.to_dict(Sample(ports=(80, 443)))["ports"] == [80, 443]


def test_optional_zero_is_kept():
    body = serde.to_dict(Sample(replicas=0, note=""))
    assert body == {"replicas": 0, "note": "", "leaf": {}}
    back = serde.from_dict(Sample, body)
    assert back.replicas == 0 and back.note == ""


def test_unstructured_goes_through_its_hooks():
    u = crd.Unstructured({"kind": "Widget", "metadata": {"name": "w"}, "spec": {"size": [1, 2]}})
    u.metadata.uid = "stamped"
    body = serde.to_dict(u)
    assert body == oracle_to_dict(u)
    assert body["metadata"] == {"name": "w", "uid": "stamped"}
    back = serde.from_dict(crd.Unstructured, body)
    assert isinstance(back, crd.Unstructured) and back["spec"] == {"size": [1, 2]}
    back["spec"]["size"].append(3)  # the hook copies
    assert body["spec"] == {"size": [1, 2]}
    inside = serde.from_dict(Sample, {"custom": body})
    assert isinstance(inside.custom, crd.Unstructured)
    assert serde.to_dict(inside)["custom"] == body


def test_knobs_view_goes_through_its_hook():
    view = knobs._KnobConfigz()
    body = serde.to_dict({"ktpu-env": view})
    assert body == {"ktpu-env": view.__serde_to_dict__()}
    assert "KTPU_TRACE" in body["ktpu-env"]


def test_value_is_encoded_by_its_run_time_type():
    pod = v1.Pod(metadata=v1.ObjectMeta(name="p"))
    obj = Sample(anything=pod, thing=[Leaf(name="l"), (1, 2)], raw={"leaf": Leaf(name="r")},
                 int_or_str="http", labels={"odd": Leaf(name="in-a-str-dict")},
                 api_version=Leaf(name="in-a-str-field"), leaves=(Leaf(name="t"),))
    body = serde.to_dict(obj)
    assert body == oracle_to_dict(obj)
    assert body["anything"] == oracle_to_dict(pod) and body["anything"]["metadata"] == {"name": "p"}
    assert body["thing"] == [{"name": "l"}, [1, 2]]
    assert body["raw"] == {"leaf": {"name": "r"}}
    assert body["labels"] == {"odd": {"name": "in-a-str-dict"}}
    assert body["apiVersion"] == {"name": "in-a-str-field"}
    assert body["leaves"] == [{"name": "t"}]
    # open hints pass through on the way back
    assert serde.from_dict(Sample, body).anything == body["anything"]


def test_containers_outside_a_dataclass():
    assert serde.to_dict(None) is None
    assert serde.to_dict([Leaf(name="a"), None, (1,)]) == [{"name": "a"}, None, [1]]
    assert serde.to_dict({"k": Leaf(weight=1.0)}) == {"k": {"weight": 1.0}}
    assert serde.to_dict(Leaf) is Leaf  # a class is no instance
    assert serde.from_dict(List[Leaf], [{"name": "a"}, None]) == [Leaf(name="a"), None]
    assert serde.from_dict(Optional[Dict[str, Leaf]], {"k": {}}) == {"k": Leaf()}


def _containers(value: Any, out: list) -> list:
    """Every list and dict inside `value`, dataclass fields included."""
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        for f in dataclasses.fields(value):
            _containers(getattr(value, f.name), out)
    elif isinstance(value, dict):
        out.append(value)
        for v in value.values():
            _containers(v, out)
    elif isinstance(value, list):
        out.append(value)
        for v in value:
            _containers(v, out)
    return out


def _scribble(value: Any) -> None:
    for c in _containers(value, []):
        if isinstance(c, dict):
            c["scribbled"] = "x"
        else:
            c.append("scribbled")


def _typed_sample() -> Sample:
    return Sample(
        api_version="v1", leaf=Leaf(name="l", tags=["a"]), maybe_leaf=Leaf(tags=["b"]),
        leaves=[Leaf(tags=["c"])], by_name={"k": Leaf(tags=["d"])}, labels={"app": "web"},
        nested=[["x"], ["y"]], limits={"cpu": 1.5}, ports=(80,))


@pytest.mark.parametrize("make", [
    _typed_sample,
    lambda: _fill(v1.Pod, "typed"),
    lambda: _fill(v1.Node, "typed"),
], ids=["sample", "pod", "node"])
def test_no_aliasing_between_object_and_body(make):
    obj = make()
    pristine = repr(obj)
    body = serde.to_dict(obj)
    body_repr = repr(body)
    _scribble(body)
    assert repr(obj) == pristine, "the encoded dict shares a container with the object"

    body = serde.to_dict(obj)
    decoded = serde.from_dict(type(obj), body)
    _scribble(decoded)
    assert repr(body) == body_repr, "the decoded object shares a container with the body"


def test_no_aliasing_through_the_api_server():
    api = APIServer()
    cs = Clientset(api)
    pod = v1.Pod(metadata=v1.ObjectMeta(name="p", namespace="default", labels={"app": "web"}),
                 spec=v1.PodSpec(containers=[v1.Container(
                     name="c", resources=v1.ResourceRequirements(requests={"cpu": "1"}))]))
    created = cs.pods.create(pod)
    created.metadata.labels["app"] = "mutated"
    created.spec.containers[0].resources.requests["cpu"] = "64"
    created.spec.containers.append(v1.Container(name="extra"))
    pod.metadata.labels["app"] = "the-caller's"
    stored = cs.pods.get("p", "default")
    assert stored.metadata.labels == {"app": "web"}
    assert [c.name for c in stored.spec.containers] == ["c"]
    assert stored.spec.containers[0].resources.requests == {"cpu": "1"}
    stored.metadata.labels["app"] = "again"
    assert cs.pods.get("p", "default").metadata.labels == {"app": "web"}


def _built() -> Dict[str, float]:
    return {k[0]: v for k, v in serde.codecs_built.items()}


def test_first_use_from_eight_threads_at_once():
    leaf = dataclasses.make_dataclass(
        "FreshLeaf", [("name", str, ""), ("tags", List[str], field(default_factory=list))])
    fresh = dataclasses.make_dataclass("Fresh", [
        ("name", str, ""), ("weight", float, 0.0), ("leaf", Optional[leaf], None),
        ("leaves", List[leaf], field(default_factory=list))])
    body = {"name": "n", "weight": 2, "leaf": {"name": "l", "tags": ["t"]},
            "leaves": [{"name": "a"}, None], "unknown": 1}
    want = fresh(name="n", weight=2.0, leaf=leaf(name="l", tags=["t"]),
                 leaves=[leaf(name="a"), None])
    before = _built()
    start = threading.Barrier(8)
    results: list = []

    def work():
        start.wait(timeout=60)
        for _ in range(50):
            obj = serde.from_dict(fresh, body)
            results.append((obj, serde.to_dict(obj)))

    threads = [threading.Thread(target=work) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # switch threads in the middle of a build
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(results) == 400
    for obj, encoded in results:
        assert obj == want and type(obj.weight) is float
        assert encoded == {"name": "n", "weight": 2.0, "leaf": {"name": "l", "tags": ["t"]},
                           "leaves": [{"name": "a"}, None]}
    after = _built()
    # two classes a direction; a race may build one twice, never per call
    assert 2 <= after["decode"] - before.get("decode", 0) <= 16
    assert 2 <= after["encode"] - before.get("encode", 0) <= 16
    flat = _built()
    serde.from_dict(fresh, body)
    serde.to_dict(want)
    assert _built() == flat


@pytest.mark.parametrize("cls", [Tree, crd.JSONSchemaProps], ids=["tree", "jsonschemaprops"])
def test_self_referring_class_builds(cls):
    serde.build_codecs(cls)
    if cls is Tree:
        obj = Tree(name="root", left=Tree(name="l", kids=[Tree(name="ll")]),
                   kids=[Tree(name="k1"), Tree(name="k2", by_name={"x": Tree(name="deep")})])
    else:
        obj = crd.JSONSchemaProps(
            type="object", required=["spec"],
            properties={"spec": crd.JSONSchemaProps(
                type="array", items=crd.JSONSchemaProps(type="string"))})
    body = serde.to_dict(obj)
    assert body == oracle_to_dict(obj)
    assert serde.from_dict(cls, body) == obj == oracle_from_dict(cls, body)


def test_no_codec_is_built_by_traffic():
    api = APIServer()
    cs = Clientset(api)
    before = _built()
    assert before["encode"] >= len(REACHABLE) and before["decode"] >= len(REACHABLE)
    for i in range(1000):
        cs.pods.create(_traffic_pod(i))
    pods, _ = cs.pods.list(namespace="default")
    assert len(pods) == 1000
    assert _built() == before


def _traffic_pod(i: int) -> v1.Pod:
    labels = {"app": f"web-{i % 7}"}
    affinity = None
    if i % 3 == 0:
        affinity = v1.Affinity(pod_anti_affinity=v1.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[v1.PodAffinityTerm(
                label_selector=v1.LabelSelector(match_labels=dict(labels)),
                topology_key=v1.LABEL_HOSTNAME)]))
    return v1.Pod(
        metadata=v1.ObjectMeta(name=f"p-{i}", namespace="default", labels=labels),
        spec=v1.PodSpec(
            containers=[v1.Container(name="c0", image="app:v1", resources=v1.ResourceRequirements(
                requests={"cpu": "100m", "memory": "128Mi"}))],
            affinity=affinity,
            topology_spread_constraints=[v1.TopologySpreadConstraint(
                max_skew=1, topology_key=v1.LABEL_ZONE, when_unsatisfiable="ScheduleAnyway",
                label_selector=v1.LabelSelector(match_labels=dict(labels)))]))


def test_codecs_do_not_ask_typing_again(monkeypatch):
    """Once a class's codec exists no typing / dataclasses call is reachable
    from to_dict / from_dict."""
    pod = _traffic_pod(0)
    body = serde.to_dict(pod)
    serde.from_dict(v1.Pod, body)

    def boom(*_a, **_k):
        raise AssertionError("type hints were read on the per-call path")

    for mod, name in ((typing, "get_type_hints"), (serde, "get_origin"), (serde, "get_args"),
                      (dataclasses, "is_dataclass"), (dataclasses, "fields"),
                      (serde, "_field_plan"), (serde, "_unwrap_optional")):
        monkeypatch.setattr(mod, name, boom)
    assert serde.to_dict(pod) == body
    assert serde.from_dict(v1.Pod, body) == pod

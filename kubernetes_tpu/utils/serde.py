"""Dataclass <-> JSON-dict serialization with Kubernetes-style camelCase keys.

The reference's API types round-trip through JSON with camelCase field names
(e.g. staging/src/k8s.io/api/core/v1/types.go struct tags), through codecs
that k8s.io/code-generator writes per type. Here every API dataclass gets
the same: the first time a class is encoded or decoded, its type hints are
read ONCE and one straight-line function per direction is generated from
them (as `dataclasses` generates `__init__`), kept in a per-class table, and
every later call is that function and the nested classes' functions. No
`typing` or `dataclasses` call runs once a class's codec exists.

Conventions:
  - snake_case python field  <->  camelCase JSON key
  - a field may override its JSON key with metadata={"json": "name"}
  - zero-valued fields (None, "", 0, False, empty list/dict) are omitted on
    serialization (matches Go `omitempty`)
  - a class may take over with `__serde_to_dict__(self)` and the classmethod
    `__serde_from_dict__(data)`
"""

from __future__ import annotations

import dataclasses
import functools
import typing
from typing import Any, Callable, Dict, Type, TypeVar, Union, get_args, get_origin

from .metrics import Counter, legacy_registry

T = TypeVar("T")

codecs_built = legacy_registry.register(
    Counter(
        "serde_codecs_built_total",
        "Dataclass codecs generated from type hints (one per class and "
        "direction on first use; flat once every class in use was met).",
        ("direction",),
    )
)


def snake_to_camel(name: str) -> str:
    parts = name.split("_")
    return parts[0] + "".join(p.title() for p in parts[1:])


def _json_key(field: dataclasses.Field) -> str:
    return field.metadata.get("json", snake_to_camel(field.name))


def _is_optional(tp: Any) -> bool:
    return get_origin(tp) is Union and type(None) in get_args(tp)


def _unwrap_optional(tp: Any) -> Any:
    if _is_optional(tp):
        args = [a for a in get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


# Per-class field plan: (attr name, json key, resolved type, is_optional).
# typing.get_type_hints re-evaluates string annotations with compile() on
# EVERY call, so it is read once per class, when the codecs are built.
_PLAN_CACHE: Dict[type, list] = {}


def _field_plan(cls: type) -> list:
    plan = _PLAN_CACHE.get(cls)
    if plan is None:
        hints = typing.get_type_hints(cls)
        plan = [
            (
                f.name,
                _json_key(f),
                hints.get(f.name, f.type),
                _is_optional(hints.get(f.name, f.type)),
            )
            for f in dataclasses.fields(cls)
        ]
        _PLAN_CACHE[cls] = plan
    return plan


# The codec tables. A codec is built on first use, without a lock: two
# threads may build the same one, the second assignment wins, both are
# correct. `building` is the chain of classes whose codecs the caller is in
# the middle of generating; a class that refers to itself gets the public
# entry point for that edge, which finds the finished codec at call time.
_ENCODERS: Dict[type, Callable[[Any], Any]] = {}
_DECODERS: Dict[Any, Callable[[Any], Any]] = {}

_SCALARS = (str, int, float, bool)
# what a falsy value of a non-Optional field is, if it is to be omitted
_OMITTED_WHEN_FALSY = frozenset((type(None), str, int, float, bool, list, dict))


def build_codecs(cls: Any) -> None:
    """Build both codecs of `cls` and of every class its hints reach, so that
    no later to_dict / from_dict of them generates anything."""
    _encoder(cls)
    _decoder(cls)


def _same(value: Any) -> Any:
    return value


def _compile(name: str, body: list, ns: Dict[str, Any]) -> Callable:
    """`def <name>(o)` with `body` (the name shows in profiles and tracebacks)."""
    source = "\n".join([f"def {name}(o):"] + ["    " + line for line in body])
    exec(compile(source, f"<serde {name}>", "exec"), ns)  # noqa: S102 — made here from field names
    return ns[name]


# -- encode -----------------------------------------------------------------


def to_dict(obj: Any) -> Any:
    """Serialize a dataclass (or container of them) to JSON-compatible dicts.

    Dicts, lists and tuples are rebuilt (tuples as lists): the result shares
    no mutable container with `obj`. A value is encoded by its run-time type
    wherever it is not what the field's hint says."""
    enc = _ENCODERS.get(type(obj))
    if enc is None:
        enc = _encoder(type(obj))
    return enc(obj)


def _encode_dict(obj: dict) -> dict:
    return {k: to_dict(v) for k, v in obj.items()}


def _encode_list(obj: Any) -> list:
    return [to_dict(v) for v in obj]


def _encode_hook(obj: Any) -> Any:
    return obj.__serde_to_dict__()


def _encode_odd_falsy(out: dict, key: str, v: Any) -> None:
    """A falsy value that is no plain zero (`()`, an empty set): the omit rule
    is equality with one of the zeros, not falsiness."""
    if not (v == "" or v == 0 or v is False or v == [] or v == {}):
        out[key] = to_dict(v)


def _encoder(cls: type, building: tuple = ()) -> Callable[[Any], Any]:
    """The encoder of instances of exactly `cls`, built on first use."""
    enc = _ENCODERS.get(cls)
    if enc is None:
        if hasattr(cls, "__serde_to_dict__"):
            enc = _encode_hook
        elif dataclasses.is_dataclass(cls):
            enc = _build_dataclass_encoder(cls, building + (cls,))
        elif issubclass(cls, dict):
            enc = _encode_dict
        elif issubclass(cls, (list, tuple)):
            enc = _encode_list
        else:
            enc = _same
        _ENCODERS[cls] = enc
    return enc


def _element_type(tp: Any) -> Any:
    args = [a for a in get_args(tp) if a is not Ellipsis]
    return args[0] if len(args) == 1 else Any


def _encode_expr(tp: Any, var: str, ns: Dict[str, Any], building: tuple) -> str:
    """Source of the expression that encodes `var` as the hint `tp` says,
    behind a guard on its run-time type; whatever the guard does not know
    (a None among the elements, a tuple, a subclass) is left to to_dict."""
    tp = _unwrap_optional(tp)
    origin = get_origin(tp)
    inner = var + "x"
    if origin in (list, tuple):
        elem = _encode_expr(_element_type(tp), inner, ns, building)
        return f"[{elem} for {inner} in {var}] if type({var}) is list else to_dict({var})"
    if origin is dict:
        args = get_args(tp)
        elem = _encode_expr(args[1] if len(args) == 2 else Any, inner, ns, building)
        return (f"{{k: {elem} for k, {inner} in {var}.items()}} "
                f"if type({var}) is dict else to_dict({var})")
    if tp in _SCALARS:
        return f"{var} if type({var}) is {tp.__name__} else to_dict({var})"
    if (isinstance(tp, type) and dataclasses.is_dataclass(tp)
            and not hasattr(tp, "__serde_to_dict__") and tp not in building):
        name = f"_c{len(ns)}"
        ns[name] = tp
        ns[name + "e"] = _encoder(tp, building)
        return f"{name}e({var}) if type({var}) is {name} else to_dict({var})"
    return f"to_dict({var})"


def _build_dataclass_encoder(cls: type, building: tuple) -> Callable[[Any], dict]:
    ns: Dict[str, Any] = {
        "to_dict": to_dict, "_odd": _encode_odd_falsy, "_zeros": _OMITTED_WHEN_FALSY}
    body = ["out = {}"]
    for name, key, tp, is_opt in _field_plan(cls):
        body.append(f"v = o.{name}")
        expr = _encode_expr(tp, "v", ns, building)
        if is_opt:
            # Optional fields mirror Go pointers: a present zero value (e.g.
            # *int32 replicas = 0) is serialized, only nil is omitted.
            body += ["if v is not None:", f"    out[{key!r}] = {expr}"]
        else:
            body += ["if v:", f"    out[{key!r}] = {expr}",
                     "elif type(v) not in _zeros:", f"    _odd(out, {key!r}, v)"]
    body.append("return out")
    codecs_built.inc(direction="encode")
    return _compile(f"encode_{cls.__name__}", body, ns)


# -- decode -----------------------------------------------------------------


def from_dict(cls: Type[T], data: Any) -> T:
    """Deserialize JSON-compatible data into dataclass `cls` using type hints.

    NO ALIASING: every list and dict of a typed field is rebuilt, also where
    its elements are scalars, so the decoded object shares no mutable
    container with `data`. The body belongs to the store (or to a watch
    event that other watchers read too) and the object is handed to handlers
    that may mutate it. Only what the hints leave open (`Any`, `object`, a
    bare `dict`, a non-optional `Union`) passes through as it is.

    A key the class does not know is ignored; a missing key leaves the
    field's default; a key present with null sets None."""
    dec = _DECODERS.get(cls)
    if dec is None:
        dec = _decoder(cls)
    return dec(data)


def _decode_float(data: Any) -> Any:
    return float(data) if isinstance(data, int) else data


def _decode_list(data: Any) -> Any:
    return None if data is None else list(data)


def _decode_dict(data: Any) -> Any:
    return None if data is None else dict(data)


def _decoder(tp: Any, building: tuple = ()) -> Callable[[Any], Any]:
    """The decoder of type hint `tp` (a class or a typing form), built on
    first use."""
    dec = _DECODERS.get(tp)
    if dec is None:
        if tp in building:
            return functools.partial(from_dict, tp)
        dec = _DECODERS[tp] = _build_decoder(tp, building)
    return dec


def _build_decoder(tp: Any, building: tuple) -> Callable[[Any], Any]:
    inner = _unwrap_optional(tp)
    if inner is not tp:
        return _decoder(inner, building)
    origin = get_origin(tp)
    if origin in (list, tuple):
        elem = _decoder(_element_type(tp), building)
        if elem is _same:
            return _decode_list
        return lambda data: None if data is None else [elem(v) for v in data]
    if origin is dict:
        args = get_args(tp)
        val = _decoder(args[1] if len(args) == 2 else Any, building)
        if val is _same:
            return _decode_dict
        return lambda data: None if data is None else {
            k: val(v) for k, v in data.items()}
    if isinstance(tp, type) and hasattr(tp, "__serde_from_dict__"):
        hook = tp.__serde_from_dict__
        return lambda data: None if data is None else hook(data)
    if dataclasses.is_dataclass(tp):
        return _build_dataclass_decoder(tp, building + (tp,))
    if tp is float:
        return _decode_float
    # Any, object, a TypeVar, a non-optional Union, a scalar, a bare dict
    return _same


_INLINE_DECODE = {
    _same: "v",
    _decode_float: "float(v) if isinstance(v, int) else v",
    _decode_list: "None if v is None else list(v)",
    _decode_dict: "None if v is None else dict(v)",
}


def _build_dataclass_decoder(cls: type, building: tuple) -> Callable[[Any], Any]:
    ns: Dict[str, Any] = {"_cls": cls}
    body = ["if o is None:", "    return None", "kw = {}"]
    for name, key, tp, _is_opt in _field_plan(cls):
        dec = _decoder(tp, building)
        expr = _INLINE_DECODE.get(dec)
        if expr is None:
            slot = f"_d{len(ns)}"
            ns[slot] = dec
            expr = f"{slot}(v)"
        body += [f"if {key!r} in o:", f"    v = o[{key!r}]", f"    kw[{name!r}] = {expr}"]
    body.append("return _cls(**kw)")
    codecs_built.inc(direction="decode")
    return _compile(f"decode_{cls.__name__}", body, ns)

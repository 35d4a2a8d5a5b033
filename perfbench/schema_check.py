"""A small validator for the JSON schemas the traffic files carry (the
subset the classification template produces: objects, strings with
``maxLength``/``enum``, ``$ref`` into ``$defs``). Independent of the
program's constraint compiler, which it checks."""

from __future__ import annotations

from typing import Any, Dict, Optional


def _resolve(schema: Dict[str, Any], root: Dict[str, Any]) -> Dict[str, Any]:
    ref = schema.get("$ref")
    if ref is None:
        return schema
    node: Any = root
    for part in ref.lstrip("#/").split("/"):
        node = node[part]
    return node


def violation(
    value: Any, schema: Dict[str, Any], root: Optional[Dict[str, Any]] = None,
    path: str = "$",
) -> Optional[str]:
    """None when ``value`` satisfies ``schema``, else what is wrong."""
    root = schema if root is None else root
    schema = _resolve(schema, root)
    typ = schema.get("type")
    if "enum" in schema and value not in schema["enum"]:
        return f"{path}: {value!r} not in {schema['enum']}"
    if typ == "object":
        if not isinstance(value, dict):
            return f"{path}: not an object"
        for key in schema.get("required", []):
            if key not in value:
                return f"{path}: missing {key!r}"
        props = schema.get("properties", {})
        for key, sub in value.items():
            if key in props:
                bad = violation(sub, props[key], root, f"{path}.{key}")
                if bad:
                    return bad
            elif schema.get("additionalProperties") is False:
                return f"{path}: unexpected {key!r}"
    elif typ == "string":
        if not isinstance(value, str):
            return f"{path}: not a string"
        if "maxLength" in schema and len(value) > schema["maxLength"]:
            return f"{path}: longer than {schema['maxLength']}"
    elif typ == "integer":
        if not isinstance(value, int) or isinstance(value, bool):
            return f"{path}: not an integer"
    elif typ == "number":
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            return f"{path}: not a number"
    elif typ == "boolean":
        if not isinstance(value, bool):
            return f"{path}: not a boolean"
    elif typ == "array":
        if not isinstance(value, list):
            return f"{path}: not an array"
        items = schema.get("items")
        if items:
            for i, sub in enumerate(value):
                bad = violation(sub, items, root, f"{path}[{i}]")
                if bad:
                    return bad
    return None

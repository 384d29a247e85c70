"""Device milliseconds a traced request spent in one class of ops, out of
the trace's per-op table. ``classes`` names ``op_classes/<name>.json``: the
executable whose ops are classed (``module``) and an ordered list of rules,
each a class with regular expressions on the op's ``scope`` (its ``tf_op``:
flax module path and JAX primitive), ``category`` (``hlo_category``) and
``name``; the first rule that matches takes the op, and a rule without
expressions takes everything left, so the classes partition the module's
ops. Containers (``while``) are not in the table. Nothing of that module in
the slice: nothing to read.

A metric that names no ``classes`` is one of the expander's decode step
and finds its file from the cell's CONFIGURATION, as ``components``,
``counter`` and ``reference`` are found (harness/files.py): the
configuration's ``"op_classes": "<stem>"`` means
``op_classes/<stem>_decode.json``. So one metric a class serves every
configuration whose file has that class, and a new configuration brings a
class file and no metric. A configuration that names no stem, a file
without the class: nothing to read."""

import re


def classify(row: dict, rules: list) -> str | None:
    for rule in rules:
        if all(re.search(rule[key], row[key])
               for key in ("scope", "category", "name") if key in rule):
            return rule["class"]
    return None


def by_class(context: dict, classes: str) -> dict | None:
    """{class: device seconds in the slice} over the module's ops. Kept in
    the context: every metric of one classes file asks for the same sums."""
    memo = context.setdefault("op_class_seconds", {})
    key = (classes, id(context.get("trace")))
    if key not in memo:
        memo[key] = _by_class(context, classes)
    return memo[key]


def _by_class(context: dict, classes: str) -> dict | None:
    trace = context.get("trace")
    if not trace or not trace.get("op_table"):
        return None
    spec = context["bench"].read("op_classes", classes + ".json")
    rows = [r for r in trace["op_table"] if r["module"] == spec["module"]]
    if not rows:
        return None
    out = {rule["class"]: 0.0 for rule in spec["classes"]}
    for row in rows:
        name = classify(row, spec["classes"])
        if name is not None:
            out[name] += row["seconds"]
    return out


def decode_classes(context: dict) -> str | None:
    """The class file of the configuration's decode executable."""
    stem = (context.get("config") or {}).get("op_classes")
    return stem + "_decode" if stem else None


def read(context: dict, classes: str | None = None, cls: str = ""):
    classes = classes or decode_classes(context)
    if classes is None:
        return None
    traced = [r for r in context["records"] if r.traced]
    seconds = by_class(context, classes)
    if not seconds or not traced or cls not in seconds:
        return None
    return 1e3 * seconds[cls] / len(traced)

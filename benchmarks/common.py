"""What the harness, the runners and the readers share."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module; a name may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def check(name: str, value, limit, ok=None) -> dict:
    """One compared number beside its limit. `ok` defaults to
    value <= limit; a limit of None (not yet set) never passes."""
    if ok is None:
        ok = limit is not None and value is not None and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def held_checks(limits: dict, values: dict) -> list:
    """The checks of the numbers a runner declares it compares
    (`values`: name -> this run's reading), each against the cell's
    `limits/<cell>.json`. A number with no entry there, or with no
    `limit`, gets the limit None and is not ok: a cell with no limits
    file is never correct. A number leaves the comparison only through
    an explicit entry `{"held": false, "readings": "<why>"}`; it is
    still printed."""
    checks = []
    for name, value in values.items():
        entry = limits.get(name) or {}
        if entry.get("held") is False:
            log(f"not held {name}: value {value}; {entry.get('readings')}")
            continue
        checks.append(check(name, value, entry.get("limit")))
    return checks


def build_model(config: dict):
    import importlib

    import jax.numpy as jnp
    module, _, cls = config["model"].rpartition(".")
    kwargs = {k: config[v] for k, v in config["constructor_args"].items()}
    return getattr(importlib.import_module(module), cls)(
        dropout=0.0, dtype=jnp.dtype(config["compute_dtype"]), **kwargs)


def check_tree(model, params) -> None:
    """The benchmark's weights must be the tree the program expects."""
    import jax
    import jax.numpy as jnp
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    a = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    b = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    if a != b:
        raise SystemExit("benchmarks/weights.py no longer makes the tree "
                         "the program's model expects")

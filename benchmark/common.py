"""What the families share: mesh, keys, optimizer, errors, HLO counts."""

from __future__ import annotations

import re


def hvd_mesh(devices):
    """One process drives every chip of the cell over one ``hvd`` axis."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), ("hvd",))


def load_function(dotted: str):
    """``"trace_reduce.op_time_ms"`` -> that function of
    ``benchmark/trace_reduce.py``: how a data file names code.  A later PR
    names a module of its own."""
    import importlib

    module, _, fn = dotted.rpartition(".")
    return getattr(importlib.import_module(f"benchmark.{module}"), fn)


def make_optimizer(spec: dict):
    """The plain optax transformation a configuration's ``optimizer`` names
    (``{"name": "adamw", "args": {...}}`` -> ``optax.adamw(**args)``); the
    system wraps it in ``hvd.DistributedOptimizer``, the reference uses it
    as it is."""
    import optax

    return getattr(optax, spec["name"])(**spec["args"])


def rel_err(a, b) -> float:
    """max |a-b| / max |b| (chip_smoke.py's measure)."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(1e-12, float(np.max(np.abs(b)))))


def l2_rel_err(a, b) -> float:
    """||a-b|| / ||b||: for whole leaves, where a few elements near zero
    may differ in relative terms without the leaf being wrong."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(1e-30, np.linalg.norm(b)))


def check(name: str, value: float, tol: float) -> dict:
    return {"name": name, "value": value, "tol": tol, "ok": bool(value < tol)}


def at_least(name: str, count: int, least: int) -> dict:
    return {"name": name, "value": count, "least": least,
            "ok": bool(count >= least)}


def first_shard(tree):
    """The tree's arrays as they lie on the first device of their sharding:
    what a one-device reference reads of a replicated state, with no copy."""
    import jax

    return jax.tree_util.tree_map(lambda x: x.addressable_data(0), tree)


def leaf_paths(tree) -> dict:
    """``{keystr(path): leaf}`` for every leaf of ``tree``."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def first_moments(opt_state, param_path: str) -> list:
    """The optimizer-state leaves that hold a first moment of the parameter
    at ``param_path``: optax's ``mu`` (Adam) or ``trace`` (momentum).  After
    one step from zero they are the exchanged gradient times a constant, so
    they hold its scale where the AdamW update itself does not."""
    return [leaf for path, leaf in leaf_paths(opt_state).items()
            if path.endswith(param_path)
            and re.search(r"\.(mu|trace)\b", path[:-len(param_path)])]


# An op is "<type> all-reduce(": instruction *names* (%all-reduce.3) are
# followed by a dot or a space, never by the parenthesis.
COLLECTIVE_HLO = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def hlo_counts(text: str) -> dict:
    """Pallas kernels and collective ops in a compiled module's text."""
    counts = {"tpu_custom_call": text.count("tpu_custom_call")}
    for m in COLLECTIVE_HLO.finditer(text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts

"""Plain reference of the control plane the benchmark's deployments use,
written from the definitions and independent of the program's vectorised
compilers. The benchmark compares the tables the program deployed with
these, entry for entry, and runs the reference fabric on these.

A deployment's schedule and routing scheme are found by the names its
configuration file gives, each in a file of its own beside this one:

* ``schedules/<schedule>.py``: ``conn(tors, uplinks)``, the circuit of
  every uplink of every node in every slice, ``int32 [T, N, U]``;
* ``schemes/<scheme>.py``: ``tables(conn, **routing)``, a dict of
  ``tf_next``, ``tf_dep``, ``inj_next``, ``inj_dep`` and ``multipath``,
  given the configuration's ``routing`` less its ``scheme``. A scheme file
  may import the helpers below.

Table layout (the fabric's): ``[T, N, D, K]`` per (arrival slice mod T,
node, destination, multipath slot); ``*_next`` is the egress peer (-1 for
an empty slot), ``*_dep`` the departure-slice offset. Valid slots are
contiguous from slot 0.
"""
from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

REFERENCE = pathlib.Path(__file__).resolve().parent


def has_circuit(conn: np.ndarray) -> np.ndarray:
    """has[t, n, d]: some uplink of n connects to d in slice t."""
    T, N, U = conn.shape
    has = np.zeros((T, N, N), bool)
    for k in range(U):
        for t in range(T):
            has[t, np.arange(N), conn[t, :, k]] = True
    return has


def first_direct(conn: np.ndarray) -> np.ndarray:
    """first[t, n, d]: slices to wait at n from slice t until a circuit
    n -> d is up (the search runs to the end of the next cycle); -1 if the
    schedule never has one."""
    has = has_circuit(conn)
    T = has.shape[0]
    first = np.full(has.shape, -1, np.int32)
    for t in range(T):
        for w in range(2 * T - 1 - t, -1, -1):   # latest first, so the
            first[t] = np.where(has[(t + w) % T], w, first[t])   # least wins
    return first


def direct(conn: np.ndarray):
    """Hold each packet until the direct circuit to its destination."""
    fd = first_direct(conn)
    N = conn.shape[1]
    nxt = np.where(fd >= 0, np.arange(N, dtype=np.int32), -1)[..., None]
    dep = np.where(fd >= 0, fd, 0).astype(np.int32)[..., None]
    return nxt.astype(np.int32), dep


def load(kind: str, name: str):
    """``bench/reference/<kind>/<name>.py`` as a module."""
    path = REFERENCE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no reference for {name!r}: "
            f"{path.relative_to(REFERENCE.parent.parent)} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deployment_tables(deployment: dict) -> dict:
    """Every table of a deployment file's schedule and routing scheme."""
    conn = load("schedules", deployment["schedule"]).conn(
        deployment["tors"], deployment["uplinks"])
    routing = dict(deployment["routing"])
    tables = load("schemes", routing.pop("scheme")).tables(conn, **routing)
    tables.update(conn=conn, first_direct=first_direct(conn))
    return tables
